"""Chip-level systolic schedules (the AIE-DMA neighbour streams at pod
scale), dispatched per-recurrence through ``KernelSpec.systolic_lowering``.

Each lowering here is a hook with the signature

    lowering(plan: ExecutionPlan, mesh) -> Callable(*operands)

registered on the recurrence's ``KernelSpec`` (``registry.py``) and
invoked by ``core/codegen.lower_plan(..., backend="systolic")`` — codegen
no longer hardcodes an mm-only schedule.  Every registered spec maps to
one of four neighbour-stream schedule families (plus the GSPMD
all-gather/broadcast baselines, ``allgather_lowering`` — the
"unconstrained compiler" references for the §Perf hillclimb):

  cannon_mm / cannon_bmm
                  Cannon's algorithm on the square space mesh: A/B blocks
                  pre-skewed with static ppermutes, then rotated west/north
                  each step while partial sums accumulate in place; bmm is
                  the same ring vmapped over an unsharded batch axis.
                  Never materializes a gathered operand — edge-bandwidth
                  optimal, the direct analogue of the paper's AIE DMA edges.
  cannon_fft2d    the complex two-plane Cannon variant: real/imag planes
                  of each operand are co-rotated around the same ring, so
                  the cross products of the complex MAC stay local to the
                  chip at every step.  Both DFT stages of the four-step
                  2-D FFT (Z = F_R @ X @ F_C) ride the ring.
  halo_stencil    width-k halo exchange for star stencils: the grid
                  interior is sharded over both space axes; per sweep every
                  shard ppermutes a *k-wide* edge strip to each neighbour
                  (k = the stencil radius, derived from the recurrence's
                  access-function offsets — 1 for the 5-point star, 2 for
                  the radius-2 9-point star), chips on the array boundary
                  substitute the fixed (Dirichlet) boundary strip, and the
                  star is applied locally.  Multi-sweep (jacobi2d_ms)
                  iterates the exchange on the *updated* interior — the
                  recurrence's flow dependence on the sweep loop, executed
                  as k edge rows/columns of neighbour traffic per sweep.
  chain_conv2d / chain_fir
                  1-D neighbour chains with a shifted-window halo: the
                  output domain is sharded over the linearized mesh; each
                  shard receives the *left edge of width kernel-1* of its
                  right neighbour via one one-hop ppermute (the window tail
                  it needs to close its own outputs), and the last shard in
                  the chain substitutes the global input tail strip instead
                  (the Dirichlet analogue of the stencil boundary ring).
  ring_mttkrp     2-D ring over (i, j): Cannon over the l contraction with
                  the two factor matrices staged around the ring — C[l,j]
                  co-rotates with X's l-blocks (north), X rotates west, and
                  B[k,j] stays staged along the ring's rows (j-sharded,
                  row-replicated); the three-operand contraction runs per
                  step with ``acc_dtype`` accumulation.

A second hook family serves *fused chains* (``core/fusion.py``):
``KernelSpec.fused_systolic_lowering`` hooks take a ``FusedPlan`` and run
every chain stage back-to-back inside ONE shard_map —
``fused_halo_chain`` (one deep halo exchange feeds all stencil stages),
``fused_cannon_mm`` (one pre-skew serves back-to-back rings with the
interstage bias/activation applied shard-resident) and
``fused_cannon_fft2d`` (both DFT stages on one ring, Y never leaves the
chips).  The intermediate stays shard-resident in the acc dtype instead
of round-tripping through HBM.

Operand contracts match the specs' (see ``registry.py``).  Shard
divisibility (and, for the Cannon rings, a square space mesh) is checked
eagerly with actionable errors; halo/window widths must fit inside the
adjacent shard so every exchange stays one hop.  The accumulator/output
dtype ladder is shared with the Pallas runtime (``runtime.acc_dtype``/
``runtime.out_dtype``), which keeps integer parity with the XLA reference
bit-exact.
"""

from __future__ import annotations

from typing import TYPE_CHECKING, Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.compat import shard_map as _shard_map
from repro.core.codegen import UnsupportedLoweringError
from repro.core.recurrence import stencil_star

from . import runtime

if TYPE_CHECKING:  # pragma: no cover - typing only, avoids import cycles
    from repro.core.mapper import ExecutionPlan


def _space_axes(plan: "ExecutionPlan") -> tuple[str, str]:
    """The two mesh axes the plan's space loops fold onto (named by the
    plan's target; the concrete mesh passed to the hook must use the same
    axis names)."""
    axes = plan.target.mesh_axes
    return axes[0], axes[1] if len(axes) > 1 else axes[0]


def _require_divisible(what: str, extent: int, width: int, axis: str):
    if extent % width:
        raise UnsupportedLoweringError(
            f"{what}: extent {extent} does not divide over the {width}-wide "
            f"mesh axis {axis!r} — pad the operand or pick a mesh whose "
            "axis widths divide the space extents")


def _require_square(plan: "ExecutionPlan", mesh, what: str) -> tuple:
    ax0, ax1 = _space_axes(plan)
    n0, n1 = mesh.shape[ax0], mesh.shape[ax1]
    if n0 != n1:
        raise UnsupportedLoweringError(
            f"{what} needs a square space array, got {ax0}={n0} x "
            f"{ax1}={n1}")
    return ax0, ax1, n0


# ---------------------------------------------------------------------------
# Cannon rings: mm, the batch-vmapped bmm, and the complex two-plane fft2d
# ---------------------------------------------------------------------------

def _skew_perms(n: int) -> tuple[list, list]:
    """Cannon pre-skew as STATIC perms over the linearized (ax0, ax1) pair:
    A(i, k) -> A(i, (k+i) mod n) ; B(k, j) -> B((k+j) mod n, j)."""
    skew_a = [(r * n + ((c + r) % n), r * n + c)
              for r in range(n) for c in range(n)]
    skew_b = [(((r + c) % n) * n + c, r * n + c)
              for r in range(n) for c in range(n)]
    return skew_a, skew_b


def _rot_perm(n: int) -> list:
    """One ring rotation: every member receives from its +1 neighbour
    (A moves one hop west along ax1 / B one hop north along ax0)."""
    return [((i + 1) % n, i) for i in range(n)]


def _cannon_ring(plan: "ExecutionPlan", mesh, batched: bool) -> Callable:
    """Shared Cannon schedule; ``batched`` lifts the body over a leading
    unsharded batch axis with ``jax.vmap``."""
    ax0, ax1, steps = _require_square(plan, mesh, "cannon schedule")

    def local(a_blk, b_blk):
        skew_a, skew_b = _skew_perms(steps)
        a_blk = jax.lax.ppermute(a_blk, (ax0, ax1), skew_a)
        b_blk = jax.lax.ppermute(b_blk, (ax0, ax1), skew_b)

        acc_t = runtime.acc_dtype(a_blk.dtype)
        out_t = runtime.out_dtype(a_blk.dtype)

        def dot2d(a, b):
            if jnp.issubdtype(a.dtype, jnp.integer):
                a, b = a.astype(jnp.int32), b.astype(jnp.int32)
            return jnp.dot(a, b, preferred_element_type=acc_t)

        contract = jax.vmap(dot2d) if batched else dot2d

        def body(step, carry):
            a, b, acc = carry
            acc = acc + contract(a, b)
            a = jax.lax.ppermute(a, ax1, _rot_perm(steps))
            b = jax.lax.ppermute(b, ax0, _rot_perm(steps))
            return a, b, acc

        m, k = a_blk.shape[-2:]
        nn = b_blk.shape[-1]
        lead = a_blk.shape[:-2]
        acc = jnp.zeros(lead + (m, nn), acc_t)
        a_blk, b_blk, acc = jax.lax.fori_loop(
            0, steps, body, (a_blk, b_blk, acc)
        )
        return acc.astype(out_t)

    spec = P(None, ax0, ax1) if batched else P(ax0, ax1)
    fn = _shard_map(
        local,
        mesh=mesh,
        in_specs=(spec, spec),
        out_specs=spec,
        check=False,
    )

    def run(a, b):
        _require_divisible("cannon A rows", a.shape[-2], steps, ax0)
        _require_divisible("cannon A cols", a.shape[-1], steps, ax1)
        _require_divisible("cannon B rows", b.shape[-2], steps, ax0)
        _require_divisible("cannon B cols", b.shape[-1], steps, ax1)
        return fn(a, b)

    return run


def cannon_mm(plan: "ExecutionPlan", mesh) -> Callable:
    """Cannon-style systolic matmul over the plan's two space axes.

    A is sharded (i->ax0, k->ax1); B is sharded (k->ax0, j->ax1); C comes
    out sharded (i->ax0, j->ax1).  Each of the ``steps`` iterations
    multiplies the local blocks then rotates A west / B north via ppermute
    — the direct chip-level analogue of the paper's neighbour DMA streams,
    and it never materializes a gathered operand (edge-bandwidth optimal).
    """
    return _cannon_ring(plan, mesh, batched=False)


def cannon_bmm(plan: "ExecutionPlan", mesh) -> Callable:
    """Batched Cannon: the mm ring vmapped over the (unsharded) batch axis
    — one ppermute rotation carries every batch's block at once."""
    return _cannon_ring(plan, mesh, batched=True)


def cannon_fft2d(plan: "ExecutionPlan", mesh) -> Callable:
    """Complex two-plane Cannon for the 2-D FFT's DFT stages.

    The MXU has no complex datapath, so complex operands ride as (re, im)
    real-plane pairs; the schedule *co-rotates* both planes of A west and
    both planes of B north around the same ring, so the four cross
    products of the complex MAC (rr, ii, ri, ir) are always between
    blocks resident on the same chip — twiddle/DFT-factor application
    stays local at every step.  Both stages of the four-step decomposition
    (Y = F_R @ X, then Z = Y @ F_C) run on the same ring; the DFT matrices
    are staged host-side exactly like the Pallas path (``kernels/fft2d``).
    """
    ax0, ax1, steps = _require_square(plan, mesh, "complex cannon (fft2d)")

    def local(ar, ai, br, bi):
        skew_a, skew_b = _skew_perms(steps)
        ar = jax.lax.ppermute(ar, (ax0, ax1), skew_a)
        ai = jax.lax.ppermute(ai, (ax0, ax1), skew_a)
        br = jax.lax.ppermute(br, (ax0, ax1), skew_b)
        bi = jax.lax.ppermute(bi, (ax0, ax1), skew_b)

        def dot(a, b):
            return jnp.dot(a, b, preferred_element_type=jnp.float32)

        def body(step, carry):
            ar, ai, br, bi, accr, acci = carry
            # complex MAC on co-resident blocks (4-mult form)
            accr = accr + dot(ar, br) - dot(ai, bi)
            acci = acci + dot(ar, bi) + dot(ai, br)
            rot = _rot_perm(steps)
            ar = jax.lax.ppermute(ar, ax1, rot)
            ai = jax.lax.ppermute(ai, ax1, rot)
            br = jax.lax.ppermute(br, ax0, rot)
            bi = jax.lax.ppermute(bi, ax0, rot)
            return ar, ai, br, bi, accr, acci

        m = ar.shape[0]
        nn = br.shape[1]
        accr = jnp.zeros((m, nn), jnp.float32)
        acci = jnp.zeros((m, nn), jnp.float32)
        out = jax.lax.fori_loop(
            0, steps, body, (ar, ai, br, bi, accr, acci)
        )
        return out[4], out[5]

    spec = P(ax0, ax1)
    cfn = _shard_map(
        local,
        mesh=mesh,
        in_specs=(spec, spec, spec, spec),
        out_specs=(spec, spec),
        check=False,
    )

    def run(x_re, x_im):
        from .fft2d import dft_matrix

        r, c = x_re.shape
        _require_divisible("fft2d rows", r, steps, ax0)
        _require_divisible("fft2d cols", c, steps, ax1)
        fr_re, fr_im = dft_matrix(r)
        fc_re, fc_im = dft_matrix(c)
        y_re, y_im = cfn(fr_re, fr_im, x_re, x_im)    # stage 1: F_R @ X
        return cfn(y_re, y_im, fc_re, fc_im)          # stage 2: Y @ F_C

    return run


# ---------------------------------------------------------------------------
# Width-k halo exchange for star stencils (jacobi2d, jacobi2d_ms, 9-point)
# ---------------------------------------------------------------------------

def _star_of(plan: "ExecutionPlan") -> tuple[tuple[tuple[int, int], ...], int]:
    """(signed star offsets, radius) from the recurrence's access
    functions — the IR, not the kernel, declares the halo width."""
    star = stencil_star(plan.recurrence)
    if star is None:
        raise UnsupportedLoweringError(
            f"halo_stencil: recurrence {plan.recurrence.name!r} carries no "
            "multi-point read access — not a stencil")
    radius = 0
    for off in star:
        di, dj = off[0], off[1] if len(off) > 1 else 0
        if di and dj:
            raise UnsupportedLoweringError(
                "halo_stencil handles star stencils only (no diagonal "
                f"points / corner halos), got offset {off}")
        radius = max(radius, abs(di), abs(dj))
    return tuple((o[0], o[1] if len(o) > 1 else 0) for o in star), radius


def halo_fits(radius: int, interior: int, shards: int) -> bool:
    """Whether a one-hop halo exchange can serve ``shards`` tiles of an
    ``interior``-point axis at stencil ``radius``: each tile must be at
    least ``radius`` wide, or a halo would span a non-adjacent tile.
    Shared legality predicate between the chip-level ``halo_stencil``
    shards and the hierarchical outer row tiles (core/hierarchy.py)."""
    return shards > 0 and interior % shards == 0 and radius <= interior // shards


def halo_stencil(plan: "ExecutionPlan", mesh) -> Callable:
    """Width-k halo-exchange schedule over the plan's two space axes.

    The (h, w) interior is sharded (i->ax0, j->ax1); the four global
    boundary strips of the padded grid — now ``radius`` wide — ride along
    sharded on the matching single axis (replicated on the other).  Per
    sweep, each shard sends its ``radius``-wide edge strip one hop along
    the mesh — its bottom ``radius`` rows to the northern halo of the
    shard below, etc. — and shards on the array boundary substitute the
    fixed Dirichlet strip.  A *star* stencil (no diagonal points) needs no
    corner halos, so four one-hop strip ppermutes per sweep are the whole
    communication, whatever the radius: the recurrence's distance-k read
    deps within a sweep and, for jacobi2d_ms, the flow dep between sweeps.
    The radius and the per-point shifts come from the recurrence's access
    functions (``recurrence.stencil_star``/``halo_radius``) — radius 1
    reproduces the PR 4 jacobi2d schedule exactly, radius 2 serves the
    9-point star.
    """
    star, radius = _star_of(plan)
    ax0, ax1 = _space_axes(plan)
    n0, n1 = mesh.shape[ax0], mesh.shape[ax1]
    r = radius

    def local(x, wts, top, bot, lft, rgt):
        acc_t = runtime.acc_dtype(x.dtype)
        x = x.astype(acc_t)
        top, bot = top.astype(acc_t), bot.astype(acc_t)
        lft, rgt = lft.astype(acc_t), rgt.astype(acc_t)
        row = jax.lax.axis_index(ax0)
        col = jax.lax.axis_index(ax1)
        south_perm = [(q, q + 1) for q in range(n0 - 1)]  # edge strips S
        north_perm = [(q + 1, q) for q in range(n0 - 1)]  # edge strips N
        east_perm = [(q, q + 1) for q in range(n1 - 1)]   # edge strips E
        west_perm = [(q + 1, q) for q in range(n1 - 1)]   # edge strips W
        hl, wl = x.shape

        for t in range(wts.shape[0]):
            # neighbour strips: receive the adjacent shard's facing r-wide
            # edge; chips with no neighbour get zeros and substitute the
            # fixed global boundary strip instead (Dirichlet ring).
            halo_n = jax.lax.ppermute(x[-r:, :], ax0, south_perm)
            halo_s = jax.lax.ppermute(x[:r, :], ax0, north_perm)
            halo_w = jax.lax.ppermute(x[:, -r:], ax1, east_perm)
            halo_e = jax.lax.ppermute(x[:, :r], ax1, west_perm)
            halo_n = jnp.where(row == 0, top, halo_n)
            halo_s = jnp.where(row == n0 - 1, bot, halo_s)
            halo_w = jnp.where(col == 0, lft, halo_w)
            halo_e = jnp.where(col == n1 - 1, rgt, halo_e)
            # extended planes: vertical / horizontal shifts only (star)
            xv = jnp.concatenate([halo_n, x, halo_s], axis=0)
            xh = jnp.concatenate([halo_w, x, halo_e], axis=1)
            new = jnp.zeros_like(x)
            for s, (di, dj) in enumerate(star):
                w = wts[t, s].astype(acc_t)
                if di == 0 and dj == 0:
                    plane = x
                elif dj == 0:
                    plane = xv[r + di : r + di + hl, :]
                else:
                    plane = xh[:, r + dj : r + dj + wl]
                new = new + w * plane
            x = new
        return x

    fn = _shard_map(
        local,
        mesh=mesh,
        in_specs=(P(ax0, ax1), P(None, None), P(None, ax1), P(None, ax1),
                  P(ax0, None), P(ax0, None)),
        out_specs=P(ax0, ax1),
        check=False,
    )

    def run(grid, weights):
        h, w = grid.shape[0] - 2 * r, grid.shape[1] - 2 * r
        if h <= 0 or w <= 0:
            raise ValueError(
                f"stencil needs a grid of at least "
                f"{2 * r + 1}x{2 * r + 1} (got {grid.shape})")
        _require_divisible("stencil interior rows", h, n0, ax0)
        _require_divisible("stencil interior cols", w, n1, ax1)
        if r > h // n0 or r > w // n1:
            raise UnsupportedLoweringError(
                f"halo radius {r} exceeds the {h // n0}x{w // n1} shard — "
                "a one-hop exchange can only import the adjacent shard; "
                "use fewer chips or a larger grid")
        wts = weights if weights.ndim == 2 else weights[None, :]
        out = fn(grid[r:-r, r:-r], wts,
                 grid[:r, r:-r], grid[-r:, r:-r],
                 grid[r:-r, :r], grid[r:-r, -r:])
        return out.astype(runtime.out_dtype(grid.dtype))

    return run


# ---------------------------------------------------------------------------
# 1-D neighbour chains with shifted-window halo: conv2d and fir
# ---------------------------------------------------------------------------

def _chain(plan: "ExecutionPlan", mesh) -> tuple[tuple[str, ...], int]:
    """The linearized 1-D device chain over the plan's space axes: both
    mesh axes fold into one chain (row-major), so a rectangular mesh is
    fine and every chip joins the chain — no idle axis."""
    ax0, ax1 = _space_axes(plan)
    if ax1 == ax0:
        return (ax0,), mesh.shape[ax0]
    return (ax0, ax1), mesh.shape[ax0] * mesh.shape[ax1]


def _chain_index(axes: tuple[str, ...], mesh):
    idx = jax.lax.axis_index(axes[0])
    for ax in axes[1:]:
        idx = idx * mesh.shape[ax] + jax.lax.axis_index(ax)
    return idx


def _chain_recv_next(val, axes: tuple[str, ...], width: int):
    """Every chain member receives ``val`` from its right neighbour (one
    hop); the last member receives zeros (substituted by the caller)."""
    if width == 1:
        return jnp.zeros_like(val)
    return jax.lax.ppermute(
        val, axes if len(axes) > 1 else axes[0],
        [(i + 1, i) for i in range(width - 1)])


def chain_conv2d(plan: "ExecutionPlan", mesh) -> Callable:
    """1-D neighbour-chain conv2d with a shifted-window halo.

    The h output rows are sharded over the linearized chain (full image
    width stays local, so this is genuinely 1-D: one neighbour, one
    stream).  Each shard needs ``p-1`` rows beyond its slice to close its
    windows — exactly its right neighbour's *top* ``p-1`` rows, fetched
    with a single one-hop ppermute of the strip; the last shard in the
    chain substitutes the global input tail strip instead (the Dirichlet
    analogue).  Local compute is the shifted-window stack, widened on the
    shared acc_dtype ladder.
    """
    axes, width = _chain(plan, mesh)

    def local(x, tail, filt):
        p, q = filt.shape
        hl = x.shape[0]
        acc_t = runtime.acc_dtype(x.dtype)
        if p > 1:
            halo = _chain_recv_next(x[: p - 1, :], axes, width)
            idx = _chain_index(axes, mesh)
            halo = jnp.where(idx == width - 1, tail, halo)
            x = jnp.concatenate([x, halo], axis=0)
        x = x.astype(acc_t)
        f = filt.astype(acc_t)
        ow = x.shape[1] - q + 1
        out = jnp.zeros((hl, ow), acc_t)
        for pp in range(p):
            for qq in range(q):
                out = out + x[pp : pp + hl, qq : qq + ow] * f[pp, qq]
        return out

    fn = _shard_map(
        local,
        mesh=mesh,
        in_specs=(P(axes, None), P(None, None), P(None, None)),
        out_specs=P(axes, None),
        check=False,
    )

    def run(img, filt):
        p, q = filt.shape
        h = img.shape[0] - p + 1
        _require_divisible("conv2d output rows", h, width, "+".join(axes))
        if p - 1 > h // width:
            raise UnsupportedLoweringError(
                f"window height {p} exceeds the {h // width}-row shard — "
                "the width-(p-1) halo must come from the adjacent shard "
                "(one hop); use fewer chips or larger images")
        out = fn(img[:h], img[h:], filt)
        return out.astype(runtime.out_dtype(img.dtype))

    return run


def chain_fir(plan: "ExecutionPlan", mesh) -> Callable:
    """1-D neighbour-chain FIR: the n output samples are sharded over the
    linearized chain; each shard one-hop-receives the first ``taps-1``
    samples of its right neighbour (the shifted-window halo) and the last
    shard substitutes the global input tail."""
    axes, width = _chain(plan, mesh)

    def local(x, tail, taps):
        t = taps.shape[0]
        nl = x.shape[0]
        acc_t = runtime.acc_dtype(x.dtype)
        if t > 1:
            halo = _chain_recv_next(x[: t - 1], axes, width)
            idx = _chain_index(axes, mesh)
            halo = jnp.where(idx == width - 1, tail, halo)
            x = jnp.concatenate([x, halo])
        x = x.astype(acc_t)
        h = taps.astype(acc_t)
        out = jnp.zeros((nl,), acc_t)
        for i in range(t):
            out = out + x[i : i + nl] * h[i]
        return out

    fn = _shard_map(
        local,
        mesh=mesh,
        in_specs=(P(axes), P(None), P(None)),
        out_specs=P(axes),
        check=False,
    )

    def run(x, taps):
        t = taps.shape[0]
        n_out = x.shape[0] - t + 1
        _require_divisible("fir outputs", n_out, width, "+".join(axes))
        if t - 1 > n_out // width:
            raise UnsupportedLoweringError(
                f"tap count {t} exceeds the {n_out // width}-sample shard "
                "— the width-(t-1) halo must come from the adjacent shard "
                "(one hop); use fewer chips or longer signals")
        out = fn(x[:n_out], x[n_out:], taps)
        return out.astype(runtime.out_dtype(x.dtype))

    return run


# ---------------------------------------------------------------------------
# MTTKRP: 2-D ring over (i, j) with the factor matrices staged
# ---------------------------------------------------------------------------

def ring_mttkrp(plan: "ExecutionPlan", mesh) -> Callable:
    """2-D ring for M[i,j] += X[i,k,l] B[k,j] C[l,j].

    Cannon over the ``l`` contraction: X is sharded (i->ax0, l->ax1) and
    rotates west; the factor matrix C (l->ax0, j->ax1) co-rotates north so
    the matching l-block is always co-resident (same pre-skew as mm); the
    factor matrix B (k unsharded, j->ax1) is staged along the ring's rows
    — each column of chips holds its j-slice for the whole schedule.  One
    three-operand contraction per step, ``acc_dtype`` accumulation, output
    sharded (i->ax0, j->ax1).  The ``k`` contraction stays chip-local (it
    is a time loop of the plan).
    """
    ax0, ax1, steps = _require_square(plan, mesh, "mttkrp ring")

    def local(x_blk, b_blk, c_blk):
        skew_a, skew_b = _skew_perms(steps)
        x_blk = jax.lax.ppermute(x_blk, (ax0, ax1), skew_a)
        c_blk = jax.lax.ppermute(c_blk, (ax0, ax1), skew_b)

        acc_t = runtime.acc_dtype(x_blk.dtype)
        out_t = runtime.out_dtype(x_blk.dtype)

        def contract(x, b, c):
            if jnp.issubdtype(x.dtype, jnp.integer):
                x, b, c = (v.astype(jnp.int32) for v in (x, b, c))
            return jnp.einsum(
                "ikl,kj,lj->ij", x, b, c, preferred_element_type=acc_t)

        def body(step, carry):
            x, c, acc = carry
            acc = acc + contract(x, b_blk, c)
            x = jax.lax.ppermute(x, ax1, _rot_perm(steps))
            c = jax.lax.ppermute(c, ax0, _rot_perm(steps))
            return x, c, acc

        acc = jnp.zeros((x_blk.shape[0], c_blk.shape[1]), acc_t)
        x_blk, c_blk, acc = jax.lax.fori_loop(
            0, steps, body, (x_blk, c_blk, acc)
        )
        return acc.astype(out_t)

    fn = _shard_map(
        local,
        mesh=mesh,
        in_specs=(P(ax0, None, ax1), P(None, ax1), P(ax0, ax1)),
        out_specs=P(ax0, ax1),
        check=False,
    )

    def run(x, b, c):
        _require_divisible("mttkrp X rows (i)", x.shape[0], steps, ax0)
        _require_divisible("mttkrp X depth (l)", x.shape[2], steps, ax1)
        _require_divisible("mttkrp C rows (l)", c.shape[0], steps, ax0)
        _require_divisible("mttkrp B cols (j)", b.shape[1], steps, ax1)
        _require_divisible("mttkrp C cols (j)", c.shape[1], steps, ax1)
        return fn(x, b, c)

    return run


# ---------------------------------------------------------------------------
# Fused chains: one shard_map runs every chain stage back-to-back
# (KernelSpec.fused_systolic_lowering hooks — see core/fusion.py)
# ---------------------------------------------------------------------------

def fused_halo_chain(fused_plan, mesh) -> Callable:
    """Deep-halo schedule for stencil→stencil chains (conv2d → jacobi2d,
    jacobi2d → jacobi2d_9pt, ...).

    Every halo-family stage is a one-sided VALID window op, so the whole
    chain shrinks the grid by ``(s_h, s_w)`` — the sum of per-stage
    window shrinks.  The *final* output is sharded (ax0, ax1); each chip
    imports its east and south deep-halo strips with ONE ppermute per
    axis (width ``s_w`` / ``s_h`` — the strips the *whole chain* needs,
    not one stage), chips on the array boundary substitute the global
    tail strips, and every stage then runs chip-locally on the extended
    block in acc dtype.  The overlap region is *recomputed* by each chip
    instead of round-tripping the intermediate through HBM — the classic
    fusion trade, and the whole point: one exchange feeds all stages,
    zero intermediate materializations.
    """
    from repro.core import fusion

    ax0, ax1 = _space_axes(fused_plan.stage_plans[0])
    n0, n1 = mesh.shape[ax0], mesh.shape[ax1]
    descs = fusion.halo_stage_descs(fused_plan.chain)
    s_h, s_w = fusion.halo_shrink(fused_plan.chain)

    def local(x, bot, rgt, *wops):
        acc_t = runtime.acc_dtype(x.dtype)
        row = jax.lax.axis_index(ax0)
        col = jax.lax.axis_index(ax1)
        bh, bw = x.shape
        # east deep halo: the right neighbour's left s_w core columns;
        # the last column substitutes the global right strip.
        if s_w:
            if n1 > 1:
                he = jax.lax.ppermute(
                    x[:, :s_w], ax1, [(q + 1, q) for q in range(n1 - 1)])
            else:
                he = jnp.zeros((bh, s_w), x.dtype)
            rgt_blk = jax.lax.dynamic_slice(rgt, (row * bh, 0), (bh, s_w))
            he = jnp.where(col == n1 - 1, rgt_blk, he)
            xe = jnp.concatenate([x, he], axis=1)
        else:
            xe = x
        # south deep halo: the lower neighbour's top s_h rows of its
        # *extended* block (its east halo rides along, covering the
        # corner); the last row substitutes the global bottom strip.
        if s_h:
            if n0 > 1:
                hs = jax.lax.ppermute(
                    xe[:s_h, :], ax0, [(q + 1, q) for q in range(n0 - 1)])
            else:
                hs = jnp.zeros((s_h, xe.shape[1]), x.dtype)
            bot_blk = jax.lax.dynamic_slice(
                bot, (0, col * bw), (s_h, bw + s_w))
            hs = jnp.where(row == n0 - 1, bot_blk, hs)
            xx = jnp.concatenate([xe, hs], axis=0)
        else:
            xx = xe
        # run every stage chip-locally; the intermediate never leaves
        # the chip and stays in acc dtype between stages.
        cur = xx.astype(acc_t)
        for wi, desc in enumerate(descs):
            if desc[0] == "conv":
                p, q = desc[1]
                f = wops[wi].astype(acc_t)
                oh, ow = cur.shape[0] - p + 1, cur.shape[1] - q + 1
                nxt = jnp.zeros((oh, ow), acc_t)
                for pp in range(p):
                    for qq in range(q):
                        nxt = nxt + cur[pp:pp + oh, qq:qq + ow] * f[pp, qq]
            else:
                _, offs, (kh, kw) = desc
                wts = wops[wi]
                oh, ow = cur.shape[0] - kh + 1, cur.shape[1] - kw + 1
                nxt = jnp.zeros((oh, ow), acc_t)
                for s, (di, dj) in enumerate(offs):
                    nxt = nxt + wts[s].astype(acc_t) * \
                        cur[di:di + oh, dj:dj + ow]
            cur = nxt
        return cur

    wspecs = tuple(
        P(None, None) if desc[0] == "conv" else P(None) for desc in descs)
    fn = _shard_map(
        local,
        mesh=mesh,
        in_specs=(P(ax0, ax1), P(None, None), P(None, None), *wspecs),
        out_specs=P(ax0, ax1),
        check=False,
    )

    def run(*operands):
        stage_ops, _ = fusion.split_operands(fused_plan, operands)
        grid = stage_ops[0][0]
        wops = [*stage_ops[0][1:]]
        for ops in stage_ops[1:]:
            wops.extend(ops)
        hh, ww = grid.shape
        hf, wf = hh - s_h, ww - s_w
        _require_divisible("fused chain output rows", hf, n0, ax0)
        _require_divisible("fused chain output cols", wf, n1, ax1)
        if (n0 > 1 and s_h > hf // n0) or (n1 > 1 and s_w > wf // n1):
            raise UnsupportedLoweringError(
                f"fused deep halo {s_h}x{s_w} exceeds the "
                f"{hf // n0}x{wf // n1} shard — a one-hop exchange can "
                "only import the adjacent shard; use fewer chips or a "
                "larger grid")
        out = fn(grid[:hf, :wf], grid[hf:, :], grid[:hf, wf:], *wops)
        return out.astype(runtime.out_dtype(grid.dtype))

    return run


def fused_cannon_mm(fused_plan, mesh) -> Callable:
    """Back-to-back Cannon rings for dense→dense chains (the MLP
    up-projection → down-projection pair).

    Stage 1 is the standard ring; its accumulator lands UNSKEWED at
    (i, j) — exactly the (i→ax0, k→ax1) sharding the next stage's left
    operand needs, so C never leaves the chips: the interstage bias +
    activation applies shard-resident, then C re-skews straight into the
    next ring.  Later-stage weight operands arrive naturally sharded
    P(ax0, ax1); the interstage bias vector rides P(ax1).
    """
    from repro.core import fusion

    ax0, ax1, steps = _require_square(
        fused_plan.stage_plans[0], mesh, "fused cannon chain")
    inter = fused_plan.interstage
    n_bound = len(fused_plan.chain.stages) - 1

    def local(*blks):
        it = iter(blks)
        a, b = next(it), next(it)
        acc_t = runtime.acc_dtype(a.dtype)
        out_t = runtime.out_dtype(a.dtype)
        skew_a, skew_b = _skew_perms(steps)
        rot = _rot_perm(steps)

        def dot2d(x, y):
            if jnp.issubdtype(x.dtype, jnp.integer):
                x, y = x.astype(jnp.int32), y.astype(jnp.int32)
            return jnp.dot(x, y, preferred_element_type=acc_t)

        def ring(x, y):
            x = jax.lax.ppermute(x, (ax0, ax1), skew_a)
            y = jax.lax.ppermute(y, (ax0, ax1), skew_b)

            def body(step, carry):
                x, y, acc = carry
                acc = acc + dot2d(x, y)
                x = jax.lax.ppermute(x, ax1, rot)
                y = jax.lax.ppermute(y, ax0, rot)
                return x, y, acc

            acc = jnp.zeros((x.shape[0], y.shape[1]), acc_t)
            *_, acc = jax.lax.fori_loop(0, steps, body, (x, y, acc))
            return acc

        # same flush ladder as the unfused stages: int chains stay in
        # the (identical) int32 accumulator, so parity is bit-exact
        cur = ring(a, b).astype(out_t)
        for bnd in range(n_bound):
            bias = next(it) if fusion.interstage_has_bias(inter[bnd]) \
                else None
            cur = fusion.interstage_apply(inter[bnd], cur, bias)
            cur = ring(cur, next(it)).astype(out_t)
        return cur

    in_specs = [P(ax0, ax1), P(ax0, ax1)]
    for bnd in range(n_bound):
        if fusion.interstage_has_bias(inter[bnd]):
            in_specs.append(P(ax1))
        in_specs.append(P(ax0, ax1))
    fn = _shard_map(
        local,
        mesh=mesh,
        in_specs=tuple(in_specs),
        out_specs=P(ax0, ax1),
        check=False,
    )

    def run(*operands):
        stage_ops, _ = fusion.split_operands(fused_plan, operands)
        a, b = stage_ops[0]
        _require_divisible("fused cannon A rows", a.shape[0], steps, ax0)
        _require_divisible("fused cannon A cols", a.shape[1], steps, ax1)
        _require_divisible("fused cannon B cols", b.shape[1], steps, ax1)
        for ops in stage_ops[1:]:
            _require_divisible(
                "fused cannon stage cols", ops[0].shape[1], steps, ax1)
        return fn(*operands)

    return run


def fused_cannon_fft2d(fused_plan, mesh) -> Callable:
    """Both DFT stages of the 2-D FFT on ONE complex two-plane ring.

    The unfused chip path (``cannon_fft2d``) launches the ring twice and
    materializes Y = F_R @ X between the shard_map calls; here both
    stages run inside one shard_map, so (y_re, y_im) stay shard-resident
    — after ring 1 the Y block sits unskewed at (i, j), exactly the
    left-operand sharding ring 2 re-skews from.
    """
    ax0, ax1, steps = _require_square(
        fused_plan.stage_plans[0], mesh, "fused complex cannon (fft2d)")

    def local(fr_r, fr_i, x_r, x_i, fc_r, fc_i):
        skew_a, skew_b = _skew_perms(steps)
        rot = _rot_perm(steps)

        def dot(a, b):
            return jnp.dot(a, b, preferred_element_type=jnp.float32)

        def cring(ar, ai, br, bi):
            ar = jax.lax.ppermute(ar, (ax0, ax1), skew_a)
            ai = jax.lax.ppermute(ai, (ax0, ax1), skew_a)
            br = jax.lax.ppermute(br, (ax0, ax1), skew_b)
            bi = jax.lax.ppermute(bi, (ax0, ax1), skew_b)

            def body(step, carry):
                ar, ai, br, bi, accr, acci = carry
                accr = accr + dot(ar, br) - dot(ai, bi)
                acci = acci + dot(ar, bi) + dot(ai, br)
                ar = jax.lax.ppermute(ar, ax1, rot)
                ai = jax.lax.ppermute(ai, ax1, rot)
                br = jax.lax.ppermute(br, ax0, rot)
                bi = jax.lax.ppermute(bi, ax0, rot)
                return ar, ai, br, bi, accr, acci

            accr = jnp.zeros((ar.shape[0], br.shape[1]), jnp.float32)
            acci = jnp.zeros((ar.shape[0], br.shape[1]), jnp.float32)
            out = jax.lax.fori_loop(
                0, steps, body, (ar, ai, br, bi, accr, acci))
            return out[4], out[5]

        yr, yi = cring(fr_r, fr_i, x_r, x_i)   # stage 1: F_R @ X
        return cring(yr, yi, fc_r, fc_i)       # stage 2: Y @ F_C on-chip

    spec = P(ax0, ax1)
    fn = _shard_map(
        local,
        mesh=mesh,
        in_specs=(spec,) * 6,
        out_specs=(spec, spec),
        check=False,
    )

    def run(*operands):
        from .fft2d import dft_matrix

        x_re, x_im = operands[0], operands[1]
        r, c = x_re.shape
        _require_divisible("fused fft2d rows", r, steps, ax0)
        _require_divisible("fused fft2d cols", c, steps, ax1)
        fr_re, fr_im = dft_matrix(r)
        fc_re, fc_im = dft_matrix(c)
        return fn(fr_re, fr_im, x_re, x_im, fc_re, fc_im)

    return run


# ---------------------------------------------------------------------------
# GSPMD all-gather baselines (the "unconstrained compiler" references)
# ---------------------------------------------------------------------------

def allgather_mm(plan: "ExecutionPlan", mesh) -> Callable:
    """GSPMD-style baseline: all-gather the k-shards then one local dot.
    Used as the 'unconstrained compiler' reference in §Perf."""
    return _allgather_dot(plan, mesh, batched=False)


def allgather_bmm(plan: "ExecutionPlan", mesh) -> Callable:
    """Batched all-gather baseline (batch axis unsharded)."""
    return _allgather_dot(plan, mesh, batched=True)


def _allgather_dot(plan: "ExecutionPlan", mesh, batched: bool) -> Callable:
    ax0, ax1 = _space_axes(plan)
    lead = 1 if batched else 0

    def local(a_blk, b_blk):
        b_full = jax.lax.all_gather(b_blk, ax0, axis=lead, tiled=True)
        a_full = jax.lax.all_gather(a_blk, ax1, axis=lead + 1, tiled=True)
        if jnp.issubdtype(a_full.dtype, jnp.integer):
            a_full = a_full.astype(jnp.int32)
            b_full = b_full.astype(jnp.int32)
        return jnp.matmul(
            a_full, b_full,
            preferred_element_type=runtime.acc_dtype(a_blk.dtype),
        ).astype(runtime.out_dtype(a_blk.dtype))

    spec = P(None, ax0, ax1) if batched else P(ax0, ax1)
    return _shard_map(
        local,
        mesh=mesh,
        in_specs=(spec, spec),
        out_specs=spec,
        check=False,
    )


def allgather_stencil(plan: "ExecutionPlan", mesh) -> Callable:
    """Broadcast baseline for the star stencils: every chip receives the
    full grid (the broadcast-fabric strawman the paper's neighbour streams
    replace), runs all sweeps locally, and keeps only its own block.  The
    star (and so the pad width) comes from the recurrence's access
    functions, same as ``halo_stencil``."""
    from . import ref

    star, radius = _star_of(plan)
    padded = tuple((di + radius, dj + radius) for di, dj in star)
    ax0, ax1 = _space_axes(plan)
    n0, n1 = mesh.shape[ax0], mesh.shape[ax1]

    def local(grid, wts):
        # the generic star oracle IS the local program — every chip
        # computes all sweeps on the broadcast grid, then keeps its block
        full = ref.star2d_ms(grid, wts, padded)
        bh, bw = full.shape[0] // n0, full.shape[1] // n1
        row = jax.lax.axis_index(ax0)
        col = jax.lax.axis_index(ax1)
        return jax.lax.dynamic_slice(full, (row * bh, col * bw), (bh, bw))

    fn = _shard_map(
        local,
        mesh=mesh,
        in_specs=(P(None, None), P(None, None)),
        out_specs=P(ax0, ax1),
        check=False,
    )

    def run(grid, weights):
        h, w = grid.shape[0] - 2 * radius, grid.shape[1] - 2 * radius
        _require_divisible("stencil interior rows", h, n0, ax0)
        _require_divisible("stencil interior cols", w, n1, ax1)
        wts = weights if weights.ndim == 2 else weights[None, :]
        return fn(grid, wts).astype(runtime.out_dtype(grid.dtype))

    return run


# PR 4 name for the 5-point schedules; the machinery is now width-generic.
allgather_jacobi2d = allgather_stencil
halo_jacobi2d = halo_stencil


def _allgather_chain(plan: "ExecutionPlan", mesh, reference, out_ndim,
                     out_len) -> Callable:
    """Shared broadcast baseline for the 1-D chains: every chip receives
    the full operands, runs the reference oracle, keeps its own slice of
    the leading output axis."""
    axes, width = _chain(plan, mesh)

    def local(a, b):
        full = reference(a, b)
        bl = full.shape[0] // width
        idx = _chain_index(axes, mesh)
        start = (idx * bl,) + (0,) * (out_ndim - 1)
        return jax.lax.dynamic_slice(full, start, (bl,) + full.shape[1:])

    fn = _shard_map(
        local,
        mesh=mesh,
        in_specs=(P(), P()),
        out_specs=P(axes, None) if out_ndim == 2 else P(axes),
        check=False,
    )

    def run(a, b):
        _require_divisible("chain outputs", out_len(a, b), width,
                           "+".join(axes))
        return fn(a, b)

    return run


def allgather_conv2d(plan: "ExecutionPlan", mesh) -> Callable:
    """Broadcast baseline for the conv2d chain: full image everywhere,
    local reference conv, keep own row block."""
    from . import ref

    return _allgather_chain(
        plan, mesh, ref.conv2d, 2,
        lambda img, filt: img.shape[0] - filt.shape[0] + 1)


def allgather_fir(plan: "ExecutionPlan", mesh) -> Callable:
    """Broadcast baseline for the FIR chain: full signal everywhere,
    local reference FIR, keep own sample block."""
    from . import ref

    return _allgather_chain(
        plan, mesh, ref.fir, 1,
        lambda x, taps: x.shape[0] - taps.shape[0] + 1)


def allgather_mttkrp(plan: "ExecutionPlan", mesh) -> Callable:
    """All-gather baseline for mttkrp: gather X's l-shards (ax1) and C's
    l-shards (ax0), then one local three-operand contraction."""
    ax0, ax1 = _space_axes(plan)

    def local(x_blk, b_blk, c_blk):
        x_full = jax.lax.all_gather(x_blk, ax1, axis=2, tiled=True)
        c_full = jax.lax.all_gather(c_blk, ax0, axis=0, tiled=True)
        if jnp.issubdtype(x_full.dtype, jnp.integer):
            x_full, b_blk, c_full = (
                v.astype(jnp.int32) for v in (x_full, b_blk, c_full))
        return jnp.einsum(
            "ikl,kj,lj->ij", x_full, b_blk, c_full,
            preferred_element_type=runtime.acc_dtype(x_blk.dtype),
        ).astype(runtime.out_dtype(x_blk.dtype))

    fn = _shard_map(
        local,
        mesh=mesh,
        in_specs=(P(ax0, None, ax1), P(None, ax1), P(ax0, ax1)),
        out_specs=P(ax0, ax1),
        check=False,
    )

    def run(x, b, c):
        n0, n1 = mesh.shape[ax0], mesh.shape[ax1]
        _require_divisible("mttkrp X rows (i)", x.shape[0], n0, ax0)
        _require_divisible("mttkrp X depth (l)", x.shape[2], n1, ax1)
        _require_divisible("mttkrp C rows (l)", c.shape[0], n0, ax0)
        _require_divisible("mttkrp B cols (j)", b.shape[1], n1, ax1)
        return fn(x, b, c)

    return run


def allgather_fft2d(plan: "ExecutionPlan", mesh) -> Callable:
    """Broadcast baseline for fft2d: both real planes everywhere, local
    reference FFT, keep own (row, col) block of each plane."""
    from . import ref

    ax0, ax1 = _space_axes(plan)
    n0, n1 = mesh.shape[ax0], mesh.shape[ax1]

    def local(xr, xi):
        zr, zi = ref.fft2d(xr, xi)
        bh, bw = zr.shape[0] // n0, zr.shape[1] // n1
        row = jax.lax.axis_index(ax0)
        col = jax.lax.axis_index(ax1)
        sl = lambda z: jax.lax.dynamic_slice(  # noqa: E731
            z, (row * bh, col * bw), (bh, bw))
        return sl(zr), sl(zi)

    fn = _shard_map(
        local,
        mesh=mesh,
        in_specs=(P(None, None), P(None, None)),
        out_specs=(P(ax0, ax1), P(ax0, ax1)),
        check=False,
    )

    def run(x_re, x_im):
        _require_divisible("fft2d rows", x_re.shape[0], n0, ax0)
        _require_divisible("fft2d cols", x_re.shape[1], n1, ax1)
        return fn(x_re, x_im)

    return run
