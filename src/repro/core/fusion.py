"""Cross-recurrence fusion: chip-resident producer→consumer chains.

Every registered recurrence lowers as an island: the producer flushes its
output through HBM, the consumer replans from scratch and reads it back.
WideSA's utilization argument (and Brown's Versal advection chains, and
EA4RCA's communication avoidance) says the win is *removing that round
trip*: when two stages' space mappings are compatible, one fused schedule
can serve both from a single halo exchange / a single Cannon pre-skew,
with the intermediate staying shard-resident in the accumulator dtype.

This module is the fusion pass:

  * ``RecurrenceChain`` — the chain IR: an ordered producer→consumer
    tuple of registered ``UniformRecurrence``s.  Stage ``i+1``'s leading
    operand(s) are stage ``i``'s output(s); the chain's operand contract
    drops them (``chain_operands``).
  * ``fuse(chain, target)`` — the legality pass.  Checks, in order:
    every stage registered; at least two stages; no stage carries a
    *flow* dependence (a flow-carried loop must stay host-sequential —
    fusing across it would serialize the whole chain, so jacobi2d_ms
    never fuses); each consumer's ``KernelSpec.fusable_with`` names its
    producer; one dtype across the chain; the consumer's read footprint
    of the producer's output is exactly the producer's output domain
    (shape compatibility — for the stencil family the consumer's padded
    grid, derived from ``stencil_star``/``halo_radius``, must equal the
    producer's output); and the target mesh can carry the fused schedule
    (divisibility, the deep halo fits inside one shard, a square ring
    for the Cannon family).  Illegal chains raise ``FusionError`` with a
    machine-checkable ``reason``; ``try_fuse`` returns None instead so
    callers fall back to unfused per-stage plans.
  * ``FusedPlan`` — what a legal chain plans to: the per-stage modelled
    ``ExecutionPlan``s plus the chain-level backend decision.  Backends:
    ``fused_systolic`` (one shard_map running all stages back-to-back —
    the consumer spec's ``fused_systolic_lowering`` hook), ``xla`` /
    ``pallas`` (the single-launch jitted composition of the per-stage
    lowerings: still fused in the no-HBM-round-trip sense — XLA fuses
    the intermediate away — but without the shared exchange).
  * ``lower_fused(plan, backend, mesh)`` — the codegen dispatch target
    (``core/codegen.lower_plan`` forwards fused plans here).

Three fused schedule families (``kernels/systolic.py``):

  halo    conv2d → jacobi2d / jacobi2d_9pt and stencil→stencil pairs:
          ONE deep halo exchange (east + south strips, width = the sum
          of every stage's window shrink) feeds all stages; each chip
          recomputes the overlap region instead of round-tripping the
          intermediate (the classic fusion trade).
  cannon  mm → mm (the transformer MLP up→down pair): one pre-skew
          serves two back-to-back rings; C never leaves the chips, and
          the interstage bias+activation applies shard-resident.
  fft     fft2d_stage → fft2d_stage: both DFT stages of one 2-D FFT in
          a single shard_map (the unfused chip path launches two and
          materializes Y between them).

Autotune integration: chain table keys read ``name1+name2|dtype|
extents1+extents2|meshRxC`` (``autotune.autotune_key`` duck-types on
``.stages``); ``autotune.race`` times the fused backends against the
composition and the winner persists like any other entry.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import TYPE_CHECKING, Callable

from .mapper import ExecutionPlan, Target, best_plan as _stage_best_plan
from .partition import DTYPE_BYTES
from .recurrence import UniformRecurrence, halo_radius, stencil_star

if TYPE_CHECKING:  # pragma: no cover - typing only
    pass

#: Fused execution backends a chain entry may record.  ``xla``/``pallas``
#: are the single-launch compositions of the per-stage lowerings;
#: ``fused_systolic`` is the one-shard_map chip schedule.
FUSED_BACKENDS = ("fused_systolic", "xla", "pallas")

#: Interstage elementwise ops a boundary may apply to the shard-resident
#: intermediate (the MLP pair needs ``bias_silu``/``bias_gelu``).  A
#: ``bias``-prefixed op adds one extra (vector) chain operand after the
#: producer stage's operands.
INTERSTAGE_OPS = (None, "relu", "silu", "gelu",
                  "bias", "bias_relu", "bias_silu", "bias_gelu")

_STENCIL_NAMES = frozenset({"jacobi2d", "jacobi2d_9pt"})
_HALO_NAMES = _STENCIL_NAMES | {"conv2d"}


class FusionError(ValueError):
    """A chain failed the fusion legality pass.  ``reason`` is a stable
    machine-checkable tag: unregistered | length | flow | unfusable-pair
    | dtype-mismatch | shape-mismatch | family | mesh-mismatch |
    halo-exceeds-shard | infeasible | interstage."""

    def __init__(self, reason: str, message: str):
        super().__init__(f"[{reason}] {message}")
        self.reason = reason


@dataclasses.dataclass(frozen=True)
class RecurrenceChain:
    """Producer→consumer list of uniform recurrences (the chain IR).

    Stage ``i``'s output feeds stage ``i+1``'s leading operand(s); how
    many leading operands the intermediate covers is the producer spec's
    ``n_outputs`` (1 everywhere except the two-plane fft stage).
    """

    stages: tuple[UniformRecurrence, ...]

    @property
    def name(self) -> str:
        return "+".join(s.name for s in self.stages)

    @property
    def dtype(self) -> str:
        return self.stages[0].dtype

    def with_dtype(self, dtype: str) -> "RecurrenceChain":
        """The chain's executable dtype twin (see autotune.EXEC_DTYPE);
        dtype is structurally inert in the IR, exactly like the
        single-recurrence replace() the autotuner already does."""
        return RecurrenceChain(tuple(
            dataclasses.replace(s, dtype=dtype) for s in self.stages))


def chain(*stages: UniformRecurrence) -> RecurrenceChain:
    return RecurrenceChain(tuple(stages))


@dataclasses.dataclass(frozen=True)
class FusedPlan:
    """A legal chain's plan: per-stage modelled plans + the chain-level
    backend decision (``autotune.apply_policy`` restamps ``backend`` /
    ``provenance`` from the crossover table like any ExecutionPlan)."""

    chain: RecurrenceChain
    stage_plans: tuple[ExecutionPlan, ...]
    target: Target
    family: str                        # "halo" | "cannon" | "fft"
    interstage: tuple[str | None, ...]  # one op per stage boundary
    systolic_ok: bool                  # target mesh carries the fused ring
    predicted_bytes_saved: int         # HBM bytes the fusion removes
    backend: str = "xla"
    provenance: str = "modelled"

    @property
    def recurrence(self) -> RecurrenceChain:
        """Duck-type parity with ExecutionPlan (autotune keying)."""
        return self.chain

    @property
    def feasible(self) -> bool:
        return all(p.feasible for p in self.stage_plans)

    def describe(self) -> str:
        return (
            f"[fused {self.chain.name}/{self.chain.dtype}] "
            f"family={self.family} stages={len(self.stage_plans)} "
            f"bytes_saved={self.predicted_bytes_saved} "
            f"backend={self.backend}[{self.provenance}]"
        )


# ---------------------------------------------------------------------------
# per-family shape algebra
# ---------------------------------------------------------------------------

def _io_shape(rec: UniformRecurrence) -> tuple[tuple[int, ...],
                                               tuple[int, ...]]:
    """(input-operand shape, output shape) of one stage, from the IR."""
    if rec.name == "conv2d":
        h, w, p, q = (rec.extent(l) for l in ("h", "w", "p", "q"))
        return (h + p - 1, w + q - 1), (h, w)
    if rec.name in _STENCIL_NAMES:
        r = halo_radius(rec, ("i", "j"))
        h, w = rec.extent("i"), rec.extent("j")
        return (h + 2 * r, w + 2 * r), (h, w)
    if rec.name == "mm":
        m, n, k = (rec.extent(l) for l in ("i", "j", "k"))
        return (m, k), (m, n)
    if rec.name == "fft2d_stage":
        r, c = rec.extent("i"), rec.extent("j")
        return (r, c), (r, c)
    raise FusionError(
        "family", f"no fused shape algebra for recurrence {rec.name!r}")


def chain_family(ch: RecurrenceChain) -> str:
    names = [s.name for s in ch.stages]
    if all(n in _HALO_NAMES for n in names):
        return "halo"
    if all(n == "mm" for n in names):
        return "cannon"
    if all(n == "fft2d_stage" for n in names):
        return "fft"
    raise FusionError(
        "family",
        f"chain {'+'.join(names)} mixes fusion families (halo: "
        f"{sorted(_HALO_NAMES)}; cannon: mm; fft: fft2d_stage)")


def halo_stage_descs(ch: RecurrenceChain) -> tuple[tuple, ...]:
    """Per-stage window descriptors for the deep-halo schedule:
    ``("conv", (p, q))`` or ``("star", padded_offsets, (kh, kw))`` — the
    star geometry recovered from the IR access functions
    (``stencil_star``), re-padded into the one-sided window frame."""
    descs = []
    for rec in ch.stages:
        if rec.name == "conv2d":
            descs.append(("conv", (rec.extent("p"), rec.extent("q"))))
        else:
            r = halo_radius(rec, ("i", "j"))
            star = stencil_star(rec)
            if star is None:  # pragma: no cover - stencil specs carry one
                raise FusionError(
                    "family", f"{rec.name}: no star in the IR accesses")
            padded = tuple(
                (off[0] + r, (off[1] if len(off) > 1 else 0) + r)
                for off in star)
            descs.append(("star", padded, (2 * r + 1, 2 * r + 1)))
    return tuple(descs)


def halo_shrink(ch: RecurrenceChain) -> tuple[int, int]:
    """Total (rows, cols) a halo chain consumes beyond its final output —
    the deep-halo width one exchange must import."""
    s_h = s_w = 0
    for desc in halo_stage_descs(ch):
        kh, kw = desc[1] if desc[0] == "conv" else desc[2]
        s_h += kh - 1
        s_w += kw - 1
    return s_h, s_w


# ---------------------------------------------------------------------------
# the legality pass
# ---------------------------------------------------------------------------

def _check_mesh(ch: RecurrenceChain, family: str,
                mesh_shape: tuple[int, ...]) -> bool:
    """Mesh-level legality.  Raises FusionError when the fused schedule
    cannot run on this mesh at all; returns whether the one-shard_map
    ``fused_systolic`` backend is available (a degenerate 1-wide axis
    still permits the single-launch composition for the Cannon family,
    just not the ring)."""
    n0, n1 = (mesh_shape + (1, 1))[:2]
    if family == "halo":
        out_h, out_w = _io_shape(ch.stages[-1])[1]
        if out_h % n0 or out_w % n1:
            raise FusionError(
                "mesh-mismatch",
                f"fused output {out_h}x{out_w} does not shard over the "
                f"{n0}x{n1} mesh (both extents must divide the axis "
                "widths)")
        s_h, s_w = halo_shrink(ch)
        bh, bw = out_h // n0, out_w // n1
        if (n0 > 1 and s_h > bh) or (n1 > 1 and s_w > bw):
            raise FusionError(
                "halo-exceeds-shard",
                f"deep halo {s_h}x{s_w} exceeds the {bh}x{bw} shard — a "
                "one-hop exchange can only import the adjacent shard; "
                "use fewer chips or a larger grid")
        return True
    # cannon / fft: the fused ring needs a square space mesh; a
    # degenerate (1, k)/(k, 1) mesh has no 2-D ring but still runs the
    # single-launch composition (the serving facade's 1x8 chip).
    if n0 != n1:
        if n0 > 1 and n1 > 1:
            raise FusionError(
                "mesh-mismatch",
                f"fused {family} ring needs a square space mesh, got "
                f"{n0}x{n1} — the shared pre-skew/rotation sequence only "
                "closes on a square array")
        return False
    if n0 > 1:
        for rec in ch.stages:
            for loop in ("i", "j", "k"):
                if rec.extent(loop) % n0:
                    raise FusionError(
                        "mesh-mismatch",
                        f"{rec.name} extent {loop}={rec.extent(loop)} "
                        f"does not divide the {n0}-wide ring")
    return True


def _bytes_saved(ch: RecurrenceChain, family: str) -> int:
    """Predicted HBM bytes fusion removes vs standalone launches: one
    write + one read of every intermediate (acc-dtype elements; the fft
    family's complex intermediate rides as two real planes)."""
    from repro.kernels import runtime

    total = 0
    planes = 2 if family == "fft" else 1
    for rec in ch.stages[:-1]:
        out_shape = _io_shape(rec)[1]
        exec_dtype = "float32" if family == "fft" else rec.dtype
        acc = str(runtime.out_dtype(exec_dtype))
        per_el = DTYPE_BYTES.get(acc, 4)
        total += 2 * planes * per_el * math.prod(out_shape)
    return total


def fuse(ch: RecurrenceChain, target: Target = Target(),
         interstage: tuple[str | None, ...] | None = None) -> FusedPlan:
    """The fusion pass: legality checks (module docstring) then a
    ``FusedPlan`` carrying the per-stage modelled plans.  Raises
    ``FusionError`` (typed ``reason``) on any illegal chain."""
    from repro.kernels import registry

    if len(ch.stages) < 2:
        raise FusionError(
            "length", f"a chain needs >= 2 stages, got {len(ch.stages)}")
    specs = []
    for rec in ch.stages:
        try:
            specs.append(registry.get(rec.name))
        except registry.UnregisteredRecurrenceError as e:
            raise FusionError("unregistered", str(e)) from e
    for rec in ch.stages:
        flows = [d for d in rec.dependences() if d.kind == "flow"]
        if flows:
            raise FusionError(
                "flow",
                f"stage {rec.name} carries a flow dependence "
                f"({flows[0].array} along {flows[0].distance}) — the "
                "carried loop must stay host-sequential, so the stage "
                "cannot join a fused space mapping")
    for prod, cons_spec in zip(ch.stages[:-1], specs[1:]):
        if prod.name not in cons_spec.fusable_with:
            raise FusionError(
                "unfusable-pair",
                f"{cons_spec.name} does not declare {prod.name!r} in "
                f"fusable_with={cons_spec.fusable_with!r} (spec-author "
                "contract: docs/fusion.md)")
    dtypes = {s.dtype for s in ch.stages}
    if len(dtypes) > 1:
        raise FusionError(
            "dtype-mismatch",
            f"stages disagree on dtype: {sorted(dtypes)} — the "
            "shard-resident intermediate has one acc dtype")
    family = chain_family(ch)
    for prod, cons in zip(ch.stages[:-1], ch.stages[1:]):
        out_shape = _io_shape(prod)[1]
        in_shape = _io_shape(cons)[0]
        if out_shape != in_shape:
            raise FusionError(
                "shape-mismatch",
                f"{prod.name} output {out_shape} != {cons.name} read "
                f"footprint {in_shape} — the consumer must cover exactly "
                "the producer's output domain")
    n_bound = len(ch.stages) - 1
    inter = tuple(interstage) if interstage is not None else (
        (None,) * n_bound)
    if len(inter) != n_bound:
        raise FusionError(
            "interstage",
            f"{len(inter)} interstage ops for {n_bound} boundaries")
    for op in inter:
        if op not in INTERSTAGE_OPS:
            raise FusionError(
                "interstage", f"unknown interstage op {op!r} "
                f"(supported: {INTERSTAGE_OPS})")
        if op is not None and family != "cannon":
            raise FusionError(
                "interstage",
                f"interstage op {op!r} is only supported on the cannon "
                "(dense) family")
    systolic_ok = _check_mesh(ch, family, tuple(target.mesh_shape))
    try:
        stage_plans = tuple(
            _stage_best_plan(rec, target) for rec in ch.stages)
    except RuntimeError as e:
        raise FusionError("infeasible", str(e)) from e
    return FusedPlan(
        chain=ch,
        stage_plans=stage_plans,
        target=target,
        family=family,
        interstage=inter,
        systolic_ok=systolic_ok,
        predicted_bytes_saved=_bytes_saved(ch, family),
    )


def try_fuse(ch: RecurrenceChain, target: Target = Target(),
             interstage: tuple[str | None, ...] | None = None
             ) -> FusedPlan | None:
    """``fuse`` with the fallback contract: None on any illegal chain —
    the caller plans the stages unfused."""
    try:
        return fuse(ch, target, interstage=interstage)
    except FusionError:
        return None


def chain_from_request(kind: str, shapes, dtype: str) -> RecurrenceChain:
    """Build the chain a ``PlanRequest(kind="a+b", shape=((...), (...)))``
    names — the autotune.resolve entry point for chains."""
    from repro.kernels import registry

    names = kind.split("+")
    if len(names) != len(shapes):
        raise FusionError(
            "length",
            f"chain kind {kind!r} has {len(names)} stages but "
            f"{len(shapes)} shape tuples")
    stages = []
    for nm, args in zip(names, shapes):
        try:
            stages.append(registry.get(nm).builder(*tuple(args), dtype))
        except registry.UnregisteredRecurrenceError as e:
            raise FusionError("unregistered", str(e)) from e
    return RecurrenceChain(tuple(stages))


# ---------------------------------------------------------------------------
# operand contract
# ---------------------------------------------------------------------------

def interstage_has_bias(op: str | None) -> bool:
    return op is not None and op.startswith("bias")


def interstage_apply(op: str | None, mid, bias=None):
    """Apply one boundary's elementwise op to the intermediate (used
    identically by the fused schedules and the unfused composition, so
    the two stay comparable)."""
    if op is None:
        return mid
    import jax

    parts = op.split("_")
    if parts[0] == "bias":
        mid = mid + bias
        parts = parts[1:]
    if parts:
        mid = {"relu": jax.nn.relu, "silu": jax.nn.silu,
               "gelu": jax.nn.gelu}[parts[0]](mid)
    return mid


def operand_counts(ch: RecurrenceChain,
                   interstage: tuple[str | None, ...]) -> tuple[int, ...]:
    """Chain operand layout: stage 0 contributes its full spec arity;
    each boundary contributes one bias vector when its interstage op is
    bias-prefixed; each later stage contributes its arity minus the
    producer's ``n_outputs`` (the intermediate stays on-chain)."""
    from repro.kernels import registry

    specs = [registry.get(s.name) for s in ch.stages]
    counts = [specs[0].arity]
    for b, spec in enumerate(specs[1:]):
        counts.append(1 if interstage_has_bias(interstage[b]) else 0)
        counts.append(spec.arity - specs[b].n_outputs)
    return tuple(counts)


def split_operands(plan: FusedPlan, operands) -> tuple[list, list]:
    """(per-stage operand tuples, per-boundary bias-or-None) from the
    flat chain operand list."""
    counts = operand_counts(plan.chain, plan.interstage)
    n = sum(counts)
    if len(operands) != n:
        raise ValueError(
            f"fused chain {plan.chain.name} expects {n} operands "
            f"(layout {counts}), got {len(operands)}")
    it = iter(operands)
    stage_ops = [tuple(next(it) for _ in range(counts[0]))]
    biases = []
    for b in range(len(plan.chain.stages) - 1):
        n_bias, n_fresh = counts[1 + 2 * b], counts[2 + 2 * b]
        biases.append(next(it) if n_bias else None)
        stage_ops.append(tuple(next(it) for _ in range(n_fresh)))
    return stage_ops, biases


def chain_operands(ch: RecurrenceChain, rng,
                   interstage: tuple[str | None, ...] | None = None
                   ) -> tuple:
    """Sample operands matching the chain contract (tests / benches /
    autotune races all draw from here, mirroring ``KernelSpec.operands``)."""
    from repro.kernels import registry

    inter = tuple(interstage) if interstage is not None else (
        (None,) * (len(ch.stages) - 1))
    specs = [registry.get(s.name) for s in ch.stages]
    ops: list = list(specs[0].operands(ch.stages[0], rng))
    for b, (rec, spec) in enumerate(zip(ch.stages[1:], specs[1:])):
        if interstage_has_bias(inter[b]):
            n_cols = _io_shape(ch.stages[b])[1][-1]
            ops.append(registry._draw(rng, (n_cols,), ch.dtype))
        ops.extend(spec.operands(rec, rng)[specs[b].n_outputs:])
    return tuple(ops)


# ---------------------------------------------------------------------------
# lowering (codegen dispatch target)
# ---------------------------------------------------------------------------

def fused_available_backends(plan: FusedPlan) -> tuple[str, ...]:
    """Fused backends this process can execute for the plan's target:
    the compositions always; the one-shard_map schedule when the mesh is
    ring-legal *and* the host exposes enough devices."""
    avail = ["xla", "pallas"]
    if plan.systolic_ok:
        import jax

        try:
            n_dev = jax.local_device_count()
        except RuntimeError:  # pragma: no cover - no backend at all
            n_dev = 1
        if (n_dev >= math.prod(plan.target.mesh_shape)
                and len(plan.target.mesh_shape) >= 2):
            avail.insert(0, "fused_systolic")
    return tuple(avail)


def _composed(plan: FusedPlan, stage_fn: Callable[[int], Callable]
              ) -> Callable:
    """Single-launch composition of the per-stage lowerings: one jitted
    program, the intermediate never materializes to HBM between stages.
    The fft family is special-cased — its registered lowerings compute
    the *whole* 2-D FFT (both DFT stages), so the composition is one
    call, not two."""
    if plan.family == "fft":
        fn0 = stage_fn(0)

        def run_fft(*operands):
            stage_ops, _ = split_operands(plan, operands)
            return fn0(*stage_ops[0])

        return run_fft

    def run(*operands):
        stage_ops, biases = split_operands(plan, operands)
        cur = stage_fn(0)(*stage_ops[0])
        for b in range(len(plan.chain.stages) - 1):
            cur = interstage_apply(plan.interstage[b], cur, biases[b])
            cur = stage_fn(b + 1)(cur, *stage_ops[b + 1])
        return cur

    return run


def reference_chain(plan: FusedPlan) -> Callable:
    """The unfused oracle: per-stage XLA reference lowerings composed
    stage-wise (identical intermediate dtypes to standalone launches, so
    int chains compare bit-exact against every fused backend)."""
    from repro.kernels import registry

    specs = [registry.get(s.name) for s in plan.chain.stages]
    return _composed(plan, lambda i: specs[i].xla)


def lower_fused(plan: FusedPlan, backend: str | None = None, mesh=None,
                interpret: bool | None = None) -> Callable:
    """Executable for a fused plan.  ``fused_systolic`` dispatches the
    *consumer* spec's ``fused_systolic_lowering`` hook (one shard_map
    for the whole chain); ``xla``/``pallas`` build the single-launch
    composition."""
    from repro.kernels import registry

    backend = backend or plan.backend
    if backend == "systolic":  # codegen's chip-backend name maps through
        backend = "fused_systolic"
    if backend == "xla":
        return reference_chain(plan)
    if backend == "pallas":
        from repro.kernels import runtime

        return _composed(plan, lambda i: functools.partial(
            runtime.execute_plan, plan.stage_plans[i],
            interpret=interpret))
    if backend == "fused_systolic":
        if mesh is None:
            raise ValueError(
                "fused_systolic needs a concrete mesh (pass mesh=)")
        if not plan.systolic_ok:
            raise FusionError(
                "mesh-mismatch",
                f"plan for {plan.chain.name} was fused for the "
                "composition backends only (no ring on this mesh)")
        spec = registry.get(plan.chain.stages[-1].name)
        hook = spec.fused_systolic_lowering
        if hook is None:
            from .codegen import UnsupportedLoweringError

            raise UnsupportedLoweringError(
                f"fused_systolic: consumer spec {spec.name!r} registers "
                "no fused_systolic_lowering hook — see docs/fusion.md")
        return hook(plan, mesh)
    raise ValueError(f"unknown fused backend {backend!r}")
