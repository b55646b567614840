"""Mixture-of-Experts block: top-k routing, held experts, shared experts.

Routing: softmax over the router's experts -> top-k -> renormalised
where ``cfg.moe_norm_topk`` (DeepSeek-V2-Lite leaves the top-k weights
as they are).  An auxiliary load-balance loss (Switch-style) is
returned alongside.

A layer may hold only some of the router's experts: one chip's share of
an expert-parallel deployment (``cfg.moe_router_experts`` wider than
``cfg.moe_num_experts``).  It routes over all of them and computes the
part of the result its own experts give; what the others would add is
left out.

Four execution paths, same routing math:

  * single device — dropless: every (token, held expert) assignment is
    computed, by a grouped matmul over the held experts
    (``jax.lax.ragged_dot``) on the assignments sorted by expert.  Rows
    marked invalid (prefill pad rows, inactive decode lanes) get no
    assignment, so they neither cost expert work nor change a real row.
  * in-place decode — single device, in the paged decode scan: the held
    experts' matmuls read the scanned layer's weights in place from the
    stacked ``[L_moe, n, K, N]`` parameters (``kernels.held_experts``),
    every held expert against all the step's tokens, each token weighted
    by its top-k weight of the expert (an exact 0 where it did not pick
    it, or is not a real row).  It engages where the caller hands over
    the stacks and the layer's index (``held_at``) and
    ``inplace_stacks`` accepts them: no mesh, widths of whole 128-lane
    tiles, at most ``held_experts.MAX_ROWS`` tokens.  The ragged path
    would need each layer's slice of the stacks copied out first, since
    ``ragged_dot`` fuses no operand.  Prefill, training and the mesh
    paths keep ``ragged_dot``.
  * "TP-MoE" — experts sharded over the 'model' axis, tokens replicated
    across it; every shard computes its local experts' part, dropless as
    above, and a psum combines.  Collective cost = one all-reduce of
    activations per block, identical in shape to a dense-FFN TP
    all-reduce.
  * "EP a2a" — sequence-sharded dispatch with all_to_all to expert shards
    (see parallel/collectives.py); enabled per-config, used by the §Perf
    hillclimb to cut collective bytes (the WideSA congestion model picks
    the axis).  Its exchange is capacity-sized, ceil(T·k/E · cf) slots
    per expert with drop-on-overflow (GShard-style, ``_dispatch_indices``).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.kernels import held_experts as HE
from repro.kernels.planned import planned_bmm, planned_dense
from repro.parallel.sharding import constrain
from .layers import dense_init, _dtype


def init_moe(key, cfg):
    """The router over all ``cfg.moe_router_width`` experts; weights for
    the ``cfg.moe_num_experts`` held ones."""
    d, e, ff = cfg.d_model, cfg.moe_num_experts, cfg.moe_d_ff
    ks = jax.random.split(key, 5)
    dt = _dtype(cfg)
    p = {
        "router": dense_init(ks[0], d, cfg.moe_router_width, jnp.float32,
                             scale=0.02),
        "wg": (jax.random.normal(ks[1], (e, d, ff), jnp.float32)
               / math.sqrt(d)).astype(dt),
        "wu": (jax.random.normal(ks[2], (e, d, ff), jnp.float32)
               / math.sqrt(d)).astype(dt),
        "wd": (jax.random.normal(ks[3], (e, ff, d), jnp.float32)
               / math.sqrt(ff)).astype(dt),
    }
    if cfg.moe_shared_experts:
        sf = cfg.moe_shared_experts * cfg.moe_d_ff
        p["shared_wg"] = dense_init(ks[4], d, sf, dt)
        p["shared_wu"] = dense_init(
            jax.random.fold_in(ks[4], 1), d, sf, dt)
        p["shared_wd"] = dense_init(
            jax.random.fold_in(ks[4], 2), sf, d, dt,
            scale=1.0 / math.sqrt(sf))
    return p


def moe_specs(cfg):
    s = {
        "router": ("d_model", None),
        "wg": ("experts", "d_model", None),
        "wu": ("experts", "d_model", None),
        "wd": ("experts", None, "d_model"),
    }
    if cfg.moe_shared_experts:
        s |= {
            "shared_wg": ("d_model", "ff"),
            "shared_wu": ("d_model", "ff"),
            "shared_wd": ("ff", "d_model"),
        }
    return s


def route(cfg, logits):
    """softmax -> top-k -> renormalise (``cfg.moe_norm_topk``).
    logits: [T, E_router] (fp32)."""
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    weights, ids = jax.lax.top_k(probs, cfg.moe_top_k)  # [T, k]
    if cfg.moe_norm_topk:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    return weights, ids, probs


def load_balance_loss(cfg, probs, ids):
    """Switch-style aux loss: E * sum_e f_e * P_e over the router's E.

    probs: [..., E]; ids: [..., k] — leading axes are flattened.
    """
    e = cfg.moe_router_width
    one_hot = jax.nn.one_hot(ids.reshape(-1), e, dtype=jnp.float32)
    counts = jnp.sum(one_hot, axis=0)
    f = counts / jnp.maximum(jnp.sum(counts), 1.0)
    p_mean = jnp.mean(probs.reshape(-1, e), axis=0)
    return e * jnp.sum(f * p_mean)


def _dispatch_indices(cfg, ids, capacity):
    """Sort-based dispatch: assignment -> (expert_slot, keep, token).

    ids: [T, k].  Returns flat arrays over T*k assignments.
    """
    t, k = ids.shape
    ids_flat = ids.reshape(-1)  # assignment a = t*k + j
    order = jnp.argsort(ids_flat)  # stable: groups by expert
    sorted_experts = ids_flat[order]
    # rank within expert group
    first_idx = jnp.searchsorted(
        sorted_experts, sorted_experts, side="left"
    )
    rank = jnp.arange(t * k) - first_idx
    keep = rank < capacity
    slot = sorted_experts * capacity + jnp.minimum(rank, capacity - 1)
    token = order // k
    return order, slot, keep, token


def _expert_ffn(cfg, wg, wu, wd, x):
    """x: [E(_loc), C, d] -> [E(_loc), C, d] — the expert-stack bmm."""
    act = jax.nn.silu if cfg.act == "silu" else jax.nn.gelu
    h = act(planned_bmm(x, wg, site="moe.gate")) * planned_bmm(
        x, wu, site="moe.up")
    return planned_bmm(h, wd, site="moe.down")


def _held(ids, first, count, valid):
    """[T, k] bool: the assignments to the held experts [first,
    first + count) of the rows ``valid`` marks (all rows where None)."""
    held = (ids >= first) & (ids < first + count)
    if valid is not None:
        held = held & valid[:, None]
    return held


def _grouped_ffn(cfg, p, x_flat, weights, ids, first, valid):
    """Dropless FFN of the held experts p["w*"] [n, ...], which are the
    router's experts [first, first + n): every held assignment of a valid
    row is computed once, by ``ragged_dot`` over the assignments sorted
    by expert.  Returns y [T, d] (the held experts' weighted sum) and the
    number of assignments computed (int32)."""
    t, d = x_flat.shape
    k = ids.shape[1]
    n = p["wg"].shape[0]
    held = _held(ids, first, n, valid)
    # most held assignments any routing gives: each row picks k distinct
    # experts, at most n of them held.  The grouped matmul's time follows
    # its groups' total, not these rows (TPU v5e: [6144, 2048] rows against
    # 8 experts of 2048 x 1408 take 0.41 ms with 768 rows in the groups,
    # 0.78 ms with all of them)
    rows = t * min(k, n)
    with jax.named_scope("moe.dispatch"):
        group = jnp.where(held, ids - first, n).reshape(-1)  # n: not held
        order = jnp.argsort(group, stable=True)[:rows]
        sizes = jnp.zeros((n + 1,), jnp.int32).at[group].add(1)[:n]
        token = order // k
        xs = x_flat[token]
    act = jax.nn.silu if cfg.act == "silu" else jax.nn.gelu
    with jax.named_scope("moe.experts"):
        gate = jax.lax.ragged_dot(xs, p["wg"], sizes,
                                  preferred_element_type=jnp.float32)
        up = jax.lax.ragged_dot(xs, p["wu"], sizes,
                                preferred_element_type=jnp.float32)
        h = (act(gate) * up).astype(x_flat.dtype)
        out = jax.lax.ragged_dot(h, p["wd"], sizes,
                                 preferred_element_type=jnp.float32)
    with jax.named_scope("moe.combine"):
        keep = held.reshape(-1)[order]
        w = weights.reshape(-1)[order]
        contrib = jnp.where(keep[:, None], out * w[:, None], 0.0)
        y = jnp.zeros((t, d), jnp.float32).at[token].add(
            contrib).astype(x_flat.dtype)
    return y, jnp.sum(sizes)


def inplace_stacks(stacked, rows: int):
    """The held-expert stacks {"wg", "wu", "wd"} ``[L_moe, n, ...]`` of
    a layer stack's MoE params ``stacked`` for the in-place decode path
    on ``rows`` tokens, or None where that path does not engage (under
    a mesh, or where ``held_experts.engages`` refuses them)."""
    from repro.parallel.sharding import current_mesh

    ctx = current_mesh()
    if ctx is not None and ctx.mesh is not None:
        return None
    stacks = {k: stacked[k] for k in ("wg", "wu", "wd")}
    return stacks if HE.engages(*stacks.values(), rows) else None


def _inplace_ffn(cfg, stacks, layer, x_flat, weights, ids, first, valid):
    """The held experts' FFN of layer ``layer`` of ``stacks`` (see
    ``inplace_stacks``), read in place: every held expert against every
    row, weighted by ``w[T, n]``, the row's top-k weight of the expert
    where the row is valid and picked it and 0 elsewhere.  Returns what
    ``_grouped_ffn`` returns."""
    n = stacks["wg"].shape[1]
    held = _held(ids, first, n, valid)
    with jax.named_scope("moe.dispatch"):
        # ids of a row are distinct: each held expert gets one weight
        w = jnp.sum(jnp.where(held, weights, 0.0)[..., None]
                    * jax.nn.one_hot(ids - first, n, dtype=jnp.float32),
                    axis=1)
    with jax.named_scope("moe.experts"):
        y = HE.held_experts_ffn(x_flat, stacks["wg"], stacks["wu"],
                                stacks["wd"], layer, w, act=cfg.act)
    return y, jnp.sum(held, dtype=jnp.int32)


def moe_ffn_tokens(cfg, p, x_flat, *, local_experts=None, valid=None,
                   held_at=None):
    """Route + dropless held-expert FFN + combine for a flat token batch.

    x_flat: [T, d]; valid: [T] bool or None (every row real).
    ``local_experts``: (start, count) when p's expert stacks are an
    expert shard (TP-MoE path; each shard computes its own experts'
    part, later psum'd).  Otherwise p holds the router's experts [0,
    cfg.moe_num_experts).  ``held_at``: (stacks, layer), the held
    experts' stacks ``inplace_stacks`` gave and this layer's index into
    them, for the in-place path; p then needs no expert weights.
    Returns (y_flat, aux_loss, held): ``held`` counts the (token, held
    expert) assignments computed.
    """
    logits = planned_dense(
        x_flat.astype(jnp.float32), p["router"], site="moe.router")
    weights, ids, probs = route(cfg, logits)
    aux = load_balance_loss(cfg, probs[None], ids[None])
    first = 0 if local_experts is None else local_experts[0]
    if held_at is not None:
        y, held = _inplace_ffn(cfg, *held_at, x_flat, weights, ids, first,
                               valid)
    else:
        y, held = _grouped_ffn(cfg, p, x_flat, weights, ids, first, valid)
    return y, aux, held


def _moe_shard_map(p, cfg, x, ctx, valid):
    """Explicit TP-MoE: tokens replicated over the expert ('model') axis,
    each shard computes its local experts, psum combines.  Dispatch
    scatters stay device-local (deterministic memory — a GSPMD scatter
    over the expert buffer would replicate it)."""
    from jax.sharding import PartitionSpec as P

    mesh = ctx.mesh
    exp_axis = ctx.rules.get("experts", "model")
    batch_axis = ctx.rules.get("batch", "data")
    n_exp_shards = (
        mesh.shape[exp_axis] if exp_axis in mesh.shape else 1
    )
    e = cfg.moe_num_experts
    e_loc = e // n_exp_shards

    def local_fn(x_loc, v_loc, router, wg, wu, wd):
        b_loc, s, d = x_loc.shape
        shard = jax.lax.axis_index(exp_axis)
        pp = {"router": router, "wg": wg, "wu": wu, "wd": wd}
        y, aux, _ = moe_ffn_tokens(
            cfg, pp, x_loc.reshape(b_loc * s, d),
            local_experts=(shard * e_loc, e_loc),
            valid=v_loc.reshape(b_loc * s),
        )
        y = jax.lax.psum(y, exp_axis)
        aux = jax.lax.pmean(aux, exp_axis)
        return y.reshape(b_loc, s, d), aux

    from repro.compat import shard_map as _shard_map

    fn = _shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(
            P(batch_axis, None, None),
            P(batch_axis, None),
            P(None, None),
            P(exp_axis, None, None),
            P(exp_axis, None, None),
            P(exp_axis, None, None),
        ),
        out_specs=(P(batch_axis, None, None), P()),
        check=False,
    )
    if valid is None:
        valid = jnp.ones(x.shape[:2], bool)
    return fn(x, valid, p["router"], p["wg"], p["wu"], p["wd"])


def apply_moe(p, cfg, x, *, valid=None, held_at=None):
    """MoE forward: x [B,S,d] -> ([B,S,d], aux loss, held).

    ``valid`` [B,S] bool marks the real rows (None: all); the others get
    no expert assignment.  Under a mesh the TP-MoE shard_map path runs
    (experts sharded over the 'model' axis, one activation psum per
    block); on a single device the dropless held-expert path runs, in
    place from the stacks where ``held_at`` is given (see
    ``moe_ffn_tokens``), and ``held`` counts the (token, held expert)
    assignments it computed (None under a mesh).  The EP all-to-all
    variant lives in parallel/collectives.py and is switched in by the
    hillclimb configs (it ignores ``valid``).
    """
    from repro.parallel.sharding import current_mesh

    b, s, d = x.shape
    ctx = current_mesh()
    held = None
    if ctx is not None and ctx.mesh is not None and cfg.moe_ep:
        from repro.parallel.collectives import moe_ep_alltoall
        y, aux = moe_ep_alltoall(cfg, p, x, ctx)
    elif ctx is not None and ctx.mesh is not None:
        y, aux = _moe_shard_map(p, cfg, x, ctx, valid)
    else:
        y, aux, held = moe_ffn_tokens(
            cfg, p, x.reshape(b * s, d),
            valid=None if valid is None else valid.reshape(b * s),
            held_at=held_at)
        y = y.reshape(b, s, d)
    if cfg.moe_shared_experts:
        act = jax.nn.silu if cfg.act == "silu" else jax.nn.gelu
        h = act(planned_dense(x, p["shared_wg"], site="moe.shared_gate")) * \
            planned_dense(x, p["shared_wu"], site="moe.shared_up")
        h = constrain(h, "batch", None, "ff")
        y = y + planned_dense(h, p["shared_wd"], site="moe.shared_down")
    return y, aux, held
