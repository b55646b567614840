"""Distributed-optimization collectives.

    quantized_psum       — int8 gradient all-reduce with stochastic rounding
                           (4x wire bytes vs fp32, 2x vs bf16)
    ring_allgather_matmul— collective matmul: all-gather decomposed into a
                           ppermute ring so each hop's chunk multiplies
                           while the next hop is in flight (the WideSA
                           neighbour-stream schedule for TP matmuls)
    moe_ep_alltoall      — expert-parallel MoE dispatch via all_to_all
                           (sequence-sharded tokens -> expert shards),
                           the §Perf alternative to the TP-MoE psum path
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.compat import shard_map as _shard_map


# ---------------------------------------------------------------------------
# collective wire-byte models (used by the hierarchical outer cost model)
# ---------------------------------------------------------------------------

def ring_allgather_bytes(shard_bytes: int, group: int) -> int:
    """Total wire bytes for a ring all-gather of ``group`` shards of
    ``shard_bytes`` each: every shard transits ``group - 1`` hops."""
    if group <= 1:
        return 0
    return int(group) * (int(group) - 1) * int(shard_bytes)


def ring_allreduce_bytes(payload_bytes: int, group: int) -> int:
    """Total wire bytes for a ring all-reduce of one ``payload_bytes``
    buffer over ``group`` ranks: reduce-scatter + all-gather, each moving
    ``(group - 1) / group`` of the payload per rank — ``2 * (group - 1) *
    payload`` in total (the standard 2(p-1)/p identity summed over p)."""
    if group <= 1:
        return 0
    return 2 * (int(group) - 1) * int(payload_bytes)


def halo_exchange_bytes(strip_bytes: int, boundaries: int) -> int:
    """Total wire bytes for a halo exchange across ``boundaries`` internal
    tile boundaries: each boundary carries one ``strip_bytes`` strip in
    each direction."""
    if boundaries <= 0:
        return 0
    return 2 * int(strip_bytes) * int(boundaries)


# ---------------------------------------------------------------------------
# int8 quantized all-reduce (stochastic rounding)
# ---------------------------------------------------------------------------

def quantized_psum(x: jax.Array, axis: str, key: jax.Array) -> jax.Array:
    """All-reduce with int8 payload.

    Per-tensor max-abs scale (one extra scalar psum-max), stochastic
    rounding so E[dequant] == x, int32 accumulation to avoid overflow at
    up to 2^23 participants.
    """
    xf = x.astype(jnp.float32)
    amax = jax.lax.pmax(jnp.max(jnp.abs(xf)), axis)
    scale = jnp.maximum(amax, 1e-30) / 127.0
    scaled = xf / scale
    noise = jax.random.uniform(key, x.shape, jnp.float32, -0.5, 0.5)
    q = jnp.clip(jnp.round(scaled + noise), -127, 127).astype(jnp.int8)
    total = jax.lax.psum(q.astype(jnp.int32), axis)
    return total.astype(jnp.float32) * scale


# ---------------------------------------------------------------------------
# collective (ring) matmul
# ---------------------------------------------------------------------------

def ring_reduce_scatter_matmul(x_loc: jax.Array, w_loc: jax.Array,
                               axis: str, axis_size: int) -> jax.Array:
    """Streamed TP matmul:  y = X @ W  with X column-sharded [m, k_loc] and
    W row-sharded [k_loc, n] over the contraction axis.

    The local partial  P_i = x_loc @ w_loc  would normally be combined by
    one big all-reduce; here the reduction is a ppermute ring over row
    chunks of P so every hop's transfer overlaps the next chunk's MXU work
    (the paper's neighbour-DMA stream schedule applied to the TP
    reduction).  Returns the *reduce-scattered* result: shard i holds row
    chunk i of y, shape [m / axis_size, n] — i.e. sequence-sharded output,
    which the transformer consumes directly in SP layouts.
    """
    idx = jax.lax.axis_index(axis)
    n_sh = axis_size
    perm = [(i, (i + 1) % n_sh) for i in range(n_sh)]
    # Accumulate in the plan's acc dtype (int -> int32, float -> fp32),
    # not the input dtype: int8 partials overflow past 2^24 in fp32 MACs
    # and bf16 ring hops flush every chunk-add to 8 mantissa bits.  The
    # ring sums below then stay in acc precision end to end.
    acc_t = (jnp.int32 if jnp.issubdtype(x_loc.dtype, jnp.integer)
             else jnp.float32)
    p_loc = jnp.dot(x_loc, w_loc, preferred_element_type=acc_t)
    m = p_loc.shape[0]
    assert m % n_sh == 0, (m, n_sh)
    m_loc = m // n_sh

    def chunk(c):
        return jax.lax.dynamic_slice_in_dim(p_loc, c * m_loc, m_loc, 0)

    acc = chunk((idx + 1) % n_sh)

    def body(s, acc):
        acc = jax.lax.ppermute(acc, axis, perm)
        c = (idx + 1 - s) % n_sh
        return acc + chunk(c)

    acc = jax.lax.fori_loop(1, n_sh, body, acc)
    # shard i now holds fully-reduced chunk (i+2) % n_sh; realign so shard
    # i holds chunk i
    realign = [(i, (i + 2) % n_sh) for i in range(n_sh)]
    if n_sh > 1:
        acc = jax.lax.ppermute(acc, axis, realign)
    return acc


# ---------------------------------------------------------------------------
# EP all-to-all MoE (hillclimb path)
# ---------------------------------------------------------------------------

def moe_ep_alltoall(cfg, p, x, ctx):
    """Expert-parallel MoE: sequence-sharded dispatch + all_to_all.

    x: [B, S, d] logical.  Inside shard_map tokens are sharded over BOTH
    the batch axes and the expert axis (sequence split), so the dispatch
    buffer is 1/ep the size of the TP-MoE path and the collective is two
    all_to_alls of the *dispatched* tokens instead of a psum of ALL tokens
    — the congestion-model win the paper's PLIO assignment corresponds to.
    The exchange is capacity-sized (ceil(T·k/E · cf) slots per expert,
    drop-on-overflow), and the mesh holds all of the router's experts.
    """
    from repro.models.moe import _dispatch_indices, _expert_ffn, route

    mesh = ctx.mesh
    exp_axis = ctx.rules.get("experts", "model")
    batch_axis = ctx.rules.get("batch", "data")
    ep = mesh.shape[exp_axis]
    e, k = cfg.moe_num_experts, cfg.moe_top_k
    e_loc = e // ep

    def local_fn(x_loc, router, wg, wu, wd):
        b_loc, s_loc, d = x_loc.shape
        t_loc = b_loc * s_loc
        xf = x_loc.reshape(t_loc, d)
        cap = max(1, int(math.ceil(
            t_loc * k * cfg.moe_capacity_factor / e)))
        logits = xf.astype(jnp.float32) @ router
        weights, ids, probs = route(cfg, logits)
        from repro.models.moe import load_balance_loss
        aux = load_balance_loss(cfg, probs, ids)
        order, slot, keep, token = _dispatch_indices(cfg, ids, cap)
        w_flat = weights.reshape(-1)[order]

        buf = jnp.zeros((e * cap, d), xf.dtype)
        buf = buf.at[slot].add(
            jnp.where(keep[:, None], xf[token], 0).astype(xf.dtype))
        # [E, cap, d] -> a2a -> [E_loc, ep*cap, d]
        buf = buf.reshape(e, cap, d)
        buf = jax.lax.all_to_all(
            buf, exp_axis, split_axis=0, concat_axis=1, tiled=True)
        out = _expert_ffn(cfg, wg, wu, wd, buf)
        out = jax.lax.all_to_all(
            out, exp_axis, split_axis=1, concat_axis=0, tiled=True)
        out = out.reshape(e * cap, d)

        contrib = out[slot] * w_flat[:, None].astype(xf.dtype) \
            * keep[:, None].astype(xf.dtype)
        y = jnp.zeros((t_loc, d), xf.dtype).at[token].add(contrib)
        aux = jax.lax.pmean(aux, exp_axis)
        return y.reshape(b_loc, s_loc, d), aux

    fn = _shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(
            P(batch_axis, exp_axis, None),  # sequence-sharded tokens
            P(None, None),
            P(exp_axis, None, None),
            P(exp_axis, None, None),
            P(exp_axis, None, None),
        ),
        out_specs=(P(batch_axis, exp_axis, None), P()),
        check=False,
    )
    return fn(x, p["router"], p["wg"], p["wu"], p["wd"])
