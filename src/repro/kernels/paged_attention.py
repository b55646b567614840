"""Paged decode attention: one Pallas kernel that reads each lane's K/V
blocks in place from the stacked block pools, up to the lane's position,
and joins the current token's row to the same softmax.

The pools are ``[L, NB, bs, Hkv*hd]``: one row holds every kv head of
one token (``transformer.init_paged_pools`` says why), and they stay in
HBM as they lie.  The layer, the flattened block tables and each lane's
row count arrive by scalar prefetch.  The grid runs over lanes; inside a
lane a loop walks chunks of up to ``P`` blocks, and each block of a
chunk that holds rows of the lane is fetched by its own DMA, the table
giving its page, into one of two VMEM buffers while the other chunk is
computed.  The next chunk's DMAs, of this lane or of the next lane that
has rows, start before the current chunk is waited on.  A block at or
past the lane's row count is never fetched, and a lane with no pooled
rows (position 0, or inactive) costs neither a DMA nor a loop step.

The DMAs are started and waited on by loops whose trip count is the
number of blocks to move, not by one traced copy per block: the decode
program traces the kernel in every process's set-up, compile cache or
not, and loops keep that trace small whatever the chunk size.

Heads need no per-head slicing: ``q`` enters block-diagonal, as
``q_bd[Hq, Hkv*hd]`` with query head ``j`` in the ``hd`` columns of its
kv head ``j // G`` and zeros elsewhere, so scores are one
``q_bd @ K_chunk^T`` and values one ``P @ V_chunk``, of which each head
keeps its own ``hd`` columns (outside the kernel).

The softmax is online, with the running max, sum and accumulator in
float32; the matmuls take their operands in the compute dtype (that of
``q``), pool rows upcast to it as the masked path upcasts them.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import runtime

#: Score of a row that is not attended to.  Finite, so that the running
#: max of a lane with no pooled rows stays a number.
MASKED = -1e30
#: Target bytes of one chunk of one pool in VMEM (two slots per pool).
CHUNK_BYTES = 512 * 1024
#: Width of the running max and sum: lane-dense rows of the vreg.
STAT_LANES = 128
#: Pool dtypes the kernel reads: the serving cache dtypes (fp8 rows are
#: upcast in VMEM).
POOL_DTYPES = tuple(jnp.dtype(d) for d in (
    jnp.bfloat16, jnp.float32, jnp.float8_e4m3fn))


def engages(pool, cfg) -> bool:
    """Whether the kernel serves the decode attention of ``cfg`` over
    ``pool`` ([L, NB, bs, row]), decided from what they show: a GQA
    model's K/V pool of 2-D rows of all kv heads (not MLA's latent
    pools), a dtype the kernel reads, rows of whole 128-lane tiles and
    blocks of whole sublane tiles.  Anything else keeps the masked
    path."""
    if cfg.use_mla or pool.ndim != 4:
        return False
    dtype = jnp.dtype(pool.dtype)
    bs, row = pool.shape[-2:]
    return (dtype in POOL_DTYPES and row == cfg.n_kv_heads * cfg.hd
            and row % runtime.MXU_LANES == 0
            and bs % runtime.sublanes(dtype) == 0)


def _chunk_blocks(pool, blocks_per_lane: int) -> int:
    """Blocks per chunk: about ``CHUNK_BYTES`` of one pool, at most a
    lane's whole table."""
    bs, row = pool.shape[-2:]
    rows = CHUNK_BYTES // (row * jnp.dtype(pool.dtype).itemsize)
    return max(1, min(blocks_per_lane, rows // bs))


def _kernel(layer_ref, tables_ref, rows_ref, q_ref, kn_ref, vn_ref, k_hbm,
            v_hbm, o_ref, kbuf, vbuf, sems, slot_ref, m_ref, l_ref, acc_ref,
            *, lanes, blocks_per_lane, chunk, bs, scale):
    b = pl.program_id(0)
    layer = layer_ref[0]
    bk = chunk * bs

    def next_lane(start):
        """The first lane at or after ``start`` with pooled rows, or
        ``lanes``."""
        return jax.lax.while_loop(
            lambda i: jnp.logical_and(
                i < lanes, rows_ref[jnp.minimum(i, lanes - 1)] == 0),
            lambda i: i + 1, start)

    def dma(lane, c, slot, op):
        """Start (or wait for) the K and V copies of every block of chunk
        ``c`` of ``lane`` that holds rows of the lane."""
        first = lane * blocks_per_lane + c * chunk
        n = jnp.clip(pl.cdiv(rows_ref[lane], bs) - c * chunk, 0, chunk)

        def one(i, carry):
            page = tables_ref[first + i]
            rows = pl.ds(pl.multiple_of(i * bs, bs), bs)
            for j, (pool, buf) in enumerate(((k_hbm, kbuf), (v_hbm, vbuf))):
                cp = pltpu.make_async_copy(pool.at[layer, page],
                                           buf.at[slot, rows], sems.at[j, slot])
                getattr(cp, op)()
            return carry

        jax.lax.fori_loop(0, n, one, 0)

    @pl.when(b == 0)
    def _first():
        first = next_lane(0)
        slot_ref[0] = 0

        @pl.when(first < lanes)
        def _():
            dma(first, 0, 0, "start")

    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, MASKED)
    l_ref[...] = jnp.zeros_like(l_ref)
    n = rows_ref[b]
    q = q_ref[0]
    dt = q.dtype
    precision = jax.lax.Precision.HIGHEST if dt == jnp.float32 else None

    after = next_lane(b + 1)

    def body(c, slot):
        last = (c + 1) * bk >= n
        nxt = jnp.where(last, after, b)

        @pl.when(nxt < lanes)
        def _():
            dma(nxt, jnp.where(last, 0, c + 1), 1 - slot, "start")

        dma(b, c, slot, "wait")
        s = jax.lax.dot_general(
            q, kbuf[slot].astype(dt), (((1,), (1,)), ((), ())),
            precision=precision, preferred_element_type=jnp.float32) * scale
        row = c * bk + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
        s = jnp.where(row < n, s, MASKED)
        # rows past the count may hold stale or never-written VMEM
        col = c * bk + jax.lax.broadcasted_iota(jnp.int32, (bk, 1), 0)
        v = jnp.where(col < n, vbuf[slot].astype(dt), 0)
        m_prev, l_prev = m_ref[...], l_ref[...]
        m_next = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_next)
        p = jnp.exp(s - m_next[:, :1])
        m_ref[...] = m_next
        l_ref[...] = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha[:, :1] + jax.lax.dot_general(
            p.astype(dt), v, (((1,), (0,)), ((), ())),
            precision=precision, preferred_element_type=jnp.float32)
        return 1 - slot

    slot_ref[0] = jax.lax.fori_loop(0, pl.cdiv(n, bk), body, slot_ref[0])

    # the current token's row, not in the pool yet, is the key at ``pos``:
    # q_bd is zero outside each head's own columns, so a row-wise sum is
    # each head's score against it
    s_new = jnp.sum(q.astype(jnp.float32) * kn_ref[0].astype(jnp.float32),
                    axis=1, keepdims=True) * scale
    m_prev = m_ref[...][:, :1]
    m_all = jnp.maximum(m_prev, s_new)
    alpha, p_new = jnp.exp(m_prev - m_all), jnp.exp(s_new - m_all)
    acc = acc_ref[...] * alpha + p_new * vn_ref[0].astype(jnp.float32)
    l_all = l_ref[...][:, :1] * alpha + p_new
    o_ref[0] = (acc / l_all).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def paged_attention(q_bd, k_new, v_new, pool_k, pool_v, layer, tables, rows,
                    *, scale, interpret=None):
    """Decode attention of one layer: each lane's pooled rows and its new
    row.

    q_bd [B, Hq, R] block-diagonal queries (R = Hkv*hd) in the compute
    dtype; k_new, v_new [B, R] the current token's K/V rows as stored
    (the pool dtype) or in the compute dtype; pool_k, pool_v [L, NB, bs,
    R]; layer int32 scalar; tables [B, T] int32; rows [B] int32: lane
    ``b`` attends to its pooled rows ``0 .. rows[b]-1`` (0: none) and
    then to its new row.  Returns [B, Hq, R] in the compute dtype: the
    softmax-weighted values, of which head ``j`` keeps the ``hd`` columns
    of its kv head.
    """
    lanes, hq, width = q_bd.shape
    bs = pool_k.shape[2]
    blocks_per_lane = tables.shape[1]
    chunk = _chunk_blocks(pool_k, blocks_per_lane)
    dt = q_bd.dtype
    per_lane = lambda b, *_: (b, 0, 0)  # noqa: E731
    kernel = functools.partial(
        _kernel, lanes=lanes, blocks_per_lane=blocks_per_lane, chunk=chunk,
        bs=bs, scale=scale)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(lanes,),
            in_specs=[
                pl.BlockSpec((1, hq, width), per_lane),
                pl.BlockSpec((1, 1, width), per_lane),
                pl.BlockSpec((1, 1, width), per_lane),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((1, hq, width), per_lane),
            scratch_shapes=[
                pltpu.VMEM((2, chunk * bs, width), pool_k.dtype),
                pltpu.VMEM((2, chunk * bs, width), pool_v.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.SMEM((1,), jnp.int32),
                pltpu.VMEM((hq, STAT_LANES), jnp.float32),
                pltpu.VMEM((hq, STAT_LANES), jnp.float32),
                pltpu.VMEM((hq, width), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct((lanes, hq, width), dt),
        name="paged_attention",
        interpret=runtime.resolve_interpret(interpret),
        # a DMA started in one lane's step is waited on in a later one
        compiler_params=runtime.compiler_params(
            dimension_semantics=("arbitrary",)),
    )(jnp.reshape(layer, (1,)).astype(jnp.int32),
      tables.reshape(-1).astype(jnp.int32), rows.astype(jnp.int32),
      q_bd, k_new.reshape(lanes, 1, width).astype(dt),
      v_new.reshape(lanes, 1, width).astype(dt), pool_k, pool_v)
