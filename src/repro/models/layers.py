"""Shared transformer layers: norms, rotary, GQA attention, GLU MLP.

Pure-function style: ``init_*`` returns a params dict (+ a parallel tree of
logical sharding axes from ``*_specs``), ``apply`` functions are pure.  All
matmuls are the paper's MM recurrence: projection/MLP GEMMs go through
``kernels.planned.planned_dense`` and the attention score/value
contractions through ``planned_bmm``, so every dense/attention/decode GEMM
executes on mapper-planned tiles (with an XLA fallback for shapes the
mapper rejects and a ``planned.configure(enabled=False)`` escape hatch).
Chip-level sharding still comes from parallel.sharding rules.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.kernels import paged_attention as PA
from repro.kernels.planned import (planned_bmm, planned_dense,
                                   planned_mlp_pair)
from repro.parallel.sharding import constrain


def _dtype(cfg):
    return jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32


# ---------------------------------------------------------------------------
# init helpers
# ---------------------------------------------------------------------------

def dense_init(key, d_in, d_out, dtype, scale=None):
    scale = scale if scale is not None else 1.0 / math.sqrt(d_in)
    return (jax.random.normal(key, (d_in, d_out), jnp.float32) * scale).astype(
        dtype
    )


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------

def rmsnorm(x, w, eps):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps)).astype(x.dtype) * w


def layernorm(x, w, b, eps):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    return ((xf - mu) * jax.lax.rsqrt(var + eps)).astype(x.dtype) * w + b


# ---------------------------------------------------------------------------
# rotary embedding
# ---------------------------------------------------------------------------

def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's magnitude factor ``0.1 * mscale * ln(factor) + 1`` (1 where
    the factor does not stretch)."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def rope_freqs(hd: int, cfg):
    """Inverse frequencies [hd/2] of base ``cfg.rope_theta``.  With YaRN
    (``cfg.rope_scaling_factor``) they ramp from the base frequencies
    (dims below ``beta_fast``'s correction dim) to the base over the
    factor (dims above ``beta_slow``'s), linearly in between, the
    correction dims taken over the original positions (DeepSeek-V2's
    ``DeepseekV2YarnRotaryEmbedding``)."""
    theta = cfg.rope_theta
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    factor = cfg.rope_scaling_factor
    if not factor:
        return inv

    def corr_dim(rotations):
        return hd * math.log(cfg.rope_original_positions / (
            rotations * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(corr_dim(cfg.rope_beta_fast)), 0)
    high = min(math.ceil(corr_dim(cfg.rope_beta_slow)), hd - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(hd // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    return inv / factor * ramp + inv * (1.0 - ramp)


def rope_attn_scale(cfg) -> float:
    """Factor on the softmax scale: YaRN's ``m**2``, with ``m`` the
    magnitude factor at ``rope_mscale_all_dim`` (1 without YaRN)."""
    if not cfg.rope_scaling_factor or not cfg.rope_mscale_all_dim:
        return 1.0
    return yarn_mscale(cfg.rope_scaling_factor, cfg.rope_mscale_all_dim) ** 2


def apply_rope(x, positions, cfg):
    """x: [..., S, H, hd]; positions: [..., S].  Rotates the two halves
    of the head dim; YaRN scales cos and sin by ``m(mscale) /
    m(mscale_all_dim)``."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, cfg)  # [hd/2]
    ang = positions[..., None].astype(jnp.float32) * freqs  # [..., S, hd/2]
    cos = jnp.cos(ang)[..., None, :]
    sin = jnp.sin(ang)[..., None, :]
    factor = cfg.rope_scaling_factor
    if factor:
        m = yarn_mscale(factor, cfg.rope_mscale) / yarn_mscale(
            factor, cfg.rope_mscale_all_dim)
        if m != 1.0:
            cos, sin = cos * m, sin * m
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# GQA attention
# ---------------------------------------------------------------------------

def init_attention(key, cfg):
    d, hq, hkv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    ks = jax.random.split(key, 5)
    dt = _dtype(cfg)
    p = {
        "wq": dense_init(ks[0], d, hq * hd, dt),
        "wk": dense_init(ks[1], d, hkv * hd, dt),
        "wv": dense_init(ks[2], d, hkv * hd, dt),
        "wo": dense_init(ks[3], hq * hd, d, dt, scale=1.0 / math.sqrt(hq * hd)),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((hq * hd,), dt)
        p["bk"] = jnp.zeros((hkv * hd,), dt)
        p["bv"] = jnp.zeros((hkv * hd,), dt)
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((hd,), dt)
        p["k_norm"] = jnp.ones((hd,), dt)
    return p


def attention_specs(cfg):
    s = {
        "wq": ("d_model", "heads"),
        "wk": ("d_model", "kv_heads"),
        "wv": ("d_model", "kv_heads"),
        "wo": ("heads", "d_model"),
    }
    if cfg.qkv_bias:
        s |= {"bq": ("heads",), "bk": ("kv_heads",), "bv": ("kv_heads",)}
    if cfg.qk_norm:
        s |= {"q_norm": (None,), "k_norm": (None,)}
    return s


def _qkv(p, cfg, x, positions):
    b, s, d = x.shape
    hq, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    q = planned_dense(x, p["wq"], site="attn.q")
    k = planned_dense(x, p["wk"], site="attn.k")
    v = planned_dense(x, p["wv"], site="attn.v")
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(b, s, hq, hd)
    k = k.reshape(b, s, hkv, hd)
    v = v.reshape(b, s, hkv, hd)
    if cfg.qk_norm:
        q = rmsnorm(q, p["q_norm"], cfg.norm_eps)
        k = rmsnorm(k, p["k_norm"], cfg.norm_eps)
    if cfg.use_rope:
        q = apply_rope(q, positions, cfg)
        k = apply_rope(k, positions, cfg)
    q = constrain(q, "batch", None, "heads", None)
    k = constrain(k, "batch", None, "kv_heads", None)
    return q, k, v


def _gqa_scores(qg, k, site):
    """einsum("bqhgd,bkhd->bhgqk", preferred_element_type=f32) as a
    planned bmm: operands stay in the compute dtype and the kernel
    flushes its fp32 accumulator (no fp32 copy of the KV cache).

    qg: [B,Sq,Hkv,G,hd]; k: [B,Skv,Hkv,hd].  The (B, Hkv) axes collapse to
    the bmm batch, (G, Sq) to its M extent, hd is the contraction.
    """
    b, sq, hkv, group, hd = qg.shape
    skv = k.shape[1]
    qb = qg.transpose(0, 2, 3, 1, 4).reshape(b * hkv, group * sq, hd)
    kb = k.transpose(0, 2, 3, 1).reshape(b * hkv, hd, skv)
    s = planned_bmm(qb, kb, site=site, out_dtype=jnp.float32)
    return s.reshape(b, hkv, group, sq, skv)


def _gqa_values(w, v, site):
    """einsum("bhgqk,bkhd->bqhgd") as a planned bmm.

    w: [B,Hkv,G,Sq,Skv] (already in v.dtype); v: [B,Skv,Hkv,hd].
    """
    b, hkv, group, sq, skv = w.shape
    hd = v.shape[-1]
    wb = w.reshape(b * hkv, group * sq, skv)
    vb = v.transpose(0, 2, 1, 3).reshape(b * hkv, skv, hd)
    out = planned_bmm(wb, vb, site=site)
    return out.reshape(b, hkv, group, sq, hd).transpose(0, 3, 1, 2, 4)


def sdpa(q, k, v, *, causal: bool, q_offset=None, kv_len=None,
         chunk=None):
    """q: [B,Sq,Hq,hd]; k/v: [B,Skv,Hkv,hd] (GQA broadcast).

    ``kv_len`` ([B] int32, optional) masks key rows at positions
    ``>= kv_len[b]`` — the streaming cross-attention contract: a padded
    enc K/V cache only partially filled contributes exact zeros for the
    unwritten tail (same -1e30 trick as the decode mask, so a full cache
    with ``kv_len == Skv`` is bitwise identical to no mask).

    ``chunk`` (int, optional) applies a block-causal mask on top:
    query position ``qp`` sees key position ``kp`` iff
    ``qp // chunk >= kp // chunk`` — full attention inside a chunk plus
    all earlier chunks, the streaming encoder's self-attention pattern.
    """
    b, sq, hq, hd = q.shape
    skv, hkv = k.shape[1], k.shape[2]
    group = hq // hkv
    qg = q.reshape(b, sq, hkv, group, hd)
    logits = _gqa_scores(qg, k, "attn.scores") / math.sqrt(hd)
    qpos = jnp.arange(sq)[:, None] + (
        q_offset if q_offset is not None else 0
    )
    kpos = jnp.arange(skv)[None, :]
    if causal:
        mask = qpos >= kpos
        logits = jnp.where(mask[None, None, None], logits, -1e30)
    if chunk is not None:
        bmask = (qpos // chunk) >= (kpos // chunk)
        logits = jnp.where(bmask[None, None, None], logits, -1e30)
    if kv_len is not None:
        vmask = kpos < kv_len[:, None]  # [B, Skv]
        logits = jnp.where(vmask[:, None, None, None], logits, -1e30)
    w = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    out = _gqa_values(w, v, "attn.values")
    return out.reshape(b, sq, hq, hd)


# threshold above which attention switches to the blockwise (flash-style)
# path — S^2 logits at 32k would be terabytes
BLOCKWISE_SEQ_THRESHOLD = 2048
Q_CHUNK = 512
K_CHUNK = 1024


def blockwise_attention(q, k, v, *, causal: bool, scale=None,
                        q_chunk=Q_CHUNK, k_chunk=K_CHUNK,
                        block_skip: bool = False):
    """Flash-style attention: scan over q chunks, inner scan over kv chunks
    with an online softmax.  Never materializes more than
    [B, H, q_chunk, k_chunk] logits.

    q: [B,Sq,H,hd_qk]; k: [B,Skv,H,hd_qk]; v: [B,Skv,H,hd_v] — heads must
    already be GQA-expanded (H == Hq) so the head axis shards over 'model'
    regardless of the kv-head count.
    """
    b, sq, h, dqk = q.shape
    skv = k.shape[1]
    dv = v.shape[-1]
    scale = scale if scale is not None else 1.0 / math.sqrt(dqk)
    q_chunk = min(q_chunk, sq)
    k_chunk = min(k_chunk, skv)
    pad_q = (-sq) % q_chunk
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
    nq = q.shape[1] // q_chunk
    pad_k = (-skv) % k_chunk
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
    nk = k.shape[1] // k_chunk

    # [nq, B, H, qc, d] layout for scan
    qs = jnp.moveaxis(
        q.reshape(b, nq, q_chunk, h, dqk), (1, 3), (0, 2))
    ks = jnp.moveaxis(
        k.reshape(b, nk, k_chunk, h, dqk), (1, 3), (0, 2))
    vs = jnp.moveaxis(
        v.reshape(b, nk, k_chunk, h, dv), (1, 3), (0, 2))

    kv_valid = jnp.arange(k.shape[1]) < skv  # mask padded kv tail

    def q_body(_, qi_qc):
        qi, qc = qi_qc  # qc: [B,H,qck,dqk]
        m0 = jnp.full((b, h, q_chunk), -1e30, jnp.float32)
        l0 = jnp.zeros((b, h, q_chunk), jnp.float32)
        a0 = jnp.zeros((b, h, q_chunk, dv), jnp.float32)

        def k_body(carry, ki_kc):
            m, l, acc = carry
            ki, kc, vc = ki_kc
            s = jnp.einsum("bhqd,bhkd->bhqk", qc, kc,
                           preferred_element_type=jnp.float32) * scale
            kpos = ki * k_chunk + jnp.arange(k_chunk)
            valid = jax.lax.dynamic_slice_in_dim(
                kv_valid, ki * k_chunk, k_chunk)
            if causal:
                qpos = qi * q_chunk + jnp.arange(q_chunk)
                mask = (qpos[:, None] >= kpos[None, :]) & valid[None, :]
            else:
                mask = jnp.broadcast_to(valid[None, :],
                                        (q_chunk, k_chunk))
            s = jnp.where(mask[None, None], s, -1e30)
            m_new = jnp.maximum(m, s.max(axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l = l * corr + p.sum(axis=-1)
            acc = acc * corr[..., None] + jnp.einsum(
                "bhqk,bhkd->bhqd", p.astype(vc.dtype), vc
            ).astype(jnp.float32)
            return (m_new, l, acc), None

        (m, l, acc), _ = jax.lax.scan(
            k_body, (m0, l0, a0),
            (jnp.arange(nk), ks, vs))
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return None, out

    if block_skip and causal:
        # triangular schedule: q chunk qi only visits kv chunks containing
        # any unmasked position (k_chunk-granular) — ~halves attention
        # flops.  Unrolled over q chunks so each inner scan has a static
        # trip count.
        outs = []
        for qi in range(nq):
            hi = min(((qi + 1) * q_chunk + k_chunk - 1) // k_chunk, nk)
            m0 = jnp.full((b, h, q_chunk), -1e30, jnp.float32)
            l0 = jnp.zeros((b, h, q_chunk), jnp.float32)
            a0 = jnp.zeros((b, h, q_chunk, dv), jnp.float32)

            def k_body(carry, ki_kc, qi=qi):
                m, l, acc = carry
                ki, kc, vc = ki_kc
                s_ = jnp.einsum("bhqd,bhkd->bhqk", qs[qi], kc,
                                preferred_element_type=jnp.float32) * scale
                kpos = ki * k_chunk + jnp.arange(k_chunk)
                valid = jax.lax.dynamic_slice_in_dim(
                    kv_valid, ki * k_chunk, k_chunk)
                qpos = qi * q_chunk + jnp.arange(q_chunk)
                mask = (qpos[:, None] >= kpos[None, :]) & valid[None, :]
                s_ = jnp.where(mask[None, None], s_, -1e30)
                m_new = jnp.maximum(m, s_.max(axis=-1))
                pp = jnp.exp(s_ - m_new[..., None])
                corr = jnp.exp(m - m_new)
                l = l * corr + pp.sum(axis=-1)
                acc = acc * corr[..., None] + jnp.einsum(
                    "bhqk,bhkd->bhqd", pp.astype(vc.dtype), vc
                ).astype(jnp.float32)
                return (m_new, l, acc), None

            (m, l, acc), _ = jax.lax.scan(
                k_body, (m0, l0, a0),
                (jnp.arange(hi), ks[:hi], vs[:hi]))
            outs.append(acc / jnp.maximum(l, 1e-30)[..., None])
        outs = jnp.stack(outs)
    else:
        _, outs = jax.lax.scan(q_body, None, (jnp.arange(nq), qs))
    # outs: [nq, B, H, qc, dv] -> [B, S, H, dv]
    out = jnp.moveaxis(outs, (0, 2), (1, 3)).reshape(
        b, nq * q_chunk, h, dv)
    return out[:, :sq].astype(v.dtype)


def gqa_expand(k, hq):
    """[B,S,Hkv,hd] -> [B,S,Hq,hd] by group repetition (so the head axis
    shards over 'model' even when Hkv doesn't divide the axis)."""
    hkv = k.shape[2]
    if hkv == hq:
        return k
    return jnp.repeat(k, hq // hkv, axis=2)


def attention_core(q, k, v, *, causal: bool, q_offset=None,
                   block_skip: bool = False, kv_len=None, chunk=None):
    """Pick direct vs blockwise by sequence length.  The streaming masks
    (``kv_len``/``chunk``) only exist on the direct path — streaming
    encoder chunks are far below the blockwise threshold."""
    sq, skv = q.shape[1], k.shape[1]
    if (kv_len is not None or chunk is not None
            or max(sq, skv) <= BLOCKWISE_SEQ_THRESHOLD):
        return sdpa(q, k, v, causal=causal, q_offset=q_offset,
                    kv_len=kv_len, chunk=chunk)
    hq = q.shape[2]
    k = constrain(gqa_expand(k, hq), "batch", None, "heads", None)
    v = constrain(gqa_expand(v, hq), "batch", None, "heads", None)
    return blockwise_attention(q, k, v, causal=causal,
                               block_skip=block_skip and causal)


def apply_attention(p, cfg, x, positions, *, causal=True, chunk=None):
    b, s, d = x.shape
    q, k, v = _qkv(p, cfg, x, positions)
    out = attention_core(q, k, v, causal=causal,
                         block_skip=cfg.causal_block_skip, chunk=chunk)
    out = out.reshape(b, s, cfg.n_heads * cfg.hd)
    return planned_dense(out, p["wo"], site="attn.out")


def _masked_decode_attention(p, cfg, q, kseq, vseq, pos, *, sites):
    """Shared one-token GQA decode core: masked scores over a [B,Skv,...]
    K/V view (contiguous cache or block-table gather — the caller
    picks), softmax, value readout, output projection.

    Rows with kpos > pos are masked to -1e30, so uninitialized (or
    pad-bucket) cache rows contribute exact zeros — the property that
    makes the paged gather bit-identical to the contiguous cache."""
    b = q.shape[0]
    compute_dt = _dtype(cfg)
    skv = kseq.shape[1]
    hq, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    group = hq // hkv
    qg = q.reshape(b, 1, hkv, group, hd)
    logits = _gqa_scores(
        qg, kseq.astype(compute_dt), sites[0]
    ) / math.sqrt(hd)
    kpos = jnp.arange(skv)[None, :]
    mask = kpos <= pos[:, None]
    logits = jnp.where(mask[:, None, None, None], logits, -1e30)
    w = jax.nn.softmax(logits, axis=-1).astype(compute_dt)
    out = _gqa_values(w, vseq.astype(compute_dt), sites[1])
    out = out.reshape(b, 1, hq * hd)
    return planned_dense(out, p["wo"], site="attn.out")


def apply_attention_decode(p, cfg, x, cache_k, cache_v, pos):
    """One-token decode: x [B,1,d]; cache [B,S,Hkv,hd]; pos [B] int32.

    The contiguous-cache decode of ``ModelAPI.decode``: no engine serves
    it, it is the plain reference the paged engine is tested against and
    what ``launch.dryrun`` lowers.  Low-precision caches (fp8) are
    storage-only: reads upcast to the compute dtype (bf16 math, fp8 HBM
    traffic — the serving pattern)."""
    q, k, v = _qkv(p, cfg, x, pos[:, None])
    # write new kv at pos
    cache_k = jax.vmap(
        lambda c, kk, pp: jax.lax.dynamic_update_slice(
            c, kk.astype(c.dtype), (pp, 0, 0))
    )(cache_k, k, pos)
    cache_v = jax.vmap(
        lambda c, vv, pp: jax.lax.dynamic_update_slice(
            c, vv.astype(c.dtype), (pp, 0, 0))
    )(cache_v, v, pos)
    out = _masked_decode_attention(
        p, cfg, q, cache_k, cache_v, pos,
        sites=("attn.decode_scores", "attn.decode_values"))
    return out, cache_k, cache_v


# ---------------------------------------------------------------------------
# block-paged KV cache primitives (continuous-batching serving)
# ---------------------------------------------------------------------------

def paged_write_layers(pool, rows, block_tables, pos, active):
    """Scatter one token's K/V rows of every layer into a stacked block
    pool in one scatter: pool [L, NB, bs, ...]; rows [L, B, ...];
    block_tables [B, T] int32; pos [B] int32 (the row each lane writes);
    active [B] bool.  Inactive lanes MUST NOT write — their table rows
    may point at blocks since re-allocated to another lane — so their
    index is forced out of range and dropped by the scatter
    (``mode="drop"``), never clamped onto a live row."""
    with jax.named_scope("kv.write"):
        nl, nb, bs = pool.shape[:3]
        blk = jnp.take_along_axis(
            block_tables, (pos // bs)[:, None], axis=1)[:, 0]
        idx = blk * bs + pos % bs
        idx = jnp.where(active, idx, nb * bs)  # OOB sentinel -> dropped
        flat = pool.reshape(nl, nb * bs, *pool.shape[3:])
        # index (layer, row) pairs: with the layer a window axis instead,
        # XLA makes the row axis major and relayouts the whole pool
        layer = jnp.arange(nl)[:, None]
        flat = flat.at[layer, idx[None, :]].set(
            rows.astype(pool.dtype), mode="drop")
        return flat.reshape(pool.shape)


def paged_gather(pool, block_tables, layer):
    """Assemble each lane's logical K/V sequence of layer ``layer`` from
    its block table: stacked pool [L, NB, bs, ...]; block_tables [B, T]
    -> [B, T*bs, ...].  Rows past the lane's ``pos`` are garbage (freed
    or never-written blocks) — the decode mask hides them, exactly like
    the zero tail of a contiguous cache."""
    with jax.named_scope("kv.gather"):
        # [B, T, bs, ...]
        g = pool[layer, block_tables]
        return g.reshape(block_tables.shape[0], -1, *g.shape[3:])


def with_row_at(seq, row, pos):
    """seq [B, S, ...] with each lane's ``row`` [B, ...] at ``pos``, cast
    to ``seq``'s (the pool's) dtype as a stored row would be: the current
    token's K/V before ``paged_write_layers`` stores it."""
    lanes = jnp.arange(seq.shape[0])
    return seq.at[lanes, pos].set(row.astype(seq.dtype), mode="drop")


def _paged_kernel_attention(cfg, q, k, v, pool_k, pool_v, layer,
                            block_tables, rows):
    """One-token GQA attention through ``kernels.paged_attention``: each
    lane's pooled rows ``0 .. rows-1`` read in place, and its new row
    (``k``, ``v`` [B, Hkv*hd], not in the pools yet) in the same softmax.
    q [B, 1, Hq, hd] -> [B, 1, Hq*hd] in the compute dtype."""
    b = q.shape[0]
    hq, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    dt = _dtype(cfg)
    # query head j in the hd columns of its kv head j // G, zeros elsewhere
    own = jax.nn.one_hot(jnp.arange(hq) // (hq // hkv), hkv, dtype=dt)
    with jax.named_scope("attn.paged_attention"):
        q_bd = q.reshape(b, hq, 1, hd).astype(dt) * own[None, :, :, None]
        out = PA.paged_attention(
            q_bd.reshape(b, hq, hkv * hd), k, v, pool_k, pool_v, layer,
            block_tables, rows, scale=1.0 / math.sqrt(hd))
    # head j = h*G + g keeps the columns of kv head h
    out = jnp.diagonal(out.reshape(b, hkv, hq // hkv, hkv, hd),
                       axis1=1, axis2=3)
    return jnp.moveaxis(out, -1, 1).reshape(b, 1, hq * hd)


def apply_attention_decode_stacked(p, cfg, x, pool_k, pool_v, layer,
                                   block_tables, pos, active):
    """One-token decode of layer ``layer`` against stacked [L, NB, bs,
    ...] block pools, which it only reads: the new K/V row is returned,
    cast to the pool dtype, for ``paged_write_layers`` to store after the
    layer scan, shaped as a pool row (all heads flattened, [B, Hkv*hd],
    or [B, Hkv, hd]).  Where ``kernels.paged_attention`` engages on the
    pools, it reads each active lane's rows below ``pos`` in place and
    joins the new row to their softmax.  Otherwise the new row joins the
    gathered sequence at ``pos`` under the mask: for every active lane
    the same math and bits as writing the row into the pool before
    gathering it."""
    b = x.shape[0]
    q, k, v = _qkv(p, cfg, x, pos[:, None])
    row = (b, *pool_k.shape[3:])
    k = k.reshape(row).astype(pool_k.dtype)
    v = v.reshape(row).astype(pool_v.dtype)
    if PA.engages(pool_k, cfg):
        # an inactive lane reads no pooled row; its output is not used
        out = _paged_kernel_attention(cfg, q, k, v, pool_k, pool_v, layer,
                                      block_tables, jnp.where(active, pos, 0))
        return planned_dense(out, p["wo"], site="attn.out"), k, v
    kseq = with_row_at(paged_gather(pool_k, block_tables, layer), k, pos)
    vseq = with_row_at(paged_gather(pool_v, block_tables, layer), v, pos)
    heads = (cfg.n_kv_heads, cfg.hd)
    out = _masked_decode_attention(
        p, cfg, q, kseq.reshape(b, -1, *heads), vseq.reshape(b, -1, *heads),
        pos, sites=("attn.paged_scores", "attn.paged_values"))
    return out, k, v


# ---------------------------------------------------------------------------
# GLU MLP
# ---------------------------------------------------------------------------

def init_mlp(key, cfg, d_ff=None):
    d = cfg.d_model
    ff = d_ff or cfg.d_ff
    ks = jax.random.split(key, 3)
    dt = _dtype(cfg)
    p = {
        "wu": dense_init(ks[1], d, ff, dt),
        "wd": dense_init(ks[2], ff, d, dt, scale=1.0 / math.sqrt(ff)),
    }
    if cfg.mlp_glu:
        p["wg"] = dense_init(ks[0], d, ff, dt)
    else:
        p["bu"] = jnp.zeros((ff,), dt)
        p["bd"] = jnp.zeros((d,), dt)
    return p


def mlp_specs(cfg):
    s = {
        "wu": ("d_model", "ff"),
        "wd": ("ff", "d_model"),
    }
    if cfg.mlp_glu:
        s["wg"] = ("d_model", "ff")
    else:
        s |= {"bu": ("ff",), "bd": (None,)}
    return s


def apply_mlp(p, cfg, x):
    if cfg.mlp_glu:
        act = jax.nn.silu if cfg.act == "silu" else jax.nn.gelu
        h = act(planned_dense(x, p["wg"], site="mlp.gate")) * planned_dense(
            x, p["wu"], site="mlp.up")
        h = constrain(h, "batch", None, "ff")
        return planned_dense(h, p["wd"], site="mlp.down")
    # non-GLU: up -> bias+act -> down is exactly the registry's mm+mm
    # fusion chain — route it through the fused facade so serving traffic
    # exercises chain plans; the output bias stays outside the chain
    out = planned_mlp_pair(
        x, p["wu"], p["bu"], p["wd"],
        act="silu" if cfg.act == "silu" else "gelu", site="mlp.pair")
    return out + p["bd"]


# ---------------------------------------------------------------------------
# norm dispatch (rms | layer)
# ---------------------------------------------------------------------------

def init_norm(cfg):
    d = cfg.d_model
    dt = _dtype(cfg)
    if cfg.norm == "layer":
        return {"w": jnp.ones((d,), dt), "b": jnp.zeros((d,), dt)}
    return {"w": jnp.ones((d,), dt)}


def norm_specs(cfg):
    if cfg.norm == "layer":
        return {"w": (None,), "b": (None,)}
    return {"w": (None,)}


def apply_norm(p, cfg, x):
    if cfg.norm == "layer":
        return layernorm(x, p["w"], p["b"], cfg.norm_eps)
    return rmsnorm(x, p["w"], cfg.norm_eps)
