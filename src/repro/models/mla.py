"""Multi-head Latent Attention (DeepSeek-V2) — low-rank KV compression.

Train/prefill expand the latent; decode runs the *absorbed* form against the
compressed cache (c_kv + shared rope key per token), which is the MLA
serving trick: per-token cache is (kv_lora_rank + rope_head_dim) elements
instead of 2*H*hd.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.kernels.planned import planned_dense
from repro.parallel.sharding import constrain
from .layers import apply_rope, dense_init, rmsnorm, rope_attn_scale, _dtype


def init_mla(key, cfg):
    d = cfg.d_model
    h = cfg.n_heads
    nope, rope, vd = cfg.nope_head_dim, cfg.rope_head_dim, cfg.v_head_dim
    ks = jax.random.split(key, 8)
    dt = _dtype(cfg)
    p = {}
    qh = h * (nope + rope)
    if cfg.q_lora_rank:
        p["wdq"] = dense_init(ks[0], d, cfg.q_lora_rank, dt)
        p["q_norm"] = jnp.ones((cfg.q_lora_rank,), dt)
        p["wuq"] = dense_init(ks[1], cfg.q_lora_rank, qh, dt)
    else:
        p["wq"] = dense_init(ks[1], d, qh, dt)
    p["wdkv"] = dense_init(ks[2], d, cfg.kv_lora_rank, dt)
    p["kv_norm"] = jnp.ones((cfg.kv_lora_rank,), dt)
    p["wkr"] = dense_init(ks[3], d, rope, dt)
    p["wuk"] = dense_init(ks[4], cfg.kv_lora_rank, h * nope, dt)
    p["wuv"] = dense_init(ks[5], cfg.kv_lora_rank, h * vd, dt)
    p["wo"] = dense_init(ks[6], h * vd, d, dt, scale=1.0 / math.sqrt(h * vd))
    return p


def mla_specs(cfg):
    s = {
        "wdkv": ("d_model", None),
        "kv_norm": (None,),
        "wkr": ("d_model", None),
        "wuk": (None, "heads"),
        "wuv": (None, "heads"),
        "wo": ("heads", "d_model"),
    }
    if cfg.q_lora_rank:
        s |= {"wdq": ("d_model", None), "q_norm": (None,),
              "wuq": (None, "heads")}
    else:
        s |= {"wq": ("d_model", "heads")}
    return s


def _queries(p, cfg, x, positions):
    b, s, _ = x.shape
    h, nope, rope = cfg.n_heads, cfg.nope_head_dim, cfg.rope_head_dim
    if cfg.q_lora_rank:
        cq = rmsnorm(planned_dense(x, p["wdq"], site="mla.q_down"),
                     p["q_norm"], cfg.norm_eps)
        q = planned_dense(cq, p["wuq"], site="mla.q_up")
    else:
        q = planned_dense(x, p["wq"], site="mla.q")
    q = q.reshape(b, s, h, nope + rope)
    qn, qr = q[..., :nope], q[..., nope:]
    qr = apply_rope(qr, positions, cfg)
    return constrain(qn, "batch", None, "heads", None), constrain(
        qr, "batch", None, "heads", None)


def _latent(p, cfg, x, positions):
    ckv = rmsnorm(planned_dense(x, p["wdkv"], site="mla.kv_down"),
                  p["kv_norm"], cfg.norm_eps)
    # [B,S,1,rope] shared across heads
    kr = planned_dense(x, p["wkr"], site="mla.k_rope")[:, :, None, :]
    kr = apply_rope(kr, positions, cfg)
    return ckv, kr[:, :, 0, :]


def apply_mla(p, cfg, x, positions, *, causal=True):
    """Training/prefill path: expand latent to per-head K/V.

    Long sequences route through blockwise attention with the nope and
    rope score terms fused by concatenating along the head dim:
    q_cat = [qn ; qr], k_cat = [kn ; kr broadcast] so q_cat.k_cat equals
    qn.kn + qr.kr — one flash pass instead of two logits tensors.
    """
    from .layers import BLOCKWISE_SEQ_THRESHOLD, blockwise_attention

    b, s, _ = x.shape
    h, nope, vd = cfg.n_heads, cfg.nope_head_dim, cfg.v_head_dim
    rope = cfg.rope_head_dim
    qn, qr = _queries(p, cfg, x, positions)
    ckv, kr = _latent(p, cfg, x, positions)
    kn = planned_dense(ckv, p["wuk"], site="mla.k_up").reshape(
        b, s, h, nope)
    v = planned_dense(ckv, p["wuv"], site="mla.v_up").reshape(b, s, h, vd)
    kn = constrain(kn, "batch", None, "heads", None)
    v = constrain(v, "batch", None, "heads", None)
    scale = rope_attn_scale(cfg) / math.sqrt(nope + rope)

    if s > BLOCKWISE_SEQ_THRESHOLD:
        q_cat = jnp.concatenate([qn, qr], axis=-1)
        k_cat = jnp.concatenate(
            [kn, jnp.broadcast_to(kr[:, :, None, :], (b, s, h, rope))],
            axis=-1)
        out = blockwise_attention(
            q_cat, k_cat, v, causal=causal, scale=scale,
            block_skip=cfg.causal_block_skip and causal)
        out = out.reshape(b, s, h * vd)
        return planned_dense(out, p["wo"], site="mla.out")

    logits = (
        jnp.einsum("bqhd,bkhd->bhqk", qn, kn,
                   preferred_element_type=jnp.float32)
        + jnp.einsum("bqhd,bkd->bhqk", qr, kr,
                     preferred_element_type=jnp.float32)
    ) * scale
    if causal:
        qpos = jnp.arange(s)[:, None]
        kpos = jnp.arange(s)[None, :]
        logits = jnp.where((qpos >= kpos)[None, None], logits, -1e30)
    w = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    out = jnp.einsum("bhqk,bkhd->bqhd", w, v).reshape(b, s, h * vd)
    return planned_dense(out, p["wo"], site="mla.out")


def _absorbed_decode(p, cfg, qn, qr, ckv_seq, kr_seq, pos):
    """Absorbed scoring + latent readout over a [B,Skv,...] latent view
    (contiguous lane cache or block-table gather).  Rows past ``pos``
    are masked, so garbage tail rows contribute exact zeros."""
    b = qn.shape[0]
    h, nope, vd = cfg.n_heads, cfg.nope_head_dim, cfg.v_head_dim
    rope, kvl = cfg.rope_head_dim, cfg.kv_lora_rank
    # absorb W_uk into q:  q_abs[h, kvl] = qn[h] @ W_uk[h]^T
    wuk = p["wuk"].reshape(kvl, h, nope)
    q_abs = jnp.einsum("bqhd,lhd->bqhl", qn, wuk)  # [B,1,H,kvl]
    scale = rope_attn_scale(cfg) / math.sqrt(nope + rope)
    with jax.named_scope("attn.latent_scores"):
        logits = (
            jnp.einsum("bqhl,bkl->bhqk", q_abs, ckv_seq,
                       preferred_element_type=jnp.float32)
            + jnp.einsum("bqhd,bkd->bhqk", qr, kr_seq,
                         preferred_element_type=jnp.float32)
        ) * scale
    kpos = jnp.arange(ckv_seq.shape[1])[None, :]
    mask = kpos <= pos[:, None]
    logits = jnp.where(mask[:, None, None], logits, -1e30)
    w = jax.nn.softmax(logits, axis=-1).astype(ckv_seq.dtype)
    with jax.named_scope("attn.latent_values"):
        out_lat = jnp.einsum("bhqk,bkl->bqhl", w, ckv_seq)  # [B,1,H,kvl]
    wuv = p["wuv"].reshape(kvl, h, vd)
    out = jnp.einsum("bqhl,lhd->bqhd", out_lat, wuv).reshape(b, 1, h * vd)
    return planned_dense(out, p["wo"], site="mla.out")


def apply_mla_decode(p, cfg, x, cache_ckv, cache_kr, pos):
    """Absorbed decode: score/readout in the compressed latent space.

    cache_ckv: [B, S, kv_lora]; cache_kr: [B, S, rope]; pos: [B].  The
    contiguous-cache decode: the plain reference for
    ``apply_mla_decode_paged``, served by no engine.
    """
    qn, qr = _queries(p, cfg, x, pos[:, None])  # [B,1,H,*]
    ckv_new, kr_new = _latent(p, cfg, x, pos[:, None])
    cache_ckv = jax.vmap(
        lambda c, n, pp: jax.lax.dynamic_update_slice(
            c, n.astype(c.dtype), (pp, 0))
    )(cache_ckv, ckv_new, pos)
    cache_kr = jax.vmap(
        lambda c, n, pp: jax.lax.dynamic_update_slice(
            c, n.astype(c.dtype), (pp, 0))
    )(cache_kr, kr_new, pos)
    out = _absorbed_decode(p, cfg, qn, qr, cache_ckv, cache_kr, pos)
    return out, cache_ckv, cache_kr


def apply_mla_decode_paged(p, cfg, x, pool_ckv, pool_kr, layer,
                           block_tables, pos):
    """Block-paged absorbed decode of layer ``layer``: the compressed
    latent cache lives in stacked [L, NB, bs, ...] block pools indexed
    through per-lane block tables, which this only reads.  The new latent
    rows join the gathered sequence at ``pos`` and are returned, cast to
    the pool dtype, for ``layers.paged_write_layers`` to store after the
    layer scan (see ``layers.apply_attention_decode_stacked``)."""
    from .layers import paged_gather, with_row_at

    qn, qr = _queries(p, cfg, x, pos[:, None])
    ckv_new, kr_new = _latent(p, cfg, x, pos[:, None])
    ckv_new = ckv_new[:, 0].astype(pool_ckv.dtype)
    kr_new = kr_new[:, 0].astype(pool_kr.dtype)
    ckv_seq = with_row_at(paged_gather(pool_ckv, block_tables, layer),
                          ckv_new, pos)
    kr_seq = with_row_at(paged_gather(pool_kr, block_tables, layer),
                         kr_new, pos)
    out = _absorbed_decode(p, cfg, qn, qr, ckv_seq, kr_seq, pos)
    return out, ckv_new, kr_new
