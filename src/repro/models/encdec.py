"""Encoder-decoder transformer (whisper-base backbone).

The model consumes frame embeddings [B, frames, d] — produced offline
(training stubs feed them precomputed) or by the planned audio frontend
(``serve/frontend.py``: FIR -> fused fft2d chain -> conv2d, see
docs/streaming.md).  Encoder: non-causal self-attention blocks
(layernorm + classic GELU MLP, sinusoidal positions); streaming serving
runs it chunk-by-chunk (``encode_chunk``) under the equivalent
block-causal mask (``encode(chunk=C)``).  Decoder: causal
self-attention + cross-attention to the encoder output (masked past
``enc_len`` while an utterance is still streaming in), learned
positions.  use_rope=False for both.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.kernels.planned import planned_dense
from repro.parallel.sharding import constrain
from . import layers as L


def _maybe_remat(fn, cfg):
    if cfg.remat == "full":
        return jax.checkpoint(fn)
    if cfg.remat == "dots":
        return jax.checkpoint(
            fn,
            policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
        )
    return fn


def _res_constrain(cfg, x):
    if cfg.seq_parallel:
        return constrain(x, "batch", "seq_sp", None)
    return x


def sinusoids(length: int, channels: int):
    log_timescale = math.log(10000.0) / (channels // 2 - 1)
    inv = jnp.exp(-log_timescale * jnp.arange(channels // 2))
    ang = jnp.arange(length)[:, None] * inv[None, :]
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=1)


def init_cross_attention(key, cfg):
    # same projection structure as self-attention (kv from encoder states)
    return L.init_attention(key, cfg)


def init_enc_layer(key, cfg):
    k1, k2 = jax.random.split(key)
    return {
        "ln1": L.init_norm(cfg),
        "attn": L.init_attention(k1, cfg),
        "ln2": L.init_norm(cfg),
        "mlp": L.init_mlp(k2, cfg),
    }


def init_dec_layer(key, cfg):
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "ln1": L.init_norm(cfg),
        "attn": L.init_attention(k1, cfg),
        "ln_x": L.init_norm(cfg),
        "xattn": init_cross_attention(k2, cfg),
        "ln2": L.init_norm(cfg),
        "mlp": L.init_mlp(k3, cfg),
    }


def init_params(key, cfg):
    ks = jax.random.split(key, 6)
    dt = L._dtype(cfg)
    enc_keys = jax.random.split(ks[0], cfg.n_enc_layers)
    dec_keys = jax.random.split(ks[1], cfg.n_layers)
    return {
        "enc_layers": jax.vmap(lambda k: init_enc_layer(k, cfg))(enc_keys),
        "dec_layers": jax.vmap(lambda k: init_dec_layer(k, cfg))(dec_keys),
        "embed": (jax.random.normal(
            ks[2], (cfg.vocab, cfg.d_model), jnp.float32) * 0.02).astype(dt),
        "pos_dec": (jax.random.normal(
            ks[3], (cfg.max_positions, cfg.d_model),
            jnp.float32) * 0.01).astype(dt),
        "ln_enc": L.init_norm(cfg),
        "ln_f": L.init_norm(cfg),
    }


def param_specs(cfg):
    def stacked(base):
        return jax.tree.map(
            lambda ax: ("layers",) + ax, base,
            is_leaf=lambda x: isinstance(x, tuple))

    enc = {
        "ln1": L.norm_specs(cfg), "attn": L.attention_specs(cfg),
        "ln2": L.norm_specs(cfg), "mlp": L.mlp_specs(cfg),
    }
    dec = {
        "ln1": L.norm_specs(cfg), "attn": L.attention_specs(cfg),
        "ln_x": L.norm_specs(cfg), "xattn": L.attention_specs(cfg),
        "ln2": L.norm_specs(cfg), "mlp": L.mlp_specs(cfg),
    }
    return {
        "enc_layers": stacked(enc),
        "dec_layers": stacked(dec),
        "embed": ("vocab", "d_model"),
        "pos_dec": (None, "d_model"),
        "ln_enc": L.norm_specs(cfg),
        "ln_f": L.norm_specs(cfg),
    }


def _cross_attend(p, cfg, x, enc_k, enc_v, kv_len=None):
    """x [B,Sq,d] queries against precomputed encoder K/V.

    ``kv_len`` ([B] int32, optional) is the streaming mask: encoder K/V
    rows at positions >= kv_len[b] (the unwritten tail of a padded,
    partially-streamed enc cache) contribute exact zeros.  A full cache
    with kv_len == F is bitwise identical to passing no mask."""
    b, sq, _ = x.shape
    hq, hd = cfg.n_heads, cfg.hd
    q = planned_dense(x, p["wq"], site="xattn.q").reshape(b, sq, hq, hd)
    if cfg.qkv_bias:
        q = q + p["bq"].reshape(hq, hd)
    out = L.attention_core(q, enc_k, enc_v, causal=False, kv_len=kv_len)
    return planned_dense(out.reshape(b, sq, hq * hd), p["wo"],
                         site="xattn.out")


def _enc_kv(p, cfg, enc_out):
    b, s, _ = enc_out.shape
    hkv, hd = cfg.n_kv_heads, cfg.hd
    k = planned_dense(enc_out, p["wk"], site="xattn.k").reshape(
        b, s, hkv, hd)
    v = planned_dense(enc_out, p["wv"], site="xattn.v").reshape(
        b, s, hkv, hd)
    if cfg.qkv_bias:
        k = k + p["bk"].reshape(hkv, hd)
        v = v + p["bv"].reshape(hkv, hd)
    return k, v


def encode(p, cfg, frames, chunk=None):
    """frames: [B, F, d] stub embeddings -> encoder states.

    ``chunk`` (int, optional) applies the streaming block-causal mask:
    frame f only attends to frames in its own chunk and earlier ones
    (``f // chunk >= f' // chunk``) — the whole-utterance view of the
    incremental ``encode_chunk`` schedule."""
    x = frames.astype(L._dtype(cfg))
    x = x + sinusoids(x.shape[1], cfg.d_model).astype(x.dtype)
    x = constrain(x, "batch", None, None)
    positions = jnp.broadcast_to(jnp.arange(x.shape[1]), x.shape[:2])

    def body(x, lp):
        h = L.apply_norm(lp["ln1"], cfg, x)
        x = x + L.apply_attention(lp["attn"], cfg, h, positions,
                                  causal=False, chunk=chunk)
        h = L.apply_norm(lp["ln2"], cfg, x)
        return _res_constrain(cfg, x + L.apply_mlp(lp["mlp"], cfg, h)), None

    body = _maybe_remat(body, cfg)
    x, _ = jax.lax.scan(body, x, p["enc_layers"], unroll=cfg.scan_unroll)
    return L.apply_norm(p["ln_enc"], cfg, x)


def decode_train(p, cfg, tokens, enc_out):
    """Teacher-forced decoder pass -> hidden states."""
    b, s = tokens.shape
    x = p["embed"][tokens].astype(L._dtype(cfg)) + p["pos_dec"][:s]
    x = constrain(x, "batch", None, None)
    positions = jnp.broadcast_to(jnp.arange(s), (b, s))

    def body(x, lp):
        h = L.apply_norm(lp["ln1"], cfg, x)
        x = x + L.apply_attention(lp["attn"], cfg, h, positions, causal=True)
        h = L.apply_norm(lp["ln_x"], cfg, x)
        ek, ev = _enc_kv(lp["xattn"], cfg, enc_out)
        x = x + _cross_attend(lp["xattn"], cfg, h, ek, ev)
        h = L.apply_norm(lp["ln2"], cfg, x)
        return _res_constrain(cfg, x + L.apply_mlp(lp["mlp"], cfg, h)), None

    body = _maybe_remat(body, cfg)
    x, _ = jax.lax.scan(body, x, p["dec_layers"], unroll=cfg.scan_unroll)
    return L.apply_norm(p["ln_f"], cfg, x)


def loss_fn(p, cfg, batch):
    """batch: frames [B,F,d], tokens [B,S], labels [B,S]."""
    enc_out = encode(p, cfg, batch["frames"])
    hidden = decode_train(p, cfg, batch["tokens"], enc_out)
    logits = planned_dense(hidden, p["embed"].T.astype(hidden.dtype),
                           site="lm_head")
    logits = constrain(logits, "batch", None, "vocab")
    labels = batch["labels"]
    lbl = jnp.maximum(labels, 0)
    mask = (labels >= 0).astype(jnp.float32)
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, lbl[..., None], axis=-1)[..., 0]
    return ((lse - picked) * mask).sum() / jnp.maximum(mask.sum(), 1.0)


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------

def init_cache(cfg, batch, max_seq, enc_frames=None, dtype=jnp.bfloat16):
    nl = cfg.n_layers
    f = enc_frames or cfg.enc_frames
    return {
        "k": jnp.zeros((nl, batch, max_seq, cfg.n_kv_heads, cfg.hd), dtype),
        "v": jnp.zeros((nl, batch, max_seq, cfg.n_kv_heads, cfg.hd), dtype),
        "enc_k": jnp.zeros((nl, batch, f, cfg.n_kv_heads, cfg.hd), dtype),
        "enc_v": jnp.zeros((nl, batch, f, cfg.n_kv_heads, cfg.hd), dtype),
        # valid encoder rows per lane: cross-attention masks rows past
        # this (streaming fills enc_k/enc_v chunk-by-chunk; offline
        # prefill sets the full frame count, an all-true no-op mask)
        "enc_len": jnp.zeros((batch,), jnp.int32),
        "pos": jnp.zeros((batch,), jnp.int32),
    }


def cache_specs(cfg):
    return {
        "k": ("layers", "batch", None, "kv_heads", None),
        "v": ("layers", "batch", None, "kv_heads", None),
        "enc_k": ("layers", "batch", None, "kv_heads", None),
        "enc_v": ("layers", "batch", None, "kv_heads", None),
        "enc_len": ("batch",),
        "pos": ("batch",),
    }


def prefill(p, cfg, frames, tokens, max_seq, cache_dtype=jnp.bfloat16,
            last_index=None):
    """Encode audio, precompute cross K/V, run the teacher-forced prompt.

    ``last_index`` ([B] int32, optional): bucketed prefill — logits come
    from each lane's last valid token instead of position -1 (tokens may
    be right-padded), and ``cache["pos"]`` is set past it."""
    b, s = tokens.shape
    enc_out = encode(p, cfg, frames)
    enc_k, enc_v = enc_kv_chunk(p, cfg, enc_out, cache_dtype)

    x = p["embed"][tokens].astype(L._dtype(cfg)) + p["pos_dec"][:s]
    positions = jnp.broadcast_to(jnp.arange(s), (b, s))

    def body(x, inp):
        lp, ek, ev = inp
        h = L.apply_norm(lp["ln1"], cfg, x)
        q, k, v = L._qkv(lp["attn"], cfg, h, positions)
        x = x + planned_dense(
            L.attention_core(q, k, v, causal=True).reshape(b, s, -1),
            lp["attn"]["wo"], site="attn.out")
        h = L.apply_norm(lp["ln_x"], cfg, x)
        x = x + _cross_attend(lp["xattn"], cfg, h, ek, ev)
        h = L.apply_norm(lp["ln2"], cfg, x)
        x = x + L.apply_mlp(lp["mlp"], cfg, h)
        return x, (k.astype(cache_dtype), v.astype(cache_dtype))

    x, (ks, vs) = jax.lax.scan(body, x, (p["dec_layers"], enc_k, enc_v),
                               unroll=cfg.scan_unroll)
    x = L.apply_norm(p["ln_f"], cfg, x)
    if last_index is None:
        sel = x[:, -1:]
        pos = jnp.full((b,), s, jnp.int32)
    else:
        idx = last_index.astype(jnp.int32)
        sel = jnp.take_along_axis(x, idx[:, None, None], axis=1)
        pos = idx + 1
    logits = planned_dense(sel, p["embed"].T.astype(x.dtype),
                           site="lm_head")[:, 0]

    cache = init_cache(cfg, b, max_seq, enc_k.shape[2], cache_dtype)
    pad = [(0, 0)] * 5
    pad[2] = (0, max_seq - s)
    cache["k"] = jnp.pad(ks, pad)
    cache["v"] = jnp.pad(vs, pad)
    cache["enc_k"] = enc_k
    cache["enc_v"] = enc_v
    cache["enc_len"] = jnp.full((b,), enc_k.shape[2], jnp.int32)
    cache["pos"] = pos
    return logits, cache


# ---------------------------------------------------------------------------
# streaming (chunked) serving
# ---------------------------------------------------------------------------

def enc_kv_chunk(p, cfg, enc_out, cache_dtype=jnp.bfloat16):
    """Per-decoder-layer cross-attention K/V for a block of encoder
    states: enc_out [B, C, d] -> ([nl, B, C, hkv, hd], same) in the
    cache dtype.  Offline prefill calls it once with the whole
    utterance; the streaming engines call it once per chunk."""
    def kv_body(_, lp):
        ek, ev = _enc_kv(lp["xattn"], cfg, enc_out)
        return None, (ek.astype(cache_dtype), ev.astype(cache_dtype))

    _, (enc_k, enc_v) = jax.lax.scan(kv_body, None, p["dec_layers"],
                                     unroll=cfg.scan_unroll)
    return enc_k, enc_v


def init_enc_cache(cfg, batch, f_max=None):
    """Incremental encoder self-attention state for chunked streaming:
    per-enc-layer K/V padded to ``f_max`` frames plus the fill clock."""
    f = f_max or cfg.enc_frames
    dt = L._dtype(cfg)
    ne = cfg.n_enc_layers
    return {
        "k": jnp.zeros((ne, batch, f, cfg.n_kv_heads, cfg.hd), dt),
        "v": jnp.zeros((ne, batch, f, cfg.n_kv_heads, cfg.hd), dt),
        "len": jnp.zeros((batch,), jnp.int32),
    }


def encode_chunk(p, cfg, ec, frames_chunk):
    """One streaming encoder step: run ``frames_chunk`` [B, C, d]
    through the encoder with each layer attending over its cached K/V of
    all earlier chunks plus this one (the incremental view of the
    block-causal ``encode(chunk=C)`` mask), append this chunk's K/V to
    the cache, and return ``(new_ec, enc_states [B, C, d])``.

    Every chunk traces the same [C]-query x [f_max]-key shapes, so
    feeding an utterance chunk-by-chunk across engine steps is bitwise
    identical to replaying the same chunks inside one
    ``prefill_streaming`` call.  The chunk clock is batch-uniform
    (``ec["len"][0]``) — the engines feed one lane at a time."""
    b, c, _ = frames_chunk.shape
    dt = L._dtype(cfg)
    start = ec["len"][0]
    f_max = ec["k"].shape[2]
    pos_table = sinusoids(f_max, cfg.d_model).astype(dt)
    x = frames_chunk.astype(dt)
    x = x + jax.lax.dynamic_slice_in_dim(pos_table, start, c, axis=0)
    positions = jnp.broadcast_to(start + jnp.arange(c), (b, c))
    kv_len = jnp.broadcast_to(start + c, (b,))

    def body(x, inp):
        lp, ck, cv = inp
        h = L.apply_norm(lp["ln1"], cfg, x)
        q, k, v = L._qkv(lp["attn"], cfg, h, positions)
        ck = jax.lax.dynamic_update_slice(
            ck, k.astype(ck.dtype), (0, start, 0, 0))
        cv = jax.lax.dynamic_update_slice(
            cv, v.astype(cv.dtype), (0, start, 0, 0))
        attn = L.attention_core(q, ck, cv, causal=False, kv_len=kv_len)
        x = x + planned_dense(attn.reshape(b, c, -1), lp["attn"]["wo"],
                              site="attn.out")
        h = L.apply_norm(lp["ln2"], cfg, x)
        x = x + L.apply_mlp(lp["mlp"], cfg, h)
        return x, (ck, cv)

    x, (ks, vs) = jax.lax.scan(body, x, (p["enc_layers"], ec["k"], ec["v"]),
                               unroll=cfg.scan_unroll)
    new_ec = {"k": ks, "v": vs, "len": ec["len"] + c}
    return new_ec, L.apply_norm(p["ln_enc"], cfg, x)


def prefill_decoder(p, cfg, enc_k, enc_v, enc_len, tokens, max_seq,
                    cache_dtype=jnp.bfloat16, last_index=None):
    """Teacher-forced decoder prompt pass against already-built encoder
    K/V ([nl, B, F, hkv, hd], rows past ``enc_len`` masked) — the
    decoder half of ``prefill``, split out so streaming admission can
    run it after only the first audio chunk has been encoded."""
    b, s = tokens.shape
    x = p["embed"][tokens].astype(L._dtype(cfg)) + p["pos_dec"][:s]
    positions = jnp.broadcast_to(jnp.arange(s), (b, s))

    def body(x, inp):
        lp, ek, ev = inp
        h = L.apply_norm(lp["ln1"], cfg, x)
        q, k, v = L._qkv(lp["attn"], cfg, h, positions)
        x = x + planned_dense(
            L.attention_core(q, k, v, causal=True).reshape(b, s, -1),
            lp["attn"]["wo"], site="attn.out")
        h = L.apply_norm(lp["ln_x"], cfg, x)
        x = x + _cross_attend(lp["xattn"], cfg, h, ek, ev, kv_len=enc_len)
        h = L.apply_norm(lp["ln2"], cfg, x)
        x = x + L.apply_mlp(lp["mlp"], cfg, h)
        return x, (k.astype(cache_dtype), v.astype(cache_dtype))

    x, (ks, vs) = jax.lax.scan(body, x, (p["dec_layers"], enc_k, enc_v),
                               unroll=cfg.scan_unroll)
    x = L.apply_norm(p["ln_f"], cfg, x)
    if last_index is None:
        sel = x[:, -1:]
        pos = jnp.full((b,), s, jnp.int32)
    else:
        idx = last_index.astype(jnp.int32)
        sel = jnp.take_along_axis(x, idx[:, None, None], axis=1)
        pos = idx + 1
    logits = planned_dense(sel, p["embed"].T.astype(x.dtype),
                           site="lm_head")[:, 0]

    cache = init_cache(cfg, b, max_seq, enc_k.shape[2], cache_dtype)
    pad = [(0, 0)] * 5
    pad[2] = (0, max_seq - s)
    cache["k"] = jnp.pad(ks, pad)
    cache["v"] = jnp.pad(vs, pad)
    cache["enc_k"] = enc_k
    cache["enc_v"] = enc_v
    cache["enc_len"] = enc_len.astype(jnp.int32)
    cache["pos"] = pos
    return logits, cache


def prefill_streaming(p, cfg, frames, tokens, max_seq, chunk,
                      cache_dtype=jnp.bfloat16, last_index=None,
                      f_max=None):
    """Whole-utterance prefill through the *streaming* encoder: replays
    the same per-chunk ``encode_chunk``/``enc_kv_chunk`` computation the
    engines run one chunk per step, so the resulting enc cache is
    bitwise identical to incremental feeding; the decoder prompt pass
    then cross-attends with ``enc_len == F``.  The offline comparator
    for the streaming parity tests."""
    b, s = tokens.shape
    f = frames.shape[1]
    if f % chunk:
        raise ValueError(f"frames {f} not a multiple of chunk {chunk}")
    fm = f_max or cfg.enc_frames
    nl = cfg.n_layers
    ec = init_enc_cache(cfg, b, fm)
    enc_k = jnp.zeros((nl, b, fm, cfg.n_kv_heads, cfg.hd), cache_dtype)
    enc_v = jnp.zeros_like(enc_k)
    for i in range(f // chunk):
        fc = jax.lax.dynamic_slice_in_dim(frames, i * chunk, chunk, axis=1)
        ec, enc_out_c = encode_chunk(p, cfg, ec, fc)
        ek, ev = enc_kv_chunk(p, cfg, enc_out_c, cache_dtype)
        enc_k = jax.lax.dynamic_update_slice(
            enc_k, ek, (0, 0, i * chunk, 0, 0))
        enc_v = jax.lax.dynamic_update_slice(
            enc_v, ev, (0, 0, i * chunk, 0, 0))
    enc_len = jnp.full((b,), f, jnp.int32)
    logits, cache = prefill_decoder(p, cfg, enc_k, enc_v, enc_len, tokens,
                                    max_seq, cache_dtype, last_index)
    return logits, cache, ec


def paged_layout(cfg) -> dict:
    """Paged-cache leaf kinds: the growing decoder self-attention K/V
    pages through block tables; the cross-attention encoder K/V is a
    fixed-size per-lane block (``lane`` leaves — written at admit and
    grown in place by streaming chunk feeds, nothing to page); the
    per-lane valid-frame count is a ``lane_scalar``."""
    del cfg
    return {"k": "paged", "v": "paged", "enc_k": "lane", "enc_v": "lane",
            "enc_len": "lane_scalar"}


def init_paged_pools(cfg, num_blocks, block_size, max_lanes,
                     dtype=jnp.bfloat16):
    nl = cfg.n_layers
    f = cfg.enc_frames
    return {
        "k": jnp.zeros(
            (nl, num_blocks, block_size, cfg.n_kv_heads, cfg.hd), dtype),
        "v": jnp.zeros(
            (nl, num_blocks, block_size, cfg.n_kv_heads, cfg.hd), dtype),
        "enc_k": jnp.zeros(
            (nl, max_lanes, f, cfg.n_kv_heads, cfg.hd), dtype),
        "enc_v": jnp.zeros(
            (nl, max_lanes, f, cfg.n_kv_heads, cfg.hd), dtype),
        "enc_len": jnp.zeros((max_lanes,), jnp.int32),
    }


def decode_step_paged(p, cfg, pools, tokens, block_tables, pos, active):
    """Block-paged decode twin of ``decode_step``.  The layer scan reads
    the stacked self-attention K/V pools in place (the scan's inputs are
    the layer parameters, the layer indices and the lanes' resident
    encoder K/V for cross-attention), and every layer's new row is
    written after it in one scatter per pool, with inactive-lane writes
    dropped."""
    x = p["embed"][tokens].astype(L._dtype(cfg))
    x = x + jnp.take_along_axis(
        p["pos_dec"][None].astype(x.dtype),
        pos[:, None, None].astype(jnp.int32), axis=1)

    def body(x, inp):
        lp, layer, ek, ev = inp
        h = L.apply_norm(lp["ln1"], cfg, x)
        attn, k, v = L.apply_attention_decode_stacked(
            lp["attn"], cfg, h, pools["k"], pools["v"], layer,
            block_tables, pos, active)
        x = x + attn
        h = L.apply_norm(lp["ln_x"], cfg, x)
        x = x + _cross_attend(lp["xattn"], cfg, h, ek, ev,
                              kv_len=pools["enc_len"])
        h = L.apply_norm(lp["ln2"], cfg, x)
        x = x + L.apply_mlp(lp["mlp"], cfg, h)
        return x, (k, v)

    x, (ks, vs) = jax.lax.scan(
        body, x,
        (p["dec_layers"], jnp.arange(cfg.n_layers), pools["enc_k"],
         pools["enc_v"]), unroll=cfg.scan_unroll)
    x = L.apply_norm(p["ln_f"], cfg, x)
    logits = planned_dense(x, p["embed"].T.astype(x.dtype),
                           site="lm_head")[:, 0]
    new_pools = dict(
        pools,
        k=L.paged_write_layers(pools["k"], ks, block_tables, pos, active),
        v=L.paged_write_layers(pools["v"], vs, block_tables, pos, active))
    return logits, new_pools


def decode_step(p, cfg, cache, tokens):
    """Contiguous-cache decode: tokens [B,1] -> (logits [B,V], cache).
    No engine serves it; it is the plain reference the paged engine is
    tested against."""
    b = tokens.shape[0]
    pos = cache["pos"]
    x = p["embed"][tokens].astype(L._dtype(cfg))
    x = x + jnp.take_along_axis(
        p["pos_dec"][None].astype(x.dtype),
        pos[:, None, None].astype(jnp.int32), axis=1)

    def body(x, inp):
        lp, ck, cv, ek, ev = inp
        h = L.apply_norm(lp["ln1"], cfg, x)
        attn, ck, cv = L.apply_attention_decode(lp["attn"], cfg, h, ck, cv,
                                                pos)
        x = x + attn
        h = L.apply_norm(lp["ln_x"], cfg, x)
        x = x + _cross_attend(lp["xattn"], cfg, h, ek, ev,
                              kv_len=cache["enc_len"])
        h = L.apply_norm(lp["ln2"], cfg, x)
        x = x + L.apply_mlp(lp["mlp"], cfg, h)
        return x, (ck, cv)

    x, (ks, vs) = jax.lax.scan(
        body, x,
        (p["dec_layers"], cache["k"], cache["v"],
         cache["enc_k"], cache["enc_v"]), unroll=cfg.scan_unroll)
    x = L.apply_norm(p["ln_f"], cfg, x)
    logits = planned_dense(x, p["embed"].T.astype(x.dtype),
                           site="lm_head")[:, 0]
    new_cache = dict(cache, k=ks, v=vs, pos=pos + 1)
    return logits, new_cache
