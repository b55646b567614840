"""Hybrid SSM + shared-attention model (zamba2 family).

Trunk of Mamba2 blocks with ONE weight-shared (attention + GLU-MLP) block
applied after every ``attn_every`` SSM blocks (zamba2's shared transformer
block; we model a single shared block without per-invocation LoRA — noted
in DESIGN.md §5).  The trunk scans; the shared block applications unroll
(n_layers/attn_every of them), each with its own KV cache slot.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.parallel.sharding import constrain
from repro.kernels.planned import planned_dense
from . import layers as L
from . import ssm as SSM


def n_attn_apps(cfg) -> int:
    if cfg.attn_every <= 0:
        return 0  # pure SSM (mamba2 family)
    return cfg.n_layers // cfg.attn_every


def init_params(key, cfg):
    ks = jax.random.split(key, 5)
    dt = L._dtype(cfg)
    trunk_keys = jax.random.split(ks[0], cfg.n_layers)
    p = {
        "embed": (jax.random.normal(
            ks[1], (cfg.vocab, cfg.d_model), jnp.float32) * 0.02).astype(dt),
        "trunk": jax.vmap(
            lambda k: {"ln": L.init_norm(cfg),
                       "mamba": SSM.init_mamba(k, cfg)}
        )(trunk_keys),
        "ln_f": L.init_norm(cfg),
        "lm_head": L.dense_init(ks[4], cfg.d_model, cfg.vocab, dt),
    }
    if n_attn_apps(cfg):
        p["shared"] = {
            "ln1": L.init_norm(cfg),
            "attn": L.init_attention(ks[2], cfg),
            "ln2": L.init_norm(cfg),
            "mlp": L.init_mlp(ks[3], cfg),
        }
    return p


def param_specs(cfg):
    trunk = {"ln": L.norm_specs(cfg), "mamba": SSM.mamba_specs(cfg)}
    s = {
        "embed": ("vocab", "d_model"),
        "trunk": jax.tree.map(
            lambda ax: ("layers",) + ax, trunk,
            is_leaf=lambda x: isinstance(x, tuple)),
        "ln_f": L.norm_specs(cfg),
        "lm_head": ("d_model", "vocab"),
    }
    if n_attn_apps(cfg):
        s["shared"] = {
            "ln1": L.norm_specs(cfg),
            "attn": L.attention_specs(cfg),
            "ln2": L.norm_specs(cfg),
            "mlp": L.mlp_specs(cfg),
        }
    return s


def _shared_block(p, cfg, x, positions):
    h = L.apply_norm(p["ln1"], cfg, x)
    x = x + L.apply_attention(p["attn"], cfg, h, positions)
    h = L.apply_norm(p["ln2"], cfg, x)
    return x + L.apply_mlp(p["mlp"], cfg, h)


def _maybe_remat(fn, cfg):
    if cfg.remat == "full":
        return jax.checkpoint(fn)
    if cfg.remat == "dots":
        return jax.checkpoint(
            fn,
            policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
        )
    return fn


def forward(p, cfg, tokens):
    b, s = tokens.shape
    x = p["embed"][tokens].astype(L._dtype(cfg))
    x = constrain(x, "batch", None, None)
    positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    trunk = p["trunk"]

    def blk(x, lp):
        h = L.apply_norm(lp["ln"], cfg, x)
        out = x + SSM.apply_mamba(lp["mamba"], cfg, h)
        if cfg.seq_parallel:
            out = constrain(out, "batch", "seq_sp", None)
        return out, None

    blk = _maybe_remat(blk, cfg)

    if n_attn_apps(cfg) == 0:  # pure SSM trunk
        x, _ = jax.lax.scan(blk, x, trunk, unroll=cfg.scan_unroll)
        return L.apply_norm(p["ln_f"], cfg, x)

    # trunk segments of `every` mamba blocks, shared attn between segments
    every = cfg.attn_every
    shared_fn = _maybe_remat(
        lambda x: _shared_block(p["shared"], cfg, x, positions), cfg)

    def seg_body(x, seg_params):
        x, _ = jax.lax.scan(blk, x, seg_params, unroll=cfg.scan_unroll)
        x = shared_fn(x)
        return x, None

    n_seg = cfg.n_layers // every
    rem = cfg.n_layers - n_seg * every
    seg = jax.tree.map(
        lambda a: a[: n_seg * every].reshape(
            (n_seg, every) + a.shape[1:]), trunk)
    x, _ = jax.lax.scan(seg_body, x, seg, unroll=cfg.scan_unroll)
    if rem:
        tail = jax.tree.map(lambda a: a[n_seg * every:], trunk)
        x, _ = jax.lax.scan(blk, x, tail, unroll=cfg.scan_unroll)
    return L.apply_norm(p["ln_f"], cfg, x)


def loss_fn(p, cfg, batch):
    hidden = forward(p, cfg, batch["tokens"])
    logits = planned_dense(hidden, p["lm_head"].astype(hidden.dtype),
                           site="lm_head")
    logits = constrain(logits, "batch", None, "vocab").astype(jnp.float32)
    labels = batch["labels"]
    lbl = jnp.maximum(labels, 0)
    mask = (labels >= 0).astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, lbl[..., None], axis=-1)[..., 0]
    return ((lse - picked) * mask).sum() / jnp.maximum(mask.sum(), 1.0)


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------

def init_cache(cfg, batch, max_seq, dtype=jnp.bfloat16):
    napp = n_attn_apps(cfg)
    di, ns = cfg.d_inner, cfg.ssm_state
    cache = {
        "conv": jnp.zeros(
            (cfg.n_layers, batch, cfg.ssm_conv - 1, di + 2 * ns), dtype),
        "ssm": jnp.zeros(
            (cfg.n_layers, batch, cfg.ssm_heads, cfg.ssm_headdim, ns),
            jnp.float32),
        "pos": jnp.zeros((batch,), jnp.int32),
    }
    if napp:
        cache["k"] = jnp.zeros(
            (napp, batch, max_seq, cfg.n_kv_heads, cfg.hd), dtype)
        cache["v"] = jnp.zeros(
            (napp, batch, max_seq, cfg.n_kv_heads, cfg.hd), dtype)
    return cache


def cache_specs(cfg):
    s = {
        "conv": ("layers", "batch", None, "ssm_heads"),
        "ssm": ("layers", "batch", "ssm_heads", None, None),
        "pos": ("batch",),
    }
    if n_attn_apps(cfg):
        s["k"] = ("layers", "batch", None, "kv_heads", None)
        s["v"] = ("layers", "batch", None, "kv_heads", None)
    return s


def _trunk_prefill_body(cfg, cache_dtype):
    def body(x, lp):
        h = L.apply_norm(lp["ln"], cfg, x)
        out, st, conv_tail = SSM.apply_mamba(
            lp["mamba"], cfg, h, return_cache=True)
        return x + out, (conv_tail.astype(cache_dtype), st)
    return body


def prefill(p, cfg, tokens, max_seq, cache_dtype=jnp.bfloat16):
    """Prompt pass building SSM states + shared-attn KV caches (scanned)."""
    b, s = tokens.shape
    x = p["embed"][tokens].astype(L._dtype(cfg))
    positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    napp = n_attn_apps(cfg)
    body = _trunk_prefill_body(cfg, cache_dtype)
    convs, ssms, kvs = [], [], []

    if napp == 0:
        x, (conv_t, ssm_t) = jax.lax.scan(body, x, p["trunk"],
                                          unroll=cfg.scan_unroll)
        convs, ssms = [conv_t], [ssm_t]
    else:
        every = cfg.attn_every
        n_seg = cfg.n_layers // every
        seg = jax.tree.map(
            lambda a: a[: n_seg * every].reshape(
                (n_seg, every) + a.shape[1:]), p["trunk"])
        for si in range(n_seg):
            seg_i = jax.tree.map(lambda a: a[si], seg)
            x, (conv_t, ssm_t) = jax.lax.scan(body, x, seg_i,
                                              unroll=cfg.scan_unroll)
            convs.append(conv_t)
            ssms.append(ssm_t)
            h = L.apply_norm(p["shared"]["ln1"], cfg, x)
            q, k, v = L._qkv(p["shared"]["attn"], cfg, h, positions)
            attn = L.attention_core(q, k, v, causal=True).reshape(b, s, -1) @ \
                p["shared"]["attn"]["wo"]
            x = x + attn
            h = L.apply_norm(p["shared"]["ln2"], cfg, x)
            x = x + L.apply_mlp(p["shared"]["mlp"], cfg, h)
            kvs.append((k.astype(cache_dtype), v.astype(cache_dtype)))
        rem = cfg.n_layers - n_seg * every
        if rem:
            tail = jax.tree.map(lambda a: a[n_seg * every:], p["trunk"])
            x, (conv_t, ssm_t) = jax.lax.scan(body, x, tail,
                                              unroll=cfg.scan_unroll)
            convs.append(conv_t)
            ssms.append(ssm_t)

    x = L.apply_norm(p["ln_f"], cfg, x)
    logits = planned_dense(x[:, -1:], p["lm_head"].astype(x.dtype),
                           site="lm_head")[:, 0]

    cache = init_cache(cfg, b, max_seq, cache_dtype)
    if napp:
        pad = [(0, 0)] * 5
        pad[2] = (0, max_seq - s)
        cache["k"] = jnp.pad(jnp.stack([k for k, _ in kvs]), pad)
        cache["v"] = jnp.pad(jnp.stack([v for _, v in kvs]), pad)
    cache["conv"] = jnp.concatenate(convs, axis=0) if len(convs) > 1 \
        else convs[0]
    cache["ssm"] = jnp.concatenate(ssms, axis=0) if len(ssms) > 1 \
        else ssms[0]
    cache["pos"] = jnp.full((b,), s, jnp.int32)
    return logits, cache


def paged_layout(cfg) -> dict:
    """Paged-cache leaf kinds: the recurrent SSM/conv states are *per
    lane* (``lane`` leaves, [L, max_lanes, ...] — a lane's state is a
    fixed-size recurrence, there is nothing to page), while the shared
    attention K/V pages like any transformer cache."""
    layout = {"conv": "lane", "ssm": "lane"}
    if n_attn_apps(cfg):
        layout["k"] = "paged"
        layout["v"] = "paged"
    return layout


def init_paged_pools(cfg, num_blocks, block_size, max_lanes,
                     dtype=jnp.bfloat16):
    napp = n_attn_apps(cfg)
    di, ns = cfg.d_inner, cfg.ssm_state
    pools = {
        "conv": jnp.zeros(
            (cfg.n_layers, max_lanes, cfg.ssm_conv - 1, di + 2 * ns),
            dtype),
        "ssm": jnp.zeros(
            (cfg.n_layers, max_lanes, cfg.ssm_heads, cfg.ssm_headdim, ns),
            jnp.float32),
    }
    if napp:
        pools["k"] = jnp.zeros(
            (napp, num_blocks, block_size, cfg.n_kv_heads, cfg.hd), dtype)
        pools["v"] = jnp.zeros(
            (napp, num_blocks, block_size, cfg.n_kv_heads, cfg.hd), dtype)
    return pools


def decode_step_paged(p, cfg, pools, tokens, block_tables, pos, active):
    """Block-paged decode twin of ``decode_step``.  SSM/conv states are
    per-lane and always advance (inactive lanes evolve garbage that the
    next admit overwrites).  Shared-attention application ``si`` reads
    layer ``si`` of the stacked K/V pools in place; every application's
    new row is written after the trunk in one scatter per pool, with
    inactive-lane writes dropped."""
    x = p["embed"][tokens].astype(L._dtype(cfg))
    napp = n_attn_apps(cfg)
    conv_dt = pools["conv"].dtype

    def blk_body(x, inp):
        lp, conv_c, ssm_c = inp
        h = L.apply_norm(lp["ln"], cfg, x)
        mc = {"conv": conv_c.astype(jnp.float32), "ssm": ssm_c}
        out, mc = SSM.apply_mamba_step(lp["mamba"], cfg, h, mc)
        return x + out, (mc["conv"].astype(conv_dt), mc["ssm"])

    if napp == 0:
        x, (new_conv, new_ssm) = jax.lax.scan(
            blk_body, x, (p["trunk"], pools["conv"], pools["ssm"]),
            unroll=cfg.scan_unroll)
        new_pools = {"conv": new_conv, "ssm": new_ssm}
    else:
        every = cfg.attn_every
        n_seg = cfg.n_layers // every
        seg = jax.tree.map(
            lambda a: a[: n_seg * every].reshape(
                (n_seg, every) + a.shape[1:]),
            (p["trunk"], pools["conv"], pools["ssm"]))
        new_conv, new_ssm, rows_k, rows_v = [], [], [], []
        for si in range(n_seg):
            seg_i = jax.tree.map(lambda a: a[si], seg)
            x, (nc, ns_) = jax.lax.scan(blk_body, x, seg_i,
                                        unroll=cfg.scan_unroll)
            new_conv.append(nc)
            new_ssm.append(ns_)
            h = L.apply_norm(p["shared"]["ln1"], cfg, x)
            attn, k, v = L.apply_attention_decode_stacked(
                p["shared"]["attn"], cfg, h, pools["k"], pools["v"], si,
                block_tables, pos, active)
            rows_k.append(k)
            rows_v.append(v)
            x = x + attn
            h = L.apply_norm(p["shared"]["ln2"], cfg, x)
            x = x + L.apply_mlp(p["shared"]["mlp"], cfg, h)
        rem = cfg.n_layers - n_seg * every
        if rem:
            tail = jax.tree.map(
                lambda a: a[n_seg * every:],
                (p["trunk"], pools["conv"], pools["ssm"]))
            x, (nc, ns_) = jax.lax.scan(blk_body, x, tail,
                                        unroll=cfg.scan_unroll)
            new_conv.append(nc)
            new_ssm.append(ns_)
        new_pools = {
            name: L.paged_write_layers(pools[name], jnp.stack(rows),
                                       block_tables, pos, active)
            for name, rows in (("k", rows_k), ("v", rows_v))}
        new_pools["conv"] = jnp.concatenate(new_conv, axis=0)
        new_pools["ssm"] = jnp.concatenate(new_ssm, axis=0)

    x = L.apply_norm(p["ln_f"], cfg, x)
    logits = planned_dense(x, p["lm_head"].astype(x.dtype),
                           site="lm_head")[:, 0]
    return logits, new_pools


def decode_step(p, cfg, cache, tokens):
    """Contiguous-cache decode: tokens [B,1] -> (logits [B,V], cache).
    No engine serves it; it is the plain reference the paged engine is
    tested against."""
    b = tokens.shape[0]
    pos = cache["pos"]
    x = p["embed"][tokens].astype(L._dtype(cfg))
    napp = n_attn_apps(cfg)
    conv_dt = cache["conv"].dtype

    def blk_body(x, inp):
        lp, conv_c, ssm_c = inp
        h = L.apply_norm(lp["ln"], cfg, x)
        mc = {"conv": conv_c.astype(jnp.float32), "ssm": ssm_c}
        out, mc = SSM.apply_mamba_step(lp["mamba"], cfg, h, mc)
        return x + out, (mc["conv"].astype(conv_dt), mc["ssm"])

    if napp == 0:
        x, (new_conv, new_ssm) = jax.lax.scan(
            blk_body, x, (p["trunk"], cache["conv"], cache["ssm"]),
            unroll=cfg.scan_unroll)
        new_cache = {"conv": new_conv, "ssm": new_ssm, "pos": pos + 1}
    else:
        every = cfg.attn_every
        n_seg = cfg.n_layers // every
        seg = jax.tree.map(
            lambda a: a[: n_seg * every].reshape(
                (n_seg, every) + a.shape[1:]),
            (p["trunk"], cache["conv"], cache["ssm"]))
        new_conv, new_ssm, new_k, new_v = [], [], [], []
        for si in range(n_seg):
            seg_i = jax.tree.map(lambda a: a[si], seg)
            x, (nc, ns_) = jax.lax.scan(blk_body, x, seg_i,
                                        unroll=cfg.scan_unroll)
            new_conv.append(nc)
            new_ssm.append(ns_)
            h = L.apply_norm(p["shared"]["ln1"], cfg, x)
            attn, ck, cv = L.apply_attention_decode(
                p["shared"]["attn"], cfg, h, cache["k"][si],
                cache["v"][si], pos)
            new_k.append(ck)
            new_v.append(cv)
            x = x + attn
            h = L.apply_norm(p["shared"]["ln2"], cfg, x)
            x = x + L.apply_mlp(p["shared"]["mlp"], cfg, h)
        rem = cfg.n_layers - n_seg * every
        if rem:
            tail = jax.tree.map(
                lambda a: a[n_seg * every:],
                (p["trunk"], cache["conv"], cache["ssm"]))
            x, (nc, ns_) = jax.lax.scan(blk_body, x, tail,
                                        unroll=cfg.scan_unroll)
            new_conv.append(nc)
            new_ssm.append(ns_)
        new_cache = {
            "k": jnp.stack(new_k),
            "v": jnp.stack(new_v),
            "conv": jnp.concatenate(new_conv, axis=0),
            "ssm": jnp.concatenate(new_ssm, axis=0),
            "pos": pos + 1,
        }

    x = L.apply_norm(p["ln_f"], cfg, x)
    logits = planned_dense(x, p["lm_head"].astype(x.dtype),
                           site="lm_head")[:, 0]
    return logits, new_cache
