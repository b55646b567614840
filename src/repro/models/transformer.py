"""Decoder-only LM assembly: dense / MoE / MLA families, scanned layers.

Layer parameters are stacked along a leading L axis and executed with
``lax.scan`` — essential to keep the 512-device dry-run HLO compact (a
60-layer unrolled MoE program would take minutes to partition).  Families:

  dense  — GQA attention + GLU MLP (stablelm, qwen*, codeqwen)
  vlm    — dense backbone; patch embeddings prepended by the stub frontend
  moe    — GQA or MLA attention + MoE FFN (olmoe, deepseek-v2)

Remat policy per config (none | dots | full) wraps the scanned block.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.kernels import paged_attention as PA
from repro.kernels.planned import planned_dense
from repro.parallel.sharding import constrain
from . import layers as L
from . import mla as MLA
from . import moe as MOE


# ---------------------------------------------------------------------------
# layer init / specs
# ---------------------------------------------------------------------------

def _attn_init(key, cfg):
    if cfg.use_mla:
        return MLA.init_mla(key, cfg)
    return L.init_attention(key, cfg)


def _attn_specs(cfg):
    if cfg.use_mla:
        return MLA.mla_specs(cfg)
    return L.attention_specs(cfg)


def init_layer(key, cfg, *, moe: bool):
    k1, k2, k3 = jax.random.split(key, 3)
    p = {
        "ln1": L.init_norm(cfg),
        "attn": _attn_init(k1, cfg),
        "ln2": L.init_norm(cfg),
    }
    if moe:
        p["moe"] = MOE.init_moe(k2, cfg)
    else:
        p["mlp"] = L.init_mlp(k3, cfg)
    return p


def layer_specs(cfg, *, moe: bool):
    s = {
        "ln1": L.norm_specs(cfg),
        "attn": _attn_specs(cfg),
        "ln2": L.norm_specs(cfg),
    }
    if moe:
        s["moe"] = MOE.moe_specs(cfg)
    else:
        s["mlp"] = L.mlp_specs(cfg)
    return s


def _stack_init(key, cfg, n, *, moe: bool):
    keys = jax.random.split(key, max(n, 1))
    if n == 0:
        return None
    return jax.vmap(lambda k: init_layer(k, cfg, moe=moe))(keys)


def _stacked_specs(cfg, *, moe: bool):
    """Prepend the (unsharded) layer axis to every leaf's logical axes."""
    base = layer_specs(cfg, moe=moe)
    return jax.tree.map(
        lambda ax: ("layers",) + ax, base,
        is_leaf=lambda x: isinstance(x, tuple),
    )


# ---------------------------------------------------------------------------
# block application
# ---------------------------------------------------------------------------

def _apply_block(p, cfg, x, positions, *, moe: bool):
    h = L.apply_norm(p["ln1"], cfg, x)
    if cfg.use_mla:
        attn = MLA.apply_mla(p["attn"], cfg, h, positions)
    else:
        attn = L.apply_attention(p["attn"], cfg, h, positions)
    x = x + attn
    h = L.apply_norm(p["ln2"], cfg, x)
    if moe:
        y, aux, _ = MOE.apply_moe(p["moe"], cfg, h)
    else:
        y, aux = L.apply_mlp(p["mlp"], cfg, h), jnp.zeros((), jnp.float32)
    x = x + y
    if cfg.seq_parallel:
        # Megatron-SP: residual stream sequence-sharded between blocks —
        # the TP combine becomes reduce-scatter + all-gather pairs
        x = constrain(x, "batch", "seq_sp", None)
    else:
        x = constrain(x, "batch", None, None)
    return x, aux


def _maybe_remat(fn, cfg):
    if cfg.remat == "full":
        return jax.checkpoint(fn)
    if cfg.remat == "dots":
        return jax.checkpoint(
            fn,
            policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
        )
    return fn


def _scan_blocks(stacked, cfg, x, positions, *, moe: bool):
    if stacked is None:
        return x, jnp.zeros((), jnp.float32)

    def body(carry, lp):
        x, aux = carry
        x, a = _apply_block(lp, cfg, x, positions, moe=moe)
        return (x, aux + a), None

    body = _maybe_remat(body, cfg)
    (x, aux), _ = jax.lax.scan(
        body, (x, jnp.zeros((), jnp.float32)), stacked,
        unroll=cfg.scan_unroll)
    return x, aux


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------

def init_params(key, cfg):
    ks = jax.random.split(key, 4)
    dt = L._dtype(cfg)
    n_dense, n_moe = _layer_split(cfg)
    p = {
        "embed": (jax.random.normal(ks[0], (cfg.vocab, cfg.d_model),
                                    jnp.float32) * 0.02).astype(dt),
        "ln_f": L.init_norm(cfg),
        "dense_layers": _stack_init(ks[1], cfg, n_dense, moe=False),
        "moe_layers": _stack_init(ks[2], cfg, n_moe, moe=True),
    }
    if not cfg.tie_embeddings:
        p["lm_head"] = L.dense_init(ks[3], cfg.d_model, cfg.vocab, dt)
    if cfg.vlm_patches:
        p["patch_proj"] = L.dense_init(
            jax.random.fold_in(ks[3], 7), cfg.d_model, cfg.d_model, dt)
    return {k: v for k, v in p.items() if v is not None}


def param_specs(cfg):
    n_dense, n_moe = _layer_split(cfg)
    s = {
        "embed": ("vocab", "d_model"),
        "ln_f": L.norm_specs(cfg),
    }
    if n_dense:
        s["dense_layers"] = _stacked_specs(cfg, moe=False)
    if n_moe:
        s["moe_layers"] = _stacked_specs(cfg, moe=True)
    if not cfg.tie_embeddings:
        s["lm_head"] = ("d_model", "vocab")
    if cfg.vlm_patches:
        s["patch_proj"] = ("d_model", None)
    return s


def _layer_split(cfg) -> tuple[int, int]:
    if cfg.family == "moe":
        return cfg.moe_first_dense, cfg.n_layers - cfg.moe_first_dense
    return cfg.n_layers, 0


def embed_tokens(p, cfg, tokens, extra_embeds=None):
    """tokens [B,S_text] (+ optional [B,P,d] patch embeds prepended)."""
    x = p["embed"][tokens].astype(L._dtype(cfg))
    if extra_embeds is not None:
        pe = extra_embeds.astype(x.dtype)
        if "patch_proj" in p:
            pe = planned_dense(pe, p["patch_proj"], site="vlm.patch_proj")
        x = jnp.concatenate([pe, x], axis=1)
    return constrain(x, "batch", None, None)


def forward(p, cfg, tokens, extra_embeds=None):
    """Full-sequence forward -> (hidden [B,S,d], aux_loss)."""
    x = embed_tokens(p, cfg, tokens, extra_embeds)
    positions = jnp.broadcast_to(
        jnp.arange(x.shape[1]), (x.shape[0], x.shape[1]))
    x, aux1 = _scan_blocks(p.get("dense_layers"), cfg, x, positions,
                           moe=False)
    x, aux2 = _scan_blocks(p.get("moe_layers"), cfg, x, positions, moe=True)
    x = L.apply_norm(p["ln_f"], cfg, x)
    return x, aux1 + aux2


def logits_fn(p, cfg, hidden):
    head = p["embed"].T if cfg.tie_embeddings else p["lm_head"]
    logits = planned_dense(hidden, head.astype(hidden.dtype),
                           site="lm_head")
    return constrain(logits, "batch", None, "vocab")


def loss_fn(p, cfg, batch):
    """batch: {tokens [B,S], labels [B,S], (extra_embeds)}.

    labels hold the next token; positions with label < 0 are masked.
    For VLM, labels cover only the text region (patch positions excluded).
    """
    tokens = batch["tokens"]
    labels = batch["labels"]
    hidden, aux = forward(p, cfg, tokens, batch.get("extra_embeds"))
    if cfg.vlm_patches:
        hidden = hidden[:, -tokens.shape[1]:]  # text region only
    lbl = jnp.maximum(labels, 0)
    mask = (labels >= 0).astype(jnp.float32)
    if cfg.logit_chunk and hidden.shape[1] > cfg.logit_chunk:
        nch = hidden.shape[1] // cfg.logit_chunk
        hs = hidden.reshape(hidden.shape[0], nch, cfg.logit_chunk, -1)
        ls = lbl.reshape(lbl.shape[0], nch, cfg.logit_chunk)
        ms = mask.reshape(mask.shape[0], nch, cfg.logit_chunk)

        def chunk(carry, inp):
            h, l, m = inp
            lg = logits_fn(p, cfg, h.swapaxes(0, 0))
            ll = _xent(lg, l) * m
            return carry + ll.sum(), None

        hs = jnp.moveaxis(hs, 1, 0)
        ls = jnp.moveaxis(ls, 1, 0)
        ms = jnp.moveaxis(ms, 1, 0)
        total, _ = jax.lax.scan(chunk, jnp.zeros((), jnp.float32),
                                (hs, ls, ms))
    else:
        logits = logits_fn(p, cfg, hidden)
        total = (_xent(logits, lbl) * mask).sum()
    denom = jnp.maximum(mask.sum(), 1.0)
    return total / denom + 1e-2 * aux


def _xent(logits, labels):
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(
        logits, labels[..., None], axis=-1
    )[..., 0]
    return lse - picked


# ---------------------------------------------------------------------------
# serving: prefill + decode with stacked-layer caches
# ---------------------------------------------------------------------------

def init_cache(cfg, batch, max_seq, dtype=jnp.bfloat16):
    n_dense, n_moe = _layer_split(cfg)
    L_total = cfg.n_layers
    if cfg.use_mla:
        return {
            "ckv": jnp.zeros(
                (L_total, batch, max_seq, cfg.kv_lora_rank), dtype),
            "kr": jnp.zeros(
                (L_total, batch, max_seq, cfg.rope_head_dim), dtype),
            "pos": jnp.zeros((batch,), jnp.int32),
        }
    return {
        "k": jnp.zeros(
            (L_total, batch, max_seq, cfg.n_kv_heads, cfg.hd), dtype),
        "v": jnp.zeros(
            (L_total, batch, max_seq, cfg.n_kv_heads, cfg.hd), dtype),
        "pos": jnp.zeros((batch,), jnp.int32),
    }


def cache_specs(cfg):
    if cfg.use_mla:
        return {
            "ckv": ("layers", "batch", None, None),
            "kr": ("layers", "batch", None, None),
            "pos": ("batch",),
        }
    return {
        "k": ("layers", "batch", None, "kv_heads", None),
        "v": ("layers", "batch", None, "kv_heads", None),
        "pos": ("batch",),
    }


def _decode_blocks(stacked, cfg, x, cache_slices, pos, *, moe: bool,
                   layer_offset: int):
    """Scan one token through a stacked block group, updating its caches."""
    if stacked is None:
        return x, cache_slices

    def body(x, inp):
        lp, cs = inp
        h = L.apply_norm(lp["ln1"], cfg, x)
        if cfg.use_mla:
            attn, ckv, kr = MLA.apply_mla_decode(
                lp["attn"], cfg, h, cs["ckv"], cs["kr"], pos)
            new_cs = {"ckv": ckv, "kr": kr}
        else:
            attn, ck, cv = L.apply_attention_decode(
                lp["attn"], cfg, h, cs["k"], cs["v"], pos)
            new_cs = {"k": ck, "v": cv}
        x = x + attn
        h = L.apply_norm(lp["ln2"], cfg, x)
        if moe:
            y, _, _ = MOE.apply_moe(lp["moe"], cfg, h)
        else:
            y = L.apply_mlp(lp["mlp"], cfg, h)
        return x + y, new_cs

    x, new_caches = jax.lax.scan(body, x, (stacked, cache_slices),
                                 unroll=cfg.scan_unroll)
    return x, new_caches


def decode_step(p, cfg, cache, tokens):
    """Contiguous-cache decode: tokens [B,1] -> (logits [B,V], new
    cache).  No engine serves it; it is the plain reference the paged
    engine is tested against and what ``launch.dryrun`` lowers."""
    pos = cache["pos"]
    x = embed_tokens(p, cfg, tokens)
    n_dense, n_moe = _layer_split(cfg)

    def slices(lo, hi):
        return {
            k: v[lo:hi] for k, v in cache.items() if k != "pos"
        }

    x, cs_dense = _decode_blocks(
        p.get("dense_layers"), cfg, x, slices(0, n_dense), pos,
        moe=False, layer_offset=0)
    x, cs_moe = _decode_blocks(
        p.get("moe_layers"), cfg, x, slices(n_dense, cfg.n_layers), pos,
        moe=True, layer_offset=n_dense)
    x = L.apply_norm(p["ln_f"], cfg, x)
    logits = logits_fn(p, cfg, x)[:, 0]

    new_cache = {"pos": pos + 1}
    for k in cache:
        if k == "pos":
            continue
        parts = []
        if cs_dense is not None and n_dense:
            parts.append(cs_dense[k])
        if cs_moe is not None and n_moe:
            parts.append(cs_moe[k])
        new_cache[k] = jnp.concatenate(parts, axis=0) if len(parts) > 1 \
            else parts[0]
    return logits, new_cache


def paged_layout(cfg) -> dict:
    """Leaf kinds for the block-paged serving cache: ``paged`` leaves are
    [L, NB, bs, ...] block pools indexed per-lane through block tables;
    there are no per-lane leaves for this family."""
    if cfg.use_mla:
        return {"ckv": "paged", "kr": "paged"}
    return {"k": "paged", "v": "paged"}


def init_paged_pools(cfg, num_blocks, block_size, max_lanes,
                     dtype=jnp.bfloat16):
    L_total = cfg.n_layers
    del max_lanes  # no per-lane state in this family
    if cfg.use_mla:
        return {
            "ckv": jnp.zeros(
                (L_total, num_blocks, block_size, cfg.kv_lora_rank),
                dtype),
            "kr": jnp.zeros(
                (L_total, num_blocks, block_size, cfg.rope_head_dim),
                dtype),
        }
    # one K/V row is all heads' Hkv*hd values: a [bs, Hkv*hd] block then
    # fills whole (16, 128) bf16 TPU tiles and the pools keep a row-major
    # layout, where a trailing [Hkv, hd] lays the block axis minor-most
    # and every gather and scatter relayouts the whole pool first
    shape = (L_total, num_blocks, block_size, cfg.n_kv_heads * cfg.hd)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def paged_kernel(cfg, pools) -> bool:
    """Whether ``decode_step_paged`` attends through
    ``kernels.paged_attention`` on these pools (MLA's latent pools keep
    their own path)."""
    return "k" in pools and PA.engages(pools["k"], cfg)


def moe_inplace(p, lanes: int) -> bool:
    """Whether ``decode_step_paged`` of ``lanes`` lanes computes its
    MoE layers' held experts in place from the stacked parameters
    (``moe.inplace_stacks``)."""
    moe = p.get("moe_layers")
    return moe is not None and MOE.inplace_stacks(moe["moe"], lanes) \
        is not None


def _decode_blocks_paged(stacked, cfg, x, pools, layers, block_tables,
                         pos, active, *, moe: bool):
    """Paged twin of ``_decode_blocks``.  The stacked [L, NB, bs, ...]
    pools stay whole and loop-invariant: the scan's inputs are the layer
    parameters and the layer indices ``layers``, each layer gathers its
    lanes' rows straight from the stacked pools, and the scan returns
    each layer's new rows ([n, B, ...] per leaf) for the caller to write
    once.  Pools passed as the scan's inputs and outputs would be sliced
    out and written back whole, layer by layer, on every step.  MoE
    layers route only the active lanes; the scan also returns each MoE
    layer's count of (token, held expert) assignments (None for dense
    layers).  Where ``moe.inplace_stacks`` accepts them, the held
    experts' stacks leave the scan's inputs too and stay whole and
    loop-invariant: each layer's expert matmuls read its weights in
    place (``kernels.held_experts``), at index ``layer - layers[0]``,
    instead of from a slice the scan copies out."""
    valid = active[:, None]
    stacks = MOE.inplace_stacks(stacked["moe"], x.shape[0]) if moe \
        else None
    if stacks is not None:
        stacked = dict(stacked, moe={k: v for k, v in stacked["moe"].items()
                                     if k not in stacks})

    def body(x, inp):
        lp, layer = inp
        h = L.apply_norm(lp["ln1"], cfg, x)
        if cfg.use_mla:
            attn, ckv, kr = MLA.apply_mla_decode_paged(
                lp["attn"], cfg, h, pools["ckv"], pools["kr"], layer,
                block_tables, pos)
            rows = {"ckv": ckv, "kr": kr}
        else:
            attn, k, v = L.apply_attention_decode_stacked(
                lp["attn"], cfg, h, pools["k"], pools["v"], layer,
                block_tables, pos, active)
            rows = {"k": k, "v": v}
        x = x + attn
        h = L.apply_norm(lp["ln2"], cfg, x)
        held = None
        if moe:
            at = None if stacks is None else (stacks, layer - layers[0])
            y, _, held = MOE.apply_moe(lp["moe"], cfg, h, valid=valid,
                                       held_at=at)
        else:
            y = L.apply_mlp(lp["mlp"], cfg, h)
        return x + y, (rows, held)

    return jax.lax.scan(body, x, (stacked, layers), unroll=cfg.scan_unroll)


def decode_step_paged(p, cfg, pools, tokens, block_tables, pos, active):
    """Block-paged decode: tokens [B,1]; block_tables [B,T] int32; pos
    [B] int32; active [B] bool -> (logits [B,V], new pools), and for an
    MoE model a third output: the step's (token, held expert) assignments
    of the active lanes, summed over the MoE layers (int32 scalar).

    ``pos``/tables/``active`` are host-owned inputs (the engine advances
    pos and edits tables between steps), so the compiled executable's
    shapes never depend on which requests are in flight.  The layer scans
    only read the pools; every layer's new row is written after them in
    one scatter per pool, in place where the caller donates the pools."""
    x = embed_tokens(p, cfg, tokens)
    n_dense, _ = _layer_split(cfg)
    groups = (("dense_layers", 0, n_dense, False),
              ("moe_layers", n_dense, cfg.n_layers, True))
    parts = []
    held = []
    for name, lo, hi, moe in groups:
        if p.get(name) is None:
            continue
        x, (rows, n) = _decode_blocks_paged(
            p[name], cfg, x, pools, jnp.arange(lo, hi), block_tables, pos,
            active, moe=moe)
        if moe:
            held.append(jnp.sum(n))
        parts.append(rows)
    x = L.apply_norm(p["ln_f"], cfg, x)
    logits = logits_fn(p, cfg, x)[:, 0]

    rows = jax.tree.map(lambda *r: jnp.concatenate(r), *parts)
    new_pools = {
        k: L.paged_write_layers(pool, rows[k], block_tables, pos, active)
        for k, pool in pools.items()}
    if held:
        return logits, new_pools, sum(held)
    return logits, new_pools


def prefill(p, cfg, tokens, max_seq, cache_dtype=jnp.bfloat16,
            extra_embeds=None, last_index=None):
    """Run the full prompt, build the cache, return last-token logits.

    Structured as one forward pass (XLA-friendly) that also extracts K/V.
    For simplicity and HLO compactness we re-run QKV per layer inside the
    same scan used by ``forward`` but additionally emit cache entries.

    ``last_index`` ([B] int32, optional) supports *bucketed* prefill:
    ``tokens`` may be right-padded to a bucket length and logits are then
    taken at each lane's last valid token instead of position -1, with
    ``cache["pos"]`` set past it.  Pad rows land in the cache but the
    decode mask (``kpos <= pos``) hides them until overwritten, and MoE
    layers route no pad row to an expert (routing is dropless, so a real
    row's output never depends on the others).
    """
    b, s = tokens.shape
    x = embed_tokens(p, cfg, tokens, extra_embeds)
    positions = jnp.broadcast_to(jnp.arange(x.shape[1]), x.shape[:2])
    cache = init_cache(cfg, b, max_seq, cache_dtype)
    # rows past each lane's last valid token are bucket padding: MoE
    # layers give them no expert assignment
    valid = None if last_index is None else (
        positions <= (x.shape[1] - s + last_index)[:, None])

    def mk_body(moe: bool):
        def body(x, lp):
            h = L.apply_norm(lp["ln1"], cfg, x)
            if cfg.use_mla:
                ckv, kr = MLA._latent(lp["attn"], cfg, h, positions)
                attn = MLA.apply_mla(lp["attn"], cfg, h, positions)
                entry = {"ckv": ckv.astype(cache_dtype),
                         "kr": kr.astype(cache_dtype)}
            else:
                q, k, v = L._qkv(lp["attn"], cfg, h, positions)
                attn = L.attention_core(q, k, v, causal=True)
                attn = planned_dense(
                    attn.reshape(b, x.shape[1], -1), lp["attn"]["wo"],
                    site="attn.out")
                entry = {"k": k.astype(cache_dtype),
                         "v": v.astype(cache_dtype)}
            x = x + attn
            h = L.apply_norm(lp["ln2"], cfg, x)
            if moe:
                y, _, _ = MOE.apply_moe(lp["moe"], cfg, h, valid=valid)
            else:
                y = L.apply_mlp(lp["mlp"], cfg, h)
            return x + y, entry

        return body

    entries = []
    if p.get("dense_layers") is not None:
        x, e = jax.lax.scan(mk_body(False), x, p["dense_layers"],
                            unroll=cfg.scan_unroll)
        entries.append(e)
    if p.get("moe_layers") is not None:
        x, e = jax.lax.scan(mk_body(True), x, p["moe_layers"],
                            unroll=cfg.scan_unroll)
        entries.append(e)
    x = L.apply_norm(p["ln_f"], cfg, x)
    if last_index is None:
        sel = x[:, -1:]
        pos = jnp.full((b,), x.shape[1], jnp.int32)
    else:
        # last valid *text* token per lane; offset covers prepended
        # patch embeds (vlm) so the gather indexes the hidden sequence
        off = x.shape[1] - s
        idx = (off + last_index).astype(jnp.int32)
        sel = jnp.take_along_axis(x, idx[:, None, None], axis=1)
        pos = idx + 1
    logits = logits_fn(p, cfg, sel)[:, 0]

    for key in cache:
        if key == "pos":
            continue
        stacked = jnp.concatenate([e[key] for e in entries], axis=0) \
            if len(entries) > 1 else entries[0][key]
        pad_width = [(0, 0)] * stacked.ndim
        pad_width[2] = (0, max_seq - stacked.shape[2])
        cache[key] = jnp.pad(stacked, pad_width).astype(cache_dtype)
    cache["pos"] = pos
    return logits, cache
