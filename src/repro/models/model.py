"""Unified model API over all families — the contract used by train/serve/
dry-run.

    api = build_model(cfg)
    params = api.init(key)
    loss   = api.loss(params, batch)
    logits, cache = api.prefill(params, batch, max_seq)
    logits, cache = api.decode(params, cache, tokens)

``decode``/``init_cache`` are the contiguous-cache decode: no engine
serves them (``serve.make_engine`` runs ``paged_decode`` over block
pools); they are the plain per-request reference the engine's tests
compare against, and what ``launch.dryrun`` lowers.

``batch_specs(shape)`` returns ShapeDtypeStructs for every model input — the
dry-run feeds these to jit.lower (no allocation), and the data pipeline
materializes matching arrays.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, ShapeSpec
from . import encdec as ENCDEC
from . import hybrid as HYBRID
from . import transformer as TFM


_CACHE_DTYPES = {
    "bfloat16": jnp.bfloat16,
    "float32": jnp.float32,
    "float8_e4m3fn": jnp.float8_e4m3fn,
}


def cache_dtype_of(cfg) -> "jnp.dtype":
    return _CACHE_DTYPES[cfg.kv_cache_dtype]


@dataclasses.dataclass
class ModelAPI:
    cfg: ModelConfig
    init: Callable
    param_logical: Callable
    loss: Callable
    prefill: Callable
    decode: Callable
    init_cache: Callable
    cache_logical: Callable
    batch_specs: Callable
    batch_logical: Callable
    # block-paged serving (continuous batching); every family provides
    # them.  paged_layout() maps cache leaf -> "paged" (block pool,
    # [L, NB, bs, ...]) or "lane" ([L, max_lanes, ...] resident state);
    # paged_decode(p, pools, tokens, block_tables, pos, active) keeps
    # pos/tables/active host-owned so its compiled shape never changes;
    # it returns (logits, new pools), and an MoE model's step its count
    # of (token, held expert) assignments as a third output.
    paged_init: Callable = None
    paged_decode: Callable = None
    paged_layout: Callable = None
    # paged_kernel(pools) -> whether paged_decode attends through
    # kernels.paged_attention on these pools (None: never)
    paged_kernel: Callable = None
    # paged_moe_inplace(params, lanes) -> whether paged_decode's MoE
    # layers read the held experts in place (kernels.held_experts;
    # None: never)
    paged_moe_inplace: Callable = None
    # streaming (chunked) admission — encdec only.  enc_init(b, f_max)
    # builds the incremental encoder state; enc_step(p, ec, frames_chunk)
    # appends one chunk and returns its encoder states; enc_kv(p, enc)
    # projects a chunk to per-decoder-layer cross K/V; stream_prefill(p,
    # enc_k, enc_v, enc_len, tokens, max_seq, last_index) is the
    # decoder-only prompt pass against a partially-filled enc cache.
    enc_init: Callable = None
    enc_step: Callable = None
    enc_kv: Callable = None
    stream_prefill: Callable = None


def _token_batch_specs(cfg, shape: ShapeSpec):
    b, s = shape.global_batch, shape.seq_len
    if cfg.family == "vlm":
        s_text = s - cfg.vlm_patches
        return {
            "tokens": jax.ShapeDtypeStruct((b, s_text), jnp.int32),
            "labels": jax.ShapeDtypeStruct((b, s_text), jnp.int32),
            "extra_embeds": jax.ShapeDtypeStruct(
                (b, cfg.vlm_patches, cfg.d_model), jnp.bfloat16),
        }
    if cfg.family == "encdec":
        return {
            "frames": jax.ShapeDtypeStruct(
                (b, cfg.enc_frames, cfg.d_model), jnp.bfloat16),
            "tokens": jax.ShapeDtypeStruct((b, s), jnp.int32),
            "labels": jax.ShapeDtypeStruct((b, s), jnp.int32),
        }
    return {
        "tokens": jax.ShapeDtypeStruct((b, s), jnp.int32),
        "labels": jax.ShapeDtypeStruct((b, s), jnp.int32),
    }


def _token_batch_logical(cfg):
    base = {
        "tokens": ("batch", None),
        "labels": ("batch", None),
    }
    if cfg.family == "vlm":
        base["extra_embeds"] = ("batch", None, None)
    if cfg.family == "encdec":
        base["frames"] = ("batch", None, None)
    return base


def build_model(cfg: ModelConfig) -> ModelAPI:
    if cfg.family in ("dense", "moe", "vlm"):
        def loss(p, batch):
            return TFM.loss_fn(p, cfg, batch)

        def prefill(p, batch, max_seq, last_index=None):
            return TFM.prefill(
                p, cfg, batch["tokens"], max_seq,
                cache_dtype=cache_dtype_of(cfg),
                extra_embeds=batch.get("extra_embeds"),
                last_index=last_index)

        def decode(p, cache, tokens):
            return TFM.decode_step(p, cfg, cache, tokens)

        return ModelAPI(
            cfg=cfg,
            init=lambda key: TFM.init_params(key, cfg),
            param_logical=lambda: TFM.param_specs(cfg),
            loss=loss,
            prefill=prefill,
            decode=decode,
            init_cache=lambda b, s: TFM.init_cache(
                cfg, b, s, cache_dtype_of(cfg)),
            cache_logical=lambda: TFM.cache_specs(cfg),
            batch_specs=lambda shape: _token_batch_specs(cfg, shape),
            batch_logical=lambda: _token_batch_logical(cfg),
            paged_init=lambda nb, bs, lanes: TFM.init_paged_pools(
                cfg, nb, bs, lanes, cache_dtype_of(cfg)),
            paged_decode=lambda p, pools, t, bt, pos, act:
                TFM.decode_step_paged(p, cfg, pools, t, bt, pos, act),
            paged_layout=lambda: TFM.paged_layout(cfg),
            paged_kernel=lambda pools: TFM.paged_kernel(cfg, pools),
            paged_moe_inplace=TFM.moe_inplace,
        )

    if cfg.family in ("ssm", "hybrid"):
        def loss(p, batch):
            return HYBRID.loss_fn(p, cfg, batch)

        def prefill(p, batch, max_seq, last_index=None):
            if last_index is not None:
                raise ValueError(
                    "bucketed (padded) prefill is not supported for "
                    "ssm/hybrid: the recurrent SSM state would absorb "
                    "pad tokens; prefill at the exact prompt length")
            return HYBRID.prefill(p, cfg, batch["tokens"], max_seq,
                                  cache_dtype=cache_dtype_of(cfg))

        def decode(p, cache, tokens):
            return HYBRID.decode_step(p, cfg, cache, tokens)

        return ModelAPI(
            cfg=cfg,
            init=lambda key: HYBRID.init_params(key, cfg),
            param_logical=lambda: HYBRID.param_specs(cfg),
            loss=loss,
            prefill=prefill,
            decode=decode,
            init_cache=lambda b, s: HYBRID.init_cache(
                cfg, b, s, cache_dtype_of(cfg)),
            cache_logical=lambda: HYBRID.cache_specs(cfg),
            batch_specs=lambda shape: _token_batch_specs(cfg, shape),
            batch_logical=lambda: _token_batch_logical(cfg),
            paged_init=lambda nb, bs, lanes: HYBRID.init_paged_pools(
                cfg, nb, bs, lanes, cache_dtype_of(cfg)),
            paged_decode=lambda p, pools, t, bt, pos, act:
                HYBRID.decode_step_paged(p, cfg, pools, t, bt, pos, act),
            paged_layout=lambda: HYBRID.paged_layout(cfg),
        )

    if cfg.family == "encdec":
        def loss(p, batch):
            return ENCDEC.loss_fn(p, cfg, batch)

        def prefill(p, batch, max_seq, last_index=None):
            return ENCDEC.prefill(
                p, cfg, batch["frames"], batch["tokens"], max_seq,
                cache_dtype=cache_dtype_of(cfg), last_index=last_index)

        def decode(p, cache, tokens):
            return ENCDEC.decode_step(p, cfg, cache, tokens)

        return ModelAPI(
            cfg=cfg,
            init=lambda key: ENCDEC.init_params(key, cfg),
            param_logical=lambda: ENCDEC.param_specs(cfg),
            loss=loss,
            prefill=prefill,
            decode=decode,
            init_cache=lambda b, s: ENCDEC.init_cache(
                cfg, b, s, dtype=cache_dtype_of(cfg)),
            cache_logical=lambda: ENCDEC.cache_specs(cfg),
            batch_specs=lambda shape: _token_batch_specs(cfg, shape),
            batch_logical=lambda: _token_batch_logical(cfg),
            paged_init=lambda nb, bs, lanes: ENCDEC.init_paged_pools(
                cfg, nb, bs, lanes, cache_dtype_of(cfg)),
            paged_decode=lambda p, pools, t, bt, pos, act:
                ENCDEC.decode_step_paged(p, cfg, pools, t, bt, pos, act),
            paged_layout=lambda: ENCDEC.paged_layout(cfg),
            enc_init=lambda b, f_max=None: ENCDEC.init_enc_cache(
                cfg, b, f_max),
            enc_step=lambda p, ec, fc: ENCDEC.encode_chunk(p, cfg, ec, fc),
            enc_kv=lambda p, enc: ENCDEC.enc_kv_chunk(
                p, cfg, enc, cache_dtype_of(cfg)),
            stream_prefill=lambda p, ek, ev, el, tk, ms, last_index=None:
                ENCDEC.prefill_decoder(
                    p, cfg, ek, ev, el, tk, ms,
                    cache_dtype=cache_dtype_of(cfg),
                    last_index=last_index),
        )

    raise ValueError(f"unknown family {cfg.family}")
