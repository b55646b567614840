"""Plain float32 reference of a Qwen1.5 / Qwen2 dense decoder.

Follows the published architecture (Hugging Face ``Qwen2ForCausalLM``):
token embedding; per layer RMSNorm -> attention with biased Q/K/V
projections, rotary position embedding (``rotate_half`` form, base
``rope_theta``) and causal softmax over all heads (K/V heads repeated for
grouped-query attention), unbiased output projection, residual ->
RMSNorm -> SwiGLU MLP (``down(silu(gate(x)) * up(x))``), residual; final
RMSNorm; output head tied to the embedding where the config says so.

No kernels, cache or batching: one sequence at a time, every matmul in
float32 at ``Precision.HIGHEST``.  Departures from the published model:
none in the mathematics; weights are random (made by the benchmark from
the seed) and read from the serving program's parameter layout by key
name (``embed``, ``ln_f``, ``dense_layers/{ln1,ln2,attn,mlp}``).

``quant="fp8"`` is the control of a bfloat16 model: the same forward
with both operands of every matmul rounded to float8 e4m3 (weights per
output channel, activations per row, each scaled to the format's largest
value).  ``quant="bf16"`` rounds them to bfloat16, the control of a
float32 model.
Imports nothing of the program.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
F8_MAX = 448.0


def _fp8(x, axis):
    """Round to float8 e4m3 with an absmax scale along ``axis``."""
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / F8_MAX
    scale = jnp.where(scale > 0, scale, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _bf16(x, axis):
    return x.astype(jnp.bfloat16).astype(jnp.float32)


ROUND = {"fp8": _fp8, "bf16": _bf16}


def _mm(x, w, quant):
    """x [..., k] @ w [k, n] in float32."""
    if quant:
        x, w = ROUND[quant](x, -1), ROUND[quant](w, 0)
    return jnp.matmul(x, w, precision=HIGHEST)


def _bmm(spec, a, b, quant, axes):
    if quant:
        a, b = ROUND[quant](a, axes[0]), ROUND[quant](b, axes[1])
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def rope(x, pos, theta):
    """x [S, H, hd]; pos [S]."""
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos[:, None].astype(jnp.float32) * inv          # [S, hd/2]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None]
    x1, x2 = jnp.split(x, 2, -1)
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def _layer(x, lw, c, quant):
    f32 = lambda t: t.astype(jnp.float32)  # noqa: E731
    s = x.shape[0]
    h, hkv = c["num_attention_heads"], c["num_key_value_heads"]
    hd = c["hidden_size"] // h
    eps, theta = c["rms_norm_eps"], c["rope_theta"]
    a = lw["attn"]
    y = rmsnorm(x, f32(lw["ln1"]["w"]), eps)
    q = _mm(y, f32(a["wq"]), quant) + f32(a["bq"])
    k = _mm(y, f32(a["wk"]), quant) + f32(a["bk"])
    v = _mm(y, f32(a["wv"]), quant) + f32(a["bv"])
    pos = jnp.arange(s)
    q = rope(q.reshape(s, h, hd), pos, theta)
    k = rope(k.reshape(s, hkv, hd), pos, theta)
    v = v.reshape(s, hkv, hd)
    k = jnp.repeat(k, h // hkv, axis=1)
    v = jnp.repeat(v, h // hkv, axis=1)
    logits = _bmm("qhd,khd->hqk", q, k, quant, (-1, -1)) / jnp.sqrt(
        jnp.float32(hd))
    causal = pos[:, None] >= pos[None, :]
    logits = jnp.where(causal[None], logits, -jnp.inf)
    p = jax.nn.softmax(logits, -1)
    o = _bmm("hqk,khd->qhd", p, v, quant, (-1, 0)).reshape(s, h * hd)
    x = x + _mm(o, f32(a["wo"]), quant)
    m = lw["mlp"]
    y = rmsnorm(x, f32(lw["ln2"]["w"]), eps)
    g = jax.nn.silu(_mm(y, f32(m["wg"]), quant)) * _mm(y, f32(m["wu"]),
                                                       quant)
    return x + _mm(g, f32(m["wd"]), quant)


@functools.partial(jax.jit, static_argnames=("cfg_items", "quant"))
def _logits(w, tokens, sel, cfg_items, quant):
    c = dict(cfg_items)
    x = w["embed"][tokens].astype(jnp.float32)

    def body(x, lw):
        return _layer(x, lw, c, quant), None

    x, _ = jax.lax.scan(body, x, w["dense_layers"])
    x = rmsnorm(x, w["ln_f"]["w"].astype(jnp.float32), c["rms_norm_eps"])
    head = (w["embed"].T if c["tie_word_embeddings"] else w["lm_head"])
    return _mm(x[sel], head.astype(jnp.float32), quant)


_KEYS = ("num_attention_heads", "num_key_value_heads", "hidden_size",
         "rms_norm_eps", "rope_theta", "tie_word_embeddings")


def logits(weights, config: dict, tokens, sel, quant: str | None = None):
    """Float32 logits [len(sel), vocab] of one sequence ``tokens`` [S]
    (causal, so padding after the last real token changes nothing
    before it), at the positions ``sel``."""
    items = tuple((k, config[k]) for k in _KEYS)
    return _logits(weights, jnp.asarray(tokens, jnp.int32),
                   jnp.asarray(sel, jnp.int32), items, quant)
