"""Plain float32 reference of a DeepSeek-V2 decoder (multi-head latent
attention, routed and shared experts), and the operations and bytes of
its serving steps.

Follows the published architecture (``DeepseekV2ForCausalLM`` of the
model's own ``modeling_deepseek.py``): token embedding; per layer
RMSNorm -> latent attention -> residual -> RMSNorm -> MLP -> residual;
final RMSNorm; an untied output head.

  attention  q = x W_q (no q_lora: ``q_lora_rank`` null), split per head
             into nope and rope parts; c = RMSNorm(x W_dkv) the latent,
             k_rope = x W_kr shared by the heads; keys [c W_uk ; k_rope],
             values c W_uv (the expanded form: this reference never
             absorbs W_uk or W_uv, so that the served decode's absorption
             is checked); rope at YaRN's frequencies on q_rope and k_rope;
             causal softmax at (nope + rope)^-0.5 * m^2, m = 0.1 *
             mscale_all_dim * ln(factor) + 1; output W_o.
  MLP        the first ``first_k_dense_replace`` layers: SwiGLU of
             ``intermediate_size``.  The others: a softmax router over
             ``router_experts``, greedy top-k, renormalised only where
             ``norm_topk_prob`` (else scaled by ``routed_scaling_factor``),
             the routed experts' SwiGLU of ``moe_intermediate_size``
             weighted, plus the shared experts (one SwiGLU of
             ``n_shared_experts * moe_intermediate_size``).

One chip's share: the configuration holds ``n_routed_experts`` of the
router's ``router_experts`` (experts 0 to n-1); only those add, exactly
as the program leaves the others to other chips.  Shared experts, the
dense layer and the vocabulary are whole.

No kernels, cache or batching: one sequence at a time, every matmul in
float32 at ``Precision.HIGHEST``, each layer's weights cast to float32
inside the layer scan (so that the whole model is never held in float32
beside the program's).  Departures from the published model: the rope
rotates the two halves of the rope dims, as the program does, where the
published code rotates interleaved pairs; that is a fixed permutation of
the rope columns of ``wq`` and ``wkr``, which a checkpoint loader would
apply.  Weights are random (made by the benchmark from the seed) and
read from the serving program's parameter layout by key name
(``embed``, ``lm_head``, ``ln_f``, ``dense_layers`` and ``moe_layers``
with ``ln1``, ``ln2``, ``attn``, ``mlp`` or ``moe``).

``quant`` rounds both operands of every matmul, as ``qwen.py`` does:
``"fp8"`` is the control of a bfloat16 model.  Imports nothing of the
program.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp

from chipbench.reference.qwen import HIGHEST, ROUND, rmsnorm

# ---------------------------------------------------------------------------
# the forward
# ---------------------------------------------------------------------------


def _mm(x, w, quant):
    if quant:
        x, w = ROUND[quant](x, -1), ROUND[quant](w, 0)
    return jnp.matmul(x, w, precision=HIGHEST)


def _bmm(spec, a, b, quant, axes):
    if quant:
        a, b = ROUND[quant](a, axes[0]), ROUND[quant](b, axes[1])
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def rope_tables(c: dict, dim: int, s: int):
    """cos, sin [S, dim/2] of positions 0..s-1: YaRN's inverse
    frequencies (the published ``DeepseekV2YarnRotaryEmbedding``) where
    the config has ``rope_scaling``, else the base ones."""
    base = c["rope_theta"]
    inv = 1.0 / base ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    scale = 1.0
    y = c.get("rope_scaling")
    if y:
        f, orig = y["factor"], y["original_max_position_embeddings"]

        def corr(rot):
            return (dim * math.log(orig / (rot * 2 * math.pi))
                    / (2 * math.log(base)))

        low = max(math.floor(corr(y["beta_fast"])), 0)
        high = min(math.ceil(corr(y["beta_slow"])), dim - 1)
        if low == high:
            high += 0.001
        ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                        / (high - low), 0.0, 1.0)
        inv = inv / f * ramp + inv * (1.0 - ramp)
        scale = yarn_mscale(f, y.get("mscale", 1)) / yarn_mscale(
            f, y.get("mscale_all_dim", 0))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv
    return jnp.cos(ang) * scale, jnp.sin(ang) * scale


def rope(x, cos, sin):
    """x [S, ..., dim]: the two halves rotated."""
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (cos.shape[-1],)
    cos, sin = cos.reshape(shape), sin.reshape(shape)
    x1, x2 = jnp.split(x, 2, -1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def attn_scale(c: dict) -> float:
    d = c["qk_nope_head_dim"] + c["qk_rope_head_dim"]
    scale = d ** -0.5
    y = c.get("rope_scaling")
    if y and y.get("mscale_all_dim"):
        scale *= yarn_mscale(y["factor"], y["mscale_all_dim"]) ** 2
    return scale


def _attention(x, a, c, tables, quant):
    s = x.shape[0]
    h, nope = c["num_attention_heads"], c["qk_nope_head_dim"]
    rdim, vd = c["qk_rope_head_dim"], c["v_head_dim"]
    q = _mm(x, a["wq"], quant).reshape(s, h, nope + rdim)
    qn, qr = q[..., :nope], rope(q[..., nope:], *tables)
    ckv = rmsnorm(_mm(x, a["wdkv"], quant), a["kv_norm"], c["rms_norm_eps"])
    kr = rope(_mm(x, a["wkr"], quant), *tables)             # [S, rope]
    kn = _mm(ckv, a["wuk"], quant).reshape(s, h, nope)
    v = _mm(ckv, a["wuv"], quant).reshape(s, h, vd)
    logits = (_bmm("qhd,khd->hqk", qn, kn, quant, (-1, -1))
              + _bmm("qhd,kd->hqk", qr, kr, quant, (-1, -1))) * attn_scale(c)
    pos = jnp.arange(s)
    logits = jnp.where((pos[:, None] >= pos[None, :])[None], logits,
                       -jnp.inf)
    p = jax.nn.softmax(logits, -1)
    o = _bmm("hqk,khd->qhd", p, v, quant, (-1, 0)).reshape(s, h * vd)
    return _mm(o, a["wo"], quant)


def _swiglu(x, wg, wu, wd, quant):
    return _mm(jax.nn.silu(_mm(x, wg, quant)) * _mm(x, wu, quant), wd, quant)


def _experts(x, m, c, quant):
    """The held experts' weighted sum plus the shared experts."""
    k = c["num_experts_per_tok"]
    probs = jax.nn.softmax(_mm(x, m["router"], quant), -1)   # [S, E]
    w, ids = jax.lax.top_k(probs, k)
    if c["norm_topk_prob"] and k > 1:
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    else:
        w = w * c["routed_scaling_factor"]
    held = m["wg"].shape[0]
    # gate [S, held]: each held expert's weight for each token (0 where
    # the token did not pick it)
    gate = jnp.sum(jnp.where(ids[..., None] == jnp.arange(held),
                             w[..., None], 0.0), axis=1)

    def one(acc, expert):
        g, wg, wu, wd = expert
        return acc + g[:, None] * _swiglu(x, wg, wu, wd, quant), None

    routed, _ = jax.lax.scan(one, jnp.zeros_like(x),
                             (gate.T, m["wg"], m["wu"], m["wd"]))
    return routed + _swiglu(x, m["shared_wg"], m["shared_wu"],
                            m["shared_wd"], quant)


def _layer(x, lw, c, tables, quant, moe):
    lw = jax.tree.map(lambda t: t.astype(jnp.float32), lw)
    eps = c["rms_norm_eps"]
    x = x + _attention(rmsnorm(x, lw["ln1"]["w"], eps), lw["attn"], c,
                       tables, quant)
    y = rmsnorm(x, lw["ln2"]["w"], eps)
    if moe:
        return x + _experts(y, lw["moe"], c, quant)
    m = lw["mlp"]
    return x + _swiglu(y, m["wg"], m["wu"], m["wd"], quant)


@functools.partial(jax.jit, static_argnames=("cfg_items", "quant"))
def _logits(w, tokens, sel, cfg_items, quant):
    c = {k: (dict(v) if isinstance(v, tuple) else v) for k, v in cfg_items}
    x = w["embed"][tokens].astype(jnp.float32)
    tables = rope_tables(c, c["qk_rope_head_dim"], tokens.shape[0])
    for group, moe in (("dense_layers", False), ("moe_layers", True)):
        if group not in w:
            continue

        def body(x, lw, moe=moe):
            return _layer(x, lw, c, tables, quant, moe), None

        x, _ = jax.lax.scan(body, x, w[group])
    x = rmsnorm(x, w["ln_f"]["w"].astype(jnp.float32), c["rms_norm_eps"])
    return _mm(x[sel], w["lm_head"].astype(jnp.float32), quant)


_KEYS = ("num_attention_heads", "qk_nope_head_dim", "qk_rope_head_dim",
         "v_head_dim", "rms_norm_eps", "rope_theta", "num_experts_per_tok",
         "norm_topk_prob", "routed_scaling_factor")


def logits(weights, config: dict, tokens, sel, quant: str | None = None):
    """Float32 logits [len(sel), vocab] of one sequence ``tokens`` [S]
    (causal, so padding after the last real token changes nothing
    before it), at the positions ``sel``."""
    items = tuple((k, config[k]) for k in _KEYS)
    y = config.get("rope_scaling")
    items += (("rope_scaling", tuple(sorted(y.items())) if y else None),)
    return _logits(weights, jnp.asarray(tokens, jnp.int32),
                   jnp.asarray(sel, jnp.int32), items, quant)


# ---------------------------------------------------------------------------
# the work
# ---------------------------------------------------------------------------

DTYPE_BYTES = {"float32": 4, "bfloat16": 2}


@dataclasses.dataclass(frozen=True)
class Work:
    """Operations and bytes of one chip's share of DeepSeek-V2 serving,
    from the published sizes.  Decode is counted in the absorbed form
    the program serves (scores and values against the latent cache),
    prefill in the expanded form (per-head keys and values)."""
    layers: int
    dense_layers: int
    d: int
    heads: int
    nope: int
    rope: int
    v_dim: int
    kv_rank: int
    dense_ff: int
    expert_ff: int
    shared_ff: int
    router: int          # experts the router scores
    held: int            # experts this chip holds
    top_k: int
    vocab: int
    weight_bytes: int    # bytes per weight element as served
    kv_bytes: int        # bytes per cache element

    @classmethod
    def from_config(cls, c: dict) -> "Work":
        held = c["n_routed_experts"]
        dt = DTYPE_BYTES[c["torch_dtype"]]
        return cls(
            layers=c["num_hidden_layers"],
            dense_layers=c["first_k_dense_replace"], d=c["hidden_size"],
            heads=c["num_attention_heads"], nope=c["qk_nope_head_dim"],
            rope=c["qk_rope_head_dim"], v_dim=c["v_head_dim"],
            kv_rank=c["kv_lora_rank"], dense_ff=c["intermediate_size"],
            expert_ff=c["moe_intermediate_size"],
            shared_ff=c["n_shared_experts"] * c["moe_intermediate_size"],
            router=c.get("router_experts", held), held=held,
            top_k=c["num_experts_per_tok"], vocab=c["vocab_size"],
            weight_bytes=dt, kv_bytes=dt)

    @property
    def moe_layers(self) -> int:
        return self.layers - self.dense_layers

    # -- parameters ---------------------------------------------------------
    def attn_params(self) -> int:
        """W_q, W_dkv, W_kr, W_uk, W_uv, W_o and the latent norm."""
        h, d = self.heads, self.d
        return (d * h * (self.nope + self.rope) + d * self.kv_rank
                + d * self.rope + self.kv_rank * h * (self.nope + self.v_dim)
                + h * self.v_dim * d + self.kv_rank)

    def expert_params(self) -> int:
        """One routed expert's SwiGLU."""
        return 3 * self.d * self.expert_ff

    def params(self) -> int:
        """Held parameters: every layer's attention and two norms, the
        dense layers' MLP, each MoE layer's router, shared experts and
        held experts, the embedding, the final norm and the head."""
        d = self.d
        per_layer = self.attn_params() + 2 * d
        moe = (d * self.router + 3 * d * self.shared_ff
               + self.held * self.expert_params())
        return (self.layers * per_layer
                + self.dense_layers * 3 * d * self.dense_ff
                + self.moe_layers * moe + 2 * self.vocab * d + d)

    def weight_bytes_total(self) -> int:
        return self.params() * self.weight_bytes

    def kv_bytes_per_token(self) -> int:
        """The latent row (kv_lora_rank values) and the rope key of one
        token over every layer: (512 + 64) x 2 B x 27 = 31,104 B."""
        return self.layers * (self.kv_rank + self.rope) * self.kv_bytes

    def touched(self, tokens: int) -> float:
        """Chance that ``tokens`` tokens route to a given expert at
        least once: 1 - (1 - top_k / router)^tokens."""
        return 1.0 - (1.0 - self.top_k / self.router) ** tokens

    def weight_bytes_read(self, tokens: int) -> float:
        """Least weight bytes a pass over ``tokens`` tokens reads: every
        weight once, except the embedding (only its ``tokens`` rows) and
        the held experts (each at its chance of being touched)."""
        experts = self.moe_layers * self.held * self.expert_params()
        embed = self.vocab * self.d
        rest = self.params() - experts - embed
        return self.weight_bytes * (
            rest + tokens * self.d + experts * self.touched(tokens))

    # -- operations ---------------------------------------------------------
    def mlp_flops(self) -> float:
        """One token's MLP FLOPs over all layers: the dense layers'
        SwiGLU; in each MoE layer the router, the shared experts and the
        expected top_k * held / router of its held experts."""
        d = self.d
        per_moe = (2 * d * self.router + 6 * d * self.shared_ff
                   + self.top_k * self.held / self.router * 6 * d
                   * self.expert_ff)
        return (self.dense_layers * 6 * d * self.dense_ff
                + self.moe_layers * per_moe)

    def head_flops(self) -> float:
        return 2.0 * self.d * self.vocab

    def decode_token_flops(self, context: int) -> float:
        """One decoded token over ``context`` keys (itself included),
        absorbed: q, the latent and rope key, q_nope through W_uk, scores
        over the latent and rope rows, the latent readout, W_uv, W_o;
        the MLP; the head."""
        h, d, r = self.heads, self.d, self.kv_rank
        attn = (2 * d * h * (self.nope + self.rope)
                + 2 * d * (r + self.rope) + 2 * h * self.nope * r
                + 2 * h * (r + self.rope) * context + 2 * h * r * context
                + 2 * h * r * self.v_dim + 2 * h * self.v_dim * d)
        return self.layers * attn + self.mlp_flops() + self.head_flops()

    def decode_flops(self, contexts) -> float:
        return float(sum(self.decode_token_flops(c) for c in contexts))

    def decode_bytes(self, contexts) -> float:
        """Least HBM traffic of one decode step over len(contexts) lanes:
        ``weight_bytes_read`` of that many tokens, each lane's latent
        rows up to its position and one new row per lane."""
        kv = self.kv_bytes_per_token()
        return float(self.weight_bytes_read(len(contexts))
                     + sum(c * kv for c in contexts))

    def prefill_flops(self, s: int) -> float:
        """A prompt of ``s`` real tokens, expanded: per token q, the
        latent, the rope key, per-head keys and values (W_uk, W_uv) and
        W_o, and the MLP; causal attention (token i over i + 1 keys) of
        (nope + rope)-wide scores and v-wide values; the head at the
        last position."""
        h, d, r = self.heads, self.d, self.kv_rank
        proj = (2 * d * h * (self.nope + self.rope)
                + 2 * d * (r + self.rope)
                + 2 * r * h * (self.nope + self.v_dim)
                + 2 * h * self.v_dim * d)
        pairs = s * (s + 1) / 2
        attn = 2 * h * (self.nope + self.rope + self.v_dim) * pairs
        return (s * (self.layers * proj + self.mlp_flops())
                + self.layers * attn + self.head_flops())

    def prefill_bytes(self, s: int) -> float:
        """Least HBM traffic of one prefill: ``weight_bytes_read`` of
        ``s`` tokens and the prompt's latent rows written once."""
        return float(self.weight_bytes_read(s)
                     + s * self.kv_bytes_per_token())


def work(config: dict) -> Work:
    return Work.from_config(config)
