"""Operations and bytes that the algorithm needs, from shapes alone.

Never read from a compiled program's ``cost_analysis``: that changes with
the implementation, and a kernel's roofline share has to read the same
work whatever computes it.  Model sizes come from the configuration file
as published (Hugging Face ``config.json`` key names).
"""

from __future__ import annotations

import dataclasses

DTYPE_BYTES = {"float32": 4, "bfloat16": 2, "float16": 2, "int8": 1}


# ---------------------------------------------------------------------------
# matrix multiplication
# ---------------------------------------------------------------------------

def mm_ops(m: int, n: int, k: int) -> float:
    """C[m,n] = A[m,k] @ B[k,n]: one multiply and one add per point."""
    return 2.0 * m * n * k


def mm_bytes(m: int, n: int, k: int, itemsize: int) -> float:
    """Least HBM traffic of one call: read A and B once, write C once."""
    return float(itemsize) * (m * k + k * n + m * n)


# ---------------------------------------------------------------------------
# dense decoder-only language model (GQA attention, GLU MLP)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class LM:
    layers: int
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    ff: int
    vocab: int
    tied: bool
    qkv_bias: bool
    weight_bytes: int   # bytes per weight element as served
    kv_bytes: int       # bytes per cache element

    @classmethod
    def from_config(cls, c: dict) -> "LM":
        heads = c["num_attention_heads"]
        return cls(
            layers=c["num_hidden_layers"], d=c["hidden_size"], heads=heads,
            kv_heads=c.get("num_key_value_heads", heads),
            head_dim=c.get("head_dim") or c["hidden_size"] // heads,
            ff=c["intermediate_size"], vocab=c["vocab_size"],
            tied=bool(c.get("tie_word_embeddings", False)),
            qkv_bias=bool(c.get("attention_bias", c.get("qkv_bias", False))),
            weight_bytes=DTYPE_BYTES[c["torch_dtype"]],
            kv_bytes=DTYPE_BYTES[c["torch_dtype"]])

    # -- parameters ---------------------------------------------------------
    def layer_matmul_params(self) -> int:
        q = self.heads * self.head_dim
        kv = self.kv_heads * self.head_dim
        return self.d * (q + 2 * kv) + q * self.d + 3 * self.d * self.ff

    def params(self) -> int:
        q = self.heads * self.head_dim
        kv = self.kv_heads * self.head_dim
        bias = (q + 2 * kv) if self.qkv_bias else 0
        per_layer = self.layer_matmul_params() + bias + 2 * self.d
        embed = self.vocab * self.d * (1 if self.tied else 2)
        return self.layers * per_layer + embed + self.d

    def weight_bytes_total(self) -> int:
        return self.params() * self.weight_bytes

    def kv_bytes_per_token(self) -> int:
        """K and V rows of one token over every layer."""
        return 2 * self.layers * self.kv_heads * self.head_dim * self.kv_bytes

    # -- operations ---------------------------------------------------------
    def token_flops(self, context: int) -> float:
        """Forward FLOPs of one token that attends over ``context`` keys
        (itself included), without the output head."""
        attn = 4.0 * self.layers * self.heads * self.head_dim * context
        return 2.0 * self.layers * self.layer_matmul_params() + attn

    def head_flops(self) -> float:
        return 2.0 * self.d * self.vocab

    def prefill_flops(self, s: int) -> float:
        """A prompt of ``s`` real tokens under causal attention (token i
        attends to i + 1 keys), with the head at its last position."""
        matmul = 2.0 * self.layers * self.layer_matmul_params() * s
        attn = 4.0 * self.layers * self.heads * self.head_dim * (
            s * (s + 1) / 2)
        return matmul + attn + self.head_flops()

    def prefill_bytes(self, s: int) -> float:
        """Least HBM traffic of one prefill: every weight once and the
        prompt's K/V rows written once."""
        return float(self.weight_bytes_total() + s * self.kv_bytes_per_token())

    def decode_flops(self, contexts) -> float:
        """One decode step: each lane's token over its context."""
        return sum(self.token_flops(c) + self.head_flops() for c in contexts)

    def decode_bytes(self, contexts) -> float:
        """Least HBM traffic of one decode step: every weight once, each
        active lane's K/V up to its position, and one new row per lane."""
        kv = self.kv_bytes_per_token()
        return float(self.weight_bytes_total()
                     + sum((c - 1) * kv + kv for c in contexts))
