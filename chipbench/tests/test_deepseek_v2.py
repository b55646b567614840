"""The DeepSeek-V2 configuration: its plain reference against an
independent forward, its work counts at the published sizes, a tiny
DeepSeek cell end to end through the serve driver, and the faults that
must read not correct there."""

import dataclasses
import json
import math

import jax
import numpy as np
import pytest

from chipbench import weights
from chipbench.drivers import serve as drv
from chipbench.reference import deepseek_v2 as ref
from chipbench.tests import tiny
from chipbench.tests.test_runs import measure
from tests.test_held_experts import plain_logits

LITE = json.loads((tiny.BENCH / "configs" / "deepseek-v2-lite.json")
                  .read_text())

#: DeepSeek-V2-Lite's published keys at a test size: MLA without q_lora,
#: 1 dense + 3 MoE layers, 4 of a router's 16 experts held, top-6 not
#: renormalised, 2 shared experts, YaRN; float32 (see tiny.py)
DSV2_TINY = dict(
    {k: LITE[k] for k in ("model_type", "hidden_act", "rms_norm_eps",
                          "rope_theta", "first_k_dense_replace",
                          "norm_topk_prob", "routed_scaling_factor",
                          "tie_word_embeddings", "q_lora_rank")},
    name="dsv2-tiny", source="test size", hidden_size=64,
    intermediate_size=96, num_attention_heads=4, num_key_value_heads=4,
    num_hidden_layers=4, vocab_size=512, kv_lora_rank=32,
    qk_rope_head_dim=16, qk_nope_head_dim=16, v_head_dim=16,
    moe_intermediate_size=16, n_routed_experts=4, router_experts=16,
    num_experts_per_tok=6, n_shared_experts=2, torch_dtype="float32",
    rope_scaling=dict(LITE["rope_scaling"],
                      original_max_position_embeddings=64),
    program=dict(LITE["program"], moe_router_experts=16,
                 rope_original_positions=64),
    reference="deepseek_v2", reduced=["n_routed_experts"])
SETTINGS = dict(tiny.CELLS["qwen-tiny-closed"][3])
CELL = "dsv2-tiny-closed"


def _weights(config, seed=2**31 + 5):
    _, shapes = drv.engine(config, SETTINGS)
    return weights.make(shapes, seed, config["hidden_size"])


# ---------------------------------------------------------------------------
# the reference
# ---------------------------------------------------------------------------

def test_reference_matches_an_independent_forward():
    """The reference's logits are those of the float64 forward written
    from the published equations in the tier-1 tests, on the same random
    weights, at every position of a sequence."""
    w = _weights(DSV2_TINY)
    toks = np.arange(40, dtype=np.int32) * 11 % 512
    got = np.asarray(ref.logits(w, DSV2_TINY, toks, np.arange(40)))
    want = plain_logits(w, drv.model_config(DSV2_TINY), toks)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


def test_reference_by_hand_routing_and_scale():
    """A hand-made case: the router sends every token to experts 0..5
    with known weights, un-normalised; the rope tables and the attention
    scale are YaRN's of DeepSeek-V2-Lite."""
    c = dict(DSV2_TINY)
    x = np.ones((3, 64), np.float32) / 8
    router = np.zeros((64, 16), np.float32)
    router[:, :6] = np.log(np.arange(6, 0, -1))[None] * 8 / 64
    m = {"router": router,
         "wg": np.zeros((4, 64, 16), np.float32),
         "wu": np.zeros((4, 64, 16), np.float32),
         "wd": np.zeros((4, 16, 64), np.float32),
         "shared_wg": np.zeros((64, 32), np.float32),
         "shared_wu": np.zeros((64, 32), np.float32),
         "shared_wd": np.zeros((32, 64), np.float32)}
    # expert e's SwiGLU gives silu(1) * 1 on output column e
    for e in range(4):
        m["wg"][e][:, 0] = m["wu"][e][:, 0] = 1.0 / 8
        m["wd"][e][0, e] = 1.0
    y = np.asarray(ref._experts(x, m, c, None))
    probs = np.arange(6, 0, -1) / (21 + 10)    # 6..1 and ten of weight 1
    silu1 = 1 / (1 + math.exp(-1))
    np.testing.assert_allclose(y[:, :4], np.tile(probs[:4] * silu1, (3, 1)),
                               rtol=1e-5)
    assert np.all(y[:, 4:] == 0)
    lite = dict(LITE)
    m2 = 0.1 * 0.707 * math.log(40) + 1
    assert ref.attn_scale(lite) == pytest.approx(192 ** -0.5 * m2 * m2)
    cos, sin = ref.rope_tables(lite, 64, 2)
    base = 1.0 / 10000 ** (np.arange(0, 64, 2) / 64)
    ang = np.arctan2(np.asarray(sin[1], np.float64),
                     np.asarray(cos[1], np.float64))
    np.testing.assert_allclose(ang[:10], base[:10], rtol=1e-4)
    np.testing.assert_allclose(ang[23:], base[23:] / 40, rtol=1e-4)


# ---------------------------------------------------------------------------
# the work counts at the published sizes
# ---------------------------------------------------------------------------

def test_work_counts_at_the_published_sizes():
    lm = ref.work(LITE)
    assert (lm.router, lm.held, lm.top_k, lm.moe_layers) == (64, 8, 6, 26)
    assert lm.params() == 3_110_989_312          # 3.11 B held
    assert lm.kv_bytes_per_token() == 576 * 2 * 27 == 31_104
    experts = 26 * 8 * 3 * 2048 * 1408 * 2        # 3.6 GB
    embed = 102400 * 2048 * 2
    rest = 2 * lm.params() - experts - embed
    t = 64
    touched = 1 - (1 - 6 / 64) ** t
    ctx = [960] * t
    want = rest + t * 2048 * 2 + experts * touched + t * 960 * 31_104
    assert lm.decode_bytes(ctx) == pytest.approx(want, rel=1e-12)
    assert lm.decode_bytes(ctx) == pytest.approx(7.71e9, rel=0.01)
    # decode: absorbed, per token
    h, d, r = 16, 2048, 512
    attn = (2 * d * h * 192 + 2 * d * 576 + 2 * h * 128 * r
            + 2 * h * 576 * 960 + 2 * h * r * 960 + 2 * h * r * 128
            + 2 * h * 128 * d)
    mlp = 6 * d * 10944 + 26 * (2 * d * 64 + 6 * d * 2816
                                + 6 * 8 / 64 * 6 * d * 1408)
    assert lm.decode_flops([960]) == pytest.approx(
        27 * attn + mlp + 2 * d * 102400, rel=1e-12)
    # prefill: expanded
    s = 512
    proj = 2 * d * h * 192 + 2 * d * 576 + 2 * r * h * 256 + 2 * h * 128 * d
    attn_p = 2 * h * (192 + 128) * s * (s + 1) / 2
    assert lm.prefill_flops(s) == pytest.approx(
        s * (27 * proj + mlp) + 27 * attn_p + 2 * d * 102400, rel=1e-12)
    assert lm.prefill_bytes(s) == pytest.approx(
        rest + s * 2048 * 2 + experts * (1 - (58 / 64) ** s)
        + s * 31_104, rel=1e-12)


# ---------------------------------------------------------------------------
# a tiny cell end to end, and its faults
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def dsv2_root(tmp_path_factory):
    cells = {CELL: ("dsv2-tiny", "closed", 1, SETTINGS)}
    return tiny.make_root(tmp_path_factory.mktemp("dsv2"), cells=cells,
                          configs=(DSV2_TINY,))


@pytest.mark.parametrize("trace", [0, 1])
def test_tiny_deepseek_runs_through_the_serve_driver(dsv2_root, trace):
    out = measure(dsv2_root, CELL, trace)
    assert out["correct"] is True, out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    if trace:
        # top-6 of 16 with 4 held: a decoded token brings at most 4 held
        # assignments to a MoE layer (1.5 at uniform routing)
        v = out["metrics"]["held_experts_per_token.all_steps"]["value"]
        assert 0 < v <= 4
    else:
        assert {"output_tok_s", "itl_p95_ms", "setup_s"} <= set(
            out["metrics"])


def _renormalised_topk(monkeypatch):
    from repro.models import moe

    real = moe.route
    monkeypatch.setattr(moe, "route", lambda cfg, logits: real(
        dataclasses.replace(cfg, moe_norm_topk=True), logits))


def _no_yarn_attention_factor(monkeypatch):
    from repro.models import mla

    monkeypatch.setattr(mla, "rope_attn_scale", lambda cfg: 1.0)


@pytest.mark.parametrize("fault", [_renormalised_topk,
                                   _no_yarn_attention_factor],
                         ids=["topk-renormalised", "yarn-m2-left-out"])
def test_fault_is_not_correct(dsv2_root, monkeypatch, fault):
    fault(monkeypatch)
    out = measure(dsv2_root, CELL, trace=0, seconds=1.5)
    assert out["correct"] is False, out["checks"]
