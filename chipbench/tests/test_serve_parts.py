"""What the serve driver takes from a configuration by name: its
reference module (the forward that decides ``correct`` and the work
counts the metrics read), the weight rule for every leaf the program's
transformer families build, and the published keys.  The Qwen cells
must read the same weights, reference logits and work counts as before
the driver took them by name: the digests below pin them."""

import dataclasses
import hashlib
import json

import jax
import numpy as np
import pytest

from chipbench import weights
from chipbench.drivers import serve as drv
from chipbench.reference import qwen as ref
from chipbench.tests import tiny
from chipbench.tests.test_runs import measure

QWEN = json.loads((tiny.BENCH / "configs" / "qwen1.5-0.5b.json").read_text())
SEED = 2**31 + 77

#: sha256 of the tiny Qwen weights' bytes, leaf by leaf in flatten order,
#: made by ``weights.make`` from SEED before the rule covered MoE and MLA
WEIGHTS_SHA256 = \
    "3fcbdf784939edcd506381a8614e90495725ff72450ecffb2e9fdad9e1665d58"
#: the reference's logits of those weights at positions 0, 19 and 39 of
#: the tokens 7 * i mod 512 (first four of the vocabulary), and the sum
#: of their absolute values, from the same commit
LOGITS_HEAD = [[2.7933387756347656, -3.4064977169036865,
                1.8497213125228882, -1.029208779335022],
               [2.870725631713867, -1.8613696098327637,
                -0.9380801916122437, -3.084956407546997],
               [2.2553250789642334, -2.2664453983306885,
                -0.04165472090244293, -2.7719902992248535]]
LOGITS_ABS_SUM = 2445.791015625


@pytest.fixture(scope="module")
def tiny_qwen():
    _, shapes = drv.engine(tiny.QWEN_TINY, tiny.CELLS["qwen-tiny-closed"][3])
    return weights.make(shapes, SEED, tiny.QWEN_TINY["hidden_size"])


def test_tiny_qwen_weights_are_unchanged(tiny_qwen):
    h = hashlib.sha256()
    for leaf in jax.tree.leaves(tiny_qwen):
        h.update(np.asarray(leaf).tobytes())
    assert h.hexdigest() == WEIGHTS_SHA256


def test_qwen_reference_logits_are_unchanged(tiny_qwen):
    toks = np.arange(40, dtype=np.int32) * 7 % 512
    got = np.asarray(ref.logits(tiny_qwen, tiny.QWEN_TINY, toks,
                                np.array([0, 19, 39], np.int32)))
    np.testing.assert_allclose(got[:, :4], LOGITS_HEAD, rtol=1e-6)
    assert float(np.abs(got).sum()) == pytest.approx(LOGITS_ABS_SUM,
                                                     rel=1e-6)


def _smoke(arch, **change):
    from repro.configs import get_smoke_config

    return dataclasses.replace(get_smoke_config(arch), **change)


@pytest.mark.parametrize("arch,change", [
    ("qwen1.5-0.5b", {}),                      # dense, QKV bias
    ("olmoe-1b-7b", {}),                       # MoE, GQA with qk_norm
    ("deepseek-v2-236b", {}),                  # MoE + shared, MLA, q_lora
    ("deepseek-v2-236b", {"q_lora_rank": 0}),  # MLA without q_lora
], ids=["qwen", "olmoe", "deepseek-v2", "deepseek-v2-no-q-lora"])
def test_weights_cover_every_leaf(arch, change):
    from repro.models.model import build_model

    cfg = _smoke(arch, **change)
    shapes = jax.eval_shape(build_model(cfg).init, jax.random.PRNGKey(0))
    params = weights.make(shapes, 5, cfg.d_model)
    flat, _ = jax.tree_util.tree_flatten_with_path(params)
    for path, leaf in flat:
        name = str(path[-1].key)
        x = np.asarray(leaf)
        assert np.isfinite(x).all(), path
        if name.endswith("norm") or any(
                str(k.key).startswith("ln") for k in path[:-1]):
            assert abs(x.mean() - 1.0) < 0.2, path
        elif x.ndim >= 2 and name not in ("embed", "lm_head") \
                and not name.startswith("b"):
            # fan-in scale: unit variance after the product
            assert x.std() * np.sqrt(x.shape[-2]) == pytest.approx(
                1.0, rel=0.25), path


def test_published_keys_map_by_name():
    from repro.configs.base import ModelConfig

    # Qwen's file holds none of the MoE or MLA keys: its ModelConfig is
    # the one the driver built before they were mapped
    assert drv.model_config(QWEN) == ModelConfig(
        name="qwen1.5-0.5b", family="dense", n_layers=24, d_model=1024,
        n_heads=16, n_kv_heads=16, d_ff=2816, vocab=151936,
        rope_theta=1e6, norm_eps=1e-6, tie_embeddings=True, act="silu",
        dtype="bfloat16", kv_cache_dtype="bfloat16", qkv_bias=True)
    lite = dict(QWEN, **{
        "n_routed_experts": 64, "num_experts_per_tok": 6,
        "n_shared_experts": 2, "moe_intermediate_size": 1408,
        "first_k_dense_replace": 1, "kv_lora_rank": 512,
        "q_lora_rank": 0, "qk_rope_head_dim": 64,
        "qk_nope_head_dim": 128, "v_head_dim": 128,
        "program": {"family": "moe", "use_mla": True}})
    mc = drv.model_config(lite)
    assert (mc.moe_num_experts, mc.moe_top_k, mc.moe_shared_experts,
            mc.moe_d_ff, mc.moe_first_dense) == (64, 6, 2, 1408, 1)
    assert (mc.kv_lora_rank, mc.q_lora_rank, mc.rope_head_dim,
            mc.nope_head_dim, mc.v_head_dim) == (512, 0, 64, 128, 128)


PROBE = '''"""A second reference, found by name: the Qwen reference, with each
call recorded in a file beside this one."""
from pathlib import Path

from chipbench.reference import qwen as _qwen

CALLS = Path(__file__).with_suffix(".calls")


def _note(what):
    with CALLS.open("a") as f:
        f.write(what + "\\n")


def logits(*a, **k):
    _note("logits")
    return _qwen.logits(*a, **k)


def work(config):
    _note("work")
    return _qwen.work(config)
'''


def _probe_root(tmp_path, reference):
    cfg = dict(tiny.QWEN_TINY, name="qwen-probe", reference=reference)
    cells = {"qwen-probe-closed": ("qwen-probe", "closed", 1,
                                   tiny.CELLS["qwen-tiny-closed"][3])}
    root = tiny.make_root(tmp_path, cells=cells, configs=(cfg,))
    (root / "chipbench" / "reference" / "qwen_probe.py").write_text(PROBE)
    return root


def test_a_second_reference_is_found_by_name(tmp_path):
    root = _probe_root(tmp_path, "qwen_probe")
    out = measure(root, "qwen-probe-closed", trace=0, seconds=1.5)
    assert out["correct"] is True, out["checks"]
    calls = (root / "chipbench" / "reference" / "qwen_probe.calls"
             ).read_text().split()
    assert calls.count("work") == 1 and calls.count("logits") >= 1


def test_a_missing_reference_stops_the_run(tmp_path):
    root = _probe_root(tmp_path, "no_such_model")
    with pytest.raises(FileNotFoundError, match=r"no_such_model\.py"):
        measure(root, "qwen-probe-closed", trace=0, seconds=1.5)
