"""The plain reference agrees with the program's paged prefill and decode
logits at a small size, and a reference one precision step lower falls
outside that agreement."""

import jax
import numpy as np
import pytest

from chipbench import weights
from chipbench.reference import qwen as ref
from chipbench.tests import tiny

#: float32 program against the float32 reference: both compute every
#: product in float32; what is left is summation order and the program's
#: bf16-free softmax, far below a bf16 rounding (2**-8).
LIMIT = 1e-4


@pytest.fixture(scope="module")
def served():
    """A float32 copy of the tiny model served through the paged engine,
    with the logits of every prefill and decode step it ran."""
    from repro.serve import make_engine

    from chipbench.drivers import serve as drv

    config = dict(tiny.QWEN_TINY, torch_dtype="float32")
    eng = make_engine(drv.model_config(config), kind="paged", max_lanes=2,
                      max_seq=128, block_size=16)
    shapes = jax.eval_shape(eng.api.init, jax.random.PRNGKey(0))
    params = weights.make(shapes, 123, config["hidden_size"])
    eng.load(params)
    seen = {"prefill": [], "decode": []}
    dec = eng._decode_exec

    def decode(*a):
        logits, pools = dec(*a)
        # the engine's host-side tables as this step saw them
        seen["decode"].append((eng.kv.pos.copy(), eng.kv.active.copy(),
                               np.array(logits)))
        return logits, pools

    eng._decode_exec = decode
    fn_for = eng._prefill_fn

    def prefill_fn(*a):
        fn = fn_for(*a)

        def run(*b):
            logits, cache = fn(*b)
            seen["prefill"].append(np.asarray(logits[0]))
            return logits, cache
        return run

    eng._prefill_fn = prefill_fn
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 512, n, dtype=np.int32) for n in (23, 40)]
    for p in prompts:
        eng.submit(p, max_new_tokens=6)
    done = eng.run_until_drained()
    return config, params, prompts, sorted(done, key=lambda r: r.rid), seen


def _rel(a, b):
    return float(np.max(np.abs(a - b)) / np.max(np.abs(b)))


def _reference(config, params, req, quant=None):
    seq = np.concatenate([req.prompt, np.asarray(req.output[:-1], np.int32)])
    sel = len(req.prompt) - 1 + np.arange(len(req.output))
    return np.asarray(ref.logits(params, config, seq, sel, quant=quant))


def test_prefill_logits_agree(served):
    config, params, prompts, done, seen = served
    for req, got in zip(done, seen["prefill"]):
        want = _reference(config, params, req)[0]
        assert _rel(got, want) <= LIMIT
        assert _rel(got, _reference(config, params, req, "bf16")[0]) > LIMIT


def test_decode_logits_agree(served):
    config, params, prompts, done, seen = served
    for lane, req in enumerate(done):
        want = _reference(config, params, req)
        low = _reference(config, params, req, "bf16")
        got = [lg[lane] for pos, act, lg in seen["decode"] if act[lane]]
        assert len(got) == len(req.output) - 1
        for j, g in enumerate(got):
            assert _rel(g, want[j + 1]) <= LIMIT
        assert max(_rel(g, low[j + 1]) for j, g in enumerate(got)) > LIMIT
