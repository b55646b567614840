"""A run with the timed path broken underneath must come out not
correct: one case for each fault the cell can have."""

import jax
import jax.numpy as jnp
import pytest

from chipbench.tests.test_runs import measure, root  # noqa: F401


def _alter_answer(monkeypatch):
    from repro.kernels import runtime

    real = runtime.execute_plan
    monkeypatch.setattr(runtime, "execute_plan",
                        lambda *a, **k: real(*a, **k).at[0, 0].add(1.0))


def _alter_ring_answer(monkeypatch):
    import repro.core

    real = repro.core.lower_plan

    def lower(*a, **k):
        fn = real(*a, **k)
        return lambda x, y: fn(x, y).at[0, 0].add(1.0)

    monkeypatch.setattr(repro.core, "lower_plan", lower)


def _drop_exchange(monkeypatch):
    monkeypatch.setattr(jax.lax, "ppermute", lambda x, axis_name, perm: x)


def _wrap_decode(monkeypatch, wrap):
    from repro.serve.engine import PagedServeEngine

    real = PagedServeEngine.load

    def load(self, params):
        real(self, params)
        self._decode_exec = wrap(self._decode_exec)

    monkeypatch.setattr(PagedServeEngine, "load", load)


def _alter_token(monkeypatch):
    def wrap(dec):
        def f(*a):
            logits, pools = dec(*a)
            return logits.at[:, 7].set(1e4), pools
        return f
    _wrap_decode(monkeypatch, wrap)


def _state_unchanged(monkeypatch):
    def wrap(dec):
        def f(p, pools, *a):
            logits, _ = dec(p, pools, *a)
            return logits, pools
        return f
    _wrap_decode(monkeypatch, wrap)


def _half_batch(monkeypatch):
    def wrap(dec):
        def f(*a):
            logits, pools = dec(*a)
            half = logits.shape[0] // 2
            return logits.at[:half].set(jnp.zeros_like(logits[:half])), pools
        return f
    _wrap_decode(monkeypatch, wrap)


CASES = [
    ("mm-tiny", _alter_answer),
    ("mm-tiny-2x2", _alter_ring_answer),
    ("mm-tiny-2x2", _drop_exchange),
] + [(cell, fault) for cell in ("qwen-tiny-closed", "qwen-tiny-open")
     for fault in (_alter_token, _state_unchanged, _half_batch)]


@pytest.mark.parametrize("cell,fault", CASES,
                         ids=[f"{c}-{f.__name__[1:]}" for c, f in CASES])
def test_fault_is_not_correct(root, monkeypatch, cell, fault):  # noqa: F811
    fault(monkeypatch)
    out = measure(root, cell, trace=0, seconds=1.5)
    assert out["correct"] is False, out["checks"]
