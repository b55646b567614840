"""The trace reduction: interval arithmetic by hand, and a trace the
harness recorded on one TPU v5e chip (``data/mm_1chip.xplane.pb``: the
``mm-f32-1chip`` cell, its first five seconds traced)."""

from pathlib import Path

import numpy as np
import pytest

from chipbench import trace as T

DATA = Path(__file__).parent / "data"


def test_union_overlap_gaps_by_hand():
    iv = np.array([[0, 2], [1, 3], [5, 6]], float)
    assert T.union(iv).tolist() == [[0, 3], [5, 6]]
    assert T.measure(iv) == 4
    assert T.overlap(iv, np.array([[2, 5.5]])) == 1.5
    assert T.gaps(iv, -1, 7).tolist() == [[-1, 0], [3, 5], [6, 7]]
    assert T.op_name("%fusion.3 = bf16[2]{0} fusion(%p)") == "fusion.3"


def test_idle_goes_to_the_innermost_open_span():
    ops = T.Events(np.array([[0, 10], [20, 30]], float), ["a", "b"])
    red = T.Reduced(window=(0, 40), ops=[ops], modules=[],
                    spans=[("step", 0, 40), ("wait", 12, 18)])
    assert red.busy_s() == 20e-9
    assert red.idle_share() == pytest.approx(0.5)
    idle = red.idle_by_span()
    assert idle == {"wait": pytest.approx(10e-9), "step": pytest.approx(10e-9)}


@pytest.fixture(scope="module")
def chip():
    return T.reduce(str(DATA / "mm_1chip.xplane.pb"), 1)


def test_chip_trace_window_and_busy(chip):
    assert 4.5 < chip.window_s < 5.5
    assert 0.9 < chip.busy_s() / chip.window_s <= 1.0
    calls = chip.span_iv("call")
    assert len(calls) > 50
    kernel = chip.op_seconds(
        lambda n: T.op_name(n).startswith("matmul")
        and "tpu_custom_call" in n)
    assert kernel == pytest.approx(chip.busy_s(), rel=0.02)


def test_chip_trace_programs_fall_in_their_calls(chip):
    calls = chip.span_iv("call")
    hits = chip.modules[0].in_spans(calls)
    assert all(len(h) == 1 for h in hits)
    names = {chip.modules[0].names[h[0]] for h in hits}
    assert len(names) == 1 and next(iter(names)).startswith("jit_")


def test_chip_trace_breakdown(chip):
    b = chip.breakdown()
    assert b["device_ops"][0][0].startswith("matmul")
    assert len(b["device_ops"]) <= 10
    assert {n for n, _ in b["idle_gaps"]} <= {"call", "outside spans"}
