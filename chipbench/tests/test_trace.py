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


def test_exposed_collective_share_by_hand():
    from types import SimpleNamespace

    from chipbench import harness

    reader = harness.metric_reader("exposed_collective_share")

    def ev(*ops):
        return T.Events(np.array([(s, e) for _, s, e in ops], float),
                        [f"%{n} = f32[8]{{0}} op(%p)" for n, _, _ in ops])

    # device 0: an async exchange inside a loop, half of it under a
    # convolution (the loop that holds both hides nothing); device 1: an
    # all-reduce with nothing beside it; a fusion that only reads a
    # collective's result is no collective
    dev0 = ev(("fusion.1", 0, 10), ("while.7", 10, 20),
              ("collective-permute-start.1", 10, 11),
              ("collective-permute-done.1", 11, 14), ("convolution.2", 12, 20))
    dev1 = ev(("all-reduce.3", 0, 5), ("fusion.2", 5, 20))
    dev1.names[1] = "%fusion.2 = f32[8]{0} fusion(%collective-permute-done.1)"
    red = T.Reduced(window=(0, 40), ops=[dev0, dev1], modules=[], spans=[])
    got = reader.read(SimpleNamespace(trace=red))
    assert got == pytest.approx(100.0 * (2 + 5) / 2 / 40)
    alone = T.Reduced(window=(0, 40), ops=[ev(("fusion.1", 0, 10))],
                      modules=[], spans=[])
    assert reader.read(SimpleNamespace(trace=alone)) is None
    assert reader.read(SimpleNamespace(trace=None)) is None
