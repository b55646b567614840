"""The program's marks in a trace (``program_trace.py``) and the readers
of the engine's counters: by hand, and on traces recorded on one TPU v5e
chip (``data/mm_1chip.xplane.pb``, the mm cell; the closed serving cell's
trace, cut to a few steps, is ``data/closed_decode.xplane.pb``)."""

import dataclasses
import json
from pathlib import Path

import numpy as np
import pytest

from chipbench import harness, peaks, program_trace as P, trace as T, work
from chipbench.tests import tiny

DATA = Path(__file__).parent / "data"
QWEN = tiny.BENCH / "configs" / "qwen1.5-0.5b.json"


# ---------------------------------------------------------------------------
# the recorded mm trace
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def mm():
    path = str(DATA / "mm_1chip.xplane.pb")
    return P.load(path, 1), T.reduce(path, 1)


def test_mm_trace_names_the_kernel_scope(mm):
    pt, _ = mm
    names = pt.red.ops[0].names
    i = [k for k, n in enumerate(names) if T.op_name(n) == "matmul.1"][0]
    assert pt.scopes[0][i] == "jit(<lambda>)/jit(matmul)/pallas_call:"
    assert {P.module_program(n) for n in pt.red.modules[0].names} == {
        "_lambda"}
    assert P.scope_of(pt.scopes[0][i]) == P.UNSCOPED


def test_mm_trace_ops_as_the_reduction_reads_them(mm):
    pt, red = mm
    assert pt.red.window == red.window
    assert pt.red.ops[0].names == red.ops[0].names
    assert np.array_equal(pt.red.ops[0].iv, red.ops[0].iv)
    assert len(pt.scopes) == 1 and all(pt.scopes[0])
    assert len(pt.scopes[0]) == len(red.ops[0].names)
    assert pt.spans == []           # the recurrence driver has no engine


def test_mm_trace_accepted_readings_unchanged(mm):
    """What the accepted mm readers take from the reduction, as it read
    when they were accepted."""
    _, red = mm
    assert red.window_s == 4.992032559
    assert red.busy_s() == 4.919178256
    assert red.idle_share() == pytest.approx(0.014594116151877445, rel=1e-12)
    kernel = red.op_seconds(
        harness.load_module(tiny.BENCH / "metrics" / "mm_roofline.py",
                            "mm_roofline_pin").is_kernel)
    assert kernel == 4.919178256
    assert len(red.span_iv("call")) == 77
    assert red.breakdown() == {
        "device_ops": [["matmul.1", pytest.approx(4.919178256, rel=1e-12)]],
        "idle_gaps": [["call", pytest.approx(0.072854303, rel=1e-9)]]}


# ---------------------------------------------------------------------------
# readings by hand
# ---------------------------------------------------------------------------

DEC = "jit(paged_decode_step)/while/body/closed_call/"


def _hand():
    """Two engine steps inside their harness steps: the first admits
    (prefill, write, then a decode), the second decodes only."""
    harness_steps = [("step", 0, 100), ("step", 100, 200)]
    sp = [("step", 3, 99, {"step_num": 0}), ("admit", 3, 30, {}),
          ("prefill", 4, 25, {"rid": 0, "bucket": 64, "tokens": 40}),
          ("write_prefill", 25, 30, {}), ("capacity", 30, 31, {}),
          ("decode", 31, 40, {}), ("sample", 40, 99, {}),
          ("step", 101, 199, {"step_num": 1}), ("admit", 101, 102, {}),
          ("capacity", 102, 103, {}), ("decode", 103, 110, {}),
          ("sample", 110, 185, {})]
    runs = [(5, 20, "jit_prefill_step(1)"), (28, 30, "jit_write_prefill(2)"),
            (41, 90, "jit_paged_decode_step(3)"),
            (115, 180, "jit_paged_decode_step(3)")]
    ops = [  # (start, end, name, tf_op)
        (5, 20, "%fusion.1 = f(p)", "jit(prefill_step)/attn.q/dot:"),
        (28, 30, "%scatter.1 = s(p)", "jit(write_prefill)/scatter:"),
        (41, 90, "%copy.2 = c(p)", DEC + "kv.gather/gather:"),
        (115, 135, "%copy.2 = c(p)", DEC + "kv.gather/gather:"),
        (135, 150, "%fusion.3 = f(p)",
         DEC + "attn.q/widesa.mm/jit(matmul)/pallas_call:"),
        (150, 160, "%fusion.4 = f(p)",
         DEC + "mlp.pair/mlp.up/widesa.mm/jit(matmul)/pallas_call:"),
        (160, 165, "%copy.9 = c(p)", ""),
        (165, 170, "%fusion.5 = f(p)", DEC + "attn.paged_scores/dot:"),
        (170, 175, "%scatter.2 = s(p)", DEC + "kv.write/scatter:"),
        (175, 180, "%fusion.6 = f(p)", DEC + "lm_head/dot:"),
    ]

    def events(rows):
        return T.Events(np.asarray([r[:2] for r in rows], float),
                        [r[2] for r in rows])

    red = T.Reduced((0, 200), [events(ops)], [events(runs)], harness_steps)
    return P.ProgramTrace(red, [[o[3] for o in ops]], sp)


def test_scope_is_the_innermost_site():
    assert P.scope_of(DEC + "mlp.pair/mlp.up/widesa.mm/x:") == "mlp.up"
    assert P.scope_of(DEC + "kv.gather/gather:") == "kv.gather"
    assert P.scope_of("jit(f)/mul:") == P.UNSCOPED
    assert P.scope_of("") == P.UNSCOPED
    assert P.module_program("jit_paged_decode_step(123)") == \
        "paged_decode_step"


def test_engine_idle_by_hand():
    idle = P.engine_idle(_hand())
    # gaps by midpoint: [0,5) before the engine's step, [20,28) prefill,
    # [30,41) decode, [90,115) capacity, [180,200) the outer step only
    ns = 1e-9
    assert idle["by_span"] == pytest.approx(
        {"prefill": 8 * ns, "decode": 11 * ns, "capacity": 25 * ns,
         "step": 20 * ns})
    assert idle["steps"] == 2
    assert idle["per_step_ms"] == pytest.approx(1e3 * 44 * ns / 2)
    # the harness's steps hold every gap, the inner engine spans 44 ns
    assert idle["harness_step_inner_share"] == pytest.approx(44 / 69)


def test_decode_split_kv_share_and_gemm_roofline_by_hand():
    pt = _hand()
    split = P.decode_split(pt)
    ns = 1e-9
    assert split["steps"] == 1          # the admitting step is left out
    assert split["by_scope"] == pytest.approx(
        {"kv.gather": 20 * ns, "attn.q": 15 * ns, "mlp.up": 10 * ns,
         P.UNSCOPED: 5 * ns, "attn.paged_scores": 5 * ns,
         "kv.write": 5 * ns, "lm_head": 5 * ns})
    assert split["seconds"] == pytest.approx(65 * ns)
    assert split["unscoped_top"] == [["copy.9", pytest.approx(5 * ns)]]
    assert P.decode_kv_share(split) == pytest.approx(100 * 25 / 65)
    # attn.q and mlp.up ran in the plans' Pallas calls, lm_head did not
    assert split["gemm_kernel_share"] == pytest.approx(25 / 30)
    lm = work.LM.from_config(tiny.QWEN_TINY)
    pk = peaks.peaks("TPU v5 lite")
    assert P.decode_gemm_roofline(split, lm, pk) == pytest.approx(
        100 * lm.weight_bytes_total() / pk.hbm_bw / (30 * ns))


@pytest.fixture(scope="module")
def closed():
    """Four engine steps of the closed cell (one admits), cut from a
    trace of its window recorded on one TPU v5e chip."""
    return P.load(str(DATA / "closed_decode.xplane.pb"))


def test_closed_trace_readings(closed):
    lm = work.LM.from_config(json.loads(QWEN.read_text()))
    r = P.readings(closed, lm, peaks.peaks("TPU v5 lite"))
    assert r["engine_idle"]["steps"] == 4
    assert r["engine_idle_ms"] == pytest.approx(3.905784249999999, rel=1e-9)
    assert r["engine_idle"]["harness_step_inner_share"] == 1.0
    split = r["decode_split"]
    assert split["steps"] == 3
    assert split["seconds"] == pytest.approx(0.272131783, rel=1e-9)
    assert r["decode_kv_share"] == pytest.approx(14.525989784883071,
                                                 rel=1e-9)
    assert r["decode_gemm_roofline"] == pytest.approx(49.18833180652524,
                                                      rel=1e-9)
    # every planned site of the decode step is named in the trace
    assert set(P.SCOPES) - set(split["by_scope"]) == {
        "attn.scores", "attn.values", "mlp.pair"}
    # the largest unscoped operations: the layer scan's copies of the
    # block pools and the K/V transposes ahead of the attention bmm
    assert [k for k, _ in split["unscoped_top"][:3]] == [
        "copy.76", "copy.78", "copy.79"]
    # every weight GEMM of the decode step ran in a planned Pallas call
    assert split["gemm_kernel_share"] == pytest.approx(1.0, rel=1e-9)


def test_closed_trace_reduces_as_before(closed):
    red = T.reduce(str(DATA / "closed_decode.xplane.pb"), 1)
    assert red.window == closed.red.window
    assert red.ops[0].names == closed.red.ops[0].names
    assert {n for n, _, _ in red.spans} == {"step", "account", "submit"}
    assert red.breakdown()["idle_gaps"][0][0] == "step"


def test_readings_are_none_without_engine_spans():
    pt = dataclasses.replace(_hand(), spans=[])
    r = P.readings(pt, work.LM.from_config(tiny.QWEN_TINY),
                   peaks.peaks("TPU v5 lite"))
    assert r["engine_idle_ms"] is None
    assert r["decode_kv_share"] is None and r["decode_gemm_roofline"] is None


# ---------------------------------------------------------------------------
# the engine-counter readers
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Req:
    prefill_tokens: int
    prefill_padded_tokens: int


@dataclasses.dataclass
class ParentReq:      # a program that stamps and counts nothing
    rid: int


@dataclasses.dataclass
class Track:
    req: object
    in_window: bool


def _run(reqs, in_window=None):
    in_window = in_window or [True] * len(reqs)
    return harness.Run(
        setup_s=1.0, window_s=30.0, attempted=len(reqs), failed=0,
        checks=[], memory_peak_bytes=None, peaks=None, chips=1,
        data={"tracks": [Track(r, w) for r, w in zip(reqs, in_window)]})


def _reader(name):
    return harness.metric_reader(name, tiny.BENCH.parent)


def test_prefill_token_yield_by_hand():
    reqs = [Req(600, 1024), Req(1500, 2048), Req(2000, 2048), Req(9, 9)]
    got = _reader("prefill_token_yield").read(
        _run(reqs, [True, True, True, False]))
    assert got == pytest.approx(100 * 4100 / 5120)


def test_prefill_token_yield_reads_nothing_without_the_counts():
    read = _reader("prefill_token_yield").read
    assert read(_run([ParentReq(0), ParentReq(1)])) is None
    assert read(harness.Run(
        setup_s=1.0, window_s=1.0, attempted=0, failed=0, checks=[],
        memory_peak_bytes=None, peaks=None, chips=1)) is None
