"""The yardstick's own parts: work counts, peaks, the traffic generator,
and finding a cell by its files."""

import json

import numpy as np
import pytest

from chipbench import harness, peaks, traffic, work
from chipbench.tests import tiny

QWEN = json.loads((tiny.BENCH / "configs" / "qwen1.5-0.5b.json").read_text())


def test_qwen_sizes_by_hand():
    lm = work.LM.from_config(QWEN)
    # 24 x (4 x 1024^2 + 3 x 1024 x 2816 + 3072 bias + 2 norms)
    # + 151936 x 1024 tied embedding + the final norm
    assert lm.params() == 463_987_712
    assert abs(lm.params() / 1e6 - 464) < 0.1
    assert lm.kv_bytes_per_token() == 98_304      # 2 x 24 x 1024 x 2 bytes
    assert lm.weight_bytes_total() == 2 * 463_987_712


def test_qwen_step_work_by_hand():
    lm = work.LM.from_config(QWEN)
    per_layer = 12_845_056                        # matmul weights of a layer
    assert lm.decode_flops([1]) == 2 * 24 * per_layer + 4 * 24 * 1024 \
        + 2 * 1024 * 151936
    assert lm.decode_bytes([10, 20]) == 2 * 463_987_712 + 30 * 98_304
    s = 100
    assert lm.prefill_flops(s) == 2 * 24 * per_layer * s \
        + 4 * 24 * 1024 * s * (s + 1) / 2 + 2 * 1024 * 151936


def test_mm_work_by_hand():
    assert work.mm_ops(8192, 8192, 8192) == 2 * 8192**3
    assert work.mm_bytes(8192, 8192, 8192, 4) == 3 * 4 * 8192**2


def test_peaks_unknown_device_raises():
    assert peaks.peaks("TPU v5 lite").bf16_flops == 197e12
    with pytest.raises(KeyError):
        peaks.peaks("TPU v9 imaginary")


def test_open_loop_same_work_every_seed():
    mix = {"kind": "open_poisson", "rate_per_s": 20.0,
           "prompt_len": [512, 2040], "output_len": [8, 8]}
    a = traffic.open_loop(mix, 30.0)
    assert a == traffic.open_loop(mix, 30.0)
    assert len(a) == 600
    assert [r.prompt_len for r in a] != sorted(r.prompt_len for r in a)
    gaps = np.diff([0.0] + [r.arrival_s for r in a])
    assert (gaps > 0).all() and 0 < a[-1].arrival_s < 30.0
    assert abs(gaps.mean() - 1 / 20.0) < 0.002
    assert min(r.prompt_len for r in a) >= 512
    assert max(r.prompt_len for r in a) <= 2040


def test_closed_loop_same_work_every_seed():
    mix = json.loads((tiny.BENCH / "traffic" / "chat-closed-16.json")
                     .read_text())
    a = traffic.closed_loop(mix, 1)
    b = traffic.closed_loop(mix, 3_000_000_001)
    assert len(a) == 16
    for qa, qb in zip(a, b):
        assert sorted(s.prompt_len for s in qa) == sorted(
            s.prompt_len for s in qb)
        assert sorted(s.output_len for s in qa) == sorted(
            s.output_len for s in qb)


def test_prompts_follow_the_seed():
    x = traffic.prompt_source(5, 151936)(100)
    y = traffic.prompt_source(5, 151936)(100)
    z = traffic.prompt_source(6, 151936)(100)
    assert (x == y).all() and not (x == z).all()


def test_new_cell_is_one_file_each(tmp_path):
    """A cell, its traffic and a per-layer metric added as new files are
    found by name, with no edit to an existing file."""
    root = tiny.copy_bench(tmp_path)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    (root / "chipbench" / "traffic" / "burst.json").write_text(json.dumps(
        {"kind": "open_poisson", "rate_per_s": 2.0,
         "prompt_len": [64, 128], "output_len": [16, 16]}))
    (root / "chipbench" / "workloads" / "qwen05b-burst.json").write_text(
        json.dumps({"driver": "serve", "lanes": 8}))
    bench["workloads"].append(
        {"name": "qwen05b-burst", "config": "qwen1.5-0.5b",
         "traffic": "burst", "chips": 1, "why": "test"})
    bench["per_layer"].append(
        {"name": "queue_wait_ms", "unit": "ms", "better": "lower",
         "source": "host_clock", "layer": "serve engine",
         "moves": "ttft_p95_ms", "workloads": ["qwen05b-burst"]})
    (root / "chipbench" / "metrics" / "queue_wait_ms.py").write_text(
        "def read(run):\n    return 1.5\n")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = harness.load_cell("qwen05b-burst", root)
    assert cell.traffic["rate_per_s"] == 2.0
    assert cell.config["hidden_size"] == 1024
    assert cell.settings["lanes"] == 8
    assert [m["name"] for m in cell.per_layer] == ["queue_wait_ms"]
    assert [m["name"] for m in cell.end_to_end] == ["setup_s"]
    assert harness.metric_reader("queue_wait_ms", root).read(None) == 1.5
