"""Whole runs of each driver kind at small sizes on the CPU (Pallas in
interpret mode), with the device check skipped; and the command itself,
which must refuse to run without a chip or without the program."""

import json
import os
import subprocess
import sys
import time

import pytest

from chipbench import run
from chipbench.tests import tiny

KEYS = {"correct", "attempted", "failed", "metrics", "device"}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(tmp_path_factory.mktemp("bench"))


def measure(root, cell, trace, seconds=2.0, seed=2**31 + 77):
    args = run.parse(["--workload", cell, "--seed", str(seed),
                      "--seconds", str(seconds), "--trace", str(trace)])
    line, _ = run.measure(args, root=root, program_root=tiny.BENCH.parent,
                          t_start=time.perf_counter(), check_devices=False,
                          peaks_kind="TPU v5 lite")
    return json.loads(line)


@pytest.mark.parametrize("cell", sorted(tiny.CELLS))
@pytest.mark.parametrize("trace", [0, 1])
def test_run_prints_the_result_line(root, cell, trace):
    out = measure(root, cell, trace)
    assert KEYS <= set(out) and list(out)[-1] == "checks"
    assert out["correct"] is True, out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    bench = json.loads((root / "BENCHMARK.json").read_text())
    group = bench["per_layer" if trace else "end_to_end"]
    want = {m["name"] for m in group
            if "workloads" not in m or cell in m["workloads"]}
    assert set(out["metrics"]) <= want
    if trace:
        assert out["device"]["busy_s"] > 0
        assert out["device"]["window_s"] > 0
        assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}
        assert len(out["breakdown"]["device_ops"]) <= 10
    else:
        assert set(out["metrics"]) == want, "an end-to-end metric is missing"
        assert all(m["value"] > 0 for m in out["metrics"].values())


def _command(cwd, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "mm-f32-1chip",
         "--seed", "1", "--seconds", "1", "--trace", "0", *extra],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_command_refuses_the_cpu():
    p = _command(tiny.BENCH.parent)
    assert p.returncode != 0
    assert "no accelerator" in p.stderr
    assert "{" not in p.stdout


def test_command_refuses_a_checkout_without_the_program(tmp_path):
    p = _command(tiny.copy_bench(tmp_path))
    assert p.returncode != 0
    assert "{" not in p.stdout
