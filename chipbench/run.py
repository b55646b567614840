"""The benchmark's one command.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Runs one cell of ``BENCHMARK.json`` on the chips of the machine it is
started on: checks the devices, builds the cell's inputs and weights on
the device from ``--seed``, warms up the cell's own shapes (all of it
counted as ``setup_s``), measures for ``--seconds``, checks what the
timed path produced against a plain reference, and prints as its last
line of standard output one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` (and ``breakdown`` with
``--trace 1``), then ``checks``, each number compared beside its limit.
With ``--trace 0`` the metrics are the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics, read from a profiler trace of the
window.

Exit codes: 0 with a result line; 3 where JAX finds no accelerator or
fewer chips than the cell asks for; 4 where the program under test is
not in the checkout.  Neither of those prints a result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from chipbench import harness, peaks  # noqa: E402


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", metavar="DIR",
                    help="with --trace 1, also copy the profiler's "
                         ".xplane.pb file to DIR")
    return ap.parse_args(argv)


def measure(args, *, root: Path = harness.ROOT,
            program_root: Path = harness.ROOT, t_start: float = T_START,
            check_devices: bool = True, peaks_kind: str | None = None):
    """Run one cell; returns (result line, checks).  ``root`` holds
    ``BENCHMARK.json`` and the cell's files, ``program_root`` the system
    under test.  ``check_devices`` off drives the rest of a run on
    whatever backend JAX has, with the peaks of ``peaks_kind`` (tests)."""
    cell = harness.load_cell(args.workload, root)
    harness.import_program(program_root)
    cache = harness.enable_compile_cache(program_root)
    devs = harness.devices(cell.chips) if check_devices else None
    if devs is None:
        import jax

        devs = jax.devices()[:cell.chips]
    harness.log(f"cell {cell.name}: config {cell.entry['config']}, "
                f"traffic {cell.entry['traffic']}, {cell.chips} chip(s) "
                f"{devs[0].device_kind}; compile cache {cache}")
    drv = harness.driver(cell.settings["driver"], root)
    run = drv.run(harness.Context(
        cell=cell, seed=args.seed, seconds=args.seconds,
        trace=bool(args.trace), keep_trace=args.keep_trace,
        devices=devs, t_start=t_start,
        peaks=peaks.peaks(peaks_kind or devs[0].device_kind)))
    wanted = cell.per_layer if args.trace else cell.end_to_end
    metrics = {}
    for m in wanted:
        value = harness.metric_reader(m["name"], root).read(run)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    dev = devs[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs),
              "memory_peak_bytes": run.memory_peak_bytes}
    breakdown = None
    if args.trace and run.trace is not None:
        device["busy_s"] = run.trace.busy_s()
        device["window_s"] = run.trace.window_s
        breakdown = run.trace.breakdown()
    correct = all(c.ok for c in run.checks)
    line = harness.result_line(
        correct=correct, attempted=run.attempted, failed=run.failed,
        metrics=metrics, device=device, checks=run.checks,
        breakdown=breakdown)
    return line, run.checks


def main(argv=None) -> int:
    args = parse(argv)
    try:
        line, checks = measure(args)
    except harness.NoDevice as e:
        print(f"chipbench: {e}; this benchmark runs on the chip only",
              file=sys.stderr)
        return 3
    except harness.NoProgram as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 4
    for c in checks:
        print(f"check {c.name}: {c.value!r} (limit {c.limit!r}) "
              f"{'ok' if c.ok else 'FAIL'}", file=sys.stderr, flush=True)
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
