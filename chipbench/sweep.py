"""Find an open-loop serving cell's knee: the highest arrival rate the
engine sustains.  One engine, the cell's own traffic at each rate given.

    python3 chipbench/sweep.py --workload <cell> --rates 1,2,4 \
        [--seconds 15] [--seed 1] [--out sweep.json]

For each rate it prints the requests that arrived and finished, the
output tokens per second, time to first token (median and 95th
percentile) and how many requests were still waiting when the window
closed.  A rate the engine sustains finishes its requests about as fast
as they arrive, and its backlog at the close stays near zero.  Run once
when a cell is defined; the benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from chipbench import harness, trace  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    harness.import_program()
    harness.enable_compile_cache()
    harness.devices(cell.chips)
    drv = harness.driver("serve", cell.root)
    cfg, s = cell.config, cell.settings
    eng, shapes = drv.engine(cfg, s)
    eng.load(drv.weights.make(shapes, args.seed, cfg["hidden_size"]))
    drv._warm(eng, cell.traffic, drv.traffic.prompt_source(
        args.seed, cfg["vocab_size"]))
    rows = []
    for rate in (float(r) for r in args.rates.split(",")):
        mix = dict(cell.traffic, rate_per_s=rate)
        loop = drv.Loop(eng, harness.Spans(), cfg["vocab_size"], args.seed)
        marks = {}

        def mark():
            marks["t0"] = harness.now()
            return marks["t0"]

        drv._open(loop, mix, mark, args.seconds,
                  s["drain_seconds"], trace.Session(False, None))
        t0, t1 = marks["t0"], marks["t0"] + args.seconds
        tr = [t for t in loop.tracks if t.in_window]
        ttft = [t.times[0] - t.due for t in tr if t.times]
        toks = sum(1 for t in tr for x in t.times if t0 <= x <= t1)
        waiting = sum(1 for t in tr if not t.times or t.times[0] > t1)
        rows.append({
            "rate_per_s": rate, "arrived": len(tr),
            "finished": sum(t.req.done for t in tr),
            "output_tok_s": toks / args.seconds,
            "ttft_p50_ms": float(np.percentile(ttft, 50)) * 1e3,
            "ttft_p95_ms": float(np.percentile(ttft, 95)) * 1e3,
            "waiting_at_close": waiting,
            "steps": len(loop.steps)})
        harness.log(json.dumps(rows[-1]))
        eng.finished.clear()
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(json.dumps(rows, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
