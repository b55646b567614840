"""Readings that a cell's comparison limit is set from: the program's on
a dozen seeds or more, and the control's, at the cell's own size.

    python3 chipbench/control.py --workload <cell> --seeds 1,2,3 \
        [--seconds 8] [--out control.json]

The control is the reference put in the program's place, computed one
precision step below what the configuration states: ``Precision.HIGH``
(three bf16 passes) for float32 at HIGHEST; float8 e4m3 for a bfloat16
model, bfloat16 for a float32 one.  Each seed reads both in one process.

  recurrence  per seed: new operands, one call of the program, and the
              control's product, each against the reference.
  serve       one engine; per seed: new weights, a short window of the
              cell's own traffic, then the program's reading (served
              tokens) and the control's (the token the control puts
              first, at the same positions) over the same sample.

The benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from chipbench import harness, trace  # noqa: E402

#: a served model's dtype -> the rounding of its control
SERVE_CONTROL = {"bfloat16": "fp8", "float32": "bf16"}


def recurrence(cell, seeds, devs):
    drv = harness.driver("recurrence", cell.root)
    cfg, s = cell.config, cell.settings
    prog, sharding = drv._program(cfg, s, devs)
    rows = []
    for seed in seeds:
        (a, b), = drv._operands(cfg, 1, seed, sharding)
        want = drv.ref.matmul(a, b, cfg["precision"])
        rows.append({
            "seed": seed,
            "program": drv.ref.rel_err(prog(a, b), want),
            "control": drv.ref.rel_err(drv.ref.matmul(a, b, "high"), want)})
        harness.log(json.dumps(rows[-1]))
        del a, b, want
    return rows


def serve(cell, seeds, devs, seconds, quant=None):
    """``quant`` defaults to the control of the configuration's dtype."""
    drv = harness.driver("serve", cell.root)
    cfg, s, mix = cell.config, cell.settings, cell.traffic
    eng, shapes = drv.engine(cfg, s)
    rows = []
    for k, seed in enumerate(seeds):
        params = drv.weights.make(shapes, seed, cfg["hidden_size"])
        if k == 0:
            eng.load(params)
            drv._warm(eng, mix, drv.traffic.prompt_source(
                seed + 1, cfg["vocab_size"]))
        eng.params = params
        spans = harness.Spans()
        loop = drv.Loop(eng, spans, cfg["vocab_size"], seed)
        tracer = trace.Session(False, spans)
        mark = lambda: harness.now()  # noqa: E731
        if mix["kind"] == "closed":
            drv._closed(loop, mix, seed, mark, seconds, s["warm_steps"],
                        s["drain_seconds"], tracer)
            # finish the requests that started before the window too
            while loop.busy():
                loop.step()
        else:
            drv._open(loop, mix, mark, seconds, s["drain_seconds"],
                      tracer)
        got, n = drv._check(loop.tracks, params, cfg, s, mix, seed)
        low, _ = drv._check(loop.tracks, params, cfg, s, mix, seed,
                            quant=quant or SERVE_CONTROL[cfg["torch_dtype"]])
        eng.finished.clear()
        rows.append({"seed": seed, "program": got, "control": low,
                     "tokens": n})
        harness.log(json.dumps(rows[-1]))
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    harness.import_program()
    harness.enable_compile_cache()
    devs = harness.devices(cell.chips)
    seeds = [int(x) for x in args.seeds.split(",")]
    t0 = time.perf_counter()
    if cell.settings["driver"] == "recurrence":
        rows = recurrence(cell, seeds, devs)
    else:
        rows = serve(cell, seeds, devs, args.seconds)
    out = {"workload": cell.name, "rows": rows,
           "program_max": max(r["program"] for r in rows),
           "control_min": min(r["control"] for r in rows),
           "limit": cell.settings["limit"],
           "seconds": time.perf_counter() - t0}
    harness.log(json.dumps(out))
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(json.dumps(out, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
