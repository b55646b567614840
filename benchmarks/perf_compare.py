"""Generate the §Perf before/after tables from results/dryrun{,_v2,_v3}.

    PYTHONPATH=src python -m benchmarks.perf_compare
"""

from __future__ import annotations

import glob
import json
import os

from repro.core import roofline as RL

V5E = RL.peaks(RL.V5E)  # the dry run models a v5e pod

CHIPS = {"16x16": 256, "2x16x16": 512}


def _terms(d):
    coll = sum(d["coll"].values()) if d["coll"] else 0.0
    return {
        "t_comp": d["flops"] / V5E.bf16_flops,
        "t_mem": d["bytes_accessed"] / V5E.hbm_bw,
        "t_coll": coll / V5E.ici_bw,
        "temp": (d["memory"]["temp_bytes"]
                 + d["memory"]["argument_bytes"]) / 2**30,
        "useful": d["model_flops"] / max(
            d["flops"] * CHIPS[d["mesh"]], 1.0),
    }


def best_of(dirs: list[str], name: str):
    """Latest available result for a cell across version dirs."""
    for dd in reversed(dirs):
        p = os.path.join(dd, name)
        if os.path.exists(p):
            d = json.load(open(p))
            if d.get("ok"):
                return d, dd
    return None, None


def run(csv_rows=None):
    dirs = ["results/dryrun", "results/dryrun_v2", "results/dryrun_v3"]
    names = sorted(
        {os.path.basename(p) for p in glob.glob("results/dryrun/*.json")})
    print("\n== §Perf before/after (baseline -> latest optimized) ==")
    print(f"{'cell':44s} {'t_comp':>13s} {'t_mem':>13s} {'t_coll':>13s} "
          f"{'temp GB':>13s} {'frac':>11s} src")
    for name in names:
        base = json.load(open(os.path.join(dirs[0], name)))
        if not base.get("ok"):
            continue
        opt, src = best_of(dirs[1:], name)
        tb = _terms(base)
        if opt is None:
            continue
        tn = _terms(opt)
        fb = tb["t_comp"] / max(tb["t_comp"], tb["t_mem"], tb["t_coll"])
        fn = tn["t_comp"] / max(tn["t_comp"], tn["t_mem"], tn["t_coll"])
        tag = name.replace(".json", "")
        print(f"{tag:44s} {tb['t_comp']:5.2f}>{tn['t_comp']:5.2f} "
              f"{tb['t_mem']:6.2f}>{tn['t_mem']:6.2f} "
              f"{tb['t_coll']:6.2f}>{tn['t_coll']:6.2f} "
              f"{tb['temp']:5.1f}>{tn['temp']:6.1f} "
              f"{fb:.3f}>{fn:.3f} {os.path.basename(src)}")
        if csv_rows is not None:
            csv_rows.append((
                f"perf_{tag}", 0.0,
                f"frac={fb:.3f}->{fn:.3f};temp={tn['temp']:.1f}GB"))


if __name__ == "__main__":
    run()
