"""A checkout of small cells for the CPU tests: the benchmark's own
drivers, metrics and references, with configurations a test run holds.

The tiny model is float32: at 512 tokens of vocabulary a bfloat16
program flips the greedy token as often as a float8 control does, and
the two cannot be told apart over a few hundred tokens.  In float32 the
program agrees with the reference to rounding and never flips."""

import json
import shutil
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]

QWEN_TINY = {
    "name": "qwen-tiny", "source": "test size",
    "model_type": "qwen2", "hidden_act": "silu", "hidden_size": 64,
    "intermediate_size": 128, "num_attention_heads": 4,
    "num_hidden_layers": 2, "num_key_value_heads": 4,
    "rms_norm_eps": 1e-06, "rope_theta": 1000000.0,
    "tie_word_embeddings": True, "torch_dtype": "float32",
    "vocab_size": 512, "attention_bias": True,
    "program": {"family": "dense", "qkv_bias": True}, "reference": "qwen",
    "reduced": [],
}
MM_TINY = {"name": "mm-tiny", "recurrence": "mm",
           "extents": {"i": 256, "j": 256, "k": 256},
           "dtype": "float32", "precision": "highest", "reduced": []}
CELLS = {
    "mm-tiny": ("mm-tiny", "calls", 1, {
        "driver": "recurrence", "mesh": [1, 1], "backend": "pallas",
        "operand_sets": 2, "check_calls": 2, "check_from": 3,
        "trace_seconds": 1, "limit": 1e-6}),
    "mm-tiny-2x2": ("mm-tiny", "calls-2x2", 4, {
        "driver": "recurrence", "mesh": [2, 2], "backend": "systolic",
        "operand_sets": 2, "check_calls": 2, "check_from": 3,
        "trace_seconds": 1, "limit": 1e-6}),
    "qwen-tiny-closed": ("qwen-tiny", "closed", 1, {
        "driver": "serve", "lanes": 4, "max_seq": 128, "block_size": 16,
        "prefill_buckets": [16, 32, 64], "warm_steps": 4,
        "drain_seconds": 60, "trace_seconds": 2, "check_tokens": 200,
        "check_pad": 128, "limit": 1e-3}),
    "qwen-tiny-open": ("qwen-tiny", "open", 1, {
        "driver": "serve", "lanes": 2, "max_seq": 128, "block_size": 16,
        "prefill_buckets": [32, 64, 128], "warm_steps": 0,
        "drain_seconds": 60, "trace_seconds": 2, "check_tokens": 200,
        "check_pad": 128, "limit": 1e-3}),
}
TRAFFIC = {
    "calls": {"kind": "closed_calls", "clients": 1},
    "calls-2x2": {"kind": "closed_calls", "clients": 1},
    "closed": {"kind": "closed", "clients": 4, "per_client": 8,
               "prompt_len": [8, 40], "output_len": [3, 8]},
    "open": {"kind": "open_poisson", "rate_per_s": 16.0,
             "prompt_len": [40, 100], "output_len": [8, 8]},
}


def make_root(tmp: Path, cells=CELLS, configs=(QWEN_TINY, MM_TINY)) -> Path:
    """A benchmark root under ``tmp``: BENCHMARK.json, the tiny cells'
    files, and the real drivers, metrics and references (the last each
    linked on its own, so that a test can add one)."""
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    d = tmp / "chipbench"
    for sub in ("configs", "workloads", "traffic", "reference"):
        (d / sub).mkdir(parents=True, exist_ok=True)
    for sub in ("drivers", "metrics"):
        if not (d / sub).exists():
            (d / sub).symlink_to(BENCH / sub)
    for f in (BENCH / "reference").glob("*.py"):
        if not (d / "reference" / f.name).exists():
            (d / "reference" / f.name).symlink_to(f)
    for cfg in configs:
        (d / "configs" / f"{cfg['name']}.json").write_text(json.dumps(cfg))
    for name, spec in TRAFFIC.items():
        (d / "traffic" / f"{name}.json").write_text(json.dumps(spec))
    for name, (_, _, _, settings) in cells.items():
        (d / "workloads" / f"{name}.json").write_text(json.dumps(settings))
    bench["configs"] = [
        {"name": c["name"], "source": "test", "reduced": [], "why": "test",
         "file": f"chipbench/configs/{c['name']}.json"}
        for c in configs]
    bench["workloads"] = [
        {"name": n, "config": c, "traffic": t, "chips": k, "why": "test"}
        for n, (c, t, k, _) in cells.items()]
    kinds = {n: s["driver"] for n, (_, _, _, s) in cells.items()}
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            real = {w["name"]: w for w in json.loads(
                (BENCH.parent / "BENCHMARK.json").read_text())["workloads"]}
            want = {json.loads((BENCH / "workloads" / f"{w}.json")
                               .read_text())["driver"]
                    for w in m["workloads"] if w in real}
            m["workloads"] = [n for n, k in kinds.items() if k in want]
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench, indent=1))
    return tmp


def copy_bench(tmp: Path) -> Path:
    """A copy of the committed benchmark alone (no program)."""
    shutil.copytree(BENCH, tmp / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(BENCH.parent / "BENCHMARK.json", tmp / "BENCHMARK.json")
    return tmp
