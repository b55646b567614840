"""Crossover-table contract (core/autotune.py).

Pins the four load-bearing properties of the measured-autotuning
surface:

* **Key determinism** — table keys are pure string assembly from the
  frozen IR, byte-identical across processes (no ``hash()``).
* **Rejection** — corrupt / version-mismatched / stale tables raise
  ``TableError`` from ``load_table`` and degrade to the *modelled*
  choice inside ``best_plan`` (planning never fails on a bad table).
* **The acceptance criterion** — under ``PlanPolicy(mode="cached")``
  and the committed default table, ``best_plan`` returns a measured
  winner for every registered spec's smoke + bench shapes (both keyed
  meshes) without timing anything at call time.
* **Measured-mode roundtrip** — a race persists its winner, and the
  reloaded table serves it back under ``cached`` with zero additional
  measurement.
"""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

from repro.core import PlanPolicy, Target, best_plan
from repro.core import autotune
from repro.kernels import registry

ROOT = Path(__file__).resolve().parent.parent
SINGLE = Target(name="single_chip", mesh_shape=(1, 1))


def _smoke_rec(name="mm", dtype="float32"):
    spec = registry.get(name)
    return spec.builder(*spec.smoke_args, dtype)


# ---------------------------------------------------------------------------
# key schema
# ---------------------------------------------------------------------------

def test_key_format_is_pinned():
    rec = _smoke_rec("mm")
    key = autotune.autotune_key(rec, (1, 1))
    name, dtype, extents, mesh = key.split("|")
    assert name == "mm" and dtype == "float32" and mesh == "mesh1x1"
    assert extents == "x".join(str(e) for e in rec.extents)


def test_key_is_deterministic_across_processes():
    code = (
        "import sys; sys.path.insert(0, 'src')\n"
        "from repro.core import autotune\n"
        "from repro.kernels import registry\n"
        "spec = registry.get('jacobi2d')\n"
        "rec = spec.builder(*spec.smoke_args, 'float32')\n"
        "print(autotune.autotune_key(rec, (1, 8)))\n"
    )
    proc = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        cwd=ROOT, env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stderr[-500:]
    local = autotune.autotune_key(_smoke_rec("jacobi2d"), (1, 8))
    assert proc.stdout.strip().splitlines()[-1] == local


def test_hierarchical_key_is_deterministic_across_processes():
    """The outer-mesh key component is pure string assembly too: a
    hierarchical key computed in a fresh process is byte-identical."""
    code = (
        "import sys; sys.path.insert(0, 'src')\n"
        "from repro.core import autotune\n"
        "from repro.kernels import registry\n"
        "spec = registry.get('mm')\n"
        "rec = spec.builder(*spec.smoke_args, 'int16')\n"
        "print(autotune.autotune_key(rec, (2, 2), outer_shape=(2, 4)))\n"
    )
    proc = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        cwd=ROOT, env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stderr[-500:]
    local = autotune.autotune_key(_smoke_rec("mm", "int16"), (2, 2),
                                  outer_shape=(2, 4))
    assert proc.stdout.strip().splitlines()[-1] == local
    name, dtype, extents, outer, mesh = local.split("|")
    assert (outer, mesh) == ("outer2x4", "mesh2x2")
    # flat keys are unchanged by the outer field (4-field schema)
    assert autotune.autotune_key(
        _smoke_rec("mm", "int16"), (2, 2)).count("|") == 3


def test_request_key_maps_builder_args_to_ir_extents():
    spec = registry.get("jacobi2d")
    req = autotune.PlanRequest(
        kind="jacobi2d", shape=tuple(spec.smoke_args), dtype="float32",
        target=Target(name="t", mesh_shape=(1, 8)))
    assert autotune.request_key(req) == autotune.autotune_key(
        _smoke_rec("jacobi2d"), (1, 8))


# ---------------------------------------------------------------------------
# table validation / rejection -> modelled fallback
# ---------------------------------------------------------------------------

def _entry(backend="pallas", us=None):
    return {"backend": backend,
            "us": us if us is not None else {backend: 10.0}}


@pytest.mark.parametrize("payload", [
    "{not json",
    json.dumps([1, 2, 3]),
    json.dumps({"schema": 99, "entries": {}}),              # version skew
    json.dumps({"schema": autotune.TABLE_SCHEMA}),          # no entries
    json.dumps({"schema": autotune.TABLE_SCHEMA,
                "entries": {"k": _entry(backend="vitis")}}),  # stale backend
    json.dumps({"schema": autotune.TABLE_SCHEMA,
                "entries": {"k": {"backend": "pallas",
                                  "us": {"pallas": -1}}}}),  # bad timing
])
def test_bad_tables_raise_table_error(tmp_path, payload):
    path = tmp_path / "table.json"
    path.write_text(payload, encoding="utf-8")
    with pytest.raises(autotune.TableError):
        autotune.load_table(path)


def test_missing_table_raises_table_error(tmp_path):
    with pytest.raises(autotune.TableError):
        autotune.load_table(tmp_path / "nope.json")


def test_bad_table_falls_back_to_modelled_plan(tmp_path):
    path = tmp_path / "corrupt.json"
    path.write_text("{not json", encoding="utf-8")
    errors_before = autotune.counters()["table_errors"]
    plan = best_plan(_smoke_rec("mm"), SINGLE,
                     policy=PlanPolicy(mode="cached", table_path=str(path)))
    assert plan.provenance == "modelled" and plan.backend == "pallas"
    assert autotune.counters()["table_errors"] == errors_before + 1


def test_corrupt_table_falls_back_to_modelled_hierarchical_plan(tmp_path):
    """A rejected table degrades two-level planning exactly like flat
    planning: the modelled ``HierarchicalPlan`` comes back, nothing
    raises, and the rejection is counted."""
    from repro.core import SERVING_HIERARCHICAL_TARGET

    path = tmp_path / "corrupt.json"
    path.write_text("{not json", encoding="utf-8")
    errors_before = autotune.counters()["table_errors"]
    plan = best_plan(_smoke_rec("mm"), SERVING_HIERARCHICAL_TARGET,
                     policy=PlanPolicy(mode="cached", table_path=str(path)))
    assert hasattr(plan, "outer_split")
    assert plan.provenance == "modelled"
    # two-level resolution consults the table for the outer key AND the
    # winner's inner sub-plan, so a corrupt table is rejected >= once
    assert autotune.counters()["table_errors"] > errors_before


def test_stale_hierarchical_entry_falls_back_to_modelled(tmp_path):
    """An entry-level corruption (stale backend name under a
    hierarchical key) rejects the whole table at load: cached planning
    for that key degrades to the modelled hierarchical choice."""
    from repro.core import SERVING_HIERARCHICAL_TARGET as HT

    rec = _smoke_rec("mm")
    key = autotune.autotune_key(rec, HT.mesh_shape,
                                outer_shape=HT.outer_shape)
    path = tmp_path / "stale.json"
    path.write_text(json.dumps({
        "schema": autotune.TABLE_SCHEMA,
        "entries": {key: _entry(backend="aie_v1")},
    }), encoding="utf-8")
    with pytest.raises(autotune.TableError):
        autotune.load_table(path)
    plan = best_plan(rec, HT,
                     policy=PlanPolicy(mode="cached", table_path=str(path)))
    assert hasattr(plan, "outer_split") and plan.provenance == "modelled"


def test_rewritten_table_is_picked_up_by_mtime(tmp_path):
    path = tmp_path / "t.json"
    table = autotune.new_table("v1")
    key = autotune.autotune_key(_smoke_rec("mm"), (1, 1))
    table["entries"][key] = _entry("xla", {"xla": 5.0, "pallas": 9.0})
    autotune.save_table(path, table)
    assert autotune.load_table(path)["entries"][key]["backend"] == "xla"
    table["entries"][key] = _entry("pallas", {"xla": 9.0, "pallas": 5.0})
    autotune.save_table(path, table)
    os.utime(path, ns=(path.stat().st_atime_ns,
                       path.stat().st_mtime_ns + 1))
    assert autotune.load_table(path)["entries"][key]["backend"] == "pallas"


def test_winner_clamped_to_runnable_backends(tmp_path):
    """A table measured on a big host must not dispatch this process to
    a mesh it cannot build: the stored timings pick the best *runnable*
    backend instead."""
    big = Target(name="chip_64x64", mesh_shape=(64, 64))
    rec = _smoke_rec("mm")
    assert "systolic" not in autotune.available_backends(big)
    path = tmp_path / "t.json"
    table = autotune.new_table()
    table["entries"][autotune.autotune_key(rec, big.mesh_shape)] = _entry(
        "systolic", {"systolic": 1.0, "xla": 3.0, "pallas": 7.0})
    autotune.save_table(path, table)
    plan = best_plan(rec, big,
                     policy=PlanPolicy(mode="cached", table_path=str(path)))
    assert plan.provenance == "measured"
    assert plan.backend == "xla"  # best of what this host can run


# ---------------------------------------------------------------------------
# the acceptance criterion: committed table serves everything, no timing
# ---------------------------------------------------------------------------

def test_committed_table_serves_every_bench_shape_without_timing():
    policy = PlanPolicy(mode="cached")
    before = autotune.counters()["measure_calls"]
    served = 0
    for spec in registry.specs():
        for dtype, args in registry.autotune_cases(spec):
            for mesh in ((1, 1), (1, 8)):
                rec = spec.builder(*args, dtype)
                plan = best_plan(rec, Target(name="t", mesh_shape=mesh),
                                 policy=policy)
                assert plan.provenance == "measured", (
                    f"{spec.name} {dtype} {args} mesh{mesh}: not in the "
                    "committed table — regenerate with "
                    "tools/gen_autotune.py")
                assert plan.backend in autotune.BACKENDS
                served += 1
    assert autotune.counters()["measure_calls"] == before
    # the registry's smoke+bench cases are a *subset*: the committed
    # table additionally covers the serving-shape census and the fused
    # MLP-pair chain keys (gen_autotune --serving, PR 7)
    entries = autotune.load_table(autotune.DEFAULT_TABLE_PATH)["entries"]
    assert served <= len(entries)
    assert any("+" in k for k in entries), (
        "no fused-chain keys in the committed table — regenerate with "
        "tools/gen_autotune.py")


def test_committed_table_serves_fused_mlp_pair_chains():
    """The serving MLP-pair chain entries resolve from the cache with a
    measured winner (no timing at serve time), and their nested
    per-stage measured shapes keep the entries honest."""
    from repro.core import fusion
    from repro.kernels.planned import plan_for

    table = autotune.load_table(autotune.DEFAULT_TABLE_PATH)
    chain_keys = [k for k in table["entries"] if "+" in k]
    assert chain_keys
    for key in chain_keys:
        kind, dtype, extents, _mesh = key.split("|")
        assert kind == "mm+mm"
        entry = table["entries"][key]
        assert entry["backend"] in fusion.FUSED_BACKENDS
        assert isinstance(entry["measured_shape"][0], list), key
    key = next(k for k in chain_keys if k.endswith("mesh1x8"))
    _, dtype, extents, _ = key.split("|")
    shapes = tuple(tuple(int(x) for x in part.split("x"))
                   for part in extents.split("+"))
    before = autotune.counters()["measure_calls"]
    plan = plan_for("mm+mm", shapes, dtype,
                    target=Target(name="t", mesh_shape=(1, 8)),
                    policy=PlanPolicy(mode="cached"))
    assert isinstance(plan, fusion.FusedPlan)
    assert plan.provenance == "measured"
    assert autotune.counters()["measure_calls"] == before


def test_committed_table_serves_hierarchical_serving_gemms():
    """The committed table carries the serving GEMM census under the
    serving hierarchical target's five-field keys (gen_autotune
    --hierarchy --merge), and ``best_plan`` serves every one of them as
    a measured two-level plan without timing anything."""
    from repro.core import SERVING_HIERARCHICAL_TARGET as HT

    table = autotune.load_table(autotune.DEFAULT_TABLE_PATH)
    hier_keys = [k for k in table["entries"] if "|outer" in k]
    assert hier_keys, (
        "no hierarchical keys in the committed table — regenerate with "
        "tools/gen_autotune.py --merge")
    before = autotune.counters()["measure_calls"]
    for key in hier_keys:
        name, dtype, extents, outer, mesh = key.split("|")
        assert outer == "outer" + "x".join(
            str(o) for o in HT.outer_shape), key
        assert mesh == "mesh" + "x".join(
            str(m) for m in HT.mesh_shape), key
        # mm/bmm builder args coincide with IR extents: rebuild from key
        args = tuple(int(x) for x in extents.split("x"))
        rec = registry.get(name).builder(*args, dtype)
        plan = best_plan(rec, HT, policy=PlanPolicy(mode="cached"))
        assert hasattr(plan, "outer_split"), key
        assert plan.provenance == "measured", key
        assert plan.backend in autotune.available_backends(HT), key
    assert autotune.counters()["measure_calls"] == before


def test_committed_table_entries_record_their_proxy():
    table = autotune.load_table(autotune.DEFAULT_TABLE_PATH)
    for key, entry in table["entries"].items():
        assert entry["backend"] in entry["us"], key
        assert "measured_shape" in entry and "measured_dtype" in entry, key


def test_modelled_policy_never_touches_the_table():
    before = autotune.counters()
    plan = best_plan(_smoke_rec("mm"), SINGLE,
                     policy=PlanPolicy(mode="modelled"))
    assert plan.provenance == "modelled"
    after = autotune.counters()
    assert (after["hits"], after["misses"]) == (
        before["hits"], before["misses"])


# ---------------------------------------------------------------------------
# measured mode: race -> persist -> cached roundtrip
# ---------------------------------------------------------------------------

def test_measured_roundtrip_persists_and_serves(tmp_path):
    path = tmp_path / "t.json"
    rec = _smoke_rec("mttkrp")
    measured = PlanPolicy(mode="measured", table_path=str(path),
                          reps=1, warmup=1)
    first = best_plan(rec, SINGLE, policy=measured)
    assert first.provenance == "measured"
    table = autotune.load_table(path)
    key = autotune.autotune_key(rec, SINGLE.mesh_shape)
    assert table["entries"][key]["backend"] == first.backend
    assert table["suite_median_us"] > 0
    calls = autotune.counters()["measure_calls"]
    again = best_plan(rec, SINGLE,
                      policy=PlanPolicy(mode="cached", table_path=str(path)))
    assert again.backend == first.backend
    assert autotune.counters()["measure_calls"] == calls


def test_hierarchical_measured_roundtrip_persists_and_serves(tmp_path):
    """Measured mode under a hierarchical target races the winning outer
    split's composition, persists it under the five-field key, and the
    reloaded table serves it back under ``cached`` with zero additional
    measurement — the same roundtrip contract as flat plans."""
    from repro.core import HierarchicalTarget

    path = tmp_path / "t.json"
    ht = HierarchicalTarget()
    rec = registry.get("mm").builder(64, 64, 64, "float32")
    measured = PlanPolicy(mode="measured", table_path=str(path),
                          reps=1, warmup=1)
    first = best_plan(rec, ht, policy=measured)
    assert hasattr(first, "outer_split")
    assert first.provenance == "measured"
    key = autotune.autotune_key(rec, ht.mesh_shape,
                                outer_shape=ht.outer_shape)
    table = autotune.load_table(path)
    assert table["entries"][key]["backend"] == first.backend
    calls = autotune.counters()["measure_calls"]
    again = best_plan(rec, ht,
                      policy=PlanPolicy(mode="cached", table_path=str(path)))
    assert again.backend == first.backend
    assert again.provenance == "measured"
    assert autotune.counters()["measure_calls"] == calls


def test_cached_miss_does_not_measure(tmp_path):
    path = tmp_path / "empty.json"
    autotune.save_table(path, autotune.new_table())
    counters = autotune.counters()
    plan = best_plan(_smoke_rec("fir"), SINGLE,
                     policy=PlanPolicy(mode="cached", table_path=str(path)))
    assert plan.provenance == "modelled"
    after = autotune.counters()
    assert after["measure_calls"] == counters["measure_calls"]
    assert after["misses"] == counters["misses"] + 1


def test_machine_factor_normalizes_by_suite_median():
    table = autotune.new_table()
    table["entries"] = {
        "a": _entry("pallas", {"pallas": 10.0}),
        "b": _entry("xla", {"xla": 100.0}),
        "c": _entry("pallas", {"pallas": 40.0}),
    }
    # local machine is uniformly 2x slower -> factor 2, regardless of key
    fresh = {"a": 20.0, "b": 200.0, "c": 80.0, "unshared": 1.0}
    assert autotune.machine_factor(table, fresh) == pytest.approx(2.0)
    assert autotune.machine_factor(table, {"unshared": 1.0}) == 1.0


@pytest.mark.parametrize("err,skipped", [
    ("unsupported", True),    # this backend cannot lower the recurrence
    ("compile", False),       # a compile error of an offered backend
])
def test_race_skips_only_unsupported_lowerings(monkeypatch, err, skipped):
    """A backend that cannot lower the recurrence leaves the race with a
    warning; any other failure of a backend the process offers
    propagates instead of being raced around."""
    from repro.core import codegen

    real = codegen.lower_plan

    def lower(plan, backend="pallas", **kw):
        if backend == "pallas":
            if err == "unsupported":
                raise codegen.UnsupportedLoweringError("no pallas here")
            raise RuntimeError("Mosaic refused the kernel")
        return real(plan, backend=backend, **kw)

    monkeypatch.setattr(codegen, "lower_plan", lower)
    policy = PlanPolicy(mode="measured", reps=1, warmup=0)
    rec = _smoke_rec()
    if skipped:
        with pytest.warns(UserWarning, match="pallas skipped for mm"):
            res = autotune.race(rec, SINGLE, policy)
        assert "pallas" not in res["us"] and "xla" in res["us"]
    else:
        with pytest.raises(RuntimeError, match="Mosaic refused"):
            autotune.race(rec, SINGLE, policy)
