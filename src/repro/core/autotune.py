"""Measured autotuning with a persisted crossover table.

The mapper's ``best_plan`` ranks mappings with the *modelled* cost
pipeline (space-time legality + the PLIO congestion model).  The model
picks the mapping; it does not know which *backend lowering* of that
mapping wins on a given machine — backend crossover points shift with
shape, dtype and mesh (EA4RCA's communication-avoiding schedule
selection and the Versal GEMM energy/performance study both pick
schedules empirically).  This module closes that gap:

  * ``race(rec, target, policy)`` times every backend lowering that is
    *available in this process* (``pallas`` / ``xla`` always; the
    chip-level ``systolic`` / ``allgather`` schedules when the host
    exposes enough devices for the target mesh) — warmup + median-of-k —
    and returns the winner;
  * winners persist to an on-disk JSON **crossover table** keyed by
    ``(spec, shape, dtype, mesh)`` (``autotune_key``, deterministic
    across processes: no ``hash()``, just extents in IR loop order);
  * a committed default table (``default_autotune.json`` next to this
    file, regenerated by ``tools/gen_autotune.py``) covers every
    registered spec's smoke + bench shapes, so cold-start serving gets
    measured winners with **zero measurement at serve time**;
  * ``PlanPolicy`` decides how ``mapper.best_plan`` consults all of
    this:

      ``modelled``   never touch the table (the pre-PR-6 behaviour);
      ``cached``     table hit -> stamp the measured winner on the plan;
                     miss -> the modelled choice, *no timing at call
                     time* (counted in ``counters()["misses"]``);
      ``measured``   table hit behaves like ``cached``; miss -> race the
                     backends now and persist the winner (when the
                     policy names a writable ``table_path``).

Timings in a table are raw microseconds from the generating machine.
They are comparable across machines only after normalizing by the
suite-median machine factor (the same scheme ``tools/compare_bench.py``
uses for the bench gate): ``machine_factor`` computes the median ratio
of fresh local timings to the table's entries over shared keys, and a
fresh timing divided by that factor lands in the table's time base.

Corrupt, stale or version-mismatched tables raise ``TableError`` from
``load_table``; ``best_plan`` treats every such failure as a clean
cache miss (modelled fallback + a one-time warning), never an error —
a bad table must not take serving down.
"""

from __future__ import annotations

import dataclasses
import functools
import json
import math
import statistics
import time
import warnings
from pathlib import Path
from typing import TYPE_CHECKING, Any

from .codegen import UnsupportedLoweringError
from .mapper import ExecutionPlan, Target, map_recurrence

if TYPE_CHECKING:  # pragma: no cover - typing only
    from .recurrence import UniformRecurrence

#: On-disk table schema this build reads/writes.  Bump on incompatible
#: format changes; readers reject any other value (TableError).
TABLE_SCHEMA = 1

#: The committed default table (regenerate: tools/gen_autotune.py).
DEFAULT_TABLE_PATH = Path(__file__).with_name("default_autotune.json")

#: Backend lowerings a race may time, in dispatch order.  Fused chains
#: (``core/fusion.py``) add ``fused_systolic`` — the one-shard_map
#: chain schedule; their ``xla``/``pallas`` entries are the
#: single-launch stage compositions.
BACKENDS = ("pallas", "xla", "systolic", "allgather", "fused_systolic")

#: Bench-table dtypes the kernel path does not execute natively: int32
#: packs as int16 on the AIE ladder, complex rides as real planes.  A
#: race executes the mapped dtype; the table key keeps the declared one.
EXEC_DTYPE = {"int32": "int16", "cfloat": "float32", "cint16": "int16"}


class TableError(RuntimeError):
    """A crossover table failed validation (corrupt/stale/wrong schema)."""


# ---------------------------------------------------------------------------
# policy + request (the planning surface's two value types)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class PlanPolicy:
    """How ``best_plan`` consults the crossover table (module docstring).

    ``table_path=None`` means the committed default table.  ``reps`` /
    ``warmup`` parameterize the measurement protocol of ``measured``
    mode (median of ``reps`` timed calls after ``warmup`` untimed ones).
    """

    mode: str = "modelled"          # "modelled" | "cached" | "measured"
    table_path: str | None = None   # None -> DEFAULT_TABLE_PATH
    reps: int = 3
    warmup: int = 1

    def __post_init__(self):
        if self.mode not in ("modelled", "cached", "measured"):
            raise ValueError(
                f"PlanPolicy.mode must be modelled|cached|measured, "
                f"got {self.mode!r}")

    def path(self) -> Path:
        return (Path(self.table_path) if self.table_path
                else DEFAULT_TABLE_PATH)


@dataclasses.dataclass(frozen=True)
class PlanRequest:
    """One shape-keyed planning request — the single way every call
    surface (``planned_dense``/``planned_bmm``, the serve engine, the
    benches) asks for a plan.

    ``kind``: a registered KernelSpec name; ``shape``: that spec's
    *builder* arguments (the same tuples ``KernelSpec.smoke_args`` /
    ``bench_cases`` carry — bench sizes double as autotune keys).

    A fused chain is requested as ``kind="producer+consumer"`` with
    ``shape`` a tuple of per-stage builder-arg tuples — resolution runs
    the fusion legality pass and returns a ``FusedPlan`` (or None when
    the chain is illegal: the caller falls back to unfused plans).
    """

    kind: str
    shape: tuple
    dtype: str
    target: Target
    policy: PlanPolicy = PlanPolicy()


@functools.lru_cache(maxsize=4096)
def resolve(req: PlanRequest) -> ExecutionPlan | None:
    """Best *feasible* plan for a request, or None (caller falls back).

    The one resolution path behind ``planned.plan_for``, the facade's
    dispatch and the serve engine.  Memoized per request — repeat shapes
    cost a cache hit, and the None outcome of infeasible shapes is
    cached too, so they never re-run the mapper search.
    """
    if any(d <= 0 for d in _flat_dims(req.shape)):
        return None
    if "+" in req.kind:  # fused chain request (core/fusion.py)
        if getattr(req.target, "outer_shape", None) is not None:
            # chains do not compose hierarchically (HierarchyError
            # "unsupported"); the facade falls back to unfused stage
            # plans, which DO go hierarchical
            return None
        from . import fusion
        from .mapper import best_plan

        try:
            chain = fusion.chain_from_request(
                req.kind, req.shape, req.dtype)
            plan = best_plan(chain, req.target, policy=req.policy)
        except (fusion.FusionError, RuntimeError, TypeError):
            return None
        return plan if plan.feasible else None
    from repro.kernels import registry  # late: kernels import core

    try:
        spec = registry.get(req.kind)
        rec = spec.builder(*req.shape, req.dtype)
    except (registry.UnregisteredRecurrenceError, TypeError):
        return None
    from .hierarchy import HierarchyError  # late: hierarchy imports mapper
    from .mapper import best_plan  # late bind: tests monkeypatch mapper

    try:
        plan = best_plan(rec, req.target, policy=req.policy)
    except (RuntimeError, HierarchyError):
        # HierarchyError: no legal outer split for this shape — the
        # facade degrades to its flat fallback rather than erroring
        return None
    return plan if plan.feasible else None


def _flat_dims(shape):
    """Flatten a (possibly chain-nested) request shape for validation."""
    for d in shape:
        if isinstance(d, (tuple, list)):
            yield from d
        else:
            yield d


def resolve_cache_clear() -> None:
    resolve.cache_clear()


# ---------------------------------------------------------------------------
# key schema
# ---------------------------------------------------------------------------

def autotune_key(rec: "UniformRecurrence", mesh_shape: tuple[int, ...],
                 outer_shape: tuple[int, ...] | None = None) -> str:
    """Deterministic (spec, shape, dtype, mesh) table key.

    ``<name>|<dtype>|<extents joined by 'x', IR loop order>|mesh<RxC>``
    — pure string assembly from frozen fields, so two processes (or two
    machines) derive byte-identical keys for the same request.

    Fused chains key as ``name1+name2|dtype|extents1+extents2|meshRxC``
    (same four fields; stage names and stage extents joined by ``+``) —
    duck-typed on ``.stages`` so both plan kinds share one schema.

    Hierarchical targets add a fifth field *before* the mesh —
    ``...|outer<DPxTP>|mesh<RxC>`` — so a two-level plan and the flat
    plan of the same recurrence never alias (the mesh field stays the
    *inner* chip geometry, matching ``HierarchicalTarget.mesh_shape``).
    """
    mesh = "x".join(str(m) for m in mesh_shape)
    stages = getattr(rec, "stages", None)
    if stages is not None:
        extents = "+".join(
            "x".join(str(e) for e in s.extents) for s in stages)
    else:
        extents = "x".join(str(e) for e in rec.extents)
    outer = ""
    if outer_shape is not None:
        outer = "outer" + "x".join(str(o) for o in outer_shape) + "|"
    return f"{rec.name}|{rec.dtype}|{extents}|{outer}mesh{mesh}"


def request_key(req: PlanRequest) -> str:
    """The table key a PlanRequest resolves to (builder args -> IR)."""
    from repro.kernels import registry

    outer = getattr(req.target, "outer_shape", None)
    if "+" in req.kind:
        from . import fusion

        chain = fusion.chain_from_request(req.kind, req.shape, req.dtype)
        return autotune_key(chain, req.target.mesh_shape, outer_shape=outer)
    rec = registry.get(req.kind).builder(*req.shape, req.dtype)
    return autotune_key(rec, req.target.mesh_shape, outer_shape=outer)


# ---------------------------------------------------------------------------
# table I/O + validation
# ---------------------------------------------------------------------------

def _validate(table: Any, path: Path) -> dict:
    if not isinstance(table, dict):
        raise TableError(f"{path}: table root must be an object")
    if table.get("schema") != TABLE_SCHEMA:
        raise TableError(
            f"{path}: schema {table.get('schema')!r} != supported "
            f"{TABLE_SCHEMA} (version-mismatched table)")
    entries = table.get("entries")
    if not isinstance(entries, dict):
        raise TableError(f"{path}: missing 'entries' object")
    for key, entry in entries.items():
        if not isinstance(entry, dict):
            raise TableError(f"{path}: entry {key!r} is not an object")
        backend = entry.get("backend")
        us = entry.get("us")
        if backend not in BACKENDS:
            raise TableError(
                f"{path}: entry {key!r} names unknown backend "
                f"{backend!r} (stale table?)")
        if (not isinstance(us, dict) or backend not in us
                or not all(isinstance(v, (int, float)) and v > 0
                           for v in us.values())):
            raise TableError(
                f"{path}: entry {key!r} has no positive 'us' timing "
                f"for its winner (stale table?)")
    return table


@functools.lru_cache(maxsize=32)
def _load_cached(path_str: str, mtime_ns: int) -> dict:
    path = Path(path_str)
    try:
        raw = json.loads(path.read_text(encoding="utf-8"))
    except (OSError, ValueError) as e:
        raise TableError(f"{path}: unreadable/corrupt table: {e}") from e
    return _validate(raw, path)


def load_table(path: str | Path) -> dict:
    """Load + validate a crossover table.  Raises ``TableError`` on any
    corruption (callers that must not fail catch it and treat the table
    as empty).  Cached by (path, mtime), so a rewrite is picked up."""
    path = Path(path)
    try:
        mtime = path.stat().st_mtime_ns
    except OSError as e:
        raise TableError(f"{path}: no table file: {e}") from e
    return _load_cached(str(path), mtime)


def save_table(path: str | Path, table: dict) -> None:
    path = Path(path)
    table = dict(table, schema=TABLE_SCHEMA)
    us = [e["us"][e["backend"]] for e in table.get("entries", {}).values()]
    if us:
        table["suite_median_us"] = round(statistics.median(us), 1)
    path.parent.mkdir(parents=True, exist_ok=True)
    tmp = path.with_suffix(path.suffix + ".tmp")
    tmp.write_text(json.dumps(table, indent=2, sort_keys=True) + "\n",
                   encoding="utf-8")
    tmp.replace(path)


def new_table(note: str = "") -> dict:
    return {"schema": TABLE_SCHEMA, "note": note, "entries": {}}


def machine_factor(table: dict, fresh_us: dict[str, float]) -> float:
    """Suite-median machine-speed factor between local timings and a
    table (the scheme ``tools/compare_bench.py`` applies to the bench
    gate): median over shared keys of fresh/table winner-µs.  Dividing a
    fresh timing by this factor lands it in the table's time base."""
    ratios = []
    for key, us in fresh_us.items():
        entry = table.get("entries", {}).get(key)
        if entry:
            ratios.append(us / entry["us"][entry["backend"]])
    if not ratios:
        return 1.0
    return statistics.median(ratios)


# ---------------------------------------------------------------------------
# observability counters
# ---------------------------------------------------------------------------

_COUNTERS = {"hits": 0, "misses": 0, "measure_calls": 0, "table_errors": 0}
_WARNED_PATHS: set[str] = set()


def counters() -> dict[str, int]:
    """Autotune observability: table hits/misses, in-process measurement
    races, rejected-table events.  Counted once per *unique* resolution
    (requests memoize), which is exactly what tests pin: a serving warm
    start must show ``measure_calls`` unchanged."""
    return dict(_COUNTERS)


def counters_clear() -> None:
    for k in _COUNTERS:
        _COUNTERS[k] = 0


# ---------------------------------------------------------------------------
# measurement (warmup + median-of-k race)
# ---------------------------------------------------------------------------

def available_backends(target: Target) -> tuple[str, ...]:
    """Backends this process can execute for ``target``: pallas/xla
    always; the chip-level schedules only when the host exposes enough
    devices to build the target mesh.  A hierarchical target needs
    ``dp * tp`` disjoint inner meshes for its chip backends (delegated
    to ``hierarchy.hierarchical_available_backends``)."""
    import jax

    if getattr(target, "outer_shape", None) is not None:
        from .hierarchy import hierarchical_available_backends

        return hierarchical_available_backends(target)
    avail = ["pallas", "xla"]
    try:
        n_dev = jax.local_device_count()
    except RuntimeError:  # pragma: no cover - no backend at all
        n_dev = 1
    if n_dev >= math.prod(target.mesh_shape) and len(target.mesh_shape) >= 2:
        avail += ["systolic", "allgather"]
    return tuple(avail)


def _time_backend(fn, operands, *, warmup: int, reps: int) -> float:
    import jax

    for _ in range(max(warmup, 1)):
        jax.block_until_ready(fn(*operands))
    samples = []
    for _ in range(max(reps, 1)):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*operands))
        samples.append((time.perf_counter() - t0) * 1e6)
    return statistics.median(samples)


def _skip_warning(backend: str, name: str, err: Exception) -> None:
    """A backend that cannot lower this recurrence does not enter the
    race.  Any other failure (a compile error of a backend this process
    offers, a wrong result shape) propagates."""
    warnings.warn(f"autotune race: {backend} skipped for {name}: {err}",
                  stacklevel=3)


def _race_chain(chain, target: Target, policy: PlanPolicy,
                backends: tuple[str, ...] | None) -> dict:
    """Chain variant of ``race``: times the fused backends (the
    one-shard_map schedule when the mesh/devices permit, plus the
    single-launch xla/pallas compositions) on real chain operands."""
    import numpy as np

    from . import fusion

    _COUNTERS["measure_calls"] += 1
    exec_dtype = EXEC_DTYPE.get(chain.dtype, chain.dtype)
    exec_chain = (chain if exec_dtype == chain.dtype
                  else chain.with_dtype(exec_dtype))
    plan = fusion.fuse(exec_chain, target)  # FusionError propagates
    operands = fusion.chain_operands(exec_chain, np.random.default_rng(0))
    avail = backends or fusion.fused_available_backends(plan)
    mesh = None
    if "fused_systolic" in avail:
        from repro.compat import make_mesh

        mesh = make_mesh(tuple(target.mesh_shape[:2]),
                         tuple(target.mesh_axes[:2]))
    us: dict[str, float] = {}
    for backend in avail:
        try:
            fn = fusion.lower_fused(plan, backend=backend, mesh=mesh)
            us[backend] = round(
                _time_backend(fn, operands, warmup=policy.warmup,
                              reps=policy.reps), 1)
        except (UnsupportedLoweringError, fusion.FusionError) as e:
            _skip_warning(backend, chain.name, e)
    if not us:
        raise RuntimeError(
            f"autotune race: no backend ran for chain {chain.name}")
    winner = min(us, key=us.get)
    return {
        "backend": winner,
        "us": us,
        "measured_shape": [list(s.extents) for s in exec_chain.stages],
        "measured_dtype": exec_dtype,
    }


def race(rec: "UniformRecurrence", target: Target,
         policy: PlanPolicy = PlanPolicy(mode="measured"),
         backends: tuple[str, ...] | None = None) -> dict:
    """Race the available backend lowerings of ``rec`` on real operands.

    Returns a table entry: ``{"backend": winner, "us": {backend: µs},
    "measured_shape": extents, "measured_dtype": dtype}``.  Measurement
    protocol: per backend, ``policy.warmup`` untimed calls (compile
    excluded) then the median of ``policy.reps`` timed calls.  Dtypes
    the kernels do not execute natively race on their ``EXEC_DTYPE``
    mapping (int32 -> int16 packing, complex -> real planes) — the
    relative crossover is what the table records.

    A ``fusion.RecurrenceChain`` races its fused backends instead
    (``measured_shape`` records the per-stage extents).
    """
    if hasattr(rec, "stages"):
        return _race_chain(rec, target, policy, backends)
    import numpy as np

    from repro.kernels import registry
    from .codegen import lower_plan
    from .mapper import best_plan

    _COUNTERS["measure_calls"] += 1
    spec = registry.get(rec.name)
    exec_dtype = EXEC_DTYPE.get(rec.dtype, rec.dtype)
    # dtype is a plain IR field; the access structure is dtype-free, so
    # the executable twin of a cint16/int32 recurrence is a replace()
    exec_rec = (rec if exec_dtype == rec.dtype
                else dataclasses.replace(rec, dtype=exec_dtype))
    plan = best_plan(exec_rec, target)  # modelled plan carries the tiles
    operands = spec.operands(exec_rec, np.random.default_rng(0))
    mesh = None
    avail = backends or available_backends(target)
    if (any(b in ("systolic", "allgather") for b in avail)
            and getattr(target, "outer_shape", None) is None):
        # hierarchical plans build their own per-group inner meshes in
        # lower_hierarchical; only flat chip schedules need one here
        from repro.compat import make_mesh

        mesh = make_mesh(tuple(target.mesh_shape[:2]),
                         tuple(target.mesh_axes[:2]))
    us: dict[str, float] = {}
    for backend in avail:
        try:
            fn = lower_plan(plan, backend=backend, mesh=mesh)
            us[backend] = round(
                _time_backend(fn, operands, warmup=policy.warmup,
                              reps=policy.reps), 1)
        except UnsupportedLoweringError as e:
            _skip_warning(backend, rec.name, e)
    if not us:
        raise RuntimeError(f"autotune race: no backend ran for {rec.name}")
    winner = min(us, key=us.get)
    return {
        "backend": winner,
        "us": us,
        "measured_shape": list(rec.extents),
        "measured_dtype": exec_dtype,
    }


# ---------------------------------------------------------------------------
# the policy hook mapper.best_plan calls
# ---------------------------------------------------------------------------

def apply_policy(plan: ExecutionPlan, policy: PlanPolicy) -> ExecutionPlan:
    """Stamp a modelled plan with the policy's backend decision.

    ``cached``: table hit -> measured winner (clamped to the backends
    this process can execute, by stored timings); miss -> the modelled
    plan untouched, **nothing is timed**.  ``measured``: additionally
    race + persist on a miss.  Any table failure counts as a miss (one
    warning per path, then silence).
    """
    if policy.mode == "modelled":
        return plan
    key = autotune_key(plan.recurrence, plan.target.mesh_shape,
                       outer_shape=getattr(plan.target, "outer_shape", None))
    path = policy.path()
    table: dict | None
    try:
        table = load_table(path)
    except TableError as e:
        table = None
        _COUNTERS["table_errors"] += 1
        if str(path) not in _WARNED_PATHS:
            _WARNED_PATHS.add(str(path))
            warnings.warn(
                f"autotune: rejecting table ({e}); planning falls back "
                "to the modelled choice", stacklevel=2)
    entry = (table or {}).get("entries", {}).get(key)
    if entry is not None:
        _COUNTERS["hits"] += 1
    else:
        _COUNTERS["misses"] += 1
        if policy.mode == "measured":
            entry = race(plan.recurrence, plan.target, policy)
            if policy.table_path is not None:
                table = table if table is not None else new_table(
                    "measured in-process by core/autotune.py")
                table.setdefault("entries", {})[key] = entry
                save_table(path, table)
    if entry is None:
        return plan
    backend = entry["backend"]
    if hasattr(plan, "chain"):  # FusedPlan: clamp to the fused backends
        from . import fusion

        runnable = set(fusion.fused_available_backends(plan))
    else:
        # flat and hierarchical plans share this path: available_backends
        # dispatches on the target kind (outer-aware device need)
        runnable = set(available_backends(plan.target))
    if backend not in runnable:
        timed = {b: u for b, u in entry["us"].items() if b in runnable}
        backend = (min(timed, key=timed.get) if timed
                   else plan.backend)
    return dataclasses.replace(plan, backend=backend,
                               provenance="measured")


# ---------------------------------------------------------------------------
# default-table generation (driven by tools/gen_autotune.py)
# ---------------------------------------------------------------------------

def build_default_table(meshes: tuple[tuple[int, ...], ...] = ((1, 1), (1, 8)),
                        policy: PlanPolicy = PlanPolicy(mode="measured"),
                        verbose: bool = True,
                        extra_cases: tuple = (),
                        chain_cases: tuple = ()) -> dict:
    """Measure a crossover table covering every registered spec's smoke
    and bench shapes for the given meshes.

    Bench shapes (the paper-scale Table III sizes) are raced at their
    spec's *smoke proxy* shape — racing a 8192^3 matmul in interpret
    mode is hours, and the crossover, not the absolute time, is what the
    entry records; ``measured_shape``/``measured_dtype`` keep the proxy
    honest in the table.  Regenerate on real hardware for real timings
    (``tools/gen_autotune.py``).

    ``extra_cases``: additional ``(kind, builder_args, dtype)`` cases —
    the serving GEMM shapes ``tools/gen_autotune.py --serving`` collects
    from the model stack — keyed at their real shape, raced at the
    spec's smoke proxy like bench shapes.  ``chain_cases``: fused-chain
    ``(kind, per_stage_args, dtype)`` cases, raced at their *real*
    (small) shapes; meshes where a chain is fusion-illegal are skipped.
    """
    from repro.kernels import registry

    table = new_table(
        "committed default crossover table: winners measured at each "
        "spec's smoke proxy shape (see docs/autotune.md); regenerate "
        "with tools/gen_autotune.py")
    proxy_cache: dict[tuple[str, str], dict] = {}

    def proxy_entries(spec, exec_dtype: str) -> dict:
        cache_key = (spec.name, exec_dtype)
        if cache_key not in proxy_cache:
            for mesh_shape in meshes:
                target = Target(name=f"autotune_{mesh_shape}",
                                mesh_shape=mesh_shape)
                proxy = spec.builder(*spec.smoke_args, exec_dtype)
                entry = race(proxy, target, policy)
                proxy_cache.setdefault(cache_key, {})[mesh_shape] = entry
            if verbose:
                e = proxy_cache[cache_key][meshes[0]]
                print(f"  raced {spec.name:13s} {exec_dtype:8s} "
                      f"-> {e['backend']:6s} {e['us']}")
        return proxy_cache[cache_key]

    for spec in registry.specs():
        for dtype, args in registry.autotune_cases(spec):
            entries = proxy_entries(spec, EXEC_DTYPE.get(dtype, dtype))
            for mesh_shape in meshes:
                rec = spec.builder(*args, dtype)
                key = autotune_key(rec, mesh_shape)
                table["entries"][key] = dict(entries[mesh_shape])
    for kind, args, dtype in extra_cases:
        spec = registry.get(kind)
        entries = proxy_entries(spec, EXEC_DTYPE.get(dtype, dtype))
        for mesh_shape in meshes:
            rec = spec.builder(*args, dtype)
            key = autotune_key(rec, mesh_shape)
            table["entries"][key] = dict(entries[mesh_shape])
    for kind, shapes, dtype in chain_cases:
        from . import fusion

        chain = fusion.chain_from_request(kind, shapes, dtype)
        for mesh_shape in meshes:
            target = Target(name=f"autotune_{mesh_shape}",
                            mesh_shape=mesh_shape)
            try:
                entry = race(chain, target, policy)
            except fusion.FusionError as e:
                if verbose:
                    print(f"  chain {kind} skipped on mesh "
                          f"{mesh_shape}: [{e.reason}]")
                continue
            key = autotune_key(chain, mesh_shape)
            table["entries"][key] = entry
            if verbose:
                print(f"  raced chain {kind:20s} {dtype:8s} mesh"
                      f"{mesh_shape} -> {entry['backend']} "
                      f"{entry['us']}")
    return table
