"""What every cell shares: finding its files by name, the device check,
the compile cache, host spans, compile counters, and the result line.

Everything that belongs to one configuration, traffic mix, driver kind or
metric lives in a file of its own that is found by the name given in
``BENCHMARK.json``:

  chipbench/configs/<config>.json      (the ``file`` of the config entry)
  chipbench/workloads/<cell>.json      driver kind and system settings
  chipbench/traffic/<traffic>.json     the mix's parameters
  chipbench/drivers/<kind>.py          ``run(ctx) -> Run``
  chipbench/metrics/<metric>.py        ``read(run) -> float | None``
  chipbench/reference/<name>.py        a serve configuration's
                                       ``"reference"``: ``logits`` and
                                       ``work(config)``
"""

from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import os
import sys
import time
from pathlib import Path

from chipbench import traffic

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
SPAN_PREFIX = "chipbench/"


class NoDevice(RuntimeError):
    """No accelerator, or fewer chips than the cell asks for."""


class NoProgram(RuntimeError):
    """The system under test is not in this checkout."""


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    root: Path           # holds BENCHMARK.json and chipbench/
    name: str
    chips: int
    entry: dict          # the cell's entry in BENCHMARK.json
    settings: dict       # chipbench/workloads/<cell>.json
    config: dict         # chipbench/configs/<config>.json
    traffic: dict        # chipbench/traffic/<traffic>.json
    end_to_end: list     # metric entries that this cell reports
    per_layer: list


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    entries = {w["name"]: w for w in bench["workloads"]}
    if name not in entries:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"known: {sorted(entries)}")
    entry = entries[name]
    configs = {c["name"]: c for c in bench["configs"]}
    cdir = root / "chipbench"
    return Cell(
        root=root, name=name, chips=int(entry["chips"]), entry=entry,
        settings=json.loads((cdir / "workloads" / f"{name}.json")
                            .read_text()),
        config=json.loads((root / configs[entry["config"]]["file"])
                          .read_text()),
        traffic=traffic.load(cdir / "traffic" / f"{entry['traffic']}.json"),
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, name)])


def driver(kind: str, root: Path = ROOT):
    return load_module(root / "chipbench" / "drivers" / f"{kind}.py",
                       f"chipbench_driver_{kind}")


def metric_reader(name: str, root: Path = ROOT):
    return load_module(root / "chipbench" / "metrics" / f"{name}.py",
                       "chipbench_metric_" + name.replace(".", "_"))


# ---------------------------------------------------------------------------
# the program, the device and the compile cache
# ---------------------------------------------------------------------------

def import_program(root: Path = ROOT) -> None:
    """Put the system under test on the path; fail where it is absent."""
    src = root / "src"
    if not (src / "repro" / "kernels").is_dir():
        raise NoProgram(f"no program under {src}")
    sys.path.insert(0, str(src))


def compile_cache_dir(root: Path = ROOT) -> str:
    """JAX_COMPILATION_CACHE_DIR where it is set, else the checkout's
    fixed ``.jax_cache`` (the path is part of the cache key, so it never
    moves)."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        root / ".jax_cache")


def enable_compile_cache(root: Path = ROOT) -> str:
    import jax

    path = compile_cache_dir(root)
    jax.config.update("jax_compilation_cache_dir", path)
    # every program goes into the cache, however fast it compiled, so
    # that a second run of a cell compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def devices(chips: int):
    """The first ``chips`` accelerator devices, or NoDevice."""
    import jax

    devs = jax.devices()
    if devs[0].platform not in ("tpu", "gpu"):
        raise NoDevice(f"JAX finds no accelerator (platform "
                       f"{devs[0].platform!r})")
    if len(devs) < chips:
        raise NoDevice(f"the cell needs {chips} chips, JAX finds "
                       f"{len(devs)}")
    return devs[:chips]


def memory_peak_bytes(devs) -> int | None:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devs]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


# ---------------------------------------------------------------------------
# host spans and compile counters
# ---------------------------------------------------------------------------

class Spans:
    """Host spans of the harness's own calls into the program.  Each is
    kept in memory and, while the profiler runs, also written into its
    trace (``chipbench/<name>``) on the device's clock."""

    def __init__(self):
        self.tracing = False

    @contextlib.contextmanager
    def span(self, name: str):
        if not self.tracing:
            yield
            return
        import jax

        with jax.profiler.TraceAnnotation(SPAN_PREFIX + name):
            yield


class CompileCounter:
    """Counts the programs JAX traces and compiles in this process
    (``jax.monitoring`` duration events), so that a window can show it
    compiled nothing."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax

        self.counts = {e: 0 for e in self.EVENTS}

        def listen(event, duration, **kw):
            if event in self.counts:
                self.counts[event] += 1

        jax.monitoring.register_event_duration_secs_listener(listen)

    def snapshot(self) -> dict:
        return {"traces": self.counts[self.EVENTS[0]],
                "compiles": self.counts[self.EVENTS[1]]}


# ---------------------------------------------------------------------------
# the result
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Check:
    """One number compared with its limit: ``ok`` when value <= limit."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value == self.value and self.value <= self.limit


@dataclasses.dataclass
class Context:
    """What a driver is given."""
    cell: Cell
    seed: int
    seconds: float
    trace: bool
    keep_trace: str | None
    devices: list
    t_start: float       # process start on ``time.perf_counter``
    peaks: object        # peaks.Peaks of the device kind


@dataclasses.dataclass
class Run:
    """What a driver hands back; metric readers read it.  ``data`` holds
    the driver kind's own records (calls, requests, steps)."""
    setup_s: float
    window_s: float
    attempted: int
    failed: int
    checks: list
    memory_peak_bytes: int | None
    peaks: object                 # peaks.Peaks of the device kind
    chips: int
    trace: object = None          # trace.Reduced, with --trace 1
    data: dict = dataclasses.field(default_factory=dict)


def log(*parts) -> None:
    print(*parts, flush=True)


def result_line(*, correct: bool, attempted: int, failed: int,
                metrics: dict, device: dict, checks: list[Check],
                breakdown: dict | None = None) -> str:
    out = {"correct": correct, "attempted": attempted, "failed": failed,
           "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                     for c in checks}
    return json.dumps(out)


def now() -> float:
    return time.perf_counter()
