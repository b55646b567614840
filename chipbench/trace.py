"""Reduction of a JAX profiler trace to what the per-layer metrics read.

A traced run records the harness's own host spans (``chipbench/<name>``,
written by ``jax.profiler.TraceAnnotation``) into the same trace as the
device's work.  ``reduce`` reads the ``.xplane.pb`` file with
``jax.profiler.ProfileData`` and keeps, inside the traced window (the
span ``chipbench/window``):

  * per device (planes ``/device:TPU:<n>``), every operation of its
    ``XLA Ops`` line, named as the trace names it (the whole HLO
    instruction, ``%matmul.1 = f32[...] custom-call(...)``);
  * per device, every program execution of its ``XLA Modules`` line
    (``jit_<name>(<fingerprint>)``);
  * the harness's host spans.

Busy time is the union of a device's operation intervals; the idle share
is 1 - busy / window.  Host and device clocks in one trace differ by up
to about a millisecond, so work is given to the host span that holds
its midpoint, and an idle gap to the innermost span open over its
middle.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
import shutil
import tempfile

import numpy as np

SPAN_PREFIX = "chipbench/"
WINDOW = SPAN_PREFIX + "window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):(\d+)$")


# ---------------------------------------------------------------------------
# interval arithmetic (arrays of [start, end) in ns)
# ---------------------------------------------------------------------------

def union(iv: np.ndarray) -> np.ndarray:
    """Merge overlapping intervals; returns sorted disjoint intervals."""
    if len(iv) == 0:
        return np.zeros((0, 2))
    iv = iv[np.argsort(iv[:, 0], kind="stable")]
    out = [list(iv[0])]
    for s, e in iv[1:]:
        if s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return np.asarray(out, dtype=float)


def measure(iv: np.ndarray) -> float:
    u = union(iv)
    return float((u[:, 1] - u[:, 0]).sum()) if len(u) else 0.0


def clip(iv: np.ndarray, lo: float, hi: float) -> np.ndarray:
    if len(iv) == 0:
        return np.zeros((0, 2))
    c = np.stack([np.maximum(iv[:, 0], lo), np.minimum(iv[:, 1], hi)], 1)
    return c[c[:, 1] > c[:, 0]]


def overlap(a: np.ndarray, b: np.ndarray) -> float:
    """Length of union(a) that lies inside union(b)."""
    ua = union(a)
    return sum(measure(clip(ua, lo, hi)) for lo, hi in union(b))


def gaps(busy: np.ndarray, lo: float, hi: float) -> np.ndarray:
    """Idle intervals of [lo, hi) between the busy intervals."""
    u = union(clip(busy, lo, hi))
    edges = np.concatenate([[lo], u.reshape(-1), [hi]]).reshape(-1, 2)
    return edges[edges[:, 1] > edges[:, 0]]


def op_name(name: str) -> str:
    """The HLO name of an operation the trace names by its instruction."""
    return name.split(" = ", 1)[0].lstrip("%")


# ---------------------------------------------------------------------------
# the reduced trace
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Events:
    """One device's operations (or program executions) in the window."""
    iv: np.ndarray        # [n, 2] start, end in ns
    names: list

    def select(self, pred) -> np.ndarray:
        keep = [i for i, n in enumerate(self.names) if pred(n)]
        return self.iv[keep] if keep else np.zeros((0, 2))

    def seconds(self, idx=None) -> float:
        iv = self.iv if idx is None else self.iv[idx]
        return float((iv[:, 1] - iv[:, 0]).sum()) / 1e9

    def in_spans(self, spans: np.ndarray) -> list:
        """For each span, the indices of the events whose midpoint lies
        in it."""
        mid = 0.5 * (self.iv[:, 0] + self.iv[:, 1])
        return [np.flatnonzero((mid >= s) & (mid < e)) for s, e in spans]


@dataclasses.dataclass
class Reduced:
    window: tuple                 # (start, end) ns
    ops: list                     # [Events] per device
    modules: list                 # [Events] per device (program runs)
    spans: list                   # [(name, start, end)] host spans

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over devices."""
        return float(np.mean([measure(d.iv) for d in self.ops])) / 1e9

    def idle_share(self) -> float:
        return 1.0 - self.busy_s() / self.window_s

    def span_iv(self, name: str) -> np.ndarray:
        iv = [(s, e) for n, s, e in self.spans if n == name]
        return np.asarray(iv, dtype=float).reshape(-1, 2)

    def op_seconds(self, pred) -> float:
        """Summed duration of the operations whose name ``pred`` selects,
        averaged over devices."""
        return float(np.mean([measure(d.select(pred))
                              for d in self.ops])) / 1e9

    def idle_by_span(self) -> dict:
        """Idle seconds per host span name (mean over devices)."""
        lo, hi = self.window
        spans = sorted(self.spans, key=lambda s: s[2] - s[1])  # innermost
        names = [n for n, _, _ in spans]
        se = np.asarray([(s, e) for _, s, e in spans],
                        dtype=float).reshape(-1, 2)
        acc: dict = {}
        for d in self.ops:
            for g0, g1 in gaps(d.iv, lo, hi):
                mid = 0.5 * (g0 + g1)
                hit = np.flatnonzero((se[:, 0] <= mid) & (mid < se[:, 1]))
                name = names[hit[0]] if len(hit) else "outside spans"
                acc[name] = acc.get(name, 0.0) + (g1 - g0) / 1e9
        return {k: v / len(self.ops) for k, v in acc.items()}

    def breakdown(self) -> dict:
        """The ten operations (by HLO name) that took most device time,
        and the idle time by the host span it fell in."""
        tot: dict = {}
        for d in self.ops:
            for (s, e), n in zip(d.iv, d.names):
                key = op_name(n)
                tot[key] = tot.get(key, 0.0) + (e - s) / 1e9
        top = sorted(tot.items(), key=lambda kv: -kv[1])[:10]
        idle = sorted(self.idle_by_span().items(), key=lambda kv: -kv[1])
        return {"device_ops": [[k, v / len(self.ops)] for k, v in top],
                "idle_gaps": [[k, v] for k, v in idle[:10]]}


def _stat(ev, key):
    for k, v in ev.stats:
        if k == key:
            return v
    return None


def _line(plane, line_name: str, lo: float, hi: float) -> Events:
    iv, names = [], []
    for line in plane.lines:
        if line.name != line_name:
            continue
        for ev in line.events:
            s, e = ev.start_ns, ev.start_ns + ev.duration_ns
            if lo < e and s < hi:
                iv.append((max(s, lo), min(e, hi)))
                names.append(ev.name)
    return Events(np.asarray(iv, dtype=float).reshape(-1, 2), names)


def _host_ops(planes, lo: float, hi: float) -> Events:
    """The CPU backend runs its operations on host threads: each event
    that carries an ``hlo_op`` stands in for a device operation (tests
    only; a CPU run never reports a device metric)."""
    iv, names = [], []
    for plane in planes:
        for line in plane.lines:
            for ev in line.events:
                op = _stat(ev, "hlo_op")
                s, e = ev.start_ns, ev.start_ns + ev.duration_ns
                if op is not None and lo < e and s < hi:
                    iv.append((max(s, lo), min(e, hi)))
                    names.append(op)
    return Events(np.asarray(iv, dtype=float).reshape(-1, 2), names)


def reduce(path: str, n_devices: int) -> Reduced:
    """Reduce one ``.xplane.pb`` file (or the newest under a directory)."""
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        path = sorted(glob.glob(f"{path}/**/*.xplane.pb", recursive=True),
                      key=os.path.getmtime)[-1]
    pd = ProfileData.from_file(path)
    host = [p for p in pd.planes if p.name.startswith("/host:")]
    spans = [(ev.name[len(SPAN_PREFIX):], ev.start_ns,
              ev.start_ns + ev.duration_ns)
             for p in host for line in p.lines for ev in line.events
             if ev.name.startswith(SPAN_PREFIX)]
    win = [(s, e) for n, s, e in spans if SPAN_PREFIX + n == WINDOW]
    if not win:
        raise ValueError(f"{path}: no {WINDOW!r} span")
    lo, hi = win[0]
    spans = [s for s in spans if SPAN_PREFIX + s[0] != WINDOW]
    planes = sorted((p for p in pd.planes if DEVICE_PLANE.match(p.name)),
                    key=lambda p: int(DEVICE_PLANE.match(p.name).group(2)))
    if planes:
        planes = planes[:n_devices]
        return Reduced((lo, hi), [_line(p, OPS_LINE, lo, hi) for p in planes],
                       [_line(p, MODULES_LINE, lo, hi) for p in planes],
                       spans)
    ops = _host_ops(host, lo, hi)
    if len(ops.iv) == 0:
        raise ValueError(f"{path}: no device plane and no host-run ops")
    return Reduced((lo, hi), [ops], [Events(np.zeros((0, 2)), [])], spans)


class Session:
    """The profiler around the first part of a window.  ``start`` opens
    the trace and the ``chipbench/window`` span; ``maybe_stop(t)`` closes
    both once ``t`` reaches ``start``'s ``until``; ``stop`` reduces the
    trace and deletes its files (after copying the ``.xplane.pb`` to
    ``keep``, where given)."""

    def __init__(self, enabled: bool, spans, seconds: float = float("inf"),
                 keep: str | None = None):
        self.enabled = enabled
        self.spans = spans
        self.seconds = seconds
        self.keep = keep
        self.active = False
        self.stopped_at = float("inf")
        self._dir = None
        self._win = None

    def start(self, until: float) -> None:
        if not self.enabled:
            return
        import jax

        self._dir = tempfile.mkdtemp(prefix="chipbench-trace-")
        jax.profiler.start_trace(self._dir)
        self.spans.tracing = True
        self._win = jax.profiler.TraceAnnotation(WINDOW)
        self._win.__enter__()
        self.until = until
        self.active = True

    def maybe_stop(self, t: float) -> None:
        if self.active and t >= self.until:
            self._close(t)

    def _close(self, t: float) -> None:
        import jax

        self._win.__exit__(None, None, None)
        self.spans.tracing = False
        jax.profiler.stop_trace()
        self.stopped_at = t
        self.active = False

    def stop(self, devs) -> Reduced | None:
        if not self.enabled:
            return None
        if self.active:
            import time

            self._close(time.perf_counter())
        try:
            if self.keep:
                os.makedirs(self.keep, exist_ok=True)
                for f in glob.glob(f"{self._dir}/**/*.xplane.pb",
                                   recursive=True):
                    shutil.copy(f, self.keep)
            return reduce(self._dir, len(devs))
        finally:
            shutil.rmtree(self._dir, ignore_errors=True)
