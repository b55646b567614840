"""The program's own marks in a profiler trace, read beside
``trace.reduce``: its host spans (``repro/serve.<name>``, with their
arguments) and, for every device operation, the scope path JAX gave it,
the ``tf_op`` stat of the operation's event metadata, for example
``jit(paged_decode_step)/while/body/closed_call/attn.q/widesa.mm/...``.

Window, operations, program runs, idle gaps and the attribution of work
and idle time to spans by midpoint are ``trace.py``'s.  Only the
``tf_op`` stats are decoded here: ``jax.profiler.ProfileData`` does not
expose the stats of event metadata, so the ``.xplane.pb`` file's
metadata tables are read with ``google.protobuf`` against a subset of
the XPlane schema (``tsl/profiler/protobuf/xplane.proto``; field numbers
as there, every field not named here is skipped).

    python3 chipbench/program_trace.py <file.xplane.pb> \
        --config chipbench/configs/qwen1.5-0.5b.json

prints the serving readings of one trace kept by ``run.py --trace 1
--keep-trace DIR`` as one JSON object: the device-idle time inside the
engine's steps by engine span, and the decode program's device time by
scope with its KV share and weight-GEMM roofline share.
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import sys
from pathlib import Path

import numpy as np

if __name__ == "__main__":
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from chipbench import peaks, work  # noqa: E402
from chipbench.trace import (  # noqa: E402
    DEVICE_PLANE, SPAN_PREFIX, Reduced, op_name, reduce)

ENGINE = "repro/serve."
STEP = "step"
HARNESS_STEP = SPAN_PREFIX + "step"
DECODE_PROGRAM = "paged_decode_step"
KERNEL = "widesa."          # runtime.execute_plan's scope: a Pallas call

#: Planned sites whose GEMMs read the weights (decode_gemm_roofline).
WEIGHT_GEMMS = ("attn.q", "attn.k", "attn.v", "attn.out", "mlp.gate",
                "mlp.up", "mlp.down", "mlp.pair", "lm_head")
KV = ("kv.gather", "kv.write")
#: Every scope the program puts on the serving path, innermost wins.
SCOPES = WEIGHT_GEMMS + ("attn.paged_scores", "attn.paged_values",
                         "attn.scores", "attn.values") + KV
UNSCOPED = "unscoped"


# ---------------------------------------------------------------------------
# decoding the metadata tables
# ---------------------------------------------------------------------------

#: (message, [(field, number, type)]): a capitalised type names a message,
#: a trailing ``*`` a repeated field; XStat's values form one oneof.
_SCHEMA = [
    ("XStat", [("metadata_id", 1, "int64"), ("double_value", 2, "double"),
               ("uint64_value", 3, "uint64"), ("int64_value", 4, "int64"),
               ("str_value", 5, "string"), ("ref_value", 7, "uint64")]),
    ("XEventMetadata", [("id", 1, "int64"), ("name", 2, "string"),
                        ("stats", 5, "XStat*")]),
    ("XStatMetadata", [("id", 1, "int64"), ("name", 2, "string")]),
    # map<int64, V> is on the wire a repeated {key = 1, value = 2}
    ("EventMetadataEntry", [("key", 1, "int64"),
                            ("value", 2, "XEventMetadata")]),
    ("StatMetadataEntry", [("key", 1, "int64"),
                           ("value", 2, "XStatMetadata")]),
    ("XPlane", [("id", 1, "int64"), ("name", 2, "string"),
                ("event_metadata", 4, "EventMetadataEntry*"),
                ("stat_metadata", 5, "StatMetadataEntry*")]),
    ("XSpace", [("planes", 1, "XPlane*")]),
]
_STAT_VALUES = ("double_value", "uint64_value", "int64_value", "str_value",
                "ref_value")


@functools.cache
def _xspace():
    from google.protobuf import descriptor_pb2, descriptor_pool
    from google.protobuf import message_factory

    F = descriptor_pb2.FieldDescriptorProto
    fp = descriptor_pb2.FileDescriptorProto(
        name="chipbench_xplane.proto", package="chipbench_xplane",
        syntax="proto3")
    for msg_name, fields in _SCHEMA:
        m = fp.message_type.add(name=msg_name)
        if msg_name == "XStat":
            m.oneof_decl.add(name="value")
        for name, number, typ in fields:
            rep = typ.endswith("*")
            typ = typ.rstrip("*")
            f = m.field.add(name=name, number=number,
                            label=F.LABEL_REPEATED if rep
                            else F.LABEL_OPTIONAL)
            if typ[0].isupper():
                f.type = F.TYPE_MESSAGE
                f.type_name = f".chipbench_xplane.{typ}"
            else:
                f.type = getattr(F, "TYPE_" + typ.upper())
            if msg_name == "XStat" and name in _STAT_VALUES:
                f.oneof_index = 0
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fp)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("chipbench_xplane.XSpace"))


def tf_ops(path: str) -> list:
    """Per device plane, ordered as ``trace.reduce`` orders them, the
    ``tf_op`` of each operation by the name the trace gives it (entries
    that share a name share their ``tf_op`` in the recorded traces)."""
    space = _xspace().FromString(Path(path).read_bytes())
    out = []
    for plane in space.planes:
        dev = DEVICE_PLANE.match(plane.name)
        if not dev:
            continue
        stat_names = {e.key: e.value.name for e in plane.stat_metadata}
        by_name: dict = {}
        for e in plane.event_metadata:
            op = next((stat_names.get(s.ref_value, "")
                       if s.WhichOneof("value") == "ref_value"
                       else s.str_value for s in e.value.stats
                       if stat_names.get(s.metadata_id) == "tf_op"), "")
            by_name[e.value.name] = op
        out.append((int(dev.group(2)), by_name))
    return [m for _, m in sorted(out, key=lambda km: km[0])]


# ---------------------------------------------------------------------------
# the program's marks
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class ProgramTrace:
    red: Reduced          # trace.reduce of the same file
    scopes: list          # per device, each op's ``tf_op`` ('' if none)
    spans: list           # [(name, start, end, args)] ``repro/serve.*``,
    #                       prefix dropped, inside the window


def load(path: str, n_devices: int = 1) -> ProgramTrace:
    """``trace.reduce`` of one ``.xplane.pb`` file, with the scope of
    each of its operations and the engine's spans."""
    from jax.profiler import ProfileData

    red = reduce(path, n_devices)
    scopes = [[m.get(n, "") for n in d.names]
              for d, m in zip(red.ops, tf_ops(path))]
    lo, hi = red.window
    spans = [(ev.name[len(ENGINE):], ev.start_ns,
              ev.start_ns + ev.duration_ns, dict(ev.stats))
             for p in ProfileData.from_file(path).planes
             if p.name.startswith("/host:")
             for line in p.lines for ev in line.events
             if ev.name.startswith(ENGINE)
             and lo <= ev.start_ns and ev.start_ns + ev.duration_ns <= hi]
    return ProgramTrace(red, scopes, spans)


def module_program(name: str) -> str:
    """``jit_<name>(<fingerprint>)``, as the modules line names a
    program's run, -> ``<name>``."""
    name = name.split("(", 1)[0]
    return name[4:] if name.startswith("jit_") else name


def _parts(tf_op: str) -> list:
    return [p.split(":", 1)[0] for p in tf_op.split("/")]


def scope_of(tf_op: str) -> str:
    """The innermost of ``SCOPES`` on an operation's path, else
    ``unscoped`` (operations outside every planned site and KV helper,
    and those XLA made with no metadata)."""
    for p in reversed(_parts(tf_op)):
        if p in SCOPES:
            return p
    return UNSCOPED


@dataclasses.dataclass
class Step:
    start: float
    end: float
    inner: list           # [(name, start, end)] engine spans inside it

    @property
    def decode_only(self) -> bool:
        names = {n for n, _, _ in self.inner}
        return "decode" in names and "prefill" not in names


def engine_steps(pt: ProgramTrace) -> list:
    """The engine's ``step`` spans in the window, each with the engine
    spans it holds."""
    inner = [(n, s, e) for n, s, e, _ in pt.spans if n != STEP]
    return [Step(s, e, [sp for sp in inner if s <= sp[1] and sp[2] <= e])
            for n, s, e, _ in pt.spans if n == STEP]


def leaves(iv: np.ndarray) -> np.ndarray:
    """Mask of the operations that hold no other one.  A device's ops
    line nests the operations of a loop's body inside the loop's own
    event (``while.5`` spans every layer of the decode program), so
    device time is summed over leaves alone."""
    out = np.ones(len(iv), dtype=bool)
    order = np.lexsort((-iv[:, 1], iv[:, 0]))       # by start, outer first
    for a, b in zip(order[:-1], order[1:]):
        if iv[b, 0] < iv[a, 1] and iv[b, 1] <= iv[a, 1]:
            out[a] = False
    return out


# ---------------------------------------------------------------------------
# readings
# ---------------------------------------------------------------------------

def engine_idle(pt: ProgramTrace) -> dict | None:
    """Device-idle time inside the engine's steps, by the innermost span
    open over each idle gap's midpoint (``Reduced.idle_by_span`` over the
    engine's spans and the harness's ``step`` spans that hold them).

    ``per_step_ms`` is the idle time in the inner spans (every engine
    span but ``step`` itself) per engine step; ``by_span`` the seconds
    per engine span, ``step`` holding what only the outer step covers.
    ``harness_step_inner_share`` is the share of the idle time inside the
    harness's own ``step`` spans that falls in an inner engine span.
    """
    n_steps = sum(1 for sp in pt.spans if sp[0] == STEP)
    if not n_steps or not pt.red.ops:
        return None
    harness = [sp for sp in pt.red.spans if SPAN_PREFIX + sp[0] == HARNESS_STEP]
    spans = [(HARNESS_STEP, s, e) for _, s, e in harness] + [
        (ENGINE + n, s, e) for n, s, e, _ in pt.spans]
    idle = dataclasses.replace(pt.red, spans=spans).idle_by_span()
    by_span = {k[len(ENGINE):]: v for k, v in sorted(
        idle.items(), key=lambda kv: -kv[1]) if k.startswith(ENGINE)}
    inner = sum(v for k, v in by_span.items() if k != STEP)
    in_harness = inner + by_span.get(STEP, 0.0) + idle.get(HARNESS_STEP, 0.0)
    return {"steps": n_steps,
            "per_step_ms": 1e3 * inner / n_steps,
            "by_span": by_span,
            "harness_step_inner_share": (inner / in_harness
                                         if harness and in_harness else None)}


def decode_split(pt: ProgramTrace, top: int = 8) -> dict | None:
    """The decode program's device time in decode-only engine steps, by
    scope (mean over devices, leaf operations only), its largest
    unscoped operations, and the share of the weight-GEMM sites' time
    spent in the plans' Pallas calls (``widesa.<recurrence>``)."""
    steps = [st for st in engine_steps(pt) if st.decode_only]
    if not steps or not pt.red.ops:
        return None
    step_iv = np.asarray([(st.start, st.end) for st in steps], dtype=float)
    by_scope: dict = {}
    unscoped: dict = {}
    kernel = 0.0
    used = set()
    for d, mods, scopes in zip(pt.red.ops, pt.red.modules, pt.scopes):
        runs = mods.select(lambda n: module_program(n) == DECODE_PROGRAM)
        keep = np.zeros(len(d.names), dtype=bool)
        for idx in d.in_spans(runs):
            keep[idx] = True
        keep &= leaves(d.iv)
        for k, idx in enumerate(d.in_spans(step_iv)):
            for i in idx[keep[idx]]:
                used.add(k)
                dt = (d.iv[i, 1] - d.iv[i, 0]) / 1e9
                sc = scope_of(scopes[i])
                by_scope[sc] = by_scope.get(sc, 0.0) + dt
                if sc in WEIGHT_GEMMS and any(
                        p.startswith(KERNEL) for p in _parts(scopes[i])):
                    kernel += dt
                if sc == UNSCOPED:
                    key = op_name(d.names[i])
                    unscoped[key] = unscoped.get(key, 0.0) + dt
    if not used:
        return None
    n = len(pt.red.ops)
    by_scope = {k: v / n for k, v in sorted(by_scope.items(),
                                            key=lambda kv: -kv[1])}
    total = sum(by_scope.values())
    gemm = sum(by_scope.get(k, 0.0) for k in WEIGHT_GEMMS)
    return {"steps": len(used), "seconds": total, "by_scope": by_scope,
            "unscoped_share": by_scope.get(UNSCOPED, 0.0) / total,
            "unscoped_top": [[k, v / n] for k, v in sorted(
                unscoped.items(), key=lambda kv: -kv[1])[:top]],
            "gemm_kernel_share": kernel / n / gemm if gemm else None}


def decode_kv_share(split: dict | None) -> float | None:
    """Device time of the decode program under ``kv.gather`` or
    ``kv.write`` over all of its device time, in %."""
    if not split or split["seconds"] <= 0:
        return None
    kv = sum(split["by_scope"].get(k, 0.0) for k in KV)
    return 100.0 * kv / split["seconds"]


def decode_gemm_roofline(split: dict | None, lm: work.LM,
                         pk: peaks.Peaks) -> float | None:
    """Every weight read once per decode-only step at HBM bandwidth,
    over the decode program's device time under the weight-GEMM sites
    (``WEIGHT_GEMMS``), in %."""
    if not split:
        return None
    t = sum(split["by_scope"].get(k, 0.0) for k in WEIGHT_GEMMS)
    if t <= 0:
        return None
    return 100.0 * split["steps"] * lm.weight_bytes_total() / pk.hbm_bw / t


def readings(pt: ProgramTrace, lm: work.LM, pk: peaks.Peaks) -> dict:
    idle = engine_idle(pt)
    split = decode_split(pt)
    return {"engine_idle_ms": idle and idle["per_step_ms"],
            "engine_idle": idle,
            "decode_kv_share": decode_kv_share(split),
            "decode_gemm_roofline": decode_gemm_roofline(split, lm, pk),
            "decode_split": split}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("trace", help="a .xplane.pb file")
    ap.add_argument("--config", required=True,
                    help="the model's configuration file")
    ap.add_argument("--device-kind", default="TPU v5 lite")
    args = ap.parse_args(argv)
    pt = load(args.trace)
    lm = work.LM.from_config(json.loads(Path(args.config).read_text()))
    print(json.dumps(readings(pt, lm, peaks.peaks(args.device_kind))))
    return 0


if __name__ == "__main__":
    sys.exit(main())
