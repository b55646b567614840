"""What the serving cells' per-layer readers share: the traced steps,
the device time of the decode program and of everything else in each of
them, and the whole traced window's share of the chip's peak."""

from __future__ import annotations

import dataclasses

import numpy as np


def traced_steps(run):
    """(step records, host spans) of the steps run while tracing, in
    order; None where there is no trace or they do not pair up."""
    if run.trace is None or "steps" not in run.data:
        return None
    steps = [st for st in run.data["steps"] if st.traced]
    spans = run.trace.span_iv("step")
    if not steps or len(spans) != len(steps):
        return None
    return steps, spans


@dataclasses.dataclass
class Programs:
    name: str               # the decode program, as the trace names it
    per_step: list          # its device seconds in each step
    other_per_step: list    # every other program's device seconds


def decode_program(run, steps, spans) -> Programs | None:
    """The program that takes most device time in decode-only steps,
    and per step the seconds of it and of all other programs (means
    over devices)."""
    mods = run.trace.modules
    if not mods or len(mods[0].iv) == 0:
        return None
    hits = [m.in_spans(spans) for m in mods]
    tot: dict = {}
    for k, st in enumerate(steps):
        if st.prefills or not st.decode_ctx:
            continue
        for m, idx in zip(mods, hits):
            for i in idx[k]:
                tot[m.names[i]] = tot.get(m.names[i], 0.0) + (
                    m.iv[i, 1] - m.iv[i, 0])
    if not tot:
        return None
    name = max(tot, key=tot.get)
    per, other = [], []
    for k in range(len(steps)):
        d = [sum(m.iv[i, 1] - m.iv[i, 0] for i in idx[k]
                 if m.names[i] == name) for m, idx in zip(mods, hits)]
        o = [sum(m.iv[i, 1] - m.iv[i, 0] for i in idx[k]
                 if m.names[i] != name) for m, idx in zip(mods, hits)]
        per.append(float(np.mean(d)) / 1e9)
        other.append(float(np.mean(o)) / 1e9)
    return Programs(name, per, other)


def serve_mfu(run):
    """Model FLOPs of every token the traced steps processed (prompt
    tokens with causal attention over their real length, output tokens
    with attention over their real context, the head once per emitted
    token) per second of the traced window, over chips x the chip's bf16
    peak, in %."""
    if run.trace is None or "tracks" not in run.data:
        return None
    lm = run.data["lm"]
    flops = 0.0
    for st in run.data["steps"]:
        if st.traced:
            flops += sum(lm.prefill_flops(s) for s in st.prefills)
            flops += lm.decode_flops(st.decode_ctx)
    return 100.0 * flops / run.trace.window_s / (
        run.chips * run.peaks.bf16_flops)
