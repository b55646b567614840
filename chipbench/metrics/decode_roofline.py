"""decode_roofline: the decode step's share of its memory roofline, in %.

Over the traced steps that admitted nothing (decode only): the bytes
each step needs (every weight once, each active lane's K/V up to its
position and one new row; chipbench/work.py) over HBM bandwidth, summed,
over the device time of the decode program in those steps.  The decode
program is the one that takes most device time in decode-only steps
(the trace's ``XLA Modules`` line, matched to the harness's ``step``
spans by midpoint)."""

from chipbench.metrics_common import decode_program, traced_steps


def read(run):
    found = traced_steps(run)
    if found is None:
        return None
    steps, spans = found
    dec = decode_program(run, steps, spans)
    if dec is None:
        return None
    lm = run.data["lm"]
    need, t = 0.0, 0.0
    for st, per_dev in zip(steps, dec.per_step):
        if st.prefills or not st.decode_ctx:
            continue
        need += lm.decode_bytes(st.decode_ctx) / run.peaks.hbm_bw
        t += per_dev
    return 100.0 * need / t if t > 0 else None
