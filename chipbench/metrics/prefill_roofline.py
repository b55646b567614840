"""prefill_roofline: the prefill's share of its roofline, in %.

For each prefill in the traced steps: max(FLOPs / bf16 peak, bytes / HBM
bandwidth) over its real prompt tokens with causal attention, not the
padded bucket (chipbench/work.py); summed, over the prefill programs'
device time: every program that runs in the steps that admitted a
request, other than the decode program (so the prefill itself, the
write of its cache into the block pools, and the first token's argmax).
Programs are the trace's ``XLA Modules``, matched to the harness's
``step`` spans by midpoint."""

from chipbench.metrics_common import decode_program, traced_steps


def read(run):
    found = traced_steps(run)
    if found is None:
        return None
    steps, spans = found
    dec = decode_program(run, steps, spans)
    lm, pk = run.data["lm"], run.peaks
    bound, t = 0.0, 0.0
    for k, st in enumerate(steps):
        if not st.prefills:
            continue
        bound += sum(max(lm.prefill_flops(n) / pk.bf16_flops,
                         lm.prefill_bytes(n) / pk.hbm_bw)
                     for n in st.prefills)
        t += dec.other_per_step[k] if dec else 0.0
    return 100.0 * bound / t if t > 0 else None
