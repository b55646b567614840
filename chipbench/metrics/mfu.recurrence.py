"""mfu.recurrence: the recurrence's operations per second over the traced
window (2*M*N*K of each call completed in it), over chips x the chip's
float peak (bf16 MXU rate), in %."""


def read(run):
    if run.trace is None or "ops_per_call" not in run.data:
        return None
    rate = run.data["traced_calls"] * run.data["ops_per_call"] \
        / run.trace.window_s
    return 100.0 * rate / (run.chips * run.peaks.bf16_flops)
