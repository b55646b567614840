"""mm_roofline: the Pallas mm kernel's share of its roofline, in %.

The least time of the traced calls, max(2*M*N*K / float peak, 4*(M*K +
K*N + M*N) / HBM bandwidth) per call (chipbench/work.py, chipbench/
peaks.py), over the summed device time of the kernel: the operations
the trace names ``%matmul.<n> = ... custom-call(...)`` with
``custom_call_target="tpu_custom_call"`` (the Pallas call of
kernels/widesa_mm.py)."""

from chipbench import trace as T


def is_kernel(name: str) -> bool:
    return (T.op_name(name).split(".")[0] == "matmul"
            and "tpu_custom_call" in name)


def read(run):
    if run.trace is None or "ops_per_call" not in run.data \
            or not run.data["traced_calls"]:
        return None
    t = run.trace.op_seconds(is_kernel)
    if t <= 0:
        return None
    pk = run.peaks
    bound = max(run.data["ops_per_call"] / pk.bf16_flops,
                run.data["bytes_per_call"] / pk.hbm_bw)
    return 100.0 * run.data["traced_calls"] * bound / t
