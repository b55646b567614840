"""mfu.prefill: the same whole-window share of the chip's peak as
``mfu.serve`` (``metrics_common.serve_mfu``), in the open-loop prefill
cell, where it stands beside ``prefill_roofline`` and moves
``ttft_p95_ms``."""

from chipbench.metrics_common import serve_mfu


def read(run):
    return serve_mfu(run)
