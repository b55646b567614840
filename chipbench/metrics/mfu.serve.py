"""mfu.serve: the whole serving step's share of the chip's peak, in %
(``metrics_common.serve_mfu``), as it moves ``output_tok_s``."""

from chipbench.metrics_common import serve_mfu


def read(run):
    return serve_mfu(run)
