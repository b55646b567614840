"""The control, the reference one precision step below what the
configuration states, put in the program's place, must come out not
correct where the program comes out correct, on three seeds."""

import jax
import pytest

from chipbench import control, harness
from chipbench.tests.test_runs import root  # noqa: F401


@pytest.mark.parametrize("cell", ["mm-tiny", "mm-tiny-2x2",
                                  "qwen-tiny-closed", "qwen-tiny-open"])
def test_control_fails_where_the_program_passes(root, cell):  # noqa: F811
    c = harness.load_cell(cell, root)
    devs = jax.devices()[:c.chips]
    seeds = [11, 2**31 + 3, 987654321]
    if c.settings["driver"] == "serve":
        # float8, the control of the served bfloat16 cells: the tiny
        # float32 model's own control, bfloat16, flips a greedy token too
        # seldom at 512 tokens of vocabulary to show over a few hundred
        rows = control.serve(c, seeds, devs, seconds=2.0, quant="fp8")
    else:
        rows = control.recurrence(c, seeds, devs)
    limit = c.settings["limit"]
    for r in rows:
        assert r["program"] <= limit < r["control"], r
