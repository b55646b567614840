"""The one traffic generator: reads a mix's parameter file and makes the
requests of a run from ``--seed``.

Every seed gets the same requests at the same times: lengths are a
fixed grid over the mix's range and gaps are fixed quantiles of the
exponential distribution, put in one fixed order (a replayed schedule).
The seed draws the token ids, and which closed-loop client holds which
place in the list.  So two seeds give the same work, and the spread
between runs is the system's, not the draw's: with the order drawn from
the seed, the 95th percentile of time to first token of the open-loop
prefill cell read 421-984 ms over three seeds on one TPU v5e chip.

Mix kinds (``"kind"`` in the file):

  closed_calls  one caller that sends its next call when the last one
                returns (recurrence cells).
  closed        ``clients`` callers, each submitting its next request
                when its previous one completes.
  open_poisson  independent users: arrivals at ``rate_per_s``, sent on
                schedule whatever the system's state.
"""

from __future__ import annotations

import dataclasses
import json
from pathlib import Path

import numpy as np


@dataclasses.dataclass
class Spec:
    prompt_len: int
    output_len: int
    arrival_s: float = 0.0   # open loop: due time from the window's start
    client: int = 0          # closed loop: the caller that sends it


def load(path: Path) -> dict:
    mix = json.loads(Path(path).read_text())
    if mix.get("kind") not in ("closed_calls", "closed", "open_poisson"):
        raise ValueError(f"{path}: unknown traffic kind {mix.get('kind')!r}")
    return mix


def rng_for(seed: int, stream: str) -> np.random.Generator:
    """An independent numpy stream per purpose, from any whole seed."""
    return np.random.default_rng(
        [seed % 2**63, int.from_bytes(stream.encode(), "little") % 2**63])


def grid(lo: int, hi: int, n: int) -> np.ndarray:
    """``n`` integers spread evenly over [lo, hi] (midpoints of n
    equal strata of the uniform distribution)."""
    u = (np.arange(n) + 0.5) / n
    return np.rint(lo + u * (hi - lo)).astype(np.int64)


def exp_gaps(rate: float, n: int, total: float) -> np.ndarray:
    """``n`` exponential inter-arrival quantiles at ``rate``, scaled so
    that the ``n`` arrivals fall inside ``total`` seconds."""
    u = (np.arange(n) + 0.5) / n
    g = -np.log1p(-u) / rate
    return g * (total * n / (n + 0.5)) / g.sum()


def open_loop(mix: dict, seconds: float) -> list[Spec]:
    """The requests due in a window of ``seconds``: round(rate *
    seconds) of them, arrivals sorted in time, the same for every
    seed."""
    n = max(1, int(round(mix["rate_per_s"] * seconds)))
    fixed = np.random.default_rng(0)
    plen = fixed.permutation(grid(*mix["prompt_len"], n))
    olen = fixed.permutation(grid(*mix["output_len"], n))
    arrivals = np.cumsum(fixed.permutation(
        exp_gaps(mix["rate_per_s"], n, seconds)))
    return [Spec(int(p), int(o), float(t))
            for p, o, t in zip(plen, olen, arrivals)]


def closed_loop(mix: dict, seed: int) -> list[list[Spec]]:
    """Each client's queue of requests.  One list of ``per_client``
    (prompt, output) pairs from the grids, mixed in a fixed order; client
    c starts it at offset c * per_client / clients and goes round.  So
    every seed runs the same requests at the same times, and the seed
    only chooses which client holds which offset (and the token ids)."""
    k, n = mix["per_client"], mix["clients"]
    plen, olen = grid(*mix["prompt_len"], k), grid(*mix["output_len"], k)
    fixed = np.random.default_rng(0)
    pairs = list(zip(plen[fixed.permutation(k)], olen[fixed.permutation(k)]))
    offsets = rng_for(seed, "closed_loop").permutation(n) * k // n
    return [[Spec(int(p), int(o), client=c)
             for p, o in pairs[off:] + pairs[:off]]
            for c, off in enumerate(offsets)]


def prompt_source(seed: int, vocab: int):
    """Token-id prompts drawn from the seed: call ``next_prompt(length)``
    once per request, in submission order."""
    rng = rng_for(seed, "tokens")

    def next_prompt(length: int) -> np.ndarray:
        return rng.integers(0, vocab, length, dtype=np.int32)

    return next_prompt
