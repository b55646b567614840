"""exposed_collective_share: the share of the traced window in which a
collective runs on a device with no other operation running there,
averaged over the cell's devices, in %.

A collective is an operation whose HLO name is a collective opcode
(``collective-permute``, ``all-reduce``, ``all-gather``,
``reduce-scatter``, ``all-to-all``, ``collective-broadcast``), its async
``-start`` and ``-done`` halves included: the ring's exchange in the
chip-level schedules.  Its time that no other operation of the same
device overlaps is the exchange the schedule failed to hide.  A device
runs its operations one after another, so an event that another starts
inside is a container (a ``while`` loop, a ``call``), not work: it
hides nothing.  None where the trace holds no collective."""

import re

import numpy as np

from chipbench import trace as T

COLLECTIVE = re.compile(r"(collective-permute|all-reduce|all-gather|"
                        r"reduce-scatter|all-to-all|collective-broadcast)"
                        r"(-start|-done)?(\.\d+)?$")


def is_collective(name: str) -> bool:
    return COLLECTIVE.match(T.op_name(name)) is not None


def _containers(iv: np.ndarray) -> np.ndarray:
    """Mask of the events inside which the next event starts."""
    order = np.lexsort((-iv[:, 1], iv[:, 0]))
    mask = np.zeros(len(iv), bool)
    nxt = iv[order[1:], 0]
    mask[order[:-1]] = nxt < iv[order[:-1], 1]
    return mask


def read(run):
    if run.trace is None:
        return None
    exposed, found = [], False
    for dev in run.trace.ops:
        kind = np.array([is_collective(n) for n in dev.names], bool)
        coll = dev.iv[kind]
        other = dev.iv[~kind & ~_containers(dev.iv)]
        found = found or len(coll) > 0
        exposed.append(T.measure(coll) - T.overlap(coll, other))
    if not found:
        return None
    return 100.0 * sum(exposed) / len(exposed) / 1e9 / run.trace.window_s
