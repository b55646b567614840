"""Random model weights made by the benchmark, on the device, from the
seed: one jitted call, in the type they are served in.

The tree's structure and shapes are the serving program's (``jax
.eval_shape`` of its init); the values are the benchmark's own, so the
reference and the program read the same numbers and the reference takes
nothing that the program made.  By leaf name:

  embed              normal, std 2 / sqrt(d): logits of std about 2
  norm weights       1 + 0.1 * normal (every ``ln*`` / ``*norm`` leaf)
  biases (b*)        0.1 * normal
  matrices (w*)      normal / sqrt(fan_in)
"""

from __future__ import annotations

import jax
import numpy as np

from chipbench import traffic


def _rule(path: tuple, shape, d: int):
    name = path[-1]
    if name == "embed" or name == "lm_head":
        return lambda k: jax.random.normal(k, shape) * (2.0 / np.sqrt(d))
    if any(p.startswith("ln") or p.endswith("norm") for p in path[:-1]):
        return lambda k: 1.0 + 0.1 * jax.random.normal(k, shape)
    if name.startswith("b"):
        return lambda k: 0.1 * jax.random.normal(k, shape)
    if name.startswith("w") and len(shape) >= 2:
        return lambda k: jax.random.normal(k, shape) / np.sqrt(shape[-2])
    raise ValueError(f"no weight rule for leaf {'/'.join(path)} {shape}")


def make(shapes, seed: int, d: int):
    """Weights with the structure of ``shapes`` (a tree of
    ShapeDtypeStruct), filled from ``seed``."""
    flat, tree = jax.tree_util.tree_flatten_with_path(shapes)
    paths = [tuple(getattr(k, "key", str(k)) for k in p) for p, _ in flat]
    rules = [_rule(p, s.shape, d) for p, (_, s) in zip(paths, flat)]
    dtypes = [s.dtype for _, s in flat]

    def build(key):
        keys = jax.random.split(key, len(rules))
        return jax.tree_util.tree_unflatten(
            tree, [r(k).astype(dt) for r, k, dt in zip(rules, keys, dtypes)])

    key = jax.random.PRNGKey(int(traffic.rng_for(seed, "weights")
                                 .integers(0, 2**31)))
    return jax.jit(build)(key)
