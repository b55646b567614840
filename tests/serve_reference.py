"""The plain reference the serving engine's outputs are tested against:
one request at a time through the model's contiguous decode
(``ModelAPI.prefill`` at the exact prompt length, then ``ModelAPI.decode``
at batch 1), greedy sampling."""

import jax
import jax.numpy as jnp
import numpy as np


def greedy_reference(api, params, prompt, max_new, max_seq, extra=None):
    """The ``max_new`` greedy tokens of one request: its prompt prefilled
    alone at its exact length into a ``max_seq``-row contiguous cache,
    then decoded one token a step at batch 1, each the argmax of the
    step's logits.  ``extra`` holds the request's unbatched extra inputs
    (encoder frames, patch embeddings), as ``submit`` takes them."""
    prefill = jax.jit(api.prefill, static_argnums=2)
    decode = jax.jit(api.decode)
    batch = {"tokens": jnp.asarray(np.asarray(prompt, np.int32)[None])}
    if extra:
        batch.update({k: jnp.asarray(v[None]) for k, v in extra.items()})
    logits, cache = prefill(params, batch, max_seq)
    out = [int(jnp.argmax(logits[0]))]
    while len(out) < max_new:
        logits, cache = decode(params, cache,
                               jnp.asarray([[out[-1]]], jnp.int32))
        out.append(int(jnp.argmax(logits[0])))
    return out
