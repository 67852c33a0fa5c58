"""The one greedy reference the serving tests hold an engine to.

``greedy(model, params, prompt, n)`` is the model's own continuation: the
argmax of the dense forward's row before each new token. It runs ONE jitted
forward a (model, padded length): the sequence stands in a zero-padded row of
``len(prompt) + n`` rounded up to ``PAD_TO``, and step ``i`` reads row
``len(prompt) + i - 1``. The model is causal, so a row does not see the pads
after it. The growing-length loop this replaces called the unjitted
``model.apply`` on a new shape every token (traced and compiled op by op: 14 s
for 20 tokens of ``tiny`` against 0.3 s); it is kept as ``exact_lengths=True``
for a model where a pad could move a live row, and as the reference that
``tests/unit/test_inference.py`` holds the padded one to.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np

PAD_TO = 16


@functools.lru_cache(maxsize=8)
def _forward(model):
    return jax.jit(model.apply)


def greedy(model, params, prompt, n, exact_lengths=False):
    prompt = [int(t) for t in prompt]
    out = []
    if exact_lengths:
        seq = list(prompt)
        for _ in range(n):
            logits = model.apply(params, jnp.asarray([seq], jnp.int32))
            out.append(int(jnp.argmax(logits[0, -1])))
            seq.append(out[-1])
        return out
    total = len(prompt) + n
    limit = getattr(model.config, "max_seq_len", None) or total
    padded = max(total, min(-(-total // PAD_TO) * PAD_TO, limit))
    seq = np.zeros((1, padded), np.int32)
    seq[0, :len(prompt)] = prompt
    forward = _forward(model)
    for i in range(n):
        at = len(prompt) + i
        logits = forward(params, jnp.asarray(seq))
        seq[0, at] = int(jnp.argmax(logits[0, at - 1]))
        out.append(int(seq[0, at]))
    return out
