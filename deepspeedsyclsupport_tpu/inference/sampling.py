"""Token sampling — greedy, temperature, top-k, top-p (nucleus).

The reference scatters sampling across HF ``generate`` (it never owns the sampler;
``inference/engine.py`` wraps the HF module). The TPU engine owns its jitted decode
loop, so the sampler lives here as pure jnp — one function usable under ``lax.scan``.
"""
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp


class SamplingParams(NamedTuple):
    do_sample: bool = False
    temperature: float = 1.0  # may be a traced scalar under jit
    top_k: int = 0          # 0 = disabled (structural: lax.top_k needs it static)
    top_p: float = 1.0      # 1.0 = disabled; may be a traced scalar under jit

    @property
    def structure(self) -> tuple:
        """The hashable compile-relevant part: ``do_sample``/``top_k`` pick
        branches and shapes; temperature and top_p are data (traceable), so
        one compiled program serves every temperature/top_p — only whether
        top_p filtering runs at all is structural."""
        if not self.do_sample:  # greedy never reads top_k/top_p: one
            return False, 0, False  # structure regardless of incidental knobs
        try:  # any concrete numeric >= 1.0 (int, np scalar, float) disables
            use_top_p = float(self.top_p) < 1.0
        except TypeError:  # traced scalar: filtering must be in the program
            use_top_p = True
        return True, int(self.top_k), use_top_p


@jax.jit
def split_key(key: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """``jax.random.split(key)`` as the two keys, in ONE launch (unpacking
    the ``[2, ...]`` array on the host is a second one): a serving round's
    whole bookkeeping beside its forward and its sampler."""
    first, second = jax.random.split(key)
    return first, second


def sample_token_dyn(logits: jnp.ndarray, rng: Optional[jax.Array],
                     temperature, top_p, structure) -> jnp.ndarray:
    """:func:`sample_token` with the static/traced split pre-applied:
    ``structure`` is :attr:`SamplingParams.structure` (hashable, jit-static);
    temperature/top_p are runtime operands — sweeping them reuses one
    compiled program."""
    do_sample, top_k, use_top_p = structure
    return sample_token(logits, rng, SamplingParams(
        do_sample, temperature, top_k, top_p if use_top_p else 1.0))


def sample_token(logits: jnp.ndarray, rng: Optional[jax.Array],
                 params: SamplingParams) -> jnp.ndarray:
    """logits [B, V] → token ids [B] (int32).

    ``do_sample`` and ``top_k`` must be concrete (they select program
    structure); ``temperature`` and ``top_p`` may be traced scalars.
    """
    if not params.do_sample:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    logits = logits / jnp.maximum(params.temperature, 1e-6)
    if params.top_k and params.top_k > 0:
        kth = jax.lax.top_k(logits, params.top_k)[0][..., -1:]
        logits = jnp.where(logits < kth, -jnp.inf, logits)
    if params.structure[2]:
        sorted_logits = jnp.sort(logits, axis=-1)[..., ::-1]
        probs = jax.nn.softmax(sorted_logits, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        # keep the smallest prefix with cumulative mass >= top_p (always >= 1 tok)
        keep = cum - probs < params.top_p
        cutoff = jnp.min(jnp.where(keep, sorted_logits, jnp.inf), axis=-1,
                         keepdims=True)
        logits = jnp.where(logits < cutoff, -jnp.inf, logits)
    return jax.random.categorical(rng, logits, axis=-1).astype(jnp.int32)
