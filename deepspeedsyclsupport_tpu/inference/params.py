"""Shared inference weight placement.

Both engines (v1 ``engine.py``, v2 ``engine_v2.py``) place weights the same way:
stage-0 (replicate-unless-ruled) shardings composed with the model's declarative
TP rules — the whole of the reference's auto-TP weight surgery
(``module_inject/auto_tp.py``) — then cast floating leaves to the serving dtype.
"""
from typing import Any, Optional

import jax
import jax.numpy as jnp

from ..comm.topology import MeshTopology
from ..runtime import zero as zero_lib


def place_inference_params(params: Any, topology: MeshTopology, rules, dtype):
    """Returns (placed_params, shardings)."""
    shardings = zero_lib.tree_param_shardings(
        params, topology, stage=0, extra_rules=rules)

    def place(x, s):
        x = jnp.asarray(x)
        if jnp.issubdtype(x.dtype, jnp.floating):
            x = x.astype(dtype)
        return jax.device_put(x, s)

    return jax.tree_util.tree_map(place, params, shardings), shardings


def init_inference_params(model, topology: MeshTopology, dtype, rng=None):
    """Seeded random weights for serving, generated under ``jit`` in the
    serving dtype and straight into their shards. ``model.init_params()``
    builds the fp32 tree on the default device, and casting it leaf by leaf
    keeps both copies alive (phi-2: 11.1 GB fp32 + 5.6 GB bf16 on a 16 GB
    chip); here the fp32 values only ever exist as fusion temporaries. The
    result is already what :func:`place_inference_params` would return, so
    an engine built on it places nothing."""
    init = model.init_params if rng is None else \
        (lambda: model.init_params(rng))
    shardings = zero_lib.tree_param_shardings(
        jax.eval_shape(init), topology, stage=0,
        extra_rules=getattr(model, "sharding_rules", None))

    def cast():
        return jax.tree_util.tree_map(
            lambda x: x.astype(dtype)
            if jnp.issubdtype(x.dtype, jnp.floating) else x, init())

    return jax.jit(cast, out_shardings=shardings)()
