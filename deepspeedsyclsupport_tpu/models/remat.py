"""What a checkpointed layer keeps for its backward pass.

``jax.checkpoint`` around a layer saves the layer's inputs and recomputes
the rest in the backward pass. The tensors worth keeping instead carry a
name (``jax.ad_checkpoint.checkpoint_name``, inert without a policy that
names it): the q / k / v projections' outputs and the attention sublayer's
output (``models/layers.py``), the flash kernel's ``o`` and ``lse`` (the
residuals of its ``custom_vjp``: a Pallas call is not a dot, so no listed
``jax.checkpoint_policies`` name keeps them), the MLP's gate and up products
(``layers.glu_mlp`` / ``std_mlp``, and each expert's in ``parallel/moe.py``).
What is left to recompute from them is elementwise: norms, rotary, the
activation.

The rungs, richest first (:data:`RUNGS`): ``attn+mlp`` keeps all of them,
``attn`` the attention sublayer's alone, ``nothing_saveable`` none.
:func:`choose_rung` takes the richest whose bytes, over all layers and on
one device, fit in what the device has left: arithmetic on shapes, not a
ladder of trial compiles.
"""
from typing import Dict, Optional

import jax
import numpy as np

from ..ops.flash_attention import FLASH_RESIDUAL_NAMES

ATTN_NAMES = ("attn_q", "attn_k", "attn_v", "attn_out") + FLASH_RESIDUAL_NAMES
MLP_NAMES = ("mlp_gate", "mlp_up")
RUNGS = ("attn+mlp", "attn", "nothing_saveable")
_RUNG_NAMES = {"attn+mlp": ATTN_NAMES + MLP_NAMES, "attn": ATTN_NAMES}

# The share of the device's memory the arithmetic leaves alone, for what it
# does not count (:func:`working_bytes` says what it does): the engine hands
# the model ``(1 - MARGIN) x bytes_limit`` less its resident state.
MARGIN = 0.05


def rung_policy(rung: str):
    """The ``jax.checkpoint`` policy of ``rung`` (None: nothing saved)."""
    names = _RUNG_NAMES.get(rung)
    return (jax.checkpoint_policies.save_only_these_names(*names)
            if names else None)


def rung_bytes(cfg, tokens: float, model_shards: int = 1,
               expert_shards: int = 1) -> Dict[str, int]:
    """Bytes ONE layer saves under each rung on one device, from the
    widths: ``tokens`` are the device's own (batch x sequence over the mesh
    axes that split them), projections and heads split ``model_shards``
    ways. The flash residuals are counted whatever ``attn_impl`` is (the
    XLA path saves none, so there the count is a ceiling)."""
    item = np.dtype(cfg.dtype).itemsize
    attn = tokens * ((2 * cfg.q_dim + 2 * cfg.kv_dim) * item
                     + cfg.num_heads * 4) / model_shards \
        + tokens * cfg.hidden_size * item
    products = 2 if cfg.mlp_type == "glu" else 1
    if cfg.any_moe:
        # every expert's capacity buffer, full or not (parallel/moe.py)
        k, e = cfg.num_experts_per_tok, cfg.num_experts
        rows = e * max(int(np.ceil(tokens * cfg.capacity_factor * k / e)), k)
        mlp = rows * (cfg.moe_intermediate_size or cfg.intermediate_size) \
            * products * item \
            / (model_shards * expert_shards)
    else:
        mlp = tokens * cfg.intermediate_size * products * item / model_shards
    return {"attn+mlp": int(attn + mlp), "attn": int(attn),
            "nothing_saveable": 0}


def working_bytes(cfg, tokens: float, one_layer: int,
                  model_shards: int = 1) -> int:
    """What the step holds on one device beside its state and the saved
    tensors, whatever the rung: every layer's input (the scan's carry) and
    the larger of what its two backward phases hold, which do not overlap:
    the float32 logits with their cotangent, or ``one_layer``'s named
    tensors (the richest rung's bytes) with theirs. Not counted: the
    compute-dtype copy of the weights and the compiler's own temporaries
    (:data:`MARGIN`; PERF.md section 7)."""
    carries = cfg.num_layers * tokens * cfg.hidden_size \
        * np.dtype(cfg.dtype).itemsize
    logits = 2 * tokens * cfg.vocab_size * 4 / model_shards
    return int(carries + max(2 * one_layer, logits))


def choose_rung(cfg, tokens: float, free: Optional[int],
                model_shards: int = 1, expert_shards: int = 1) -> str:
    """The richest rung of :data:`RUNGS` whose bytes over all layers fit,
    with :func:`working_bytes`, in ``free``: what one device has left beside
    the engine's resident state, margin taken off already. None (a device
    that reports no limit: the CPU) takes ``nothing_saveable``."""
    if free is None:
        return RUNGS[-1]
    per_layer = rung_bytes(cfg, tokens, model_shards, expert_shards)
    reserve = working_bytes(cfg, tokens, per_layer[RUNGS[0]], model_shards)
    return next(r for r in RUNGS
                if per_layer[r] * cfg.num_layers + reserve <= free
                or r == RUNGS[-1])
