"""Tiny serving models of each layer kind ``inference/v2/model.py`` walks,
for the tests that hold something of EVERY kind's two forwards
(``test_serve_regions.py``): name -> (preset, overrides, engine arguments)."""
import re

import jax
import jax.numpy as jnp

ENGINE = {"max_context": 64, "max_sequences": 4, "num_blocks": 32,
          "block_size": 8, "max_tokens_per_batch": 16,
          "prefill_attn": "xla", "decode_attn": "xla"}
SMALL = {"hidden_size": 32, "intermediate_size": 48, "num_heads": 4,
         "num_kv_heads": 2, "head_dim": 8, "vocab_size": 128,
         "max_seq_len": 128, "dtype": "float32"}
EXPERTS = {"num_experts": 8, "num_experts_per_tok": 3,
           "moe_intermediate_size": 16}

KINDS = {
    # the sequential block, dense
    "dense": ("tiny", {"dtype": "float32"}, {}),
    # the parallel block (one norm, both branches, one sum)
    "parallel": ("phi-2", {**SMALL, "num_kv_heads": 4, "num_layers": 2}, {}),
    # sparse experts, a share of them held
    "sparse": ("tiny-moe", {"num_experts": 8, "num_experts_per_tok": 2,
                            "num_experts_held": 4, "first_expert_held": 2,
                            "dtype": "float32"}, {}),
    # latent attention under hyper-connection streams, a leading dense layer
    "latent": ("xing4-29b-a4b", {
        **SMALL, **EXPERTS, "num_kv_heads": 4, "head_dim": 24,
        "num_layers": 3, "first_k_dense_replace": 1, "q_lora_rank": 24,
        "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
        "v_head_dim": 16}, {}),
    # Mamba-2, expert and attention layers, one mixer a layer
    "mamba": ("nemotron-3-nano", {
        **SMALL, **EXPERTS, "num_layers": 5, "layer_pattern": "MEM*E",
        "mamba_num_heads": 4, "mamba_head_dim": 8, "ssm_state_size": 16,
        "ssm_n_groups": 2, "ssm_chunk_size": 8,
        "shared_expert_intermediate_size": 48, "n_shared_experts": 1,
        "routed_write_share": None}, {}),
    # attention heads and a Mamba-2 mixer side by side, dense feed-forwards
    "side_by_side": ("falcon-h1-34b", {
        **SMALL, "num_heads": 10, "num_layers": 4, "layer_pattern": "HFHF",
        "mamba_num_heads": 4, "mamba_head_dim": 8, "ssm_state_size": 16,
        "ssm_n_groups": 2, "ssm_chunk_size": 8}, {}),
    # power retention in the place of attention
    "retention": ("brumby-14b", {
        **SMALL, "head_dim": 32, "num_layers": 2, "retention_chunk_size": 8,
        "retention_half_life": (4.0, 64.0)}, {}),
    # the gated delta rule beside gated attention and experts
    "delta_rule": ("solar-open2", {
        **SMALL, **EXPERTS, "num_layers": 4, "layer_pattern": "*EKE",
        "kda_num_heads": 2, "kda_head_dim": 8, "kda_gate_rank": 8,
        "kda_chunk_size": 4, "num_experts_held": 4,
        "routed_write_share": None}, {}),
    # a looped stack: the layers four times over, the exit gate
    "looped": ("ouro-2.6b", {**SMALL, "num_kv_heads": 4, "num_layers": 2,
                             "total_ut_steps": 3}, {}),
    # a learned indexer's selection of cached tokens, over experts
    "selection": ("keye-vl2-30b-a3b", {
        **SMALL, **EXPERTS, "num_layers": 2, "num_experts_held": 4,
        "index_topk": 8, "index_heads": 2, "index_head_dim": 8}, {}),
    # lightning attention, dense feed-forwards, a selection of blocks
    "lightning": ("minicpm-sala", {
        **SMALL, "num_layers": 4, "layer_pattern": "*FLF",
        "lightning_heads": 2, "lightning_head_dim": 8,
        "lightning_chunk_size": 8, "sparse_block_topk": 6,
        "sparse_block_size": 8, "sparse_block_kernel": 4,
        "sparse_block_stride": 2, "sparse_block_init": 1,
        "sparse_block_window": 2, "sparse_block_dense_len": 72}, {}),
}


def engine(kind, **more):
    """An engine of ``kind``'s tiny model that has run each forward ONCE
    (a prompt's chunk, then a decode step): both are in its
    ``_dispatched``."""
    from deepspeedsyclsupport_tpu.inference.v2.engine_v2 import (
        InferenceEngineV2)
    from deepspeedsyclsupport_tpu.models import build_model

    preset, overrides, args = KINDS[kind]
    model = build_model(preset, **overrides)
    params = jax.jit(model.init_params)(jax.random.PRNGKey(0))
    eng = InferenceEngineV2(model, params, dtype=jnp.float32,
                            **{**ENGINE, **args, **more})
    eng.put([1], [[5, 7, 11]])
    eng.put([1], [[2]])
    return eng


_COMPUTATION = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+)\s+\(.*->.*\{\s*$", re.M)
_INSTRUCTION = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = .*?\s([a-z][\w\-]*)\(", re.M)
# the computations an instruction LAUNCHES, by its opcode (a fusion's, a
# reduction's and a sort's run inside the one instruction)
_LAUNCHES = {"while": ("body", "condition"), "call": ("to_apply",),
             "conditional": ("true_computation", "false_computation",
                             "branch_computations"),
             "async-start": ("calls",)}


def launched(hlo_text):
    """The names of the instructions of a compiled text that run as
    operations of their own (what a device trace times): those of the entry
    computation and of every loop body, condition, call and branch reached
    from it."""
    bodies, entry = {}, None
    heads = list(_COMPUTATION.finditer(hlo_text))
    for head, nxt in zip(heads, heads[1:] + [None]):
        bodies[head.group(2)] = hlo_text[head.end():nxt.start() if nxt
                                         else len(hlo_text)]
        if head.group(1):
            entry = head.group(2)
    out, todo = set(), [entry]
    while todo:
        for line in bodies.pop(todo.pop(), "").splitlines():
            m = _INSTRUCTION.match(line)
            if not m:
                continue
            out.add(m.group(1))
            for key in _LAUNCHES.get(m.group(2), ()):
                found = re.search(key + r"=\{?([^,}]*(?:, %[^,}]*)*)\}?", line)
                if found:
                    todo += [n.strip().lstrip("%")
                             for n in found.group(1).split(",")]
    return out


# the sub-scopes (``mfu.SUB_SCOPES``) each kind's two forwards must carry
SCOPES = {
    "dense": {"lm_head"},
    "parallel": {"lm_head"},
    "sparse": {"moe_route", "moe_experts", "moe_combine"},
    "latent": {"mla_proj", "mla_absorb", "mhc", "moe_route", "moe_experts",
               "moe_combine", "moe_shared"},
    "mamba": {"ssm_proj", "ssm_conv", "ssm_scan", "ssm_gate", "moe_experts",
              "moe_shared"},
    "side_by_side": {"h1_attn", "ssm_proj", "ssm_conv", "ssm_scan",
                     "ssm_gate"},
    "retention": {"ret_proj", "ret_gate", "ret_scan"},
    "delta_rule": {"kda_proj", "kda_conv", "kda_gate", "kda_scan",
                   "attn_gate", "moe_experts"},
    "looped": {"lm_head"},
    "selection": {"moe_route", "moe_experts", "moe_combine"},
    "lightning": {"la_proj", "la_gate", "la_scan", "bsa_pool", "bsa_score",
                  "bsa_select", "bsa_attend"},
}
