"""``model_type: cohere2_moe`` on the serving path, at tiny widths that keep
the structure (TWO periods of three windowed layers and a full one, window
16, blocks of 8, four query heads a KV head, sigmoid top-2 of 8 experts
beside four averaged shared experts), float32, on the CPU: the program
(``build_model`` -> ``InferenceEngineV2`` -> ``ServingSession``, chunked
prefill and decode through BOTH pools) against the plain reference
``benchmark/families/cohere2_moe.py`` on seeded weights with every leaf
moved off its init; the two rotary conventions; eviction under ``requeue``;
planted faults, each refused by the same comparison; the scopes in both
compiled programs; the refusals' messages."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import parity
from tests.family_harness import (Harness, engines, family,  # noqa: F401
                                  moved)
from tests.unit import stream_ends

WINDOW, BLOCK, CHUNK = 16, 8, 16
HF = {"model_type": "cohere2_moe", "hidden_size": 64, "intermediate_size": 32,
      "num_hidden_layers": 8,
      "layer_types": ["sliding_attention"] * 3 + ["full_attention"]
      + ["sliding_attention"] * 3 + ["full_attention"],
      "num_attention_heads": 8, "num_key_value_heads": 2, "head_dim": 16,
      "vocab_size": 128, "rope_theta": 50000, "rotary_pct": 1,
      "sliding_window": WINDOW, "layer_norm_eps": 1e-5, "logit_scale": 1,
      "attention_bias": False, "use_qk_norm": False,
      "use_parallel_block": True, "tie_word_embeddings": True,
      "first_k_dense_replace": 0, "num_experts": 8, "num_experts_per_tok": 2,
      "num_shared_experts": 4, "norm_topk_prob": True,
      "expert_selection_fn": "sigmoid",
      "shared_expert_combination_strategy": "average"}
# both sides are float32 and differ in the FORM (a paged pool in two kinds,
# split-half rotary, one wide shared GLU, grouped GEMMs against masks over a
# whole sequence, interleaved rotary, four shared experts, one expert at a
# time): measured 2e-6 logit-std; the planted faults measure 0.003 and more
TOL = 5e-5
# under the window; past window + chunk (blocks are freed before the
# decode); past 4 x the window
PROMPTS = ([7, 3, 11, 100, 41, 9, 5], list(range(20, 61)),
           [(7 * i + 3) % 128 for i in range(90)])
IMPLS = {"xla": dict(prefill_attn="xla", decode_attn="xla"),
         "kernel": dict(prefill_attn="kernel_interpret",
                        decode_attn="pallas_interpret", atom_q_size=8)}
ENGINE = {"max_context": 256, "max_sequences": 4, "block_size": BLOCK,
          "max_tokens_per_batch": CHUNK, "num_blocks": 96, **IMPLS["xla"]}
# (a bystander holds the first blocks of both pools in every comparison)
H = Harness(HF, ENGINE, PROMPTS, bystander=True)


def overrides(family, **more):
    return {**family.program_widths(HF), "max_seq_len": 512,
            "dtype": "float32",
            # the routed experts at full weight
            "routed_write_share": None, **more}


def build(family, **more):
    from deepspeedsyclsupport_tpu.models import build_model

    widths = overrides(family, **more)
    widths.pop("experts_held")
    return build_model("command-a-plus", **widths)


@pytest.fixture(scope="module")
def built(family):
    """(Every matrix moved by its own spread: the norms' scales start at
    one.)"""
    model = build(family)
    model.seed = 3
    return model, moved(jax.jit(model.init_params)(), relative=True)


# ------------------------------------------------------------ the structure
def test_a_period_of_two_kinds_two_pools_and_no_bias(built, engines):
    from deepspeedsyclsupport_tpu.inference.v2.kv_cache import (
        kv_pool_stats, window_blocks_a_sequence)

    model, params = built
    cfg = model.config
    assert cfg.attn_period == ((WINDOW, "rope"),) * 3 + ((None, "none"),)
    assert (cfg.num_kv_layers, cfg.window_layers, cfg.period_window) \
        == (2, 6, WINDOW)
    layers = params["layers"]
    assert set(layers) == {"attn", "attn_norm", "moe"}     # ONE norm a layer
    assert set(layers["attn_norm"]) == {"scale"} \
        and set(params["final_norm"]) == {"scale"}         # and no bias
    assert set(layers["attn"]) == {"wq", "wk", "wv", "wo"}
    # four shared experts of 32 as one GLU of 128; the head is the embedding
    assert layers["moe"]["shared"]["w_gate"].shape == (8, 64, 128)
    assert "lm_head" not in params
    eng = engines()
    bound = window_blocks_a_sequence(WINDOW, eng.config)
    assert bound == (WINDOW + CHUNK + 2 * BLOCK - 3) // BLOCK == 5
    assert eng.kv.k.shape == (2, 96 * BLOCK, 2, 16)
    assert eng.kv.wk.shape == (6, 4 * bound * BLOCK, 2, 16)
    assert [a.num_blocks for a in (eng.allocator.full, eng.allocator.window,
                                   eng.allocator)] == [96, 20, 116]
    stats = kv_pool_stats(eng.kv, eng.allocator)
    assert stats["pool_bytes"] == 2 * 4 * 2 * 16 * (2 * 96 + 6 * 20) * BLOCK
    assert (stats["full_blocks_held"], stats["window_blocks_held"]) == (0, 0)


def test_what_the_preset_at_the_published_sizes_says():
    from deepspeedsyclsupport_tpu.models import get_config

    cfg = get_config("command-a-plus")
    assert (cfg.num_layers, cfg.window_layers, cfg.num_kv_layers) \
        == (32, 24, 8)
    assert (cfg.q_dim, cfg.kv_dim, cfg.shared_expert_width,
            cfg.expert_width_stored) == (16384, 1024, 16384, 4096)
    # ISSUE 53's arithmetic: a layer of one of 8 chips, 1,149.7 M
    cut = get_config("command-a-plus", num_layers=4, num_experts_held=16,
                     vocab_size=32768)
    layer = 2 * 4096 * 16384 + 2 * 4096 * 1024 + 3 * 4096 * 16384 \
        + 4096 * 128 + 16 * 3 * 4096 * 4096
    assert layer // 10**5 == 11497
    # (param_count is approximate: it counts a second norm a layer)
    assert 0 <= cut.param_count() - (
        4 * (layer + 4096) + 32768 * 4096 + 4096) <= 4 * 4096


# ------------------------------------------------------------------ parity
@pytest.mark.parametrize("impl", sorted(IMPLS))
@pytest.mark.parametrize("probe", range(len(PROMPTS)),
                         ids=["under_window", "past_window_and_chunk",
                              "past_4_windows"])
def test_chunked_prefill_then_decode_match_the_reference(built, engines, impl,
                                                         probe):
    model, params = built
    assert H.served_errors(model, params, PROMPTS[probe:probe + 1],
                           eng=engines(**IMPLS[impl])) < TOL


def test_a_mixed_round_of_three_sequences(built, engines):
    """A decode row, a whole short prompt and the first chunk of a longer
    one in ONE forward, each against its own tables of both pools."""
    model, params = built
    eng = engines()
    a, b, c = PROMPTS[1], PROMPTS[0], PROMPTS[2][:20]
    tok = int(np.asarray(eng.put([1], [a])[1]).argmax())
    out = eng.put([1, 2, 3], [[tok], b, c], drain=False)
    assert set(out) == {1, 2}                 # 1 + 7 + 8 of c's 20 rows
    out3 = eng.put([], [])
    for uid, ids, got in ((1, a + [tok], out[1]), (2, b, out[2]),
                          (3, c, out3[3])):
        want = H.reference(params, ids)[-1:]
        assert parity.row_errors(np.asarray(got)[None], want).max() < TOL, uid
    eng.flush([1, 2, 3])


def test_split_half_rotary_on_permuted_columns_is_interleaved_rotary(family):
    """The program rotates pairs (i, i + D / 2), the published checkpoint
    pairs (2i, 2i + 1): the same function under the column permutation
    ``to_interleaved`` (what ingestion applies the inverse of)."""
    from deepspeedsyclsupport_tpu.models.layers import apply_rope

    x = jax.random.normal(jax.random.PRNGKey(0), (11, 3, 16))
    pos = jnp.arange(5, 16)
    a = {"rope_theta": 50000.0}
    ours = apply_rope(x[None], pos[None], 50000.0)[0]
    theirs = family.rope_interleaved(a, family.to_interleaved(x), pos)
    np.testing.assert_allclose(family.to_interleaved(ours), theirs,
                               atol=1e-6)
    # ... and it IS a permutation, and not the identity
    cols = np.asarray(family.to_interleaved(jnp.arange(16.0)))
    assert sorted(cols) == list(range(16)) and cols[1] == 8


# ----------------------------------------------------------- planted faults
FAULTS = ["window_layers_run_full", "rotary_on_a_full_layer",
          "shared_experts_summed", "layernorm_without_the_mean",
          "a_window_block_freed_one_block_early"]


@pytest.mark.parametrize("fault", FAULTS)
def test_a_planted_fault_is_refused(built, family, monkeypatch, fault):
    """Each misreading, served on the SAME weights, against the reference:
    beyond the tolerance by well over an order. The 41-token prompt runs in
    three chunks past the window and the first freed block, so every fault
    shows in the logits of its last position: the prefill alone is compiled
    and run."""
    from deepspeedsyclsupport_tpu.inference.v2 import model as M
    from deepspeedsyclsupport_tpu.inference.v2.ragged import (
        SequenceDescriptor)

    model, params = built
    kind_of = M.AttnKind.of.__func__

    def kinds(**wrong):
        def of(cls, cfg, j):
            kind = kind_of(cls, cfg, j)
            full = kind.window is None
            return kind._replace(**{k: v[full] for k, v in wrong.items()})
        monkeypatch.setattr(M.AttnKind, "of", classmethod(of))

    if fault == "window_layers_run_full":
        kinds(window=(None, None))
    elif fault == "rotary_on_a_full_layer":
        kinds(pos_embed=("rope", "rope"))
    elif fault == "shared_experts_summed":
        model = build(family, shared_expert_combine="sum")
    elif fault == "layernorm_without_the_mean":
        model = build(family, norm_type="rmsnorm")
    else:
        sound = SequenceDescriptor.out_of_window
        monkeypatch.setattr(
            SequenceDescriptor, "out_of_window",
            lambda d, window, bs: sound(d, window - bs, bs))
    err = H.served_errors(model, params, PROMPTS[1:2], 0)
    assert err > 30 * TOL, (fault, err)


# ------------------------------------------------------- session and pools
def test_eviction_under_requeue_gives_both_lists_back(built):
    """A full pool too small for three streams: the session evicts
    (``longest_context``), the stream is prefilled again, every stream ends
    with the tokens the reference's greedy choice gives, and at idle BOTH
    pools are empty."""
    from deepspeedsyclsupport_tpu.inference.v2.config import (
        ServingPolicyConfig)
    from deepspeedsyclsupport_tpu.inference.v2.serving import ServingSession

    model, params = built
    eng = H.engine_of(model, params, num_blocks=14, max_context=64)
    sess = ServingSession(eng, ServingPolicyConfig(
        admission="none", preempt_policy="requeue"))
    prompts = {1: list(range(1, 31)), 2: list(range(40, 70)),
               3: list(range(80, 110))}
    for uid, p in prompts.items():
        sess.submit(uid, p, 14)
    out, evicted = {}, 0
    for _ in range(600):
        if sess.idle:
            break
        for e in sess.step():
            if e.kind == "token":
                out.setdefault(e.uid, []).extend(e.tokens)
            evicted += e.kind == "evict"
    assert sess.idle and evicted >= 1
    alloc = eng.allocator
    assert alloc.full.free_blocks == 14 and alloc.window.free_blocks == 20
    assert alloc.free_blocks == alloc.num_blocks == 34
    for uid, p in prompts.items():
        assert len(out[uid]) == 14
        rows = H.reference(params, p + out[uid])[len(p) - 1:-1]
        picked = rows[np.arange(14), out[uid]]
        assert ((rows.max(-1) - picked) / rows.std(-1)).max() < TOL


def test_the_round_record_counts_both_pools(engines):
    from deepspeedsyclsupport_tpu.inference.v2.config import (
        ServingPolicyConfig)
    from deepspeedsyclsupport_tpu.inference.v2.serving import ServingSession

    eng = engines()
    sess = ServingSession(eng, ServingPolicyConfig(admission="none"))
    sess.submit(1, PROMPTS[2], 8)
    sess.submit(2, PROMPTS[0], 8)
    while not sess.idle:
        sess.step()
    rounds = [s["data"] for s in sess.drain_trace()
              if s["data"].get("stage") == "round" and s["data"]["program"]]
    assert len(rounds) > 8
    for d in rounds:
        assert d["kv_window_tokens"] <= d["kv_live_ctx_tokens"]
        assert d["kv_window_blocks_held"] <= 2 * 5
        assert d["kv_full_blocks_held"] * BLOCK >= d["kv_live_ctx_tokens"]
        assert d["swa_pairs"] <= d["attn_pairs"]
        assert d["swa_atom_keys"] <= d["full_atom_keys"]
    freed = sum(d["kv_window_blocks_freed"] for d in rounds)
    # the 90-token prompt gave back all but its last window's blocks
    assert freed >= (90 + 8 - WINDOW) // BLOCK - 1
    last = rounds[-1]
    assert last["kv_window_tokens"] < 0.5 * last["kv_live_ctx_tokens"]
    assert last["program"] == "decode_forward" and last["swa_pairs"] == 0
    sess.close()


def test_the_kinds_scopes_reach_the_compiled_programs(engines):
    from benchmark import scopes

    eng = engines()
    tok = int(np.asarray(eng.put([1], [PROMPTS[0]])[1]).argmax())
    eng.put([1], [[tok]])
    eng.flush([1])
    labels = ("attn_swa", "attn_full", "moe_shared", "moe_route")
    for name, compiled in eng.compiled_programs().items():
        under = scopes.instructions_under(compiled.as_text(), labels)
        assert set(under.values()) == set(labels), name


def test_what_is_refused_says_why(built, engines):
    from deepspeedsyclsupport_tpu.models import get_config
    from deepspeedsyclsupport_tpu.models.config import ModelConfig

    model, params = built
    with pytest.raises(NotImplementedError, match="two attention kinds"):
        engines().install_prefix_cache()
    with pytest.raises(NotImplementedError, match="period of attention"):
        model.apply(params, jnp.zeros((1, 4), jnp.int32))
    period = ((WINDOW, "rope"), (None, "none"))
    for bad, why in ((dict(sliding_window=8), "sliding_window"),
                     (dict(kv_lora_rank=8, qk_rope_head_dim=4),
                      "latent attention"),
                     (dict(hc_mult=2), "hyper-connection"),
                     (dict(num_layers=3), "a multiple")):
        with pytest.raises(ValueError, match=why):
            ModelConfig(**{"num_layers": 4, "attn_period": period, **bad})
    for period in (((WINDOW, "rope"),) * 2, ((None, "none"),) * 2,
                   ((8, "rope"), (16, "rope"), (None, "none")),
                   ((WINDOW, "alibi"), (None, "none"))):
        with pytest.raises(ValueError, match="attn_period"):
            ModelConfig(num_layers=len(period) * 2, attn_period=period)
    for name, more in (("keye-vl2-30b-a3b", {}), ("brumby-14b", {}),
                       ("ouro-2.6b", {})):
        with pytest.raises(ValueError, match="attention kinds|attn_period"):
            get_config(name, **more, attn_period=((WINDOW, "rope"),
                                                  (None, "none")))
    # a model of one kind is a period of one, whatever its window
    assert get_config("mistral-7b").attn_kinds == ((4096, "rope"),)
    assert get_config("mistral-7b").window_layers == 0
    assert dataclasses.replace(model.config).attn_period \
        == model.config.attn_period


# ------------------------------------------------- a stream that ends early
@pytest.fixture(scope="module")
def ending(family):
    """(An untied head: under the tied one this tiny model's greedy streams
    repeat their first token, and the check wants an EOS mid-stream.)"""
    model = build(family, tie_embeddings=False)
    model.seed = 3
    return stream_ends.family(H.engine_of(
        model, moved(model.init_params(), relative=True), max_context=64))


@stream_ends.parametrize
def test_a_stream_that_ends_early_gives_back_what_it_held(ending, driver,
                                                          end):
    stream_ends.check(ending, driver, end)
