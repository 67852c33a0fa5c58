"""``model_type: solar_open2`` on the serving path, at tiny widths that keep
the structure (one period of a gated attention layer and three delta-rule
layers, each followed by an expert block with a shared expert, which is the
cell's own walk; two periods once, which scan as the preset's twelve do; a
quarter of the experts held), float32, on the CPU: the program (``build_model`` ->
``InferenceEngineV2`` -> ``ServingSession``, chunked prefill through the
chunked delta rule, decode through the state pool and the KV pool) against
the plain reference ``benchmark/families/solar_open2.py`` on seeded weights
with every leaf moved off its init; a mixed round; a state slot reused;
the four expert shares adding up to the uncut layer; planted faults, each
refused; the refusals' messages. ``ops/kda.py``'s two entries against the
sequential recurrence are ``tests/unit/test_kda.py``'s, eviction, requeue and
idle ``tests/unit/test_kda_state_slots.py``'s."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import parity
from tests.family_harness import (Harness, ending, engines,  # noqa: F401
                                  family, moved)
from tests.unit import stream_ends

HF = {
    "model_type": "solar_open2", "hidden_size": 64, "num_hidden_layers": 4,
    "gqa_layers": [0], "num_attention_heads": 4, "num_key_value_heads": 2,
    "head_dim": 16, "vocab_size": 128, "intermediate_size": 160,
    "linear_attn_config": {"short_conv_kernel_size": 4, "head_dim": 16,
                           "num_heads": 4, "num_kv_heads": None},
    "moe_intermediate_size": 24, "rms_norm_eps": 1e-5,
    "first_k_dense_replace": 0, "use_rope": False, "use_gqa_gate": True,
    "kda_use_full_proj": False, "kda_allow_neg_eigval": True,
    "n_routed_experts": 4, "n_shared_experts": 1, "num_experts_per_tok": 2,
    "norm_topk_prob": True, "routed_scaling_factor": 1,
    "tie_word_embeddings": False,
    # the chip's share: 4 of the router's 16 experts
    "reduced": {"n_routed_experts": {"published": 16, "run": 4}}}
ENGINE = {"max_context": 128, "max_sequences": 4, "num_blocks": 64,
          "block_size": 8, "max_tokens_per_batch": 16,
          "prefill_attn": "xla", "decode_attn": "xla"}
# both sides are float32 and differ in the order of summation and in the
# FORM of the recurrence (the WY form of a piece against token by token):
# measured 8.8e-6 logit-std with every leaf moved by 0.2 (the moved norm
# scales and decays make the logits large); the planted misreadings measure
# 1.2 and more, a bf16 state 4.4e-3
TOL = 1e-4
PROMPTS = ([7, 3, 11, 100, 41, 9, 5], list(range(60, 101)))   # 7 and 41
ENDING = {"max_context": 32, "num_blocks": 12}
H = Harness(HF, ENGINE, PROMPTS)


def overrides(family, hf=HF):
    widths = family.program_widths(hf)
    return {**{k: v for k, v in widths.items() if k != "experts_held"},
            "num_experts_held": widths["experts_held"],
            "max_seq_len": 256, "dtype": "float32", "kda_chunk_size": 8,
            "routed_write_share": None}


@pytest.fixture(scope="module")
def built(family):
    from deepspeedsyclsupport_tpu.models import build_model

    model = build_model("solar-open2", **overrides(family))
    model.seed = 3
    return model, moved(jax.jit(model.init_params)())


# ------------------------------------------------------------ the structure
def test_the_pattern_is_two_characters_a_published_layer(family):
    from deepspeedsyclsupport_tpu.inference.v2.model import layer_plan
    from deepspeedsyclsupport_tpu.models import get_config

    whole = get_config("solar-open2")
    assert whole.layer_pattern == "*EKEKEKE" * 12 and whole.num_layers == 96
    assert (whole.num_kv_layers, whole.state_layers, whole.num_moe_layers,
            whole.state_chunk_size) == (12, 36, 48, 64)
    assert layer_plan(whole.layer_pattern) == [("*EKEKEKE", 12)]
    # the cut: one period, its three KE pairs under one scan
    assert layer_plan("*EKEKEKE") == [("*", 1), ("EK", 3), ("E", 1)]
    assert family.layer_pattern(HF) == "*EKEKEKE"
    assert family.program_widths(HF)["num_layers"] == 8
    # 250 B parameters whole, 15 B of them a token's
    assert whole.param_count() / 1e9 == pytest.approx(250.3, abs=0.1)


def test_four_stacks_and_the_published_inits_range(built):
    model, _ = built
    params = model.init_params()
    cfg = model.config
    assert (cfg.pattern_count("K"), cfg.pattern_count("E"),
            cfg.num_kv_layers, cfg.pattern_count("M")) == (3, 4, 1, 0)
    k, e, a = params["kda_layers"], params["layers"], params["attn_layers"]
    assert set(k) == {"norm", "qkv_proj", "conv_w", "f_a", "f_b", "A_log",
                      "dt_bias", "b_proj", "g_a", "g_b", "o_norm", "o_proj"}
    assert k["qkv_proj"].shape == (3, 64, 192)
    assert k["conv_w"].shape == (3, 4, 192) and k["A_log"].shape == (3, 4)
    assert k["f_a"].shape == (3, 64, 16) and k["f_b"].shape == (3, 16, 64)
    assert k["dt_bias"].shape == (3, 64) and k["b_proj"].shape == (3, 64, 4)
    assert k["o_norm"]["scale"].shape == (3, 16)
    assert set(e) == {"mlp_norm", "moe"}
    assert e["moe"]["w_gate"].shape == (4, 4, 64, 24)      # 4 of 16 held
    assert e["moe"]["router"].shape == (4, 64, 16)
    assert set(e["moe"]["shared"]) == {"w_gate", "w_up", "w_down"}
    assert e["moe"]["shared"]["w_up"].shape == (4, 64, 24)
    assert set(a) == {"attn_norm", "attn"}
    assert set(a["attn"]) == {"wq", "wk", "wv", "wo", "w_g"}
    assert a["attn"]["w_g"].shape == (1, 64, 64)
    decay = np.exp(np.asarray(k["A_log"]))
    dt = np.log1p(np.exp(np.asarray(k["dt_bias"])))
    assert (1 <= decay).all() and (decay <= 16).all()
    assert (dt >= 0.999e-3).all() and (dt <= 0.1001).all()
    # half-lives from under a token to hundreds: some channel of every
    # layer carries its state across many pieces
    life = np.log(2) / (decay[:, :, None] * dt.reshape(3, 4, 16))
    assert life.min() < 2 and life.max() > 100
    assert np.asarray(e["moe"]["router_bias"]).any()


@pytest.mark.parametrize("wrong, says", [
    (dict(layer_pattern="*EKEMEKE"), "no 'M' layer beside them"),
    (dict(kda_num_heads=0), "'K' layers need kda_num_heads"),
    (dict(layer_pattern="*EKEKEKX"), "'K' \\(gated delta rule\\)"),
    (dict(layer_pattern=None, num_layers=8), "belong to a layer_pattern"),
    (dict(qkv_bias=True), "attn_out_gate: the output gate is written for"),
])
def test_what_the_pattern_refuses_says_why(built, wrong, says):
    cfg = built[0].config
    with pytest.raises(ValueError, match=says):
        dataclasses.replace(cfg, **{"layer_pattern": "*EKEKEKE",
                                    "num_layers": 8, **wrong})


# ------------------------------------------------ program against reference
@pytest.mark.parametrize("step", ["xla", "pallas_interpret"])
def test_chunked_prefill_then_decode_match_the_reference(built, monkeypatch,
                                                         step):
    """41 tokens = three chunks of 16, 16 and 9 rows in pieces of 8 (the
    second chunk starts from the first's state and tail), then six decode
    steps through the state pool and the KV pool."""
    from deepspeedsyclsupport_tpu.inference.v2 import model as model_v2
    from deepspeedsyclsupport_tpu.inference.v2 import module_registry as reg

    # no setting names a state step or the pieces' form: the registry is
    # the seam, and the interpreted kernels are put first in it for the
    # length of this test
    for kind, chosen in (("kda_step", model_v2._kda_step_fn),
                         ("kda_chunk", model_v2._kda_chunk_fn)):
        first = dataclasses.replace(
            reg.get_impl(kind, step), name="first", priority=100,
            auto_eligible=lambda ctx: True)
        monkeypatch.setitem(reg._REGISTRY[kind], "first", first)
        assert chosen() is first.fn
    assert H.served_errors(*built) < TOL


def test_two_periods_scan_as_the_presets_twelve_do(family):
    """Two periods fold into ONE scanned unit of eight characters (the
    preset's plan), where the cell's one period walks ``*``, ``EK`` x 3
    under one scan, ``E``: the prefill's last logits against the reference
    through that plan too."""
    from deepspeedsyclsupport_tpu.inference.v2.model import layer_plan
    from deepspeedsyclsupport_tpu.models import build_model

    hf = {**HF, "num_hidden_layers": 8, "gqa_layers": [0, 4]}
    model = build_model("solar-open2", **overrides(family, hf))
    assert layer_plan(model.config.layer_pattern) == [("*EKEKEKE", 2)]
    model.seed = 5
    params = moved(model.init_params())
    eng = H.engine_of(model, params)
    logits, tokens = parity.served_logits(eng, 0, PROMPTS[1], 0)
    want = H.reference(params, PROMPTS[1] + tokens, hf)
    assert parity.row_errors(logits, want[-len(logits):]).max() < TOL


def test_both_forwards_through_the_tails_kernel_are_the_xla_forms(
        built, monkeypatch):
    H.check_the_tails_kernel_is_the_xla_form(built, monkeypatch)


def test_a_mixed_round_and_a_slot_reused(built, engines):
    H.check_a_mixed_round_and_a_slot_reused(built[1], engines(), TOL)


def test_the_four_shares_add_up_to_the_uncut_layer(built, family):
    """Each of four chips holds 4 of the 16 experts under the 16-wide
    router: the four routed parts + the shared expert ONCE = the uncut
    layer."""
    from deepspeedsyclsupport_tpu.parallel.moe import moe_mlp_nodrop

    model, _ = built
    whole_cfg = dataclasses.replace(model.config, num_experts_held=None)
    from deepspeedsyclsupport_tpu.models import build_model

    uncut = moved(build_model(whole_cfg).init_params(), key=2)
    layer = jax.tree_util.tree_map(lambda a: a[1], uncut["layers"]["moe"])
    assert layer["w_up"].shape == (16, 64, 24)
    x = jax.random.normal(jax.random.PRNGKey(5), (19, 64))
    whole, routed = moe_mlp_nodrop(layer, x, whole_cfg)
    assert int(routed.sum()) == 19 * 2
    no_shared = {k: v for k, v in layer.items() if k != "shared"}
    shared = whole - moe_mlp_nodrop(no_shared, x, whole_cfg)[0]
    parts = []
    for first in (0, 4, 8, 12):
        share = dataclasses.replace(whole_cfg, num_experts_held=4,
                                    first_expert_held=first)
        held = {**no_shared, **{k: layer[k][first:first + 4]
                                for k in family.EXPERT_LEAVES}}
        part, rows = moe_mlp_nodrop(held, x, share)
        assert (rows == routed).all()          # the router's whole width
        parts.append(part)
    np.testing.assert_allclose(sum(parts) + shared, whole, atol=2e-5)
    # and the reference's expert block is the same function: whole, and the
    # share of the chip that holds experts 4-7
    a = {**family.arch(HF), "experts_held": 16}
    rest = {k: v for k, v in layer.items() if k not in family.EXPERT_LEAVES}
    stacks = {k: layer[k] for k in family.EXPERT_LEAVES}
    want, _ = family.experts(a, rest, x, stacks, 0)
    np.testing.assert_allclose(whole, want, atol=2e-5)
    second, _ = family.experts(
        {**a, "experts_held": 4, "first_expert_held": 4}, rest, x,
        {k: layer[k][4:8] for k in family.EXPERT_LEAVES}, 0)
    np.testing.assert_allclose(parts[1] + shared, second, atol=2e-5)


# ------------------------------------------------------------ planted faults
def _decay_a_head(fn):
    """``ops/kda.py``'s entry with the log-decay averaged over a head's
    channels: a scalar decay a head, Mamba-2's kind."""
    def wrong(q, k, v, g, *rest):
        return fn(q, k, v, jnp.broadcast_to(g.mean(-1, keepdims=True),
                                            g.shape), *rest)
    return wrong


def _all_fresh(fn, at):
    """An entry that takes ``pieces`` at ``at`` with every piece told it is
    its sequence's first: nothing carried from piece to piece."""
    def wrong(*args):
        args = list(args)
        row0, length, slot, fresh, count = args[at]
        args[at] = (row0, length, slot, jnp.ones_like(fresh), count)
        return fn(*args)
    return wrong


def _plant(monkeypatch, fault):
    from deepspeedsyclsupport_tpu.inference.v2 import kv_cache
    from deepspeedsyclsupport_tpu.ops import kda, ssm

    if fault == "decay_a_head_not_a_channel":
        monkeypatch.setattr(kda, "chunked", _decay_a_head(kda.chunked))
        monkeypatch.setattr(kda, "decode_step", _decay_a_head(
            kda.decode_step))
    elif fault == "no_l2_norm_on_k":
        calls, norm = [], kda.l2norm
        # the mixer norms q, then k: every second call is a key's
        monkeypatch.setattr(kda, "l2norm", lambda x: (
            calls.append(0), norm(x) if len(calls) % 2 else x)[1])
    elif fault == "state_zeroed_between_pieces":
        monkeypatch.setattr(kda, "chunked", _all_fresh(kda.chunked, 7))
    elif fault == "conv_tail_not_carried":
        monkeypatch.setattr(ssm, "conv_pieces",
                            _all_fresh(ssm.conv_pieces, 5))
    elif fault == "state_in_bf16":
        monkeypatch.setattr(kv_cache, "KDA_STATE_DTYPE", jnp.bfloat16)


FAULTS = {
    "beta_without_the_x2": {"kda_beta_scale": 1.0},
    "rotary_on_the_gqa_layer": {"pos_embed": "rope"},
    "attention_gate_left_out": {"attn_out_gate": False},
    "decay_a_head_not_a_channel": {}, "no_l2_norm_on_k": {},
    "state_zeroed_between_pieces": {}, "conv_tail_not_carried": {},
    "state_in_bf16": {},
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_planted_fault_is_refused(built, monkeypatch, fault):
    """Each misreading of the publication, served, against the reference of
    the RIGHT program: beyond the tolerance by an order or more (a bf16
    state is a rounding of the state at every step, not a misreading, and is
    held to twice the tolerance). The 41-token prompt runs in three chunks
    and six pieces, so what is not carried between pieces shows in the
    logits of its last position: the prefill alone is compiled and run."""
    from deepspeedsyclsupport_tpu.models import build_model

    model, params = built
    if FAULTS[fault]:
        model = build_model(dataclasses.replace(model.config,
                                                **FAULTS[fault]))
    _plant(monkeypatch, fault)
    err = H.served_errors(model, params, PROMPTS[1:], 0)
    assert err > (2 if fault == "state_in_bf16" else 10) * TOL, err


# ------------------------------------------------------------------ scopes
def test_the_mixers_scopes_reach_the_compiled_programs(engines):
    """What the per-layer readers find by (``benchmark/scopes.py``): the
    ``kda_*`` scopes and the attention's gate in both forwards, the state
    step under ``kda_step`` INSIDE ``kda_scan`` in both, and the pieces
    under ``kda_chunk`` inside ``kda_scan`` in the ragged forward alone (a
    decode step has no piece)."""
    from benchmark import scopes

    eng = engines()
    eng.warmup()
    labels = ("kda_proj", "kda_conv", "kda_gate", "kda_step", "attn_gate",
              "kda_chunk")
    found = {name: set(scopes.instructions_under(c.as_text(), labels)
                       .values())
             for name, c in eng.compiled_programs().items()}
    assert found["decode_forward"] == set(labels[:5])
    assert found["ragged_forward"] == set(labels)
    text = eng.compiled_programs()["ragged_forward"].as_text()
    paths = [p for _n, p in scopes._INSTRUCTION.findall(text)]
    for inner in ("kda_chunk", "kda_step"):
        mine = [p for p in paths if inner in p.split("/")]
        assert mine and all(f"kda_scan/{inner}" in p.replace(
            "kda_scan/kda_scan", "kda_scan") for p in mine), inner
    assert not any("ssm_" in p or "ret_" in p for p in paths)


# ---------------------------------------------------------------- refusals
def test_what_a_model_with_a_delta_rule_state_refuses_says_why(
        built, engines, tmp_path):
    model, params = built
    eng = engines()
    with pytest.raises(NotImplementedError, match="delta-rule ones.*snapshot"
                       " of the recurrent state at every shared block "
                       "boundary"):
        eng.install_prefix_cache()
    with pytest.raises(NotImplementedError, match="serialize.*snapshot of "
                       "the recurrent state beside the parameters"):
        eng.serialize(str(tmp_path / "snap"))
    with pytest.raises(NotImplementedError,
                       match="gated delta-rule.*chunked scan's backward is "
                       "not written"):
        model.apply(params, jnp.zeros((1, 8), jnp.int32))


def test_the_state_pool_and_its_stats(engines):
    eng = engines()
    kv = eng.kv
    # [delta-rule layers, slots + the sink, heads, key channels, value ones]
    assert kv.kda_s.shape == (3, 5, 4, 16, 16)
    assert kv.kda_s.dtype == jnp.float32
    assert kv.kda_conv.shape == (3, 3, 5, 192) and kv.k.shape[0] == 1
    assert kv.moe.load.shape == (4, 16) and kv.moe.rows is not None
    per_slot = 3 * (4 * 16 * 16 * 4 + 3 * 192 * 4)
    assert eng.state_stats() == {
        "bytes_per_slot": per_slot, "slots": 4, "slots_live": 0,
        "dtype": "float32", "layers": 3, "pool_bytes": per_slot * 5}
    assert kv.state_names == ("kda_s", "kda_conv") and kv.state_slots == 4
    eng.warmup()
    assert eng.state_stats()["slots_live"] == 0
    assert sorted(eng._state_free) == [0, 1, 2, 3]
    assert eng.allocator.free_blocks == eng.allocator.num_blocks


# ------------------------------------------------- a stream that ends early
@stream_ends.parametrize
def test_a_stream_that_ends_early_gives_back_what_it_held(ending, driver,
                                                          end):
    """The state slots too: ``stream_ends`` counts them back, and a new
    stream in a released slot starts from zeros."""
    stream_ends.check(ending, driver, end)
