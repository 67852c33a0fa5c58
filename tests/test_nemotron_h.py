"""``model_type: nemotron_h`` on the serving path, at tiny widths that keep
the structure (groups < heads, two ``M``, two ``E``, one ``*``), float32, on
the CPU: the program (``build_model`` -> ``InferenceEngineV2`` ->
``ServingSession``, chunked prefill through the chunked scan, decode through
the state pool and the KV pool) against the plain reference
``benchmark/families/nemotron_h.py`` on seeded weights with every leaf moved
off its init; a mixed round; a state slot reused; eviction under ``requeue``;
the four expert shares adding up to the uncut layer; planted faults, each
refused; the refusals' messages; the ``xla`` and Pallas-interpret state steps
agreeing."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tests.unit import stream_ends
from tests.family_harness import (Harness, ending, engines,  # noqa: F401
                                  family, moved)

HF = {
    "model_type": "nemotron_h", "hidden_size": 32, "num_hidden_layers": 5,
    "hybrid_override_pattern": "MEM*E", "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 8, "vocab_size": 128,
    "layer_norm_epsilon": 1e-5, "mamba_num_heads": 4, "mamba_head_dim": 8,
    "ssm_state_size": 16, "n_groups": 2, "conv_kernel": 4, "chunk_size": 8,
    "moe_intermediate_size": 24, "moe_shared_expert_intermediate_size": 48,
    "n_routed_experts": 8, "n_shared_experts": 1, "num_experts_per_tok": 3,
    "norm_topk_prob": True, "routed_scaling_factor": 2.5, "n_group": 1,
    "topk_group": 1, "mlp_hidden_act": "relu2", "time_step_min": 0.001,
    "time_step_max": 0.1, "time_step_floor": 1e-4,
    "tie_word_embeddings": False}
ENGINE = {"max_context": 128, "max_sequences": 4, "num_blocks": 64,
          "block_size": 8, "max_tokens_per_batch": 16,
          "prefill_attn": "xla", "decode_attn": "xla"}
ENDING = {"max_context": 32, "num_blocks": 12}
# both sides are float32 and differ in the order of summation and in the
# FORM of the recurrence (chunked against token by token): measured 2e-6
# logit-std; the planted faults measure 0.02 and more
TOL = 1e-4
PROMPTS = ([7, 3, 11, 100, 41, 9, 5], list(range(60, 101)))   # 7 and 41
H = Harness(HF, ENGINE, PROMPTS)


def overrides(family, hf=HF):
    widths = family.program_widths(hf)
    return {**{k: v for k, v in widths.items() if k != "experts_held"},
            "num_experts_held": widths["experts_held"],
            "intermediate_size": hf["moe_intermediate_size"],
            "max_seq_len": 256, "dtype": "float32",
            "routed_write_share": None}


@pytest.fixture(scope="module")
def built(family):
    from deepspeedsyclsupport_tpu.models import build_model

    model = build_model("nemotron-3-nano", **overrides(family))
    model.seed = 3
    return model, moved(jax.jit(model.init_params)())


# ------------------------------------------------------------ the structure
def test_the_layer_plan_covers_the_pattern_in_runs():
    from deepspeedsyclsupport_tpu.inference.v2.model import layer_plan
    from deepspeedsyclsupport_tpu.models import get_config

    whole = get_config("nemotron-3-nano").layer_pattern
    for pattern in (whole, whole[:26], "MEM*E", "M", "EEEE"):
        plan = layer_plan(pattern)
        assert "".join(unit * reps for unit, reps in plan) == pattern
    # 26 layers: three periods under one scan, an ME pair twice, an M
    assert layer_plan(whole[:26]) == [("MEMEM*E", 3), ("ME", 2), ("M", 1)]
    assert sum(len(u) for u, _ in layer_plan(whole)) <= 14


def test_three_stacks_and_the_published_inits_range(built):
    model, _ = built
    params = model.init_params()
    cfg = model.config
    assert (cfg.pattern_count("M"), cfg.pattern_count("E"),
            cfg.num_kv_layers) == (2, 2, 1)
    m, e = params["mamba_layers"], params["layers"]
    assert m["in_proj"].shape == (2, 32, 32 + 96 + 4)
    assert m["conv_w"].shape == (2, 4, 96) and m["A_log"].shape == (2, 4)
    assert set(e) == {"mlp_norm", "moe"} and "w_gate" not in e["moe"]
    assert e["moe"]["w_up"].shape == (2, 8, 32, 24)
    assert set(e["moe"]["shared"]) == {"fc1", "fc2"}
    assert set(params["attn_layers"]) == {"attn_norm", "attn"}
    a = np.exp(np.asarray(m["A_log"]))
    dt = np.log1p(np.exp(np.asarray(m["dt_bias"])))
    assert (1 <= a).all() and (a <= 16).all()
    assert (dt >= 0.999e-3).all() and (dt <= 0.1001).all()
    assert (np.asarray(m["D"]) == 1).all()
    assert np.asarray(e["moe"]["router_bias"]).any()


def test_the_expert_width_is_stored_on_the_lanes(family):
    """``ModelConfig.expert_width_stored``: a width past the 128 lanes is
    rounded up to them, zero columns and rows, the same function; the tiny
    width of the other tests stays as drawn."""
    from deepspeedsyclsupport_tpu.models import build_model

    hf = {**HF, "moe_intermediate_size": 136}
    wide = build_model("nemotron-3-nano", **overrides(family, hf))
    assert wide.config.expert_width_stored == 256
    params = wide.init_params()
    moe = params["layers"]["moe"]
    assert moe["w_up"].shape == (2, 8, 32, 256)
    assert moe["w_down"].shape == (2, 8, 256, 32)
    assert not np.asarray(moe["w_up"])[..., 136:].any()
    assert not np.asarray(moe["w_down"])[:, :, 136:].any()
    assert np.asarray(moe["w_up"])[..., 135].any()
    assert H.served_errors(wide, params, PROMPTS[:1], 2) < TOL


# ------------------------------------------------ program against reference
@pytest.mark.parametrize("step", ["xla", "pallas_interpret"])
def test_chunked_prefill_then_decode_match_the_reference(built, monkeypatch,
                                                         step):
    """41 tokens = three chunks of 16, 16 and 9 rows in pieces of 8 (the
    second chunk starts from the first's state), then six decode steps
    through the state pool and the KV pool."""
    from deepspeedsyclsupport_tpu.inference.v2 import model as model_v2
    from deepspeedsyclsupport_tpu.inference.v2 import module_registry as reg

    # no setting names a state step: the registry is the seam, and the
    # interpreted kernel is put first in it for the length of this test
    first = dataclasses.replace(
        reg.get_impl("ssm_step", step), name="first", priority=100,
        auto_eligible=lambda ctx: True)
    monkeypatch.setitem(reg._REGISTRY["ssm_step"], "first", first)
    assert model_v2._ssm_step_fn() is first.fn
    assert H.served_errors(*built) < TOL


def test_both_forwards_through_the_tails_kernel_are_the_xla_forms(
        built, monkeypatch):
    H.check_the_tails_kernel_is_the_xla_form(built, monkeypatch)


def test_a_mixed_round_and_a_slot_reused(built, engines):
    H.check_a_mixed_round_and_a_slot_reused(built[1], engines(), TOL)


def test_eviction_under_requeue_finishes_with_the_references_tokens(
        built):
    """A pool of 6 blocks under three streams that want 9: the session
    evicts, prefills again (a state slot from zero) and every stream ends
    with the tokens the reference's greedy choice gives."""
    from deepspeedsyclsupport_tpu.inference.v2.config import (
        ServingPolicyConfig)
    from deepspeedsyclsupport_tpu.inference.v2.serving import ServingSession

    model, params = built
    eng = H.engine_of(model, params, num_blocks=6, max_context=32)
    sess = ServingSession(eng, ServingPolicyConfig(preempt_policy="requeue"))
    prompts = {1: [1, 2, 3], 2: [4, 5, 6], 3: [7, 8, 9]}
    for uid, p in prompts.items():
        assert sess.submit(uid, p, 18) == "admitted"
    out, evicted = {}, 0
    for _ in range(400):
        if sess.idle:
            break
        for e in sess.step():
            if e.kind == "token":
                out.setdefault(e.uid, []).extend(e.tokens)
            evicted += e.kind == "evict"
    assert sess.idle and evicted > 0
    assert eng.state_stats()["slots_live"] == 0
    for uid, p in prompts.items():
        assert len(out[uid]) == 18
        rows = H.reference(params, p + out[uid])[len(p) - 1:-1]
        picked = rows[np.arange(18), out[uid]]
        assert ((rows.max(-1) - picked) / rows.std(-1)).max() < TOL


def test_the_four_shares_add_up_to_the_uncut_layer(built, family):
    """Each of four chips holds 2 of the 8 experts under the 8-wide router:
    the four routed parts + the shared expert ONCE = the uncut layer."""
    from deepspeedsyclsupport_tpu.parallel.moe import moe_mlp_nodrop

    model, params = built
    cfg = model.config
    layer = jax.tree_util.tree_map(lambda a: a[1], params["layers"]["moe"])
    x = jax.random.normal(jax.random.PRNGKey(5), (19, 32))
    whole, routed = moe_mlp_nodrop(layer, x, cfg)
    assert int(routed.sum()) == 19 * 3
    no_shared = {k: v for k, v in layer.items() if k != "shared"}
    shared = whole - moe_mlp_nodrop(no_shared, x, cfg)[0]
    parts = []
    for first in (0, 2, 4, 6):
        share = dataclasses.replace(cfg, num_experts_held=2,
                                    first_expert_held=first)
        held = {**no_shared, "w_up": layer["w_up"][first:first + 2],
                "w_down": layer["w_down"][first:first + 2]}
        part, rows = moe_mlp_nodrop(held, x, share)
        assert (rows == routed).all()          # the router's whole width
        parts.append(part)
    np.testing.assert_allclose(sum(parts) + shared, whole, atol=2e-5)
    # and the reference's expert layer is the same function
    a = family.arch(HF)
    want, _ = family.experts(
        a, {k: v for k, v in layer.items() if k not in family.EXPERT_LEAVES},
        x, {k: layer[k] for k in family.EXPERT_LEAVES}, 0)
    np.testing.assert_allclose(whole, want, atol=2e-5)


# ------------------------------------------------------------ planted faults
def _zeroed(params, stack, leaf):
    out = jax.tree_util.tree_map(lambda a: a, params)
    out[stack] = {**out[stack], leaf: jnp.zeros_like(out[stack][leaf])}
    return out


def _ungrouped_norm(y, z, scale, cfg):
    u = y * jax.nn.silu(z.astype(jnp.float32))
    u = u * jax.lax.rsqrt(jnp.mean(jnp.square(u), -1, keepdims=True)
                          + cfg.rms_norm_eps)
    return u * scale.astype(jnp.float32)


FAULTS = {
    "relu_for_relu2": dict(config={"activation": "relu"}),
    "no_renormalisation": dict(config={"norm_topk_prob": False}),
    "no_scaling_factor": dict(config={"routed_scaling_factor": 1.0}),
    "state_in_bf16": dict(state_dtype=jnp.bfloat16),
    "rotary_applied": dict(config={"pos_embed": "rope"}),
    "no_selection_bias": dict(zero=("layers", "router_bias")),
    "no_D": dict(zero=("mamba_layers", "D")),
    "no_dt_bias": dict(zero=("mamba_layers", "dt_bias")),
    "no_conv_bias": dict(zero=("mamba_layers", "conv_b")),
    "gate_norm_ungrouped": dict(patch=True),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_planted_fault_is_refused(built, monkeypatch, fault):
    """Each misreading of the publication, served, against the reference
    of the RIGHT weights: beyond the tolerance by two orders or more (the
    bf16 state reads 4.7e-4 against the right program's 2e-6: it is a
    rounding of the state at every step, not a misreading, and is held to
    twice the tolerance). The 41-token prompt runs in three chunks and six
    pieces, so every fault shows in the logits of its last position: the
    prefill alone is compiled and run."""
    from deepspeedsyclsupport_tpu.models import build_model

    model, params = built
    how, wrong = FAULTS[fault], params
    if "config" in how:
        model = build_model(dataclasses.replace(model.config,
                                                **how["config"]))
    if "zero" in how:
        stack, leaf = how["zero"]
        wrong = jax.tree_util.tree_map(lambda a: a, params)
        if stack == "layers":
            wrong["layers"] = {**params["layers"], "moe": {
                k: v for k, v in params["layers"]["moe"].items()
                if k != leaf}}
        else:
            wrong = _zeroed(params, stack, leaf)
    if "state_dtype" in how:
        from deepspeedsyclsupport_tpu.inference.v2 import kv_cache

        monkeypatch.setattr(kv_cache, "SSM_STATE_DTYPE", how["state_dtype"])
    if "patch" in how:
        from deepspeedsyclsupport_tpu.ops import ssm

        monkeypatch.setattr(ssm, "gated_norm", _ungrouped_norm)
    err = H.served_errors(model, wrong, PROMPTS[1:], 0,
                          want_params=params)
    assert err > (2 if fault == "state_in_bf16" else 100) * TOL, err


# ------------------------------------------------------------------ scopes
def test_the_mixers_scopes_reach_the_compiled_programs(engines):
    """What the per-layer readers find by (``benchmark/scopes.py``): the
    four ``ssm_*`` scopes in both forwards, and the chunked scan's pieces
    under ``ssm_chunk`` INSIDE ``ssm_scan`` in the ragged forward alone (a
    decode step has no piece), apart from the one-token rows' state step."""
    from benchmark import scopes

    eng = engines()
    eng.warmup()
    labels = ("ssm_proj", "ssm_conv", "ssm_scan", "ssm_gate", "ssm_chunk")
    found = {name: set(scopes.instructions_under(c.as_text(), labels)
                       .values())
             for name, c in eng.compiled_programs().items()}
    assert found["decode_forward"] == set(labels[:4])
    assert found["ragged_forward"] == set(labels)
    text = eng.compiled_programs()["ragged_forward"].as_text()
    paths = [p for _n, p in scopes._INSTRUCTION.findall(text)
             if "ssm_chunk" in p.split("/")]
    assert paths and all("ssm_scan/ssm_chunk" in p for p in paths)
    # the mixers' share sums the pieces too: innermost label, one count
    under_scan = scopes.instructions_under(text, labels[:4])
    assert set(scopes.instructions_under(text, ("ssm_chunk",))) \
        <= set(under_scan)


# ---------------------------------------------------------------- refusals
def test_what_a_model_with_recurrent_state_refuses_says_why(built, engines,
                                                            tmp_path):
    model, params = built
    eng = engines()
    with pytest.raises(NotImplementedError, match="Mamba-2 or power-"
                       "retention layers.*snapshot of the "
                       "recurrent state at every shared block boundary"):
        eng.install_prefix_cache()
    with pytest.raises(NotImplementedError, match="serialize.*snapshot of "
                       "the recurrent state beside the parameters"):
        eng.serialize(str(tmp_path / "snap"))
    with pytest.raises(NotImplementedError,
                       match="chunked scan's backward is not written"):
        model.apply(params, jnp.zeros((1, 8), jnp.int32))
    # nothing of this for a model without state
    assert H.engine_of(*_plain()).state_stats() is None


def _plain():
    from deepspeedsyclsupport_tpu.models import build_model

    model = build_model("tiny", dtype="float32")
    return model, model.init_params()


def test_the_state_pool_and_its_stats(engines):
    eng = engines()
    kv = eng.kv
    # [Mamba layers, slots + the sink, groups, state, heads-in-group x dim]
    assert kv.ssm.shape == (2, 5, 2, 16, 16) and kv.ssm.dtype == jnp.float32
    assert kv.conv.shape == (2, 3, 5, 96) and kv.k.shape[0] == 1
    per_slot = 2 * (4 * 8 * 16 * 4 + 3 * 96 * 4)
    assert eng.state_stats() == {
        "bytes_per_slot": per_slot, "slots": 4, "slots_live": 0,
        "dtype": "float32", "layers": 2, "pool_bytes": per_slot * 5}
    assert kv.state_names == ("ssm", "conv") and kv.state_slots == 4
    eng.warmup()
    assert eng.state_stats()["slots_live"] == 0
    assert sorted(eng._state_free) == [0, 1, 2, 3]


# ------------------------------------------------------ the two state steps
def test_the_xla_and_the_pallas_interpret_scans_agree(built):
    """``decode_step`` over six rows on five slots (two padding rows share
    the sink, one row fresh), and the chunked scan over three pieces of two
    sequences (one continuing from its slot): both state steps, the same
    numbers and the same pools."""
    from deepspeedsyclsupport_tpu.ops import ssm

    model, params = built
    cfg = model.config
    p = jax.tree_util.tree_map(lambda a: a[1], params["mamba_layers"])
    k = jax.random.split(jax.random.PRNGKey(2), 6)
    pool = jax.random.normal(k[0], (2, 6, 2, 16, 16))
    conv = jax.random.normal(k[1], (2, 3, 6, 96))
    xbc, dt = jax.random.normal(k[2], (6, 96)), jax.random.normal(k[3], (6, 4))
    slots = jnp.asarray([3, 0, 5, 5, 4, 1])
    fresh = jnp.asarray([False, True, False, False, False, False])
    def step(name):
        """``decode_step`` through one state step, as ONE program (eagerly
        every op of it is a program a shape)."""
        return jax.jit(lambda xbc, dt, pool, conv, slots, fresh:
                       ssm.decode_step(xbc, dt, p, pool, conv, 1, slots,
                                       fresh, cfg, ssm.STATE_STEPS[name]))

    got = {name: step(name)(xbc, dt, pool, conv, slots, fresh)
           for name in ("xla", "pallas_interpret")}
    for a, b in zip(got["xla"], got["pallas_interpret"]):
        live = np.asarray([0, 1, 2, 3, 4])     # the sink holds anything
        a, b = (np.asarray(t) for t in (a, b))
        if a.ndim > 2:       # the pools: [layers, slots, ...], [layers, 3, slots, .]
            a, b = (np.take(t, live, axis=1 if t.ndim == 5 else 2)
                    for t in (a, b))
        np.testing.assert_allclose(a, b, atol=1e-5)
    y, new, _ = got["xla"]
    assert not np.allclose(np.asarray(new)[1, 3], np.asarray(pool)[1, 3])
    np.testing.assert_array_equal(np.asarray(new)[0], np.asarray(pool)[0])
    # the chunked scan = the decode step token by token
    t = 20
    xbc, dt = jax.random.normal(k[4], (t, 96)), jax.random.normal(k[5], (t, 4))
    pieces = (jnp.asarray([0, 8, 11, 0, 0]), jnp.asarray([8, 3, 8, 0, 0]),
              jnp.asarray([2, 2, 0, 5, 5]),
              jnp.asarray([True, False, False, False, False]),
              jnp.asarray(3))
    y, ssm_c, conv_c = ssm.chunked_scan(xbc, dt, p, pool, conv, 1, pieces,
                                        cfg)
    ssm_s, conv_s, rows, one = pool, conv, [], step("xla")
    for i in range(19):
        slot, first = (2, i == 0) if i < 11 else (0, False)
        y_i, ssm_s, conv_s = one(xbc[i:i + 1], dt[i:i + 1], ssm_s, conv_s,
                                 jnp.asarray([slot]), jnp.asarray([first]))
        rows.append(y_i[0])
    np.testing.assert_allclose(y[:19], np.stack(rows), atol=2e-5)
    assert not np.asarray(y[19]).any()           # no piece lies there
    np.testing.assert_allclose(ssm_c[:, :5], ssm_s[:, :5], atol=2e-5)
    np.testing.assert_allclose(conv_c[:, :, :5], conv_s[:, :, :5], atol=1e-6)


# ------------------------------------------------- a stream that ends early
@stream_ends.parametrize
def test_a_stream_that_ends_early_gives_back_what_it_held(ending, driver,
                                                          end):
    """The state slots too: ``stream_ends`` counts them back, and a new
    stream in a released slot starts from zeros."""
    stream_ends.check(ending, driver, end)
