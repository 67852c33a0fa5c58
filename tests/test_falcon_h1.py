"""``model_type: falcon_h1`` on the serving path, at tiny widths that keep the
structure (five query heads a KV group, ``q_dim`` wider than the model, two
groups under four Mamba heads, three layers of ``HF``), float32, on the CPU:
the program (``build_model`` -> ``InferenceEngineV2`` -> ``ServingSession``:
chunked prefill through the paged attention AND the chunked scan of the same
layer, decode through the KV pool and the state pool at one index) against
the plain reference ``benchmark/families/falcon_h1.py`` on seeded weights with
every leaf moved off its init; a mixed round; a state slot reused; eviction
under ``requeue``; the depth cut; the fourteen multipliers, each left out and
each applied twice; the structure's misreadings, each refused; the rotary
tables at theta 1e11; the chunked scan at state 256."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import parity
from tests.family_harness import (Harness, engines, family,  # noqa: F401
                                  moved)

# multipliers of the test's own, every one off 1 and no two alike: at the
# published values a tiny model's attention would be flat (ISSUE 63)
HF = {
    "model_type": "falcon_h1", "hidden_size": 32, "intermediate_size": 48,
    "num_hidden_layers": 3, "num_attention_heads": 10,
    "num_key_value_heads": 2, "head_dim": 8, "vocab_size": 128,
    "rms_norm_eps": 1e-5, "rope_theta": 1e11, "rope_scaling": None,
    "hidden_act": "silu", "mamba_n_heads": 4, "mamba_d_head": 8,
    "mamba_d_ssm": 32, "mamba_d_state": 16, "mamba_n_groups": 2,
    "mamba_d_conv": 4, "mamba_chunk_size": 8, "mamba_use_mlp": True,
    "mamba_rms_norm": True, "mamba_norm_before_gate": False,
    "mamba_conv_bias": True, "mamba_proj_bias": False,
    "attention_bias": False, "mlp_bias": False, "projectors_bias": False,
    "attn_layer_indices": None, "tie_word_embeddings": False,
    "embedding_multiplier": 2.0, "lm_head_multiplier": 0.5,
    "attention_in_multiplier": 1.5, "attention_out_multiplier": 0.7,
    "key_multiplier": 0.6, "ssm_in_multiplier": 0.8,
    "ssm_out_multiplier": 1.3, "ssm_multipliers": [0.9, 1.2, 0.7, 1.4, 0.8],
    "mlp_multipliers": [1.25, 0.75]}
ENGINE = {"max_context": 128, "max_sequences": 4, "num_blocks": 64,
          "block_size": 8, "max_tokens_per_batch": 16,
          "prefill_attn": "xla", "decode_attn": "xla"}
# both sides are float32 and differ in the order of summation and in the
# FORM of the recurrence (chunked against token by token): measured 2e-6
# logit-std; the planted faults measure 0.01 and more
TOL = 1e-4
PROMPTS = ([7, 3, 11, 100, 41, 9, 5], list(range(60, 101)))   # 7 and 41
H = Harness(HF, ENGINE, PROMPTS)
SCALARS = ("embedding", "lm_head", "attention_in", "attention_out", "key",
           "ssm_in", "ssm_out")
MULTIPLIERS = [f"{n}_multiplier" for n in SCALARS] \
    + [f"ssm_multipliers.{i}" for i in range(5)] \
    + [f"mlp_multipliers.{i}" for i in range(2)]


def overrides(family, hf=HF):
    return {**family.program_widths(hf), "max_seq_len": 256,
            "dtype": "float32"}


@pytest.fixture(scope="module")
def built(family):
    from deepspeedsyclsupport_tpu.models import build_model

    model = build_model("falcon-h1-34b", **overrides(family))
    model.seed = 3
    return model, moved(jax.jit(model.init_params)())


@pytest.fixture(scope="module")
def served(built):
    """The right program's logits over the 41-token prompt's last position
    (three chunks, six pieces a layer) and six decode steps, with the
    sequence they are of: what every reading of the reference is held to."""
    logits, tokens = parity.served_logits(H.engine_of(*built), 0,
                                          PROMPTS[1], 6)
    return PROMPTS[1] + tokens, logits


# ------------------------------------------------------------ the structure
def test_one_stack_holds_both_halves_and_both_caches_one_index(built,
                                                               engines):
    from deepspeedsyclsupport_tpu.inference.v2.model import layer_plan
    from deepspeedsyclsupport_tpu.models import get_config

    model, params = built
    cfg = model.config
    assert layer_plan(get_config("falcon-h1-34b").layer_pattern) \
        == [("HF", 72)]                      # ONE body, scanned
    assert (cfg.pattern_count("H"), cfg.num_kv_layers, cfg.state_layers,
            cfg.mamba_layers) == (3, 3, 3, 3)
    h = params["hybrid_layers"]
    assert set(h) == {"norm", "attn", "mamba"} and "norm" not in h["mamba"]
    assert h["attn"]["wq"].shape == (3, 32, 80)      # q_dim, not d
    assert h["attn"]["wk"].shape == (3, 32, 16)
    assert h["mamba"]["in_proj"].shape == (3, 32, 32 + 96 + 4)
    assert set(params["ffn_layers"]) == {"mlp_norm", "mlp"}
    # a KV row and a state slot behind every layer
    eng = engines()
    kv = eng.kv
    assert kv.k.shape == (3, 64 * 8, 2, 8) and kv.v.shape == kv.k.shape
    assert kv.ssm.shape == (3, 5, 2, 16, 16) and kv.ssm.dtype == jnp.float32
    assert kv.conv.shape == (3, 3, 5, 96)
    per_slot = 3 * (4 * 8 * 16 * 4 + 3 * 96 * 4)
    assert eng.state_stats() == {
        "bytes_per_slot": per_slot, "slots": 4, "slots_live": 0,
        "dtype": "float32", "layers": 3, "pool_bytes": per_slot * 5}
    from deepspeedsyclsupport_tpu.inference.v2.kv_cache import kv_pool_stats
    assert kv_pool_stats(kv, eng.allocator)["pool_bytes"] \
        == 2 * 3 * 512 * 2 * 8 * 4


def test_the_draw_goes_through_the_multipliers(built):
    """``init_params`` draws a matrix that a multiplier follows at 0.02 /
    the multiplier (the mup vector's slices column by column), so that each
    product is what N(0, 0.02) gives without one."""
    model, _ = built
    h = jax.jit(model.init_params)()["hybrid_layers"]
    std = lambda w: float(jnp.std(w))                        # noqa: E731
    assert std(h["attn"]["wq"]) == pytest.approx(0.02 / 1.5, rel=0.05)
    assert std(h["attn"]["wk"]) == pytest.approx(0.02 / 1.5 / 0.6, rel=0.1)
    w = h["mamba"]["in_proj"]
    for lo, hi, m in ((0, 32, 0.9), (32, 64, 1.2), (64, 96, 0.7),
                      (96, 128, 1.4), (128, 132, 0.8)):
        assert std(w[..., lo:hi]) == pytest.approx(0.02 / 0.8 / m, rel=0.15)


def test_what_the_pattern_and_the_multipliers_refuse_says_why():
    from deepspeedsyclsupport_tpu.models import get_config

    tiny = dict(hidden_size=32, num_heads=4, head_dim=8, vocab_size=64,
                intermediate_size=48, mamba_num_heads=4, mamba_head_dim=8,
                ssm_state_size=16, ssm_n_groups=2)
    with pytest.raises(ValueError, match="an 'H' layer brings its own "
                       "attention and its own Mamba-2 mixer"):
        get_config("falcon-h1-34b", num_layers=3, layer_pattern="H*F", **tiny)
    with pytest.raises(ValueError, match="'H' .attention and Mamba-2 side "
                       "by side."):
        get_config("falcon-h1-34b", num_layers=2, layer_pattern="HX", **tiny)
    with pytest.raises(ValueError, match="multipliers are an 'H' layer's"):
        get_config("tiny", mup={"key": 0.5})
    assert get_config("tiny", mup={"mlp": [2.0, 0.5]}).mup.mlp == (2.0, 0.5)


# ------------------------------------------------ program against reference
@pytest.mark.parametrize("step", ["xla", "pallas_interpret"])
def test_chunked_prefill_then_decode_match_the_reference(built, monkeypatch,
                                                         step):
    """41 tokens = three chunks of 16, 16 and 9 rows, each through the paged
    attention AND the chunked scan's pieces of 8 of the same layer, then six
    decode steps through the KV pool and the state pool."""
    from deepspeedsyclsupport_tpu.inference.v2 import model as model_v2
    from deepspeedsyclsupport_tpu.inference.v2 import module_registry as reg

    first = dataclasses.replace(
        reg.get_impl("ssm_step", step), name="first", priority=100,
        auto_eligible=lambda ctx: True)
    monkeypatch.setitem(reg._REGISTRY["ssm_step"], "first", first)
    assert model_v2._ssm_step_fn() is first.fn
    assert H.served_errors(*built) < TOL


def test_the_attention_kernels_take_five_heads_a_group(built):
    """The interpreted Pallas kernels (the ragged atoms and the one-row
    tile) at 10 query heads over 2: the logits of the xla attention."""
    assert H.served_errors(
        *built, PROMPTS[1:], 2, prefill_attn="kernel_interpret",
        decode_attn="pallas_interpret", atom_q_size=8) < TOL


def test_a_mixed_round_and_a_slot_reused(built, engines):
    H.check_a_mixed_round_and_a_slot_reused(built[1], engines(), TOL)


def test_a_requeued_stream_finishes_with_the_references_tokens(built):
    """A pool of 6 blocks under three streams that want 9: the session
    evicts, prefills again (KV rows anew, a state slot from zero) and every
    stream ends with the tokens the reference's greedy choice gives."""
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
    assert eng.allocator.free_blocks == eng.allocator.num_blocks
    for uid, p in prompts.items():
        assert len(out[uid]) == 18
        rows = H.reference(params, p + out[uid])[len(p) - 1:-1]
        picked = rows[np.arange(18), out[uid]]
        assert ((rows.max(-1) - picked) / rows.std(-1)).max() < TOL


def test_the_cut_is_of_depth_alone(built, family):
    """Nothing but depth is cut, so no share is summed: the program of the
    first two layers, on the first two layers' leaves, is the reference's
    two-layer model."""
    from deepspeedsyclsupport_tpu.models import build_model

    _, params = built
    hf = {**HF, "num_hidden_layers": 2}
    model = build_model("falcon-h1-34b", **overrides(family, hf))
    first = {k: jax.tree_util.tree_map(lambda a: a[:2], v)
             if k.endswith("_layers") else v for k, v in params.items()}
    eng = H.engine_of(model, first)
    logits, tokens = parity.served_logits(eng, 0, PROMPTS[1], 2)
    want = H.reference(first, PROMPTS[1] + tokens, hf)
    assert parity.row_errors(logits, want[-3:]).max() < TOL
    # and the third layer is not nothing
    whole = H.reference(params, PROMPTS[1] + tokens)
    assert parity.row_errors(logits, whole[-3:]).max() > 100 * TOL


# ------------------------------------------------- the fourteen multipliers
@pytest.fixture(scope="module")
def readings(built, family, served):
    """``reading(**multipliers) -> logits``: the reference over ``served``'s
    sequence with some multipliers at values of the caller's, through ONE
    compiled walk (they enter as arguments, not as constants)."""
    ids, logits = served
    names = [k for k in family.arch(HF) if "multiplier" in k]
    walk = jax.jit(lambda p, x, m: family.sequence_logits(
        {**family.arch(HF), **m}, p, x)[-len(logits):])
    ids = jnp.asarray(ids, jnp.int32)

    def reading(**changed):
        a = {**{k: family.arch(HF)[k] for k in names}, **changed}
        return np.asarray(walk(built[1], ids, jax.tree_util.tree_map(
            jnp.float32, a)))
    return reading


def _with(name, fn):
    """``{key: value}`` of ``MULTIPLIERS``' ``name`` put through ``fn``."""
    key, _, at = name.partition(".")
    if not at:
        return {key: fn(HF[key])}
    values = list(HF[key])
    values[int(at)] = fn(values[int(at)])
    return {key: tuple(values)}


def test_the_reading_with_every_multiplier_is_the_served_one(served,
                                                             readings):
    assert parity.row_errors(served[1], readings()).max() < TOL


@pytest.mark.parametrize("how", ["left_out", "applied_twice"])
@pytest.mark.parametrize("name", MULTIPLIERS)
def test_a_multiplier_left_out_or_applied_twice_is_refused(served, readings,
                                                           name, how):
    """Each of the fourteen, at full weight: the reading of the publication
    without it (or with it squared) stands a hundred tolerances or more from
    what the program serves. (``key_multiplier`` on q in the place of k is
    NOT among the faults: the scores are bilinear, it is the same
    function.)"""
    fn = (lambda m: 1.0) if how == "left_out" else (lambda m: m * m)
    wrong = readings(**_with(name, fn))
    assert parity.row_errors(served[1], wrong).max() > 100 * TOL


# ------------------------------------------------------------ planted faults
def _zeroed(params, half, leaf):
    hybrid = params["hybrid_layers"]
    return {**params, "hybrid_layers": {**hybrid, half: {
        **hybrid[half], leaf: jnp.zeros_like(hybrid[half][leaf])}}}


def _gate_after_norm(y, z, scale, cfg):
    rows, g = y.shape[0], cfg.ssm_n_groups
    u = y.reshape(rows, g, -1)
    u = u * jax.lax.rsqrt(jnp.mean(jnp.square(u), -1, keepdims=True)
                          + cfg.rms_norm_eps)
    return u.reshape(rows, -1) * scale.astype(jnp.float32) \
        * jax.nn.silu(z.astype(jnp.float32))


def _ungrouped_norm(y, z, scale, cfg):
    u = y * jax.nn.silu(z.astype(jnp.float32))
    u = u * jax.lax.rsqrt(jnp.mean(jnp.square(u), -1, keepdims=True)
                          + cfg.rms_norm_eps)
    return u * scale.astype(jnp.float32)


@pytest.fixture(scope="module")
def tool():
    """``tools/h1_faults.py``, which plants the chip's four faults: what it
    plants them with is held here, at tiny size."""
    import importlib.util
    import os

    path = os.path.join(os.path.dirname(__file__), "..", "tools",
                        "h1_faults.py")
    spec = importlib.util.spec_from_file_location("h1_faults", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


FAULTS = {
    # the structure: compiled anew
    "the_halves_in_sequence": dict(sequence=True),
    "the_gate_after_the_norm": dict(gated_norm=_gate_after_norm),
    "gate_norm_ungrouped": dict(gated_norm=_ungrouped_norm),
    "a_head_reads_the_other_group": dict(groups_swapped=True),
    "rotary_at_theta_1e4": dict(config={"rope_theta": 1e4}),
    "state_in_bf16": dict(state_dtype=jnp.bfloat16),
    # a multiplier left out as the chip's tool plants it: the right program
    # on the matrix divided by it
    "attention_out_multiplier_left_out": dict(
        divide=("attn", "wo", "attention_out")),
    "ssm_out_multiplier_left_out": dict(
        divide=("mamba", "out_proj", "ssm_out")),
    "key_multiplier_left_out": dict(divide=("attn", "wk", "key")),
    # a leaf left out: the right program on a tree without it
    "attention_dropped_from_the_sum": dict(zero=("attn", "wo")),
    "mamba_dropped_from_the_sum": dict(zero=("mamba", "out_proj")),
    "no_D": dict(zero=("mamba", "D")),
    "no_dt_bias": dict(zero=("mamba", "dt_bias")),
    "no_conv_bias": dict(zero=("mamba", "conv_b")),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_planted_fault_is_refused(built, tool, engines, monkeypatch,
                                    fault):
    """Each misreading of the publication, served, against the reference of
    the RIGHT weights: beyond the tolerance by two orders or more (the bf16
    state is a rounding of the state at every step, not a misreading, and is
    held to twice the tolerance). The 41-token prompt runs in three chunks
    and six pieces, so every fault shows in the logits of its last position:
    the prefill alone is run."""
    from deepspeedsyclsupport_tpu.models import build_model
    from deepspeedsyclsupport_tpu.ops import ssm

    model, params = built
    how, wrong, eng = FAULTS[fault], params, None
    if "zero" in how:
        wrong = _zeroed(params, *how["zero"])
    if "divide" in how:
        half, leaf, by = how["divide"]
        wrong = tool.divided(params, half, leaf,
                             getattr(model.config.mup, by))
    if wrong is not params:     # no new program: the module's idle engine
        eng = engines()
        monkeypatch.setattr(eng, "params", wrong)
    if "sequence" in how:
        model, wrong = tool.in_sequence(model, params)
    if "config" in how:
        model = build_model(dataclasses.replace(model.config,
                                                **how["config"]))
    if "gated_norm" in how:
        monkeypatch.setattr(ssm, "gated_norm", how["gated_norm"])
    if "groups_swapped" in how:
        split = ssm._split_xbc
        monkeypatch.setattr(ssm, "_split_xbc", lambda out, cfg: tuple(
            t if i == 0 else t[:, ::-1]
            for i, t in enumerate(split(out, cfg))))
    if "state_dtype" in how:
        from deepspeedsyclsupport_tpu.inference.v2 import kv_cache

        monkeypatch.setattr(kv_cache, "SSM_STATE_DTYPE", how["state_dtype"])
    err = H.served_errors(model, wrong, PROMPTS[1:], 0, want_params=params,
                          eng=eng)
    assert err > (2 if fault == "state_in_bf16" else 100) * TOL, err


# ------------------------------------------------------------------- rotary
def test_the_rotary_tables_at_theta_1e11_against_float64(family):
    """Positions to 4,608 (the cell's longest context) at the published
    width: the program's table (``apply_rope``) and the reference's
    (``reference.rope``), both float32, against one computed in float64.
    The fastest pair's angle reaches 4,607 rad, where float32 resolves 5e-4;
    the slowest turns by under 1e-7 rad there: nothing underflows, and its
    sine keeps four digits."""
    from benchmark import reference as ref
    from deepspeedsyclsupport_tpu.models.layers import apply_rope

    d, theta = 128, 1e11
    pos = np.arange(0, 4608, 7)
    angle = pos[:, None] * theta ** (-np.arange(0, d, 2) / d)   # float64
    x = jnp.concatenate([jnp.ones((len(pos), 1, d // 2)),
                         jnp.zeros((len(pos), 1, d // 2))], -1)   # -> cos|sin
    tables = {
        "program": apply_rope(x[None], jnp.asarray(pos), theta)[0, :, 0],
        "reference": ref.rope({"rotary_dim": d, "rope_theta": theta}, x,
                              jnp.asarray(pos))[:, 0]}
    for name, table in tables.items():
        table = np.asarray(table, np.float64)
        assert np.abs(table[:, :d // 2] - np.cos(angle)).max() < 1e-3, name
        assert np.abs(table[:, d // 2:] - np.sin(angle)).max() < 1e-3, name
        slow = table[1:, -1]
        assert (slow > 0).all() and angle[-1, -1] < 1e-7
        np.testing.assert_allclose(slow, np.sin(angle[1:, -1]), rtol=1e-4)
    # theta 1e4 is another table: half the pairs differ by more than 0.5
    other = pos[:, None] * 1e4 ** (-np.arange(0, d, 2) / d)
    assert (np.abs(np.cos(other) - np.cos(angle)).max(0) > 0.5).sum() > 30


# ------------------------------------------------ the scan at state 256
def test_the_chunked_scan_is_the_state_step_a_token_at_a_time_at_state_256():
    """``ops/ssm.py`` at this family's state: 2 groups of 256 under 4 heads,
    three pieces of two sequences (one continuing from its slot), against
    ``decode_step`` token by token, both state steps."""
    from deepspeedsyclsupport_tpu.models import get_config
    from deepspeedsyclsupport_tpu.ops import ssm

    cfg = get_config("falcon-h1-34b", hidden_size=32, num_heads=4,
                     num_kv_heads=2, head_dim=8, mamba_num_heads=4,
                     mamba_head_dim=8, ssm_chunk_size=8, dtype="float32")
    assert (cfg.ssm_state_size, cfg.ssm_n_groups) == (256, 2)
    c = cfg.ssm_conv_dim
    k = jax.random.split(jax.random.PRNGKey(2), 8)
    p = {"conv_w": jax.random.normal(k[0], (4, c)) * 0.5,
         "conv_b": jax.random.normal(k[1], (c,)) * 0.5,
         "dt_bias": jax.random.normal(k[2], (4,)),
         "A_log": jnp.log(jnp.asarray([1.0, 4.0, 9.0, 16.0])),
         "D": jnp.ones((4,))}
    pool = jax.random.normal(k[3], (2, 6, 2, 256, 16))
    conv = jax.random.normal(k[4], (2, 3, 6, c))
    t = 20
    xbc = jax.random.normal(k[5], (t, c)) * 0.3
    dt = jax.random.normal(k[6], (t, 4))
    pieces = (jnp.asarray([0, 8, 11, 0, 0]), jnp.asarray([8, 3, 8, 0, 0]),
              jnp.asarray([2, 2, 0, 5, 5]),
              jnp.asarray([True, False, False, False, False]),
              jnp.asarray(3))
    y, ssm_c, conv_c = jax.jit(lambda *a: ssm.chunked_scan(
        *a, 1, pieces, cfg))(xbc, dt, p, pool, conv)
    for name in ("xla", "pallas_interpret"):
        one = jax.jit(lambda x, d, s, c, slot, fresh, name=name:
                      ssm.decode_step(x, d, p, s, c, 1, slot, fresh, cfg,
                                      ssm.STATE_STEPS[name]))
        ssm_s, conv_s, rows = pool, conv, []
        for i in range(19):
            slot, first = (2, i == 0) if i < 11 else (0, False)
            y_i, ssm_s, conv_s = one(xbc[i:i + 1], dt[i:i + 1], ssm_s,
                                     conv_s, jnp.asarray([slot]),
                                     jnp.asarray([first]))
            rows.append(y_i[0])
        np.testing.assert_allclose(y[:19], np.stack(rows), atol=5e-5)
        np.testing.assert_allclose(ssm_c[:, :5], ssm_s[:, :5], atol=5e-5)
        np.testing.assert_allclose(conv_c[:, :, :5], conv_s[:, :, :5],
                                   atol=1e-6)
    assert not np.asarray(y[19]).any()           # no piece lies there


# ------------------------------------------------------------------ scopes
def test_both_halves_and_the_head_are_scoped_in_the_compiled_programs(
        engines):
    """What the per-layer readers find by (``benchmark/scopes.py``):
    ``h1_attn`` over the attention half, the four ``ssm_*`` scopes over the
    Mamba half (the pieces under ``ssm_chunk`` in the ragged forward alone)
    and ``lm_head`` over the unembedding, in both forwards."""
    from benchmark import scopes

    eng = engines()
    eng.warmup()
    labels = ("h1_attn", "lm_head", "ssm_proj", "ssm_conv", "ssm_scan",
              "ssm_gate", "ssm_chunk")
    found = {name: set(scopes.instructions_under(c.as_text(), labels)
                       .values())
             for name, c in eng.compiled_programs().items()}
    assert found["decode_forward"] == set(labels[:-1])
    assert found["ragged_forward"] == set(labels)
    # the round records count the Mamba halves' pieces as nemotron's do
    assert eng.kv.state_kind == "ssm"
