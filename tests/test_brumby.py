"""``model_type: brumby`` on the serving path, at tiny widths that keep the
structure (two query heads a key-value head, two KV heads, head 32: 17
diagonals, so the sqrt(2) rows are there), float32, on the CPU: the program
(``build_model`` -> ``InferenceEngineV2`` -> ``ServingSession``, chunked
prefill through the chunked form, decode through the state pool, NO KV
pool) against the plain attention-form reference
``benchmark/families/brumby.py`` on seeded weights with every leaf moved off
its init; a mixed round; a state slot reused; eviction under ``requeue``;
planted faults, each refused; the scopes in both compiled programs; the
refusals' messages; the ``xla`` and Pallas-interpret state steps agreeing."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tests.family_harness import (Harness, ending, engines,  # noqa: F401
                                  family, moved)
from tests.unit import stream_ends

HF = {"model_type": "brumby", "hidden_size": 32, "num_hidden_layers": 2,
      "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 32,
      "intermediate_size": 48, "vocab_size": 128, "rope_theta": 1000000,
      "rms_norm_eps": 1e-6, "sliding_window": None,
      "tie_word_embeddings": False}
ENGINE = {"max_context": 128, "max_sequences": 4, "block_size": 8,
          "max_tokens_per_batch": 16}
ENDING = {"max_context": 32}
# both sides are float32 and differ in the FORM (a recurrence over a feature
# map against attention weights): measured 3e-6 logit-std; the planted
# faults measure 0.01 and more
TOL = 1e-4
PROMPTS = ([7, 3, 11, 100, 41, 9, 5], list(range(60, 101)))   # 7 and 41
H = Harness(HF, ENGINE, PROMPTS)


def overrides(family):
    return {**family.program_widths(HF), "intermediate_size": 48,
            "max_seq_len": 256, "dtype": "float32", "retention_chunk_size": 8,
            "retention_half_life": (4.0, 64.0)}


@pytest.fixture(scope="module")
def built(family):
    from deepspeedsyclsupport_tpu.models import build_model

    widths = overrides(family)
    widths.pop("num_kv_layers")
    model = build_model("brumby-14b", **widths)
    model.seed = 3
    return model, moved(jax.jit(model.init_params)())


# ------------------------------------------------------------ the structure
def test_the_uniform_block_with_a_gate_and_no_cached_key(built):
    model, params = built
    cfg = model.config
    assert (cfg.layer_pattern, cfg.num_kv_layers, cfg.state_layers,
            cfg.state_chunk_size) == (None, 0, 2, 8)
    attn = params["layers"]["attn"]
    assert attn["g_proj"].shape == (2, 32, 2)
    assert attn["g_bias"].shape == (2, 2)
    assert set(attn) == {"wq", "wk", "wv", "wo", "q_norm", "k_norm",
                         "g_proj", "g_bias"}
    # the drawn bias alone: half-lives log-uniform over the preset's range
    whole = dataclasses.replace(cfg, retention_half_life=(64.0, 8192.0))
    from deepspeedsyclsupport_tpu.models import build_model

    bias = np.asarray(build_model(whole).init_params()["layers"]["attn"]
                      ["g_bias"])
    keep = 1 / (1 + np.exp(-bias))
    life = -1 / np.log2(keep)
    assert (life > 63).all() and (life < 8200).all()
    assert 0.989 < keep.min() and keep.max() < 0.99992
    from deepspeedsyclsupport_tpu.models.config import ModelConfig

    for wrong in ({"retention_degree": 3}, {"sliding_window": 16},
                  {"layer_pattern": "MM"}, {"kv_lora_rank": 8}):
        with pytest.raises(ValueError, match="power retention is written "
                           "for degree 2 on one uniform stack"):
            ModelConfig(**{**dataclasses.asdict(cfg), **wrong})


def test_the_state_pool_and_a_pool_with_no_rows(engines):
    from deepspeedsyclsupport_tpu.inference.v2.kv_cache import kv_pool_stats
    from deepspeedsyclsupport_tpu.ops.retention import state_dim

    eng = engines()
    kv, dim = eng.kv, state_dim(32)
    assert dim == 17 * 32
    # no layer caches a key: pools with no rows, two state leaves
    assert kv.k.shape == kv.v.shape == (0, 32 * 8, 2, 32)
    assert kv.ret_s.shape == (2, 5, 2, 32, dim) and kv.ssm is None
    assert kv.ret_z.shape == (2, 5, 2, dim)
    assert kv.ret_s.dtype == kv.ret_z.dtype == jnp.float32
    assert kv.state_names == ("ret_s", "ret_z") and kv.state_slots == 4
    per_slot = 2 * 2 * (32 + 1) * dim * 4
    assert eng.state_stats() == {
        "bytes_per_slot": per_slot, "slots": 4, "slots_live": 0,
        "dtype": "float32", "layers": 2, "pool_bytes": per_slot * 5}
    eng.warmup()
    assert eng.state_stats()["slots_live"] == 0
    assert sorted(eng._state_free) == [0, 1, 2, 3]
    # a long context takes no block and is admitted by its slot alone
    eng.put([9], [list(range(100))])
    stats = kv_pool_stats(eng.kv, eng.allocator)
    assert (stats["pool_bytes"], stats["blocks_physical"],
            stats["occupancy"]) == (0, 0, 0.0)
    assert eng.seqs[9].blocks == [] and eng.seqs[9].n_cached == 100
    res = eng.check_schedule([1, 2, 3, 4], [120] * 4)
    assert res.admitted == (1, 2, 3) and "slots" in res.reasons[4]
    assert "context" in eng.check_schedule([9], [29]).reasons[9]
    eng.flush([9])


# ------------------------------------------------ program against reference
@pytest.mark.parametrize("step", ["xla", "pallas_interpret"])
def test_chunked_prefill_then_decode_match_the_reference(built, monkeypatch,
                                                         step):
    """41 tokens = three chunks of 16, 16 and 9 rows in pieces of 8 (every
    piece but the first starts from its slot's state), then six decode steps
    through the state pool."""
    from deepspeedsyclsupport_tpu.inference.v2 import model as model_v2
    from deepspeedsyclsupport_tpu.inference.v2 import module_registry as reg

    # no setting names a state step: the registry is the seam, and the
    # interpreted kernel is put first in it for the length of this test
    first = dataclasses.replace(
        reg.get_impl("ret_step", step), name="first", priority=100,
        auto_eligible=lambda ctx: True)
    monkeypatch.setitem(reg._REGISTRY["ret_step"], "first", first)
    assert model_v2._ret_step_fn() is first.fn
    # ... and the pieces' kernel in the place the platform's choice has
    from deepspeedsyclsupport_tpu.ops import retention

    monkeypatch.setitem(retention.PIECE_CARRIES, retention.default_impl(),
                        retention.PIECE_CARRIES[step])
    assert H.served_errors(*built) < TOL


def test_a_mixed_round_and_a_slot_reused(built, engines):
    H.check_a_mixed_round_and_a_slot_reused(built[1], engines(), TOL)


def test_eviction_under_requeue_finishes_with_the_references_tokens(
        built):
    """No block is ever wanted, so the session never evicts on its own; an
    operator's preemption (``requeue``) still takes a stream's slot, the
    stream is prefilled again from zero, prompt and emitted tokens, and
    every stream ends with the tokens the reference's greedy choice gives."""
    from deepspeedsyclsupport_tpu.inference.v2.config import (
        ServingPolicyConfig)
    from deepspeedsyclsupport_tpu.inference.v2.serving import ServingSession

    model, params = built
    eng = H.engine_of(model, params, max_context=32)
    sess = ServingSession(eng, ServingPolicyConfig(preempt_policy="requeue"))
    prompts = {1: [1, 2, 3], 2: [4, 5, 6], 3: [7, 8, 9]}
    for uid, p in prompts.items():
        assert sess.submit(uid, p, 18) == "admitted"
    out, evicted, rounds = {}, 0, 0
    for _ in range(400):
        if sess.idle:
            break
        events = sess.step()
        rounds += 1
        if rounds == 7:      # mid-decode: take stream 2's slot away
            sess._evict(2, sess.clock(), events)
        for e in events:
            if e.kind == "token":
                out.setdefault(e.uid, []).extend(e.tokens)
            evicted += e.kind == "evict"
    assert sess.idle and evicted == 1
    assert eng.state_stats()["slots_live"] == 0
    assert eng.allocator.free_blocks == eng.allocator.num_blocks
    for uid, p in prompts.items():
        assert len(out[uid]) == 18
        rows = H.reference(params, p + out[uid])[len(p) - 1:-1]
        picked = rows[np.arange(18), out[uid]]
        assert ((rows.max(-1) - picked) / rows.std(-1)).max() < TOL


# ------------------------------------------------------------ planted faults
def _regrouped(params):
    """The query heads moved on by one GROUP (and W_o's rows with them): the
    same function of a program that pairs head i with KV head i // 2, another
    where a head reads the other group's state."""
    out = jax.tree_util.tree_map(lambda a: a, params)
    attn = dict(out["layers"]["attn"])
    attn["wq"] = jnp.roll(attn["wq"].reshape(2, 32, 4, 32), 2,
                          axis=2).reshape(2, 32, 128)
    attn["wo"] = jnp.roll(attn["wo"].reshape(2, 4, 32, 32), 2,
                          axis=1).reshape(2, 128, 32)
    out["layers"] = {**out["layers"], "attn": attn}
    return out


def _ungated(params):
    out = jax.tree_util.tree_map(lambda a: a, params)
    attn = dict(out["layers"]["attn"])
    attn["g_proj"] = jnp.zeros_like(attn["g_proj"])
    attn["g_bias"] = jnp.full_like(attn["g_bias"], 30.0)
    out["layers"] = {**out["layers"], "attn": attn}
    return out


FAULTS = ["state_not_carried", "gate_dropped", "normaliser_dropped",
          "no_sqrt2_weights", "another_groups_state", "state_in_bf16"]


@pytest.mark.parametrize("fault", FAULTS)
def test_a_planted_fault_is_refused(built, monkeypatch, fault):
    """Each misreading, served, against the reference of the RIGHT weights:
    beyond the tolerance by two orders or more (the bf16 state is a rounding
    of the state at every step, not a misreading, and is held to twice the
    tolerance). The 41-token prompt runs in three chunks and six pieces, so
    every fault shows in the logits of its last position: the prefill alone
    is compiled and run."""
    from deepspeedsyclsupport_tpu.inference.v2 import kv_cache
    from deepspeedsyclsupport_tpu.ops import retention

    model, params = built
    wrong = params
    if fault == "state_not_carried":
        chunked = retention.chunked

        def forgetful(q, k, v, gam, pools, layer, pieces, cfg):
            row0, length, slot, fresh, count = pieces
            return chunked(q, k, v, gam, pools, layer,
                           (row0, length, slot, jnp.ones_like(fresh), count),
                           cfg)

        monkeypatch.setattr(retention, "chunked", forgetful)
    elif fault == "gate_dropped":
        wrong = _ungated(params)
    elif fault == "normaliser_dropped":
        monkeypatch.setattr(retention, "_normalised",
                            lambda num, den: num)
    elif fault == "no_sqrt2_weights":
        monkeypatch.setattr(
            retention, "phi_weights",
            lambda d: np.ones((d // 2 + 1, 1), np.float32))
    elif fault == "another_groups_state":
        wrong = _regrouped(params)
    elif fault == "state_in_bf16":
        monkeypatch.setattr(kv_cache, "RETENTION_STATE_DTYPE", jnp.bfloat16)
    err = H.served_errors(model, wrong, PROMPTS[1:], 0,
                          want_params=params)
    assert err > (2 if fault == "state_in_bf16" else 100) * TOL, (fault, err)


def test_the_reference_is_the_attention_form_and_the_map_squares(family):
    """``phi(a) . phi(b) = (a . b)^2`` in the program's layout, which the
    reference never builds; the reference's weights are the gates' decay
    times the squared scaled scores, nothing else."""
    from deepspeedsyclsupport_tpu.ops import retention

    a, b = jax.random.normal(jax.random.PRNGKey(0), (2, 5, 32))
    np.testing.assert_allclose(
        (retention.phi(a) * retention.phi(b)).sum(-1), (a * b).sum(-1) ** 2,
        rtol=1e-5)
    assert retention.phi(a).shape == (5, retention.state_dim(32))
    assert retention.state_dim(128) == 8320
    # ... and it imports nothing of the program
    assert "deepspeedsyclsupport_tpu" not in open(family.__file__).read()


# ------------------------------------------------------------------ scopes
def test_the_layers_scopes_reach_the_compiled_programs(engines):
    """What the per-layer readers find by (``benchmark/scopes.py``): the
    three ``ret_*`` scopes in both forwards, and the chunked form's pieces
    under ``ret_chunk`` INSIDE ``ret_scan`` in the ragged forward alone (a
    decode step has no piece), apart from the one-token rows' state step."""
    from benchmark import scopes

    eng = engines()
    eng.warmup()
    labels = ("ret_proj", "ret_gate", "ret_scan", "ret_chunk")
    found = {name: set(scopes.instructions_under(c.as_text(), labels)
                       .values())
             for name, c in eng.compiled_programs().items()}
    assert found["decode_forward"] == set(labels[:3])
    assert found["ragged_forward"] == set(labels)
    text = eng.compiled_programs()["ragged_forward"].as_text()
    paths = [p for _n, p in scopes._INSTRUCTION.findall(text)
             if "ret_chunk" in p.split("/")]
    assert paths and all("ret_scan/ret_chunk" in p for p in paths)
    under_scan = scopes.instructions_under(text, labels[:3])
    assert set(scopes.instructions_under(text, ("ret_chunk",))) \
        <= set(under_scan)


# ---------------------------------------------------------------- refusals
def test_what_a_model_with_recurrent_state_refuses_says_why(built, engines,
                                                            tmp_path):
    model, params = built
    eng = engines()
    with pytest.raises(NotImplementedError, match="Mamba-2 or power-"
                       "retention layers.*snapshot of the recurrent state "
                       "at every shared block boundary"):
        eng.install_prefix_cache()
    with pytest.raises(NotImplementedError, match="serialize.*snapshot of "
                       "the recurrent state beside the parameters"):
        eng.serialize(str(tmp_path / "snap"))
    with pytest.raises(NotImplementedError, match="power-retention model.*"
                       "chunked form's backward is not written"):
        model.apply(params, jnp.zeros((1, 8), jnp.int32))


# ------------------------------------------------------ the two state steps
def test_the_xla_and_the_pallas_interpret_steps_agree(built):
    """``decode_step`` over six rows on five slots (two padding rows share
    the sink, one row fresh): both state steps, the same numbers and the
    same pools; and the chunked form = the decode step token by token."""
    from deepspeedsyclsupport_tpu.ops import retention

    cfg = built[0].config
    dim = retention.state_dim(32)
    k = jax.random.split(jax.random.PRNGKey(2), 10)
    pools = (jax.random.normal(k[0], (2, 6, 2, 32, dim)),
             jnp.abs(jax.random.normal(k[1], (2, 6, 2, dim))) + 1.0)
    q = jax.random.normal(k[2], (6, 4, 32))
    kk, v = jax.random.normal(k[3], (2, 6, 2, 32))
    gam = -jnp.abs(jax.random.normal(k[4], (6, 2))) * 0.1
    slots = jnp.asarray([3, 0, 5, 5, 4, 1])
    fresh = jnp.asarray([False, True, False, False, False, False])
    def step(name):
        """``decode_step`` through one state step, as ONE program (eagerly
        every op of it is a program a shape)."""
        return jax.jit(lambda q, k, v, gam, state, slots, fresh:
                       retention.decode_step(q, k, v, gam, state, 1, slots,
                                             fresh, cfg,
                                             retention.STATE_STEPS[name]))

    got = {name: step(name)(q, kk, v, gam, pools, slots, fresh)
           for name in ("xla", "pallas_interpret")}
    (ya, (sa, za)), (yb, (sb, zb)) = got["xla"], got["pallas_interpret"]
    live = np.asarray([0, 1, 3, 4])           # rows 2 and 3 wrote the sink
    np.testing.assert_allclose(np.asarray(ya)[live], np.asarray(yb)[live],
                               atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(sa[:, :5], sb[:, :5], atol=1e-5)
    np.testing.assert_allclose(za[:, :5], zb[:, :5], atol=1e-5)
    assert not np.allclose(np.asarray(sa)[1, 3], np.asarray(pools[0])[1, 3])
    np.testing.assert_array_equal(np.asarray(sa)[0], np.asarray(pools[0])[0])
    # the chunked form = the decode step token by token
    t = 20
    q = jax.random.normal(k[5], (t, 4, 32))
    kk, v = jax.random.normal(k[6], (2, t, 2, 32))
    gam = -jnp.abs(jax.random.normal(k[7], (t, 2))) * 0.1
    pieces = (jnp.asarray([0, 8, 11, 0, 0]), jnp.asarray([8, 3, 8, 0, 0]),
              jnp.asarray([2, 2, 0, 5, 5]),
              jnp.asarray([True, False, False, False, False]),
              jnp.asarray(3))
    y, (s_c, z_c) = retention.chunked(q, kk, v, gam, pools, 1, pieces, cfg)
    # the pieces' kernel (the features expanded inside it) = the XLA form
    y_k, (s_k, z_k) = retention.chunked(
        q, kk, v, gam, pools, 1, pieces, cfg,
        retention.PIECE_CARRIES["pallas_interpret"])
    np.testing.assert_allclose(y_k, y, atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(s_k[:, :5], s_c[:, :5], atol=2e-5)
    np.testing.assert_allclose(z_k[:, :5], z_c[:, :5], atol=2e-5)
    state, rows, one = pools, [], step("xla")
    for i in range(19):
        slot, first = (2, i == 0) if i < 11 else (0, False)
        y_i, state = one(q[i:i + 1], kk[i:i + 1], v[i:i + 1], gam[i:i + 1],
                         state, jnp.asarray([slot]), jnp.asarray([first]))
        rows.append(y_i[0])
    np.testing.assert_allclose(y[:19], np.stack(rows), atol=2e-4, rtol=2e-4)
    assert not np.asarray(y[19]).any()           # no piece lies there
    np.testing.assert_allclose(s_c[:, :5], state[0][:, :5], atol=1e-4)
    np.testing.assert_allclose(z_c[:, :5], state[1][:, :5], atol=1e-4)


@pytest.mark.parametrize("steps", [1, 3], ids=["one_step", "three_steps"])
def test_the_tiled_walk_agrees_at_the_cells_head_width(steps):
    """The CELL's head (``d`` 128, 8,320 features: the one width at which
    the kernel's walk has more than one compute tile, 65 of them in two
    bands of rows, and a head goes out in more than one copy) under the
    interpreter against the XLA form: three rows on three slots, one of them
    fresh, and two rows of padding on the sink; two KV heads of five queries
    each. One step, and three one after another on the state the last left
    (the rows and their slots drawn anew, only the first step's row fresh)."""
    import functools
    import types

    from deepspeedsyclsupport_tpu.ops import retention

    d, hk, g, sink = 128, 2, 5, 3
    cfg = types.SimpleNamespace(attn_scale=None, head_dim=d)
    k = jax.random.split(jax.random.PRNGKey(5), 6)
    # each slot's state as six earlier tokens left it: S = sum v phi(k)^T,
    # z = sum phi(k), so the normaliser is a sum of squares as in a run
    past = retention.phi(jax.random.normal(k[0], (1, sink + 1, hk, 6, d)))
    pools = (jnp.einsum("lsjtv,lsjtf->lsjvf", jax.random.normal(
        k[1], (1, sink + 1, hk, 6, d)), past), past.sum(3))
    q = jax.random.normal(k[2], (steps, 5, hk * g, d))
    kk, v = jax.random.normal(k[3], (2, steps, 5, hk, d))
    gam = -jnp.abs(jax.random.normal(k[4], (steps, 5, hk))) * 0.1
    slots = jnp.asarray([[1, sink, 0, sink, 2], [sink, 2, 1, sink, 0],
                         [0, 1, sink, 2, sink]])
    fresh = jnp.asarray([False, False, True, False, False])
    (sa, za), (sb, zb) = pools, pools
    # (each form ONE program for all the steps: eagerly every op of a step
    # is a program of its own)
    step = {name: jax.jit(functools.partial(
        lambda name, q, k, v, gam, state, slots, fresh: retention.decode_step(
            q, k, v, gam, state, 0, slots, fresh, cfg,
            retention.STATE_STEPS[name]), name))
        for name in ("xla", "pallas_interpret")}
    for t in range(steps):
        (ya, (sa, za)), (yb, (sb, zb)) = (
            step[name](q[t], kk[t], v[t], gam[t], state, slots[t],
                       fresh & (t == 0))
            for name, state in (("xla", (sa, za)),
                                ("pallas_interpret", (sb, zb))))
        live = np.flatnonzero(np.asarray(slots[t]) != sink)
        np.testing.assert_allclose(np.asarray(ya)[live], np.asarray(yb)[live],
                                   atol=2e-5, rtol=2e-5)
        np.testing.assert_allclose(sa[:, :sink], sb[:, :sink], atol=1e-5)
        np.testing.assert_allclose(za[:, :sink], zb[:, :sink], atol=1e-5)
        if t == 0:
            # the fresh row started from zeros: its state is its own write
            np.testing.assert_allclose(
                sb[0, 0], v[0, 2][:, :, None]
                * retention.phi(kk[0, 2])[:, None, :], atol=1e-5)
    assert not np.allclose(np.asarray(sb)[0, 1], np.asarray(pools[0])[0, 1])


@pytest.mark.parametrize("d, budget, fits", [
    (128, None, True),            # the cell: 8.5 MB of the budget's 32 MiB
    (128, 8 << 20, False),        # two buffers of [128, 8320] float32: 8.5 MB
    (32, 1 << 20, True),          # a test's width: 139 KB
    (32, 1 << 10, False),
], ids=["the_cells_head", "the_cells_head_under_8_MiB", "a_tests_head",
        "a_tests_head_under_1_KiB"])
def test_the_state_step_refuses_a_head_its_two_buffers_do_not_hold(
        monkeypatch, d, budget, fits):
    """A grid step copies one KV head's WHOLE state, into one of two VMEM
    buffers: a head two of them do not hold under ``STEP_VMEM_BYTES`` is
    refused when the step is traced, not walked in smaller blocks (slower on
    the chip than the kernel this one replaced: PERF.md section 6, PR 50)."""
    import functools

    from deepspeedsyclsupport_tpu.ops import retention

    if budget:
        monkeypatch.setattr(retention, "STEP_VMEM_BYTES", budget)
    dim = retention.state_dim(d)
    assert (2 * d * dim * 4 <= retention.STEP_VMEM_BYTES) == fits
    # (a function of this call's own: a trace is cached by the function)
    step = functools.partial(
        jax.eval_shape,
        lambda *a: retention.STATE_STEPS["pallas_interpret"](*a),
        jax.ShapeDtypeStruct((1, 2, 1, d, dim), jnp.float32), 0,
        *(jax.ShapeDtypeStruct(s, t) for s, t in (
            ((2,), jnp.int32), ((2, 1), jnp.float32),
            ((2, 1, 5, d), jnp.float32), ((2, 1, d), jnp.float32),
            ((2, 1, d), jnp.float32))))
    if fits:
        y, pool = step()
        assert y.shape == (2, 1, 5, d) and pool.shape == (1, 2, 1, d, dim)
    else:
        with pytest.raises(AssertionError, match="STEP_VMEM_BYTES"):
            step()


# ------------------------------------------------- a stream that ends early
@stream_ends.parametrize
def test_a_stream_that_ends_early_gives_back_what_it_held(ending, driver,
                                                          end):
    """The state slots: ``stream_ends`` counts them back, and a new stream
    in a released slot starts from zeros; there is no block to give back."""
    stream_ends.check(ending, driver, end)
