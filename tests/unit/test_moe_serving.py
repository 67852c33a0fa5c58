"""Sparse experts on the serving path (``parallel/moe.moe_mlp_nodrop``, the
forwards' ``live`` mask, ``kv_cache.MoeCounters``): a pad row gets no expert,
the device's load counter equals ``k x live tokens`` in every layer over
rounds and through an eviction with requeue, the
round record carries the experts the forward before it touched,
mixtral's renormalised weighting is what it was, and the combine (one gather
of a token's k rows, a weighted sum in float32) is no further from a float64
sum than the scatter-add it replaced."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeedsyclsupport_tpu.inference.v2 import (
    InferenceEngineV2, ServingPolicyConfig, ServingSession)
from deepspeedsyclsupport_tpu.models import build_model, get_config
from deepspeedsyclsupport_tpu.ops import grouped_gemm
from deepspeedsyclsupport_tpu.parallel import moe
from deepspeedsyclsupport_tpu.parallel.moe import (moe_mlp_nodrop,
                                                   topk_gating, topk_weights)
from tests.unit import stream_ends


@pytest.fixture(scope="module")
def tiny_moe():
    model = build_model("tiny-moe", dtype="float32")
    return model, model.init_params()


def _layer0(params):
    return jax.tree_util.tree_map(lambda x: x[0], params["layers"]["moe"])


def _engine(tiny_moe, **kw):
    model, params = tiny_moe
    return InferenceEngineV2(model, params, dtype=jnp.float32, **{
        "block_size": 8, "max_context": 64, "max_tokens_per_batch": 16,
        "max_sequences": 4, "prefill_attn": "xla", "decode_attn": "xla",
        **kw})


def _drive(sess, requests, rounds=400):
    for uid, prompt, budget in requests:
        assert sess.submit(uid, prompt, budget) == "admitted"
    events = []
    for _ in range(rounds):
        if sess.idle:
            break
        events += sess.step()
    assert sess.idle
    return events


def _check_load(eng):
    """Every layer routed every live token k times, and nothing else."""
    stats = eng.moe_stats()
    k = eng.model.config.num_experts_per_tok
    assert stats["live_tokens"] > 0
    assert stats["load"].shape == (eng.model.config.num_layers,
                                   eng.model.config.num_experts)
    assert stats["load"].sum(1).tolist() \
        == [k * stats["live_tokens"]] * len(stats["load"])
    return stats


# ------------------------------------------------------------ the function
def _nodrop_before(p, x, cfg):
    """``moe_mlp_nodrop`` as it was before the live mask and the shared
    weighting (PR 25's tree), kept to pin mixtral's result."""
    t, d = x.shape
    e, k = cfg.num_experts, cfg.num_experts_per_tok
    logits = jnp.einsum("td,de->te", x.astype(jnp.float32),
                        p["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    gate_w, expert_idx = jax.lax.top_k(probs, k)
    gate_w = gate_w / jnp.maximum(gate_w.sum(-1, keepdims=True), 1e-9)
    flat_expert = expert_idx.reshape(t * k)
    flat_tok = jnp.repeat(jnp.arange(t), k)
    order = jnp.argsort(flat_expert, stable=True)
    sorted_tok = flat_tok[order]
    xs = x[sorted_tok]
    group_sizes = jnp.bincount(flat_expert, length=e).astype(jnp.int32)
    gate = jax.lax.ragged_dot(xs, p["w_gate"], group_sizes)
    up = jax.lax.ragged_dot(xs, p["w_up"], group_sizes)
    ys = jax.lax.ragged_dot(jax.nn.silu(gate) * up, p["w_down"], group_sizes)
    w_flat = gate_w.reshape(t * k)[order].astype(x.dtype)
    return jnp.zeros((t, d), x.dtype).at[sorted_tok].add(ys * w_flat[:, None])


@pytest.mark.parametrize("live", [None, "some"])
def test_mixtral_weighting_is_bit_identical_to_before(tiny_moe, live):
    """The WEIGHTING of PR 25 (top-k of the softmax, renormalised) against
    ``_nodrop_before``. Held to float32's rounding and no longer to the bit:
    the combine sums a token's k rows in float32 in the choices' order and
    rounds once, where ``_nodrop_before`` scatter-adds them in the sort's
    order; a wrong weight would be off by far more than an ulp."""
    model, params = tiny_moe
    cfg = model.config
    assert cfg.norm_topk_prob
    x = jax.random.normal(jax.random.PRNGKey(0), (24, cfg.hidden_size))
    mask = None if live is None else jnp.arange(24) % 3 != 1
    want = np.asarray(_nodrop_before(_layer0(params), x, cfg))
    got, _rows = moe_mlp_nodrop(_layer0(params), x, cfg, mask)
    keep = slice(None) if mask is None else np.asarray(mask)
    np.testing.assert_allclose(np.asarray(got)[keep], want[keep],
                               rtol=1e-6, atol=1e-7)


# ------------------------------------------------------------- the combine
def _scatter_add_before(ys, at, gate_w, has_expert, dtype):
    """The combine of PRs 26-57 on the rows ``combine_rows`` is handed: each
    (token, choice) row weighted in ``dtype``, a row with no expert zeroed,
    one scatter-add (k - 1 roundings a token)."""
    t, k = gate_w.shape
    rows = ys[at] * gate_w.reshape(t * k).astype(dtype)[:, None]
    if has_expert is not None:
        rows = jnp.where(has_expert[:, None], rows, 0)
    return jnp.zeros((t, ys.shape[1]), dtype).at[
        jnp.repeat(jnp.arange(t), k)].add(rows)


@pytest.mark.parametrize("impl", ["xla", "pallas_interpret"])
@pytest.mark.parametrize("held", ["all", "share"])
@pytest.mark.parametrize("live", [None, "some", "none"])
@pytest.mark.parametrize("experts", ["glu", "mlp"])
def test_the_combine_is_a_float64_sum_rounded_once(monkeypatch, experts, live,
                                                   held, impl):
    """``moe_mlp_nodrop`` in bfloat16 (8 experts, 4 a token; all held, or
    ids 2-5), with what it hands ``combine_rows`` listened to: the result
    against the same rows summed in float64 token by token is no further
    off than the scatter-add form on the same rows, a token none of whose
    choices has an expert here reads exact zeros, NaN in every row that no
    choice with an expert reads (the tiles past ``tiles.live`` among them)
    reaches no output, and the whole layer is the experts' MLPs of each
    token's choices, computed from the weights in float64."""
    share = dict(num_experts_held=4, first_expert_held=2) \
        if held == "share" else {}
    form = dict(mlp_type="mlp", activation="gelu") if experts == "mlp" else {}
    model = build_model("tiny-moe", num_experts=8, num_experts_per_tok=4,
                        num_layers=1, dtype="bfloat16", **share, **form)
    cfg = model.config
    p = jax.tree_util.tree_map(lambda w: w.astype(jnp.bfloat16),
                               _layer0(model.init_params()))
    t, k = 40, cfg.num_experts_per_tok
    x = jax.random.normal(jax.random.PRNGKey(4), (t, cfg.hidden_size),
                          jnp.bfloat16)
    mask = {None: None, "some": jnp.arange(t) % 5 != 2,
            "none": jnp.zeros(t, bool)}[live]
    monkeypatch.setattr(grouped_gemm, "default_impl", lambda: impl)
    heard = []
    combine = moe.combine_rows
    monkeypatch.setattr(moe, "combine_rows",
                        lambda *a: heard.append(a) or combine(*a))
    got, routed = moe_mlp_nodrop(p, x, cfg, mask)
    (ys, at, gate_w, has_expert, dtype), = heard
    assert got.dtype == dtype == jnp.bfloat16
    got = np.asarray(got, np.float64)
    assert int(routed.sum()) == k * (t if mask is None else int(mask.sum()))

    has = np.ones((t, k), bool) if has_expert is None \
        else np.asarray(has_expert).reshape(t, k)
    assert (has_expert is None) == (live is None and held == "all")
    rows64 = np.asarray(ys, np.float64)[np.asarray(at)].reshape(t, k, -1)
    w64 = np.asarray(gate_w, np.float64)
    want = np.zeros_like(got)
    for tok in range(t):
        for j in range(k):
            if has[tok, j]:
                want[tok] += w64[tok, j] * rows64[tok, j]
    before = np.asarray(_scatter_add_before(ys, at, gate_w, has_expert, dtype),
                        np.float64)
    assert np.abs(got - want).max() <= np.abs(before - want).max()
    assert np.abs(got - want).max() <= 2.0 ** -8 * np.abs(want).max() + 1e-30
    dead = ~has.any(1)
    assert dead.sum() >= {None: 0, "some": t // 5, "none": t}[live]
    assert not got[dead].any()

    # NaN wherever no (token, choice) with an expert reads
    read = np.zeros(ys.shape[0], bool)
    read[np.asarray(at)[has.reshape(-1)]] = True
    if impl != "xla":      # the tiles' layout, dead tiles behind the live
        assert ys.shape[0] > t * k and not read[-1]
    planted = jnp.where(read[:, None], ys, jnp.nan)
    again = combine(planted, at, gate_w, has_expert, dtype)
    assert np.array_equal(np.asarray(again, np.float64), got)

    # the layer from its weights: routing as the layer makes it, every
    # choice's expert MLP in float64
    def f64(a):
        return np.asarray(a, np.float64)

    _w, idx = topk_weights(
        moe.router_scores(x.astype(jnp.float32) @ p["router"].astype(
            jnp.float32), cfg), k, cfg.norm_topk_prob)
    idx = np.asarray(idx) - cfg.first_expert_held
    act = jax.nn.silu if experts == "glu" else jax.nn.gelu
    layer = np.zeros_like(got)
    for tok in range(t):
        for j in range(k):
            if not has[tok, j]:
                continue
            e, row = idx[tok, j], f64(x[tok])
            up = row @ f64(p["w_up"][e])
            mid = f64(act(jnp.asarray(row @ f64(p["w_gate"][e]),
                                      jnp.float32))) * up \
                if experts == "glu" else f64(act(jnp.asarray(up, jnp.float32)))
            layer[tok] += w64[tok, j] * (mid @ f64(p["w_down"][e]))
    np.testing.assert_allclose(got, layer, rtol=0,
                               atol=0.03 * max(np.abs(layer).max(), 1e-30))


def test_pad_rows_get_no_expert_and_give_zero(tiny_moe):
    model, params = tiny_moe
    cfg = model.config
    x = jax.random.normal(jax.random.PRNGKey(1), (16, cfg.hidden_size))
    live = jnp.asarray([True] * 5 + [False] * 11)
    out, rows = moe_mlp_nodrop(_layer0(params), x, cfg, live)
    assert int(rows.sum()) == cfg.num_experts_per_tok * 5
    assert not np.asarray(out)[5:].any()
    # the live rows do not see the pads: alone they give the same
    alone, rows_alone = moe_mlp_nodrop(_layer0(params), x[:5], cfg)
    assert np.array_equal(np.asarray(rows), np.asarray(rows_alone))
    np.testing.assert_allclose(np.asarray(out)[:5], np.asarray(alone),
                               rtol=1e-6, atol=1e-7)
    # no live row at all: nothing is routed
    _out, rows = moe_mlp_nodrop(_layer0(params), x, cfg,
                                jnp.zeros(16, bool))
    assert int(rows.sum()) == 0


def test_one_weighting_serves_both_paths_and_olmoe_does_not_renormalise():
    probs = jax.nn.softmax(jax.random.normal(jax.random.PRNGKey(2), (6, 8)))
    raw, idx = topk_weights(probs, 3, normalise=False)
    norm, idx2 = topk_weights(probs, 3, normalise=True)
    assert np.array_equal(np.asarray(idx), np.asarray(idx2))
    assert np.array_equal(np.asarray(raw),
                          np.asarray(jax.lax.top_k(probs, 3)[0]))
    np.testing.assert_allclose(np.asarray(norm.sum(-1)), 1.0, rtol=1e-6)
    assert float(raw.sum(-1).max()) < 1.0
    # the training path's combine weights carry the same choice
    logits = jnp.log(probs)
    for normalise, want in ((False, raw), (True, norm)):
        _d, combine, _aux = topk_gating(logits, 3, capacity=6,
                                        normalise=normalise)
        np.testing.assert_allclose(np.asarray(combine.sum((1, 2))),
                                   np.asarray(want.sum(-1)), rtol=1e-5)
    assert not get_config("olmoe-1b-7b").norm_topk_prob


# -------------------------------------------------------------- the engine
def test_load_adds_up_over_rounds_and_the_record_carries_touched(tiny_moe):
    eng = _engine(tiny_moe)
    eng.warmup()
    before = _check_load(eng)              # warm-up's forwards count too
    sess = ServingSession(eng, ServingPolicyConfig(admission="none"))
    _drive(sess, [(1, [1, 2, 3], 6), (2, list(range(4, 30)), 6),
                  (3, [7, 8, 9], 6)])
    after = _check_load(eng)
    assert after["live_tokens"] > before["live_tokens"]
    assert (after["load"] >= before["load"]).all()
    rounds = [r["data"] for r in sess.drain_trace()
              if r["data"].get("stage") == "round"]
    cfg = eng.model.config
    k, e, n_layers = (cfg.num_experts_per_tok, cfg.num_experts,
                      cfg.num_layers)
    checked = 0
    for d, nxt in zip(rounds, rounds[1:]):
        if not d["program"] or not nxt["uids"]:
            continue
        # what the round AFTER a launch read back is that forward's count:
        # one token touches k experts a layer, many at most all of them
        assert n_layers * k <= nxt["moe_touched"] \
            <= n_layers * min(e, k * d["tokens"])
        checked += 1
    assert checked >= 5
    sess.close()


def test_touched_rides_behind_the_sampled_tokens(tiny_moe):
    """``sample_drained`` with the model's tail: the same tokens as without
    it, and the last forward's ``moe_touched`` in the same read-back."""
    from deepspeedsyclsupport_tpu.inference.sampling import SamplingParams

    eng = _engine(tiny_moe)
    eng.put([1, 2], [[1, 2, 3], list(range(4, 12))])
    cfg = eng.model.config
    key = jax.random.PRNGKey(0)
    plain, none = eng.sample_drained([2, 1], key, SamplingParams())
    toks, (touched,) = eng.sample_drained([2, 1], key, SamplingParams(),
                                          tail=eng.moe_tail())
    assert none is None and toks.tolist() == plain.tolist()
    assert toks.shape == (2,)
    assert touched == int(eng.kv.moe.touched)
    assert cfg.num_layers * cfg.num_experts_per_tok <= touched \
        <= cfg.num_layers * cfg.num_experts
    assert eng.logit_rows_sliced == 0


def test_load_survives_an_eviction_and_requeue(tiny_moe):
    """A pool of 8 blocks where 4 live sequences want 16: streams are
    evicted, prefilled again and finish; every token of every forward,
    the second prefill's among them, is in the counter once."""
    eng = _engine(tiny_moe, num_blocks=8)
    sess = ServingSession(eng, ServingPolicyConfig(
        admission="none", preempt_policy="requeue"))
    events = _drive(sess, [(u, list(range(u, u + 10)), 20)
                           for u in range(1, 5)])
    assert any(e.kind == "evict" for e in events)
    done = [e for e in events if e.kind == "finish"]
    assert len(done) == 4 and {e.reason for e in done} == {"done"}
    _check_load(eng)
    assert eng.allocator.free_blocks == eng.allocator.num_blocks
    sess.close()


def test_a_dense_model_carries_no_counter_and_an_unchanged_sampler():
    model = build_model("tiny", dtype="float32")
    eng = InferenceEngineV2(model, model.init_params(), dtype=jnp.float32,
                            block_size=8, max_context=64,
                            max_tokens_per_batch=16, max_sequences=4)
    assert eng.kv.moe is None and eng.moe_stats() is None
    assert len(jax.tree_util.tree_leaves(eng.kv)) == 2
    sess = ServingSession(eng, ServingPolicyConfig(admission="none"))
    _drive(sess, [(1, [1, 2, 3], 4)])
    rounds = [r["data"] for r in sess.drain_trace()
              if r["data"].get("stage") == "round"]
    assert rounds and all(d["moe_touched"] == 0 for d in rounds)
    sess.close()


def test_a_two_matrix_expert_is_one_function_on_both_paths():
    """``mlp_type="mlp"`` with experts (a GPT-2-era block made sparse):
    ``init_params`` draws no ``w_gate`` and both the training path's
    capacity buffers and the serving path's grouped matmuls compute
    ``act(x w_up) w_down`` with the model's own activation."""
    from deepspeedsyclsupport_tpu.parallel.moe import moe_mlp

    model = build_model("tiny-moe", mlp_type="mlp", activation="gelu",
                        capacity_factor=2.0, dtype="float32")
    cfg, params = model.config, model.init_params()
    assert set(params["layers"]["moe"]) == {"router", "w_up", "w_down"}
    p = _layer0(params)
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 12, cfg.hidden_size))
    # capacity 12 x 2.0 x 2 / 4 = 12 rows an expert: nothing is dropped
    trained, _aux = moe_mlp(p, x, cfg)
    served, _rows = moe_mlp_nodrop(p, x[0], cfg)
    np.testing.assert_allclose(np.asarray(trained[0]), np.asarray(served),
                               rtol=1e-5, atol=1e-6)
    # by hand, for one token's first choice
    probs = jax.nn.softmax(x[0] @ p["router"], axis=-1)
    w, idx = topk_weights(probs, cfg.num_experts_per_tok, cfg.norm_topk_prob)
    want = sum(w[0, j] * (jax.nn.gelu(x[0, 0] @ p["w_up"][idx[0, j]])
                          @ p["w_down"][idx[0, j]])
               for j in range(cfg.num_experts_per_tok))
    np.testing.assert_allclose(np.asarray(served[0]), np.asarray(want),
                               rtol=1e-4, atol=1e-6)
    # and the whole model's training forward runs on those leaves
    ids = jnp.arange(16, dtype=jnp.int32).reshape(1, 16)
    logits = model.apply(params, ids)[0]
    assert np.isfinite(np.asarray(logits)).all()


# ------------------------------------------------- a stream that ends early
@pytest.fixture(scope="module")
def ending(tiny_moe):
    return stream_ends.family(_engine(tiny_moe, max_context=32,
                                      num_blocks=12))


@stream_ends.parametrize
def test_a_stream_that_ends_early_gives_back_what_it_held(ending, driver,
                                                          end):
    stream_ends.check(ending, driver, end)
