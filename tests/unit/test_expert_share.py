"""One chip's share of an expert-parallel layer on the serving path
(``ModelConfig.num_experts_held`` / ``first_expert_held``): the router keeps
its width, the rows routed to experts that are not here get none, the
counters ride behind the sampled tokens; and ``group_limited_greedy``
routing against a count by hand."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeedsyclsupport_tpu.inference.v2 import (
    InferenceEngineV2, ServingPolicyConfig, ServingSession)
from deepspeedsyclsupport_tpu.models import build_model, get_config
from deepspeedsyclsupport_tpu.models.config import ModelConfig
from deepspeedsyclsupport_tpu.parallel.moe import (moe_mlp_nodrop,
                                                   topk_weights)

SHARE = dict(num_experts=8, num_experts_per_tok=2, num_experts_held=2,
             first_expert_held=4, dtype="float32")


@pytest.fixture(scope="module")
def share():
    """``tiny-moe`` with 8 experts routed, experts 4 and 5 held."""
    model = build_model("tiny-moe", **SHARE)
    return model, model.init_params()


def _engine(built, **kw):
    model, params = built
    return InferenceEngineV2(model, params, dtype=jnp.float32, **{
        "block_size": 8, "max_context": 64, "max_tokens_per_batch": 16,
        "max_sequences": 4, "prefill_attn": "xla", "decode_attn": "xla",
        **kw})


# ----------------------------------------------------------------- config
def test_the_config_says_what_is_held():
    whole = get_config("tiny-moe")
    assert whole.num_experts_held is None and whole.experts_held == 4
    # the count of experts held follows an override of the router's width
    assert get_config("tiny-moe", num_experts=8).experts_held == 8
    cfg = get_config("tiny-moe", **{k: v for k, v in SHARE.items()
                                    if k != "dtype"})
    assert (cfg.num_experts, cfg.experts_held, cfg.first_expert_held) \
        == (8, 2, 4)
    with pytest.raises(ValueError, match="held of"):
        get_config("tiny-moe", num_experts=8, num_experts_held=4,
                   first_expert_held=6)
    assert get_config("deepseek-v2", num_experts_held=40).param_count() \
        < get_config("deepseek-v2").param_count() / 3


@pytest.mark.parametrize("bad", [
    dict(n_group=3), dict(n_group=4, topk_group=5),
    dict(n_group=4, topk_group=1, num_experts_per_tok=3)])
def test_a_group_limit_that_cannot_give_k_is_refused(bad):
    with pytest.raises(ValueError, match="group_limited_greedy"):
        ModelConfig(**{"num_experts": 8, "num_experts_per_tok": 2,
                       "topk_method": "group_limited_greedy",
                       "n_group": 4, "topk_group": 2, **bad})


def test_the_tree_holds_the_share_under_the_whole_router(share):
    model, params = share
    moe = params["layers"]["moe"]
    assert moe["router"].shape[1:] == (64, 8)
    assert moe["w_gate"].shape[1:] == (2, 64, 128)
    assert moe["w_down"].shape[1:] == (2, 128, 64)
    with pytest.raises(NotImplementedError, match="serving path only"):
        model.loss(params, {"input_ids": jnp.zeros((1, 8), jnp.int32)},
                   jax.random.PRNGKey(0))


# ---------------------------------------------------------------- routing
def _by_hand(probs, k, n_group, topk_group):
    """Group-limited top-k of one token, sorted python."""
    e = len(probs)
    size = e // n_group
    best = [max(probs[g * size:(g + 1) * size]) for g in range(n_group)]
    kept = sorted(range(n_group), key=lambda g: (-best[g], g))[:topk_group]
    left = [p if i // size in kept else 0.0 for i, p in enumerate(probs)]
    return sorted(range(e), key=lambda i: (-left[i], i))[:k]


def test_group_limited_top_k_against_a_count_by_hand():
    rng = np.random.default_rng(3)
    probs = rng.dirichlet(np.ones(24), size=50).astype(np.float32)
    w, idx = topk_weights(jnp.asarray(probs), 4, False, None, 16.0, (6, 2))
    plain_w, plain = topk_weights(jnp.asarray(probs), 4, False, None, 16.0)
    differs = 0
    for t in range(50):
        want = _by_hand(probs[t].tolist(), 4, 6, 2)
        assert idx[t].tolist() == want
        np.testing.assert_allclose(w[t], 16.0 * probs[t][want], rtol=1e-6)
        differs += set(want) != set(plain[t].tolist())
    assert differs > 10          # the limit changes what most tokens take
    # no limit, one group, or every group kept: the plain top-k
    for groups in (None, (1, 1), (6, 6)):
        _, same = topk_weights(jnp.asarray(probs), 4, False, None, 16.0,
                               groups)
        assert (same == plain).all()


# -------------------------------------------------------------- the layer
def test_rows_routed_elsewhere_get_no_expert(share):
    """The share's output is the held experts' part of the dense sum; the
    rows it counts are over the router's whole width."""
    model, params = share
    cfg = model.config
    p = jax.tree_util.tree_map(lambda x: x[0], params["layers"]["moe"])
    x = jax.random.normal(jax.random.PRNGKey(2), (23, 64))
    live = jnp.arange(23) != 7
    out, routed = moe_mlp_nodrop(p, x, cfg, live)
    probs = jax.nn.softmax(x @ p["router"], -1)
    w, idx = jax.lax.top_k(probs, 2)
    w = w / w.sum(-1, keepdims=True)         # tiny-moe renormalises
    want = jnp.zeros_like(x)
    for slot, e in enumerate((4, 5)):
        g = jnp.where(idx == e, w, 0.0).sum(-1) * live
        h = jax.nn.silu(x @ p["w_gate"][slot]) * (x @ p["w_up"][slot])
        want += g[:, None] * (h @ p["w_down"][slot])
    np.testing.assert_allclose(out, want, atol=2e-6)
    assert not np.asarray(out[7]).any()
    counts = np.bincount(np.asarray(idx)[np.asarray(live)].ravel(),
                         minlength=8)
    assert routed.tolist() == counts.tolist() and routed.sum() == 22 * 2


# ------------------------------------------------------------- the engine
def test_the_counters_of_a_share_ride_behind_the_tokens(share):
    from deepspeedsyclsupport_tpu.inference.sampling import SamplingParams

    eng = _engine(share)
    assert eng.kv.moe.load.shape == (2, 8) and eng.kv.moe.rows is not None
    eng.put([1, 2], [[1, 2, 3], list(range(4, 12))])
    tail = eng.moe_tail()
    assert len(tail) == 2
    toks, (touched, rows) = eng.sample_drained(
        [2, 1], jax.random.PRNGKey(0), SamplingParams(), tail=tail)
    assert toks.shape == (2,)
    stats = eng.moe_stats()
    assert stats["held"].tolist() == [4, 5]
    assert stats["load"].sum(1).tolist() == [2 * 11, 2 * 11]
    # one forward so far: its rows are the held columns of the load
    assert rows == stats["load"][:, 4:6].sum()
    assert touched == (stats["load"][:, 4:6] > 0).sum() <= 4
    assert 0 < rows < 2 * 2 * 11


def test_the_round_record_carries_moe_rows_only_for_a_share(share):
    eng = _engine(share)
    sess = ServingSession(eng, ServingPolicyConfig(admission="none"))
    for uid, prompt, budget in ((1, [1, 2, 3], 9), (2, list(range(20)), 6)):
        assert sess.submit(uid, prompt, budget) == "admitted"
    while not sess.idle:
        sess.step()
    rounds = [r["data"] for r in sess.drain_trace()
              if r["data"].get("stage") == "round"]
    counted = [d for d in rounds if "moe_rows" in d]
    assert len(counted) >= 8
    assert all(d["moe_touched"] <= min(d["moe_rows"], 4) for d in counted)
    total = sum(d["moe_rows"] for d in counted)
    assert total == eng.moe_stats()["load"][:, 4:6].sum()
    sess.close()
    # a model that holds every expert writes no such field
    model = build_model("tiny-moe", dtype="float32")
    whole = InferenceEngineV2(model, model.init_params(), dtype=jnp.float32,
                              block_size=8, max_context=64,
                              max_tokens_per_batch=16, max_sequences=4,
                              prefill_attn="xla", decode_attn="xla")
    assert whole.kv.moe.rows is None and len(whole.moe_tail()) == 1
    assert "held" not in whole.moe_stats()
    sess = ServingSession(whole, ServingPolicyConfig(admission="none"))
    assert sess.submit(1, [1, 2, 3], 4) == "admitted"
    while not sess.idle:
        sess.step()
    assert not any("moe_rows" in r["data"] for r in sess.drain_trace())
    sess.close()


def test_the_round_report_moves_both_counts_back_onto_their_launch():
    """``reqtrace.round_phases``: what the device counted rides on the NEXT
    record; the report gives each launch its own, and prints ``moe_rows``
    only where a program wrote it."""
    from deepspeedsyclsupport_tpu.monitor import reqtrace

    def record(i, touched=None, rows=None):
        data = {"stage": "round", "round": i, "t0": float(i),
                "t1": i + 0.5, "launch_t": i + 0.1,
                "program": "decode_forward", "tokens": 4, "phases": {}}
        if touched is not None:
            data.update(moe_touched=touched, moe_rows=rows)
        return {"name": "serve/stage", "data": data}

    share = [record(0), record(1, 3, 10), record(2, 4, 20)]
    got = reqtrace.round_phases([("r", "a", share)])["programs"][
        "decode_forward"]
    assert got["moe_touched"] == pytest.approx((3 + 4 + 0) / 3)
    assert got["moe_rows"] == pytest.approx((10 + 20 + 0) / 3)
    whole = [{"name": "serve/stage", "data": {
        **r["data"], "moe_touched": 2}} for r in (record(0), record(1))]
    got = reqtrace.round_phases([("r", "a", whole)])["programs"][
        "decode_forward"]
    assert got["moe_touched"] == 1.0 and "moe_rows" not in got
