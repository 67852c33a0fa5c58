"""``model_type: xing4_0`` at tiny widths, float32, on the CPU: the served
path (chunked prefill, then decode through the LATENT pool, absorbed
attention) against ``benchmark/families/xing4_0.py`` (expanded attention, no
cache) on seeded weights, through the XLA attention and through both Pallas
kernels interpreted; absorbed against expanded attention on one layer's
weights; misreadings of the architecture that the tolerance must refuse; and
a tiny cell that reports what ``xing4-docs-sat`` reports."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import parity, spec

TINY_XING4 = {
    "model_type": "xing4_0", "hidden_size": 64, "intermediate_size": 96,
    "moe_intermediate_size": 32, "num_hidden_layers": 3,
    "first_k_dense_replace": 1, "num_attention_heads": 4, "vocab_size": 512,
    "q_lora_rank": 24, "kv_lora_rank": 32, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 16, "n_routed_experts": 8,
    "num_experts_per_tok": 3, "n_shared_experts": 1, "norm_topk_prob": True,
    "scoring_func": "sigmoid", "topk_method": "noaux_tc",
    "routed_scaling_factor": 2, "hc_mult": 4, "hc_sinkhorn_iters": 20,
    "hc_eps": 1e-06, "mhc_h_res_clamp_min": -30, "mhc_h_res_clamp_max": 30,
    "rms_norm_eps": 1e-06, "rope_theta": 10000,
    # 16 original positions: the test's 47 pass them, as the cell's pass 4096
    "rope_scaling": {"type": "yarn", "factor": 64, "beta_fast": 32,
                     "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 16},
    "preset": "xing4-29b-a4b"}
ENGINE = {"max_context": 128, "max_sequences": 4, "num_blocks": 32,
          "block_size": 16, "max_tokens_per_batch": 16,
          "prefill_attn": "xla", "decode_attn": "xla"}
# Both sides are float32. They differ in the order of summation and in the
# FORM of attention: the program scores q_nope W_UK against the cached
# latent and takes the attended latent up through W_UV, the reference
# expands k and v per head. Measured 1.1e-5 logit-std served (XLA attention
# and interpreted kernels alike), 1.9e-6 one attention layer; the wrong
# programs below measure 0.02 to 1.6, so 1e-4 is nine times what rounding
# gives and two to four orders under what a misreading gives.
TOL = 1e-4
PROMPTS = ([7, 3, 11, 200, 41, 9, 5], list(range(100, 141)))   # 7 and 41


@pytest.fixture(scope="module")
def family():
    return spec.Bench().family(TINY_XING4)


def overrides(family):
    return {**family.program_widths(TINY_XING4), "head_dim": 24,
            "max_seq_len": 256, "dtype": "float32"}


@pytest.fixture(scope="module")
def built(family):
    """The model and seeded weights with EVERY leaf moved off its init: norm
    scales start at one, and where a norm sits would not matter."""
    from deepspeedsyclsupport_tpu.models import build_model

    model = build_model("xing4-29b-a4b", **overrides(family))
    model.seed = 3
    leaves, tree = jax.tree_util.tree_flatten(model.init_params())
    keys = jax.random.split(jax.random.PRNGKey(1), len(leaves))
    params = jax.tree_util.tree_unflatten(tree, [
        x + 0.2 * jax.random.normal(k, x.shape)
        for x, k in zip(leaves, keys)])
    return model, params


def served_errors(built, family, **engine):
    """Worst row error of the served path over two requests, one shorter
    and one longer than ``max_tokens_per_batch`` (3 chunks), 6 decode steps
    each, against the reference's forward of the whole sequence."""
    import deepspeedsyclsupport_tpu as dstpu
    from deepspeedsyclsupport_tpu.inference.v2.engine_v2 import (
        InferenceEngineV2)

    model, params = built
    eng = InferenceEngineV2(
        model, params, dtype="float32",
        topology=dstpu.build_topology(dp=1, devices=jax.devices()[:1]),
        **{**ENGINE, **engine})
    arch = family.arch(TINY_XING4)
    worst = 0.0
    for uid, prompt in enumerate(PROMPTS):
        logits, tokens = parity.served_logits(eng, uid, prompt, 6)
        want = family.sequence_logits(
            arch, params, jnp.asarray(prompt + tokens, jnp.int32))
        worst = max(worst, float(parity.row_errors(
            logits, np.asarray(want)[-len(logits):]).max()))
    return worst


def test_the_preset_has_the_published_widths(family):
    """The catalog's ``config`` for Xing4.0-29B-A4B, key for key."""
    from deepspeedsyclsupport_tpu.models import get_config

    published = {k: v for k, v in spec.Bench().config(
        "xing4-29b-a4b-d6").items() if k not in ("reduced", "assumed")}
    published.update(num_hidden_layers=40)
    assert (published["hidden_size"], published["kv_lora_rank"],
            published["n_routed_experts"], published["hc_mult"]) == (
        3584, 512, 64, 4)
    cfg = get_config("xing4-29b-a4b")
    want = family.program_widths(published)
    assert {k: getattr(cfg, k) for k in want} == want
    assert cfg.max_seq_len == 262144 and cfg.rms_norm_eps == 1e-6
    assert not cfg.tie_embeddings and cfg.activation == "silu"
    a = family.arch(published)
    # what moe_roofline's expert_work reads is ONE routed expert's width
    assert a["intermediate_size"] == 1024
    assert a["dense_intermediate_size"] == 9216
    assert a["softmax_scale"] == pytest.approx(cfg.softmax_scale)
    # "A4B": a token meets ~4 B weights in products (3.9 B; 29.5 B in all)
    assert family.matmul_params(a) == pytest.approx(3.9e9, rel=0.03)


def test_the_configuration_departs_from_the_source_only_where_it_says():
    """Every key of the catalog's ``config`` is in the file under the same
    name with the same value, but for what stands under ``reduced``: the
    depth, and the multi-token-prediction block, which the trunk's forward
    leaves out and no weight instantiates."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    try:
        rows = [json.loads(ln) for ln in open(catalog)]
    except OSError:
        pytest.skip("the catalog is not on this machine")
    row = next(r for r in rows if r["name"] == "Xing4.0-29B-A4B")
    cfg = spec.Bench().config("xing4-29b-a4b-d6")
    assert cfg["source"] == row["source_url"]
    differs = {k for k, v in row["config"].items() if cfg.get(k) != v}
    assert differs == set(cfg["reduced"]) == {"num_hidden_layers",
                                              "num_nextn_predict_layers"}
    for k, r in cfg["reduced"].items():
        assert (r["published"], r["run"]) == (row["config"][k], cfg[k])
        assert r["counts"] == "layers"
    assert cfg["num_nextn_predict_layers"] == 0
    # both leading dense layers stay whole
    assert cfg["first_k_dense_replace"] == row["config"][
        "first_k_dense_replace"] == 2
    assert cfg["overrides"] == {"num_layers": 6}


@pytest.mark.parametrize("attn", ["xla", "kernels_interpreted"])
def test_served_prefill_chunks_then_decode_match_the_reference(
        built, family, attn):
    engine = {} if attn == "xla" else {
        "prefill_attn": "kernel_interpret", "decode_attn": "pallas_interpret",
        "atom_q_size": 8}
    assert served_errors(built, family, **engine) < TOL


def test_absorbed_attention_matches_expanded_on_the_same_weights(
        built, family):
    """One layer's attention alone: the program's absorbed queries against
    the rows it would cache, softmax, ``W_UV`` and ``W_o``, held against the
    reference's expanded attention of the same sequence."""
    from deepspeedsyclsupport_tpu.inference.v2 import model as M

    model, params = built
    cfg = model.config
    p = jax.tree_util.tree_map(lambda x: x[1], params["layers"]["attn"])
    x = jax.random.normal(jax.random.PRNGKey(5), (37, cfg.hidden_size))
    pos = jnp.arange(37)
    q, (rows,) = M._mla_rows(p, x, cfg, pos)
    assert q.shape == (37, 4, 40) and rows.shape == (37, 40)
    scores = jnp.einsum("thd,cd->thc", q, rows) / np.sqrt(rows.shape[-1])
    scores = jnp.where((pos[:, None] >= pos[None, :])[:, None, :], scores,
                       -jnp.inf)
    latent = jnp.einsum("thc,cr->thr", jax.nn.softmax(scores, -1),
                        rows[:, :cfg.kv_lora_rank])
    got = M._mla_out(p, latent, cfg, 37)
    with jax.default_matmul_precision("highest"):
        want = family.attention(family.arch(TINY_XING4), p, x)
    err = np.abs(np.asarray(got - want)).max() / np.asarray(want).std()
    assert err < TOL, err


def _wrong(family, monkeypatch, what):
    """A plausible misreading, put on the reference's side (the served path
    is right), or for the routed scale into the program's own config."""
    if what in ("bias_in_the_weights", "softmax_router"):
        def router(a, p, x):
            k = a["num_experts_per_tok"]
            logits = x @ p["router"]
            if what == "softmax_router":
                w_of = jax.nn.softmax(logits, -1)
                pick = w_of + p["router_bias"]
            else:                       # the bias weighs, not only picks
                w_of = pick = jax.nn.sigmoid(logits) + p["router_bias"]
            _, idx = jax.lax.top_k(pick, k)
            w = jnp.take_along_axis(w_of, idx, -1)
            w = w / w.sum(-1, keepdims=True) * a["routed_scaling_factor"]
            return (jax.nn.one_hot(idx, a["num_experts"])
                    * w[..., None]).sum(1), jnp.ones(x.shape[0])
        monkeypatch.setattr(family, "router", router)
    elif what == "rows_only":           # a softmax over each row, no Sinkhorn
        real_maps = family.hc_maps

        def hc_maps(a, hc, X):
            pre, post, res = real_maps(
                {**a, "hc_sinkhorn_iters": 0}, hc, X)
            return pre, post, res / res.sum(2, keepdims=True)
        monkeypatch.setattr(family, "hc_maps", hc_maps)
    elif what == "one_map_for_both_sublayers":
        real_block = family.block
        monkeypatch.setattr(family, "block", lambda a, p, X, *rest: real_block(
            a, {**p, "hc_mlp": p["hc_attn"]}, X, *rest))
    elif what == "plain_rotary":            # no YaRN blend
        real_arch = family.arch
        monkeypatch.setattr(family, "arch", lambda hf: {
            **real_arch(hf), "rope_scaling": {
                **hf["rope_scaling"], "factor": 1}})
    elif what == "scale_without_mscale":
        real_arch = family.arch
        monkeypatch.setattr(family, "arch", lambda hf: {
            **real_arch(hf), "softmax_scale": 24 ** -0.5})
    else:
        raise KeyError(what)


@pytest.mark.parametrize("wrong", [
    "bias_in_the_weights", "softmax_router", "rows_only",
    "one_map_for_both_sublayers", "plain_rotary", "scale_without_mscale"])
def test_a_wrong_program_fails_the_tolerance(built, family, monkeypatch,
                                             wrong):
    _wrong(family, monkeypatch, wrong)
    assert served_errors(built, family) > 100 * TOL


# ------------------------------------------------- the cell's own readers
def test_the_benchmark_is_sound_with_the_new_entries():
    bench = spec.Bench()
    assert bench.problems() == []
    cell = bench.cell("xing4-docs-sat")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "xing4-29b-a4b-d6", "docs-8k-sat", 1)
    reports = {m["name"] for section in ("end_to_end", "per_layer")
               for m in bench.metrics_of("xing4-docs-sat", section)}
    assert {"itl_p95_ms", "setup_s", "serve_tok_s.p95", "mla_share_pct",
            "mla_prefill_roofline", "mla_decode_roofline", "mhc_share_pct",
            "kv_bytes_per_token", "moe_p95_roofline",
            "expert_load_max_over_mean.p95",
            "ragged_tile_fill_pct.p95"} <= reports
    # tokens/s swing too widely here to be judged (PERF.md section 2): the
    # cell's end-to-end metric is the tail of the gaps, and every per-layer
    # metric it reports moves that one
    assert "serve_tok_s" not in reports
    for m in bench.metrics_of("xing4-docs-sat", "per_layer"):
        if "workloads" in m:     # without the key: every cell's, setup_s
            assert "xing4-docs-sat" in m["workloads"]
            assert m["moves"] == "itl_p95_ms"


def test_the_mix_is_the_issues_grid():
    from benchmark import traffic

    mix = spec.Bench().traffic("docs-8k-sat")
    pairs = traffic.length_pairs(mix, mix["count"])
    prompts = sorted(p for p, _ in pairs)
    assert (mix["kind"], mix["clients"], len(pairs)) == ("closed", 16, 64)
    assert 2048 <= prompts[0] < 2600 and 11500 < prompts[-1] <= 12288
    assert 6000 < sum(prompts) / 64 < 6700
    assert {o for _, o in pairs} <= set(range(64, 193))
    cfg = spec.Bench().config("xing4-29b-a4b-d6")["engine"]
    # the longest pairing fits a context; 16 of the longest fit the pool
    assert max(p + o for p, o in pairs) <= cfg["max_context"]
    assert 16 * -(-max(p + o for p, o in pairs) // cfg["block_size"]) \
        <= cfg["num_blocks"]


@pytest.fixture(scope="module")
def tiny_cell(tmp_path_factory, family):
    """A tiny ``xing4_0`` cell beside ``tests/benchmark/tiny.py``'s,
    reporting what ``xing4-docs-sat`` reports, driven once on the CPU."""
    from . import tiny

    root = tmp_path_factory.mktemp("bench")
    bench = tiny.make_root(root)
    doc = bench.doc
    cfg = {**TINY_XING4, "source": "tests", "path": "serve",
           "overrides": {k: v for k, v in overrides(family).items()
                         if k != "dtype"},
           "dtype": "float32",
           "engine": {**ENGINE, "max_tokens_per_batch": 32},
           "policy": {"admission": "none"}}
    (root / "extra" / "configs" / "tiny-xing4.json").write_text(
        json.dumps(cfg))
    doc["configs"].append({"name": "tiny-xing4", "source": "tests",
                           "reduced": [], "why": "tiny",
                           "file": "extra/configs/tiny-xing4.json"})
    doc["workloads"].append({"name": "tiny-xing4-cell", "chips": 1,
                             "config": "tiny-xing4", "why": "tiny",
                             "traffic": "tiny-closed"})
    for m in doc["end_to_end"] + doc["per_layer"]:
        if "xing4-docs-sat" in m.get("workloads", ()):
            m["workloads"].append("tiny-xing4-cell")
    (root / "BENCHMARK.json").write_text(json.dumps(doc))
    bench = spec.Bench(root)
    assert bench.problems() == []
    return tiny.drive(bench, "tiny-xing4-cell", seed=2**31 + 13)


def test_the_cell_runs_is_checked_and_counts_its_pool(tiny_cell):
    obs, m = tiny_cell
    assert obs["correct"] and obs["failed"] == 0 and obs["attempted"] > 4
    assert m["serve_tok_s.p95"] > 0 and m["live_seqs_mean.p95"] > 1
    assert m["itl_p99_ms.p95"] >= m["itl_p95_ms"] > 0
    eng = obs["engine"]
    # three layers of one 40-wide float32 row (no lane padding off the TPU)
    assert eng.kv.v is None and m["kv_bytes_per_token"] == 3 * 40 * 4
    assert 1.0 <= m["expert_load_max_over_mean.p95"] < 4.0
    stats = eng.moe_stats()
    assert stats["load"].shape == (2, 8)
    assert (stats["load"].sum(1) == 3 * stats["live_tokens"]).all()
    assert eng.allocator.free_blocks == eng.allocator.num_blocks


def test_the_records_carry_what_the_rooflines_read(tiny_cell):
    from benchmark import spans

    obs, _m = tiny_cell
    records = spans.window_records(obs)
    assert records and all(
        "attn_pairs" in d and "dec_ctx_tokens" in d for d in records)
    assert any(d["attn_pairs"] for d in records)
    for d in records:
        if d["program"] == "decode_forward":
            # every row a one-token chunk: its context with its own token
            assert d["attn_pairs"] == 0
            assert d["dec_ctx_tokens"] == d["ctx_tokens"] + d["n_seqs"]


@pytest.mark.parametrize("name", ["mla_share_pct", "mla_prefill_roofline",
                                  "mla_decode_roofline", "mhc_share_pct"])
def test_a_trace_reader_reads_nothing_without_a_trace(tiny_cell, name):
    """No trace (the CPU), and a program's record without the fields (the
    parent): nothing to read, nothing raised."""
    obs, m = tiny_cell
    assert name not in m
    assert spec.Bench().reader(name)(obs) is None
    assert spec.Bench().reader(name)(
        {**obs, "stages": [], "engine": None}) is None


def test_the_rooflines_count_by_hand():
    """One forward at the cell's widths. Prefill: a pair meets 32 heads x 2
    x (576 + 512) FLOPs a layer; its bytes are the floor of pairs / 768 rows
    of 1,280 B. Decode: six calls each read every one-token row's context."""
    bench = spec.Bench()
    cfg = bench.config("xing4-29b-a4b-d6")
    arch = bench.family(cfg).arch(cfg)
    prefill = bench._module("metrics", "mla_prefill_roofline").prefill_work
    fl, by = prefill(arch, attn_pairs=768 * 4000, max_chunk=768,
                     row_bytes=1280)
    assert fl == 6 * 768 * 4000 * 32 * 2 * 1088 == 6 * 768 * 4000 * 69632
    assert by == 6 * 4000 * 1280
    decode = bench._module("metrics", "mla_decode_roofline").decode_bytes
    assert decode(calls=6, dec_ctx_tokens=12 * 6000, row_bytes=1280) \
        == 6 * 72000 * 1280
    kv = bench.reader("kv_bytes_per_token")
    import types
    pool = np.zeros((6, 128, 640), jnp.bfloat16)
    assert kv({"engine": types.SimpleNamespace(kv=types.SimpleNamespace(
        k=pool, v=None))}) == 7680
    assert kv({"engine": types.SimpleNamespace(kv=types.SimpleNamespace(
        k=np.zeros((6, 128, 32, 128), jnp.bfloat16),
        v=np.zeros((6, 128, 32, 128), jnp.bfloat16)))}) == 98304
