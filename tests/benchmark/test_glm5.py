"""The ``glm_moe_dsa`` configuration (GLM-5), its cell and what the
accepted readers read of it: the file against the catalog row and the
program's preset; why the vocabulary is whole; the family's counts by hand;
every share of a tiny deployment adding up to the uncut layer; the
selection's three ``work`` functions counted for a LATENT pool, on
``test_keye``'s hand-made trace."""
import copy
import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import spec
from tests.benchmark import test_keye as keye
from tests.test_glm5 import HF, TOPK

CELL, CONFIG, MIX = "glm5-docs-sat", "glm-5-ep16-d5", "docs-16k-sat"
# the accepted entries the cell joins (ISSUE 65, Tentpole 7)
JOINED = ["select_share_pct", "select_pick_share_pct",
          "select_prefill_roofline", "select_decode_roofline",
          "select_score_roofline", "dsa_kept_pct", "index_bytes_per_token",
          "kv_bytes_per_token", "mla_share_pct", "moe_share_pct.p95",
          "moe_p95_roofline", "expert_load_max_over_mean.p95",
          "round_p50_ms.p95", "ragged_fwd_ms.p95", "decode_fwd_ms.p95",
          "serve_tok_s.p95", "live_seqs_mean.p95"]
# ... and those it must NOT: they reckon every cached latent read, and under
# a selection the kernel reads 2,048 of them (a share over 100 % is refused)
NOT_JOINED = ["mla_prefill_roofline", "mla_decode_roofline",
              "mla_decode_mxu_roofline", "ragged_row_fill_pct"]
V5E = keye.V5E


@pytest.fixture(scope="module")
def family():
    return spec.Bench().family(HF)


def _catalog_row():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    try:
        rows = [json.loads(ln) for ln in open(catalog)]
    except OSError:
        pytest.skip("the catalog is not on this machine")
    return next(r for r in rows if r["name"] == "GLM-5")


# ------------------------------------------------- the file and the preset
def test_the_configuration_is_the_source_but_for_what_reduced_lists():
    row = _catalog_row()
    cfg = spec.Bench().config(CONFIG)
    assert cfg["source"] == row["source_url"]
    assert cfg["model_type"] == "glm_moe_dsa"
    cuts = {"first_k_dense_replace": (3, 1, "layers"),
            "n_routed_experts": (256, 16, "experts"),
            "num_hidden_layers": (78, 5, "layers"),
            "num_nextn_predict_layers": (1, 0, "layers")}
    assert {k: (c["published"], c["run"], c["counts"])
            for k, c in cfg["reduced"].items()} == cuts
    assert all(c["why"] for c in cfg["reduced"].values())
    for key, value in row["config"].items():
        assert cfg[key] == (cuts[key][1] if key in cuts else value), key
    assert cfg["layer_shared_by"] == 16 and 16 * 16 == 256
    assert set(cfg["assumed"]) >= {
        "indexer_key_norm", "indexer_rotary", "rope_layout", "indexer_score",
        "selection", "text_only", "weights", "dtype", "kv_pool",
        "attention_form"}
    assert cfg["engine"] == {
        "max_context": 24576, "max_sequences": 16, "num_blocks": 6272,
        "block_size": 64, "max_tokens_per_batch": 768,
        "prefill_attn": "kernel", "decode_attn": "pallas"}
    assert cfg["policy"] == {"admission": "none", "preempt_policy": "requeue"}
    assert (cfg["path"], cfg["dtype"], cfg["overrides"]) == (
        "serve", "bfloat16", {"num_layers": 5, "first_k_dense_replace": 1,
                              "num_experts_held": 16})
    assert cfg["deployment"] and cfg["policy_why"] and cfg["arithmetic"]


def test_the_vocabulary_is_whole_because_a_sixteenth_is_refused():
    """``reduced`` as it stands is sound; a copy that slices the vocabulary
    is refused either way: an eighth is no sixteenth of a 16-way share, and
    a sixteenth is under the floor."""
    cfg = spec.Bench().config(CONFIG)
    assert spec.reduced_problems(cfg) == []
    for part in (8, 16):
        cut = copy.deepcopy(cfg)
        cut["vocab_size"] = 154880 // part
        cut["reduced"]["vocab_size"] = {
            "published": 154880, "run": 154880 // part,
            "counts": "vocabulary", "why": "a share of the rows"}
        wrong = spec.reduced_problems(cut)
        assert wrong and all("vocab_size" in w for w in wrong), wrong
    # ... and the three leading dense layers cannot all stay at this depth
    deep = copy.deepcopy(cfg)
    deep["first_k_dense_replace"] = 3
    del deep["reduced"]["first_k_dense_replace"]
    assert any("leading dense" in w for w in spec.reduced_problems(deep))


def test_the_preset_has_the_published_widths(family):
    from deepspeedsyclsupport_tpu.models import get_config

    whole = get_config("glm-5")
    want = family.program_widths(_catalog_row()["config"])
    assert {k: getattr(whole, k) for k in want} == want
    cfg = spec.Bench().config(CONFIG)
    cut = get_config("glm-5", **cfg["overrides"])
    want = family.program_widths(cfg)
    assert {k: getattr(cut, k) for k in want} == want
    assert (whole.latent_kv_dim, whole.index_topk, whole.index_heads,
            whole.index_head_dim, whole.index_rope_dim,
            whole.index_q_latent) == (576, 2048, 32, 128, 64, True)
    assert whole.param_count() / 1e9 == pytest.approx(743.9, abs=0.1)
    # ISSUE 65's arithmetic: 10.38 GiB of weights here
    assert cut.param_count() * 2 / 2**30 == pytest.approx(10.38, abs=0.01)
    # the pool: 5 x (one latent row of 640 lanes + one indexer key of 128)
    engine = cfg["engine"]
    slots = engine["num_blocks"] * engine["block_size"]
    per_token = 5 * (640 + cut.index_head_dim) * 2
    assert (per_token, slots) == (7680, 401408)
    assert per_token * slots / 2**30 == pytest.approx(2.87, abs=0.01)


def test_the_familys_counts_against_a_hand_count(family):
    a = family.arch(HF)
    assert (a["num_experts"], a["experts_held"], a["intermediate_size"],
            a["index_topk"], a["num_dense_layers"],
            a["index_rope_dim"]) == (8, 4, 32, TOPK, 1, 8)
    attn = 64 * 24 + 24 * 4 * 20 + 64 * 24 + 16 * 4 * 28 + 4 * 16 * 64
    index = 24 * 2 * 16 + 64 * (16 + 2)
    assert family.attention_params(a) == (attn, index)
    moe = 64 * 8 + 3 * 64 * 32 * (3 + 1)
    assert family.matmul_params(a) == 3 * (attn + index) + 3 * 64 * 96 \
        + 2 * moe + 64 * 128
    # 20 positions: the first 8 see 1..8 keys, the other 12 see topk = 8
    pairs, scored = 8 * 9 // 2 + 12 * 8, 20 * 21 // 2
    assert family.train_flops_per_token(a, 20) == 6 * family.matmul_params(
        a) + 3 * 2 * (20 + 16) * 4 * 3 * pairs / 20 \
        + 3 * 2 * 2 * 16 * 3 * scored / 20
    # what the rooflines count, at the CELL's widths: a selected token is
    # ONE row of 1,280 B a layer for all 64 heads, a pair 64 x 2 x (576 +
    # 512) FLOPs, an indexer pair 32 x 128 x 2, an indexer key 256 B
    whole = family.arch(spec.Bench().config(CONFIG))
    assert family.latent_row_bytes(whole) == 1280
    assert family.pair_flops(whole) == 64 * 2 * (576 + 512)
    assert family.selected_attention_work(whole, 1000, 50) == (
        5 * 1000 * 64 * 2 * 1088, 5 * 50 * 1280)
    assert family.selected_rows_work(whole, 2048) == (
        5 * 2048 * 64 * 2 * 1088, 5 * 2048 * 1280)
    assert family.index_work(whole, 1000, 40000) == (
        5 * 1000 * 32 * 128 * 2, 5 * 40000 * 256)
    # one gathered row serves 64 heads: 109 FLOP a byte, under the v5e's
    # ridge of 240, so the rows' floor is still the bytes' on this chip
    ops, nbytes = family.selected_rows_work(whole, 2048)
    assert ops / nbytes == pytest.approx(108.8, abs=0.1)


def test_every_share_adds_up_to_the_uncut_layer(family):
    """One expert layer over 8 experts under its 8-wide sigmoid router with
    a selection bias, cut four ways: the routed parts the four shares give
    (two experts each, through the PROGRAM's layer told which experts it
    holds), with the shared expert counted ONCE, are the reference's uncut
    layer, renormalised over all 3 chosen and x 2.5 whatever is held; each
    share alone is the reference's share."""
    from deepspeedsyclsupport_tpu.models import get_config
    from deepspeedsyclsupport_tpu.parallel.moe import moe_mlp_nodrop
    from tests.test_glm5 import overrides

    cfg = get_config("glm-5", **{**overrides(family), "num_experts": 8,
                                 "num_experts_held": 2})
    d, fe, e = 64, 32, 8
    # (drawn on the host: a draw a leaf on the device is a program a shape)
    rng = np.random.default_rng(7)
    draw = lambda *shape, by=0.2: jnp.asarray(  # noqa: E731
        by * rng.standard_normal(shape), jnp.float32)
    glu = lambda *lead: {"w_gate": draw(*lead, d, fe),  # noqa: E731
                         "w_up": draw(*lead, d, fe),
                         "w_down": draw(*lead, fe, d)}
    whole = {"router": draw(d, e, by=0.3), "router_bias": draw(e, by=0.3),
             **glu(e), "shared": glu()}
    x = draw(37, d, by=1.0)
    live = jnp.arange(37) < 33                      # four pad rows
    part = lambda lo, hi: {  # noqa: E731
        "router": whole["router"], "router_bias": whole["router_bias"],
        "shared": whole["shared"],
        **{k: whole[k][lo:hi] for k in family.EXPERT_LEAVES}}
    a = {**family.arch(HF), "num_experts": e, "experts_held": e}
    with jax.default_matmul_precision("highest"):
        # (jitted: op by op under "highest" this test took 9.5 s)
        (want, _), shared, (gates, _) = jax.jit(lambda p, x: (
            family.experts(a, p, x), family.swiglu(p["shared"], x),
            family.router(a, p, x)))(part(0, e), x)
        routed = jnp.zeros_like(x)
        for first in range(0, e, 2):
            share = dataclasses.replace(cfg, first_expert_held=first)
            got, rows = jax.jit(lambda p, x, share=share: moe_mlp_nodrop(
                p, x, share, live))(part(first, first + 2), x)
            alone, _ = jax.jit(lambda p, x, first=first: family.experts(
                {**a, "first_expert_held": first}, p, x))(
                    part(first, first + 2), x)
            assert np.abs(np.asarray(got - alone))[:33].max() \
                < 1e-4 * float(jnp.std(want))
            assert rows.shape == (e,) and int(rows.sum()) == 33 * 3
            routed += got - shared       # every chip computes it alike
    err = np.abs(np.asarray(routed + shared - want))[:33].max() \
        / float(jnp.std(want))
    assert err < 1e-4, err
    np.testing.assert_allclose(gates.sum(-1), 2.5, atol=1e-5)
    assert ((gates > 0).sum(-1) == 3).all()


def test_index_gaps_are_the_topk_th_scores_margin(family):
    from deepspeedsyclsupport_tpu.models import build_model
    from tests.test_glm5 import overrides

    model = build_model("glm-5", **overrides(family))
    params = jax.jit(model.init_params)(jax.random.PRNGKey(2))
    ids = np.random.default_rng(0).integers(0, 128, 30).astype(np.int32)
    a = family.arch(HF)
    gaps, routed = jax.jit(lambda p, i: (
        family.index_gaps(a, p, i), family.router_gaps(a, p, i)))(params, ids)
    gaps = np.asarray(gaps)
    assert gaps.shape == (3, 30)
    assert (gaps[:, :TOPK] == 1.0).all()        # no more than topk seen
    assert (gaps[:, TOPK:] >= 0).all() and np.isfinite(gaps).all()
    assert (gaps[:, TOPK:] > 0).mean() > 0.5
    # the expert layers alone route
    assert routed.shape == (2, 30)


def test_a_long_sequences_logits_are_its_last_rows(family, monkeypatch):
    """Past ``DENSE_BYTES`` the reference unembeds the last ``TAIL_ROWS``
    rows alone: what the harness reads of it (``greedy_margins``' rows
    before the emitted tokens, ``parity``'s last rows inside its ``jit`` and
    ``np.asarray(...)[-n:]`` outside) is the dense answer's rows, and a row
    before the tail raises."""
    from deepspeedsyclsupport_tpu.models import build_model
    from tests.test_glm5 import overrides

    model = build_model("glm-5", **overrides(family))
    params = jax.jit(model.init_params)(jax.random.PRNGKey(4))
    ids = np.random.default_rng(1).integers(0, 128, 20).astype(np.int32)
    a = family.arch(HF)
    dense = np.asarray(jax.jit(
        lambda p, i: family.sequence_logits(a, p, i))(params, ids))
    assert dense.shape == (20, 128)
    monkeypatch.setattr(family, "DENSE_BYTES", 1024)
    monkeypatch.setattr(family, "TAIL_ROWS", 6)
    tail = jax.jit(lambda p, i: family.sequence_logits(a, p, i))(params, ids)
    assert isinstance(tail, family.TailLogits)
    assert (len(tail), tail.shape, tail.tail.shape) == (20, (20, 128),
                                                        (6, 128))
    np.testing.assert_allclose(tail[16:-1], dense[16:-1], atol=1e-5)
    np.testing.assert_allclose(np.asarray(tail, np.float32)[-4:], dense[-4:],
                               atol=1e-5)
    inside = jax.jit(
        lambda p, i: family.sequence_logits(a, p, i)[-5:])(params, ids)
    np.testing.assert_allclose(inside, dense[-5:], atol=1e-5)
    with pytest.raises(IndexError, match="only the last 6 rows"):
        tail[10:]


# ------------------------------------------------------------ the benchmark
def test_the_benchmark_is_sound_with_the_new_entries():
    bench = spec.Bench()
    assert bench.problems() == []
    cell = bench.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, MIX, 1)
    assert len(cell["why"]) <= 200
    assert bench._entry("configs", CONFIG)["reduced"] == [
        "first_k_dense_replace", "n_routed_experts", "num_hidden_layers",
        "num_nextn_predict_layers"]
    assert len(bench.doc["workloads"]) >= 16 and len(
        bench.doc["configs"]) >= 15
    e2e = {m["name"] for m in bench.metrics_of(CELL, "end_to_end")}
    assert e2e >= {"itl_p95_ms", "setup_s"}
    reports = {m["name"] for m in bench.metrics_of(CELL, "per_layer")}
    # supersets: an entry appended later breaks nothing here
    assert reports >= {"start_to_chip_s", "setup_cache_miss_programs",
                       *JOINED}
    assert not reports & set(NOT_JOINED)
    for m in bench.doc["per_layer"]:
        if m["name"] in JOINED:
            assert CELL in m["workloads"] and m["moves"] == "itl_p95_ms"
    from benchmark.reference import layer_kind

    kind = layer_kind(bench.family(bench.config(CONFIG)), "selection")
    assert set(kind["roles"]) == {"score", "select", "attend"}
    assert all(callable(kind[part]["work"])
               for part in ("score", "prefill", "rows"))
    assert kind["rows"]["kernels"] == ()       # the gather is XLA's


def test_the_mix_is_the_issues_grid_and_fits_the_pool():
    from benchmark import traffic

    bench = spec.Bench()
    mix, cfg = bench.traffic(MIX), bench.config(CONFIG)["engine"]
    pairs = traffic.length_pairs(mix, mix["count"])
    assert (mix["kind"], mix["clients"], mix["order"]) == (
        "closed", 16, "lanes")
    assert mix["prompt_len"] == {"dist": "uniform", "min": 8192,
                                 "max": 24064}
    assert mix["output_len"] == {"dist": "uniform", "min": 128, "max": 512}
    assert mix["clients"] == cfg["max_sequences"] == mix["count"]
    assert [p for p, _ in pairs] == list(range(8688, 24064, 992))
    assert sorted(o for _, o in pairs) == list(range(140, 512, 24))
    # the lanes hold the grid: 16 x (16,128 + 320) tokens in flight
    assert sum(p + o for p, o in pairs) == 16 * (16128 + 320)
    assert 24064 + 512 == cfg["max_context"]
    # 16 callers on the longest pair there can be: 6,144 of 6,272 blocks
    assert 16 * -(-cfg["max_context"] // cfg["block_size"]) == 6144 \
        <= cfg["num_blocks"]
    # every context is 4 to 12 times the indexer's topk
    assert min(p for p, _ in pairs) >= 4 * 2048
    assert max(p + o for p, o in pairs) <= 12 * 2048


# ------------------------- the accepted readers, through this family's work
def _traced_obs(family):
    """``test_keye``'s hand-made trace (two mixed rounds of a 768-row chunk
    at 40 k beside 7 one-token rows, and a decode step of 8 rows, the same
    labels and kernels) under THIS family and configuration."""
    obs = keye.traced_obs(family)
    obs["config"] = spec.Bench().config(CONFIG)
    return obs


def test_the_selects_rooflines_count_a_latent_pool(family):
    bench = spec.Bench()
    obs = _traced_obs(family)
    pairs = 768 * 40000 + 768 * 769 // 2
    # a chunk's selected pairs: every head's two products over the row
    ideal = 5 * 768 * 2048 * 64 * 2 * 1088 / 197e12
    assert 5 * (pairs / 768) * 1280 / 819e9 < ideal        # compute-bound
    assert bench.reader("select_prefill_roofline")(obs) == pytest.approx(
        100 * ideal / 0.060, rel=1e-6)
    # one-token rows: 7, 8, 7 rows x 2048 selected latent rows x 5 layers,
    # the LARGER of the bytes' and the FLOPs' seconds (the bytes' here)
    t_bytes = 22 * 2048 * 1280 * 5 / 819e9
    t_flops = 22 * 2048 * 64 * 2 * 1088 * 5 / 197e12
    assert t_flops < t_bytes
    assert bench.reader("select_decode_roofline")(obs) == pytest.approx(
        100 * t_bytes / (3 * 0.009), rel=1e-6)
    fast = {**obs, "peaks": {**V5E, "hbm_bytes_per_s": 4 * 819e9}}
    assert bench.reader("select_decode_roofline")(fast) == pytest.approx(
        100 * t_flops / (3 * 0.009), rel=1e-6)              # ... the FLOPs'
    # the indexer: the rows' contexts as 256 B keys + the chunks' scores
    floor = 22 * 30000 * 256 * 5 / 819e9 \
        + 2 * 5 * pairs * 32 * 128 * 2 / 197e12
    assert bench.reader("select_score_roofline")(obs) == pytest.approx(
        100 * floor / (2 * 0.016 + 0.006), rel=1e-6)
    for name in ("select_prefill_roofline", "select_decode_roofline",
                 "select_score_roofline"):
        assert 0 < bench.reader(name)(obs) < 100
    assert bench.reader("select_share_pct")(obs) > 0


@pytest.mark.parametrize("name", ["select_share_pct", "select_pick_share_pct",
                                  "select_prefill_roofline",
                                  "select_decode_roofline",
                                  "select_score_roofline"])
def test_a_reader_reads_nothing_where_there_is_nothing(family, name):
    """A program without the scopes or the record's counts (the parent on
    this cell's files, had it run): ``None``, not 0, and nothing raised."""
    bench = spec.Bench()
    bare = keye.traced_obs(family, scopes=False, dsa=False)
    bare["config"] = spec.Bench().config(CONFIG)
    assert bench.reader(name)(bare) is None
    assert bench.reader(name)({**bare, "stages": [], "rounds": [],
                               "engine": None}) is None
