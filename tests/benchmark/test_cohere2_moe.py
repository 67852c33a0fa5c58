"""The ``cohere2_moe`` configuration, its cell and its readers: the file
against the catalog row and the program's preset; the family's counts by
hand; the eight shares of a layer adding up to the uncut layer; a tiny cell
of the family driven on the CPU through ``tiny.drive``; the new readers on
hand-made observations."""
import json
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import spec

CELL, CONFIG, MIX = "cmdaplus-rag-sat", "command-a-plus-ep8-d4", \
    "rag-mixed-64k-sat"
NEW = ["swa_attn_share_pct", "full_attn_share_pct", "swa_prefill_roofline",
       "full_prefill_roofline", "kv_window_held_pct"]
ALIASES = {
    "swa_attn_share_pct": ("attn_kind_share_pct", {"kind": "swa"}),
    "full_attn_share_pct": ("attn_kind_share_pct", {"kind": "full"}),
    "swa_prefill_roofline": ("attn_kind_prefill_roofline", {"kind": "swa"}),
    "full_prefill_roofline": ("attn_kind_prefill_roofline",
                              {"kind": "full"})}
# the accepted readers that read something in this cell and that it joined
# at no entry in PR 62, once a cell might
JOINED = ["live_seqs_mean", "itl_p99_ms.moe", "round_p50_ms.moe",
          "share_ragged_rounds_pct.moe", "serve_program_gib.moe",
          "ragged_fwd_ms.moe", "serve_idle_pct.moe",
          "moe_share_pct", "moe_tile_fill_pct", "expert_load_max_over_mean",
          "launch_ahead_pct", "ragged_row_fill_pct"]
# ... and those that wait: the one that miscounts over two KV pools (to be
# mended first), the experts' roofline (another count of the same rows) and
# the decode forward's time: 99.4 % of this mix's rounds are mixed, so a
# traced tail of five seconds may hold no ``decode_forward`` to time
NOT_JOINED = ["kv_bytes_per_token.tok", "moe_roofline", "decode_fwd_ms.moe"]
V5E = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
WINDOW = 16
HF = {"model_type": "cohere2_moe", "hidden_size": 64, "intermediate_size": 32,
      "num_hidden_layers": 4,
      "layer_types": ["sliding_attention"] * 3 + ["full_attention"],
      "num_attention_heads": 8, "num_key_value_heads": 2, "head_dim": 16,
      "vocab_size": 256, "rope_theta": 50000, "rotary_pct": 1,
      "sliding_window": WINDOW, "layer_norm_eps": 1e-5, "logit_scale": 1,
      "attention_bias": False, "use_qk_norm": False,
      "use_parallel_block": True, "tie_word_embeddings": True,
      "first_k_dense_replace": 0, "num_experts": 8, "num_experts_per_tok": 3,
      "num_shared_experts": 4, "norm_topk_prob": True,
      "expert_selection_fn": "sigmoid",
      "shared_expert_combination_strategy": "average",
      "layer_shared_by": 2,
      "reduced": {"num_experts": {"published": 16, "run": 8,
                                  "counts": "experts", "why": "tiny"}}}
ENGINE = {"max_context": 128, "max_sequences": 4, "num_blocks": 64,
          "block_size": 8, "max_tokens_per_batch": 32,
          "prefill_attn": "xla", "decode_attn": "xla"}
CALL = ('%{name}.1 = bf16[8,4]{{1,0}} custom-call(%a), '
        'custom_call_target="tpu_custom_call"')


@pytest.fixture(scope="module")
def family():
    return spec.Bench().family(HF)


def overrides(family, hf=HF, **more):
    widths = {**family.program_widths(hf), "max_seq_len": 256, **more}
    held = widths.pop("experts_held")
    if held != widths["num_experts"]:
        widths["num_experts_held"] = held
    return widths


def _catalog_row():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    try:
        rows = [json.loads(ln) for ln in open(catalog)]
    except OSError:
        pytest.skip("the catalog is not on this machine")
    return next(r for r in rows if r["name"] == "command-a-plus-05-2026")


# ------------------------------------------------- the file and the preset
def test_the_configuration_is_the_source_but_for_what_reduced_lists():
    row = _catalog_row()
    cfg = spec.Bench().config(CONFIG)
    assert cfg["source"] == row["source_url"]
    assert cfg["model_type"] == "cohere2_moe"
    types_ = row["config"]["layer_types"]
    cuts = {"num_hidden_layers": (32, 4, "layers"),
            "layer_types": (types_, types_[:4], "layers"),
            "num_experts": (128, 16, "experts"),
            "vocab_size": (262144, 32768, "vocabulary")}
    assert {k: (c["published"], c["run"], c["counts"])
            for k, c in cfg["reduced"].items()} == cuts
    for key, value in row["config"].items():
        assert cfg[key] == (cuts[key][1] if key in cuts else value), key
    # one whole period, and every published width
    assert cfg["layer_types"] == ["sliding_attention"] * 3 \
        + ["full_attention"]
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"],
            cfg["intermediate_size"], cfg["num_experts_per_tok"],
            cfg["sliding_window"], cfg["num_shared_experts"]) \
        == (4096, 128, 8, 128, 4096, 8, 4096, 4)
    assert cfg["layer_shared_by"] == 8 and 32768 * 8 == 262144
    assert set(cfg["assumed"]) >= {
        "expert_width", "shared_experts", "window_edge", "full_layers",
        "rotary", "router", "vision", "weights", "kv_pool", "prefix_cache"}
    assert "vision tower is not run" in cfg["deployment"]
    assert cfg["engine"] == {
        "max_context": 66560, "max_sequences": 24, "num_blocks": 12288,
        "block_size": 64, "max_tokens_per_batch": 768,
        "prefill_attn": "kernel", "decode_attn": "pallas"}
    assert cfg["policy"] == {"admission": "none", "preempt_policy": "requeue"}
    assert (cfg["path"], cfg["dtype"], cfg["overrides"]) == (
        "serve", "bfloat16",
        {"num_layers": 4, "num_experts_held": 16, "vocab_size": 32768})


def test_the_preset_has_the_published_widths(family):
    from deepspeedsyclsupport_tpu.models import get_config

    whole = get_config("command-a-plus")
    want = family.program_widths(_catalog_row()["config"])
    assert {k: getattr(whole, k) for k in want} == want
    cfg = spec.Bench().config(CONFIG)
    cut = get_config("command-a-plus", **cfg["overrides"])
    want = family.program_widths(cfg)
    assert {k: getattr(cut, k) for k in want} == want
    assert want["attn_period"] == ((4096, "rope"),) * 3 + ((None, "none"),)
    assert (want["num_experts"], want["experts_held"]) == (128, 16)
    # ISSUE 53's arithmetic: resident weights 9.47 GB = 8.82 GiB in bf16,
    # a cached token 4,096 B a layer
    assert round(2 * cut.param_count() / 2**30, 2) == 8.82
    assert 2 * cut.num_kv_heads * cut.head_dim * 2 == 4096


def test_the_familys_counts_against_a_hand_count(family):
    a = family.arch(HF)
    assert a["layer_kinds"] == ("sliding",) * 3 + ("full",)
    assert family.period_of(a["layer_kinds"] * 2) == a["layer_kinds"]
    assert (a["num_experts"], a["experts_held"]) == (16, 8)
    attn = 64 * 128 * 2 + 64 * 32 * 2
    layer = attn + 64 * 16 + 3 * 64 * 32 * (3 + 4)
    assert family.matmul_params(a) == 4 * layer + 64 * 256
    # a windowed layer's query sees min(position + 1, 16) keys
    seq = 40
    windowed = 16 * 17 // 2 + (seq - 16) * 16
    assert family.attention_pairs(a, seq) == 3 * windowed \
        + seq * (seq + 1) // 2
    assert family.attention_pairs(a, 10) == 4 * 55
    assert family.train_flops_per_token(a, seq) == 6 * (
        4 * layer + 64 * 256) + 3 * 4 * 16 * 8 * (
            3 * windowed + seq * (seq + 1) // 2) / seq
    with pytest.raises(ValueError, match="layer_types"):
        family.arch({**HF, "num_hidden_layers": 8})


def test_the_benchmark_is_sound_with_the_new_entries():
    bench = spec.Bench()
    assert bench.problems() == []
    doc = bench.doc
    assert len(doc["workloads"]) >= 12 and len(doc["configs"]) >= 11
    assert sum(w["chips"] == 4 for w in doc["workloads"]) == 1
    cell = bench.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, MIX, 1)
    assert len(cell["why"]) <= 200
    assert bench._entry("configs", CONFIG)["reduced"] == [
        "layer_types", "num_experts", "num_hidden_layers", "vocab_size"]
    e2e = {m["name"] for m in bench.metrics_of(CELL, "end_to_end")}
    assert e2e == {"serve_tok_s", "setup_s"}
    reports = {m["name"] for m in bench.metrics_of(CELL, "per_layer")}
    # a superset: an entry appended later breaks nothing here
    assert reports >= {"start_to_chip_s", *NEW, *JOINED}
    assert not reports & set(NOT_JOINED)
    for m in doc["per_layer"]:
        if m["name"] in (*NEW, *JOINED):
            assert CELL in m["workloads"] and m["moves"] == "serve_tok_s"
        if m["name"] in NEW:
            assert m["workloads"][0] == CELL and m["unit"] == "%"
    for name, (stem, args) in ALIASES.items():
        assert bench.resolved(name) == (stem, args)


def test_the_mix_is_the_issues_and_fits_the_context():
    from benchmark import traffic

    bench = spec.Bench()
    mix, cfg = bench.traffic(MIX), bench.config(CONFIG)["engine"]
    pairs = traffic.length_pairs(mix, mix["count"])
    assert (mix["kind"], mix["clients"], len(pairs)) == ("closed", 24, 24)
    assert mix["prompt_len"] == {"dist": "lognormal", "min": 512,
                                 "max": 65536, "median": 8192, "sigma": 1.1}
    assert mix["output_len"] == {"dist": "uniform", "min": 128, "max": 512}
    assert mix["clients"] == cfg["max_sequences"]
    assert mix["prompt_len"]["max"] + mix["output_len"]["max"] \
        <= cfg["max_context"]
    # the grid: a quarter under the window, a quarter past 4 x it, short
    # and long in one queue; all 24 in flight fit the full pool 2.6 times
    prompts = [p for p, _ in pairs]
    assert (min(prompts), max(prompts)) == (966, 50318)
    assert sum(p < 4096 for p in prompts) == 6 == sum(
        p > 4 * 4096 for p in prompts)
    assert sum(-(-(p + o) // 64) for p, o in pairs) == 4688 \
        < cfg["num_blocks"] / 2.6
    # benchmark.parity's probes: under the window, past window + chunk,
    # past 4 x the window
    from benchmark import parity

    probes = [len(p) for p, _ in parity.probes(mix, 100, 0)]
    assert probes == [966, 8383, 50318]
    assert probes[0] < 4096 < 4096 + 768 < probes[1] and \
        probes[2] > 4 * 4096


# ------------------------------------------------------- the shares add up
def test_the_eight_shares_add_up_to_the_uncut_layer(family):
    """Every chip routes over all 16 experts and holds two of them; each
    adds the averaged shared experts. The eight shares' routed parts plus
    the shared experts ONCE are the uncut layer, in the program
    (``moe_mlp_nodrop``) and in the reference (``ffn``) alike."""
    from deepspeedsyclsupport_tpu.models import build_model
    from deepspeedsyclsupport_tpu.parallel.moe import moe_mlp_nodrop

    hf = {**HF, "num_experts": 16, "reduced": {}}
    whole = build_model("command-a-plus", **overrides(
        family, hf, dtype="float32", routed_write_share=None))
    whole.seed = 5
    layers = whole.init_params()["layers"]
    moe = jax.tree_util.tree_map(lambda w: w[1], layers["moe"])
    x = jax.random.normal(jax.random.PRNGKey(1), (21, 64), jnp.float32)
    arch = family.arch(hf)
    want, _ = family.ffn(arch, layers["moe"], 1, x)
    out, routed = moe_mlp_nodrop(moe, x, whole.config)
    np.testing.assert_allclose(out, want, atol=2e-6)
    assert int(routed.sum()) == 21 * 3
    shared = (jax.nn.silu(x @ moe["shared"]["w_gate"])
              * (x @ moe["shared"]["w_up"])) @ moe["shared"]["w_down"] / 4
    total = 0.0
    for first in range(0, 16, 2):
        part = build_model("command-a-plus", **overrides(
            family, hf, dtype="float32", num_experts_held=2,
            first_expert_held=first)).config
        held = {**moe, **{k: moe[k][first:first + 2]
                          for k in ("w_gate", "w_up", "w_down")}}
        got, routed_i = moe_mlp_nodrop(held, x, part)
        ref, _ = family.ffn({**arch, "first_expert_held": first},
                            {**layers["moe"], **{
                                k: layers["moe"][k][:, first:first + 2]
                                for k in ("w_gate", "w_up", "w_down")}}, 1, x)
        np.testing.assert_allclose(got, ref, atol=2e-6)
        np.testing.assert_array_equal(routed_i, routed)
        total = total + (got - shared)
    np.testing.assert_allclose(total + shared, want, atol=5e-6)
    # and "average" is a quarter of what "sum" adds
    summed = build_model("command-a-plus", **overrides(
        family, hf, dtype="float32", shared_expert_combine="sum")).config
    np.testing.assert_allclose(moe_mlp_nodrop(moe, x, summed)[0] - out,
                               3 * shared, atol=5e-6)


# ------------------------------------------------------------ the tiny cell
@pytest.fixture(scope="module")
def tiny_cell(tmp_path_factory, family):
    from . import tiny

    root = tmp_path_factory.mktemp("bench")
    bench = tiny.make_root(root)
    doc, name = bench.doc, "tiny-cmdaplus"
    cfg = {**HF, "source": "tests", "path": "serve",
           "preset": "command-a-plus", "overrides": overrides(family),
           "dtype": "float32", "engine": ENGINE,
           "policy": {"admission": "none", "preempt_policy": "requeue"}}
    (root / "extra" / "configs" / f"{name}.json").write_text(json.dumps(cfg))
    doc["configs"].append({"name": name, "source": "tests",
                           "reduced": ["num_experts"], "why": "tiny",
                           "file": f"extra/configs/{name}.json"})
    doc["workloads"].append({"name": f"{name}-cell", "chips": 1,
                             "config": name, "why": "tiny",
                             "traffic": "tiny-closed"})
    # the tiny cell lists what the real one does AND the readers that read
    # something here and wait at the real sizes (NOT_JOINED)
    for m in doc["end_to_end"] + doc["per_layer"]:
        if CELL in m.get("workloads", ()) or m["name"] in NOT_JOINED:
            m["workloads"].append(f"{name}-cell")
    (root / "BENCHMARK.json").write_text(json.dumps(doc))
    bench = spec.Bench(root)
    assert bench.problems() == []
    return tiny.drive(bench, f"{name}-cell", seed=2**31 + 53)


def test_the_cell_runs_is_checked_and_reports_what_the_real_cell_lists(
        tiny_cell):
    obs, m = tiny_cell
    assert obs["correct"] and obs["failed"] == 0 and obs["attempted"] > 4
    by_name = {x["name"]: x for x in spec.Bench().doc["per_layer"]}
    untraced = {n for n in (*JOINED, *NOT_JOINED)
                if by_name[n]["source"] != "device_trace"}
    assert untraced <= set(m), untraced - set(m)
    assert m["serve_tok_s"] > 0 and m["live_seqs_mean"] > 1
    # what a token costs the FULL pool: one layer of the four
    assert m["kv_bytes_per_token.tok"] == 2 * 2 * 16 * 4
    assert 0 < m["kv_window_held_pct"] <= 100
    eng = obs["engine"]
    # what the harness's leak check reads sees both pools, both empty
    assert eng.allocator.num_blocks == 64 + eng.allocator.window.num_blocks
    assert eng.allocator.free_blocks == eng.allocator.num_blocks
    assert eng.moe_stats()["held"].tolist() == list(range(8))


def test_the_records_carry_both_pools_counts(tiny_cell):
    from benchmark import spans

    obs, _m = tiny_cell
    launched = [d for d in spans.round_records(obs) if d["program"]]
    assert len(launched) > 10
    for d in launched:
        assert 0 < d["kv_window_tokens"] <= d["kv_live_ctx_tokens"]
        assert d["kv_full_blocks_held"] * 8 >= d["kv_live_ctx_tokens"]
        assert d["swa_pairs"] <= d["attn_pairs"]
        assert d["kv_window_blocks_freed"] >= 0
    assert any(d["kv_window_blocks_freed"] for d in launched)
    assert any(d["kv_window_tokens"] < d["kv_live_ctx_tokens"]
               for d in launched)


# --------------------------------------- the new readers, hand-made traces
def traced_obs(family, swa_s=0.004, full_s=0.012, dec_s=0.0005, mlp_s=0.02,
               named=True, counts=True):
    """``obs`` of a traced run at the CELL's widths: five rounds, the middle
    three traced, each a ``ragged_forward`` over ONE 768-row chunk that
    starts at position 32,000 beside 23 one-token rows; on the device, per
    forward, three windowed layers' atom kernels (``swa_s`` together), the
    full layer's (``full_s``), the four layers' one-row kernels and the
    experts' fusion."""
    from benchmark import spans
    from deepspeedsyclsupport_tpu.inference.v2.ragged import (
        SequenceDescriptor, attention_work, window_work)

    cfg = spec.Bench().config(CONFIG)
    chunk = [SequenceDescriptor(uid=0, n_cached=32000)]
    pairs = attention_work(chunk, [768], 64)[0]
    swa_pairs, swa_keys, full_keys = window_work(chunk, [768], 4096, 64)
    offset, rounds, t = 5.0, [], 100.0
    for took in (0.050, 0.061, 0.072, 0.083, 0.094):
        rounds.append((t, t + took, 24, 0))
        t += took + 0.001
    stages, host, modules, ops = [], [], [], []
    names = ("paged_swa_prefill", "paged_full_prefill", "paged_swa_decode",
             "paged_full_decode") if named else ("ragged_prefill",) * 2 \
        + ("paged_decode",) * 2
    for i, (t0, t1, *_) in enumerate(rounds):
        data = {"stage": "round", "round": i, "t0": t0 + 1e-4,
                "t1": t1 - 1e-4, "launch_t": t0 + 0.0031, "tokens": 791,
                "program": "ragged_forward", "n_seqs": 24,
                "attn_pairs": pairs}
        if counts:
            data.update(swa_pairs=swa_pairs, swa_atom_keys=swa_keys,
                        full_atom_keys=full_keys,
                        kv_live_ctx_tokens=340000, kv_window_tokens=95200)
        stages.append({"name": "serve/stage", "data": data})
        if 1 <= i <= 3:
            at = t0 + offset
            host += [[spans.ROUND_SPAN, at, t1 - t0],
                     ["PjitFunction(ragged_forward)", at + 0.002, 0.001]]
            modules.append(["jit_ragged_forward(7)", at + 0.004, 0.045])
            start = at + 0.005
            for text, took in (
                    (CALL.format(name=names[0]), swa_s),
                    (CALL.format(name=names[1]), full_s),
                    (CALL.format(name=names[2]), 3 * dec_s),
                    (CALL.format(name=names[3]), dec_s),
                    ("%fusion.6 = bf16[768,4096]{1,0} fusion(%x)", mlp_s)):
                ops.append([text, start, took])
                start += took
    return {"trace": {"host": host, "devices": {"/device:TPU:0": {
                "modules": modules, "ops": ops}}},
            "trace_window": (rounds[1][0] + offset - 1e-3,
                             rounds[3][1] + offset + 1e-3),
            "window": (rounds[0][0] - 1e-3, rounds[-1][1] + 1e-3),
            "rounds": rounds, "stages": stages,
            "engine": types.SimpleNamespace(), "config": cfg, "peaks": V5E,
            "family": family}, (pairs, swa_pairs, swa_keys, full_keys)


def test_the_readers_on_a_chunk_at_32k_of_context(family):
    """A 768-row chunk at 32,000: the full layer scores 24.9 M pairs (0.84
    TFLOP at 128 heads: compute-bound by far), a windowed layer ONLY the
    3.1 M inside the window, its 12 atoms reading 4,159 keys each and not
    32 k. At the floor itself a share reads 100 and cannot pass it."""
    bench = spec.Bench()
    obs, (pairs, swa_pairs, swa_keys, full_keys) = traced_obs(family)
    assert pairs == 768 * 32000 + 768 * 769 // 2
    assert swa_pairs == 768 * 4096
    assert swa_keys == 12 * (4095 + 64) and full_keys == sum(
        32000 + 64 * (i + 1) for i in range(12))
    per_pair = 128 * 4 * 128
    full_ideal = pairs * per_pair / 197e12
    assert full_ideal > full_keys * 4096 / 819e9        # compute-bound
    assert bench.reader("full_prefill_roofline")(obs) == pytest.approx(
        100 * full_ideal / 0.012, rel=1e-6)
    swa_ideal = 3 * swa_pairs * per_pair / 197e12
    assert bench.reader("swa_prefill_roofline")(obs) == pytest.approx(
        100 * swa_ideal / 0.004, rel=1e-6)
    for name, ideal, kw in (("full_prefill_roofline", full_ideal,
                             {"full_s": full_ideal}),
                            ("swa_prefill_roofline", swa_ideal,
                             {"swa_s": swa_ideal})):
        at_floor, _ = traced_obs(family, **kw)
        assert bench.reader(name)(at_floor) == pytest.approx(100.0, rel=1e-6)
    busy = 0.004 + 0.012 + 4 * 0.0005 + 0.02
    assert bench.reader("swa_attn_share_pct")(obs) == pytest.approx(
        100 * (0.004 + 3 * 0.0005) / busy, rel=1e-6)
    assert bench.reader("full_attn_share_pct")(obs) == pytest.approx(
        100 * (0.012 + 0.0005) / busy, rel=1e-6)
    assert bench.reader("kv_window_held_pct")(obs) == pytest.approx(28.0)


@pytest.mark.parametrize("name", NEW)
def test_a_new_reader_reads_nothing_where_there_is_nothing(family, name):
    """A program that names no kernel by kind and counts neither pool (the
    parent; every model of one attention kind); an untraced run."""
    read = spec.Bench().reader(name)
    plain, _ = traced_obs(family, named=False, counts=False)
    assert read(plain) is None
    untraced, _ = traced_obs(family)
    untraced["trace"] = None
    if name != "kv_window_held_pct":
        assert read(untraced) is None
    assert read({**untraced, "stages": [], "rounds": []}) is None
