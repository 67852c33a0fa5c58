"""The ``KeyeVL2`` configuration, its cell and its readers: the file against
the catalog row and the program's preset; the family's rotary, shares and
counts by hand; a tiny cell of the family driven on the CPU through
``tiny.drive``; the new readers on hand-made observations."""
import dataclasses
import json
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import spec

CELL, CONFIG, MIX = "keye-video-sat", "keye-vl2-30b-a3b-ep8-d12", \
    "video-32k-sat"
NEW = ["select_share_pct", "select_pick_share_pct", "select_prefill_roofline",
       "select_decode_roofline", "select_score_roofline", "dsa_kept_pct",
       "index_bytes_per_token"]
ALIASES = {"select_pick_share_pct": {"reader": "select_share_pct",
                                    "args": {"role": "select"}},
           "serve_tok_s.p95": {"reader": "serve_tok_s"},
           "ragged_fwd_ms.p95": {"reader": "ragged_fwd_ms"},
           "decode_fwd_ms.p95": {"reader": "decode_fwd_ms"},
           "moe_share_pct.p95": {"reader": "moe_share_pct"},
           "serve_idle_pct.p95": {"reader": "serve_idle_pct"},
           "share_ragged_rounds_pct.p95": {
               "reader": "ragged_round_share_pct"}}
# the readers ISSUE 45 asked for and the full list had no place for: since
# PR 48 the cell JOINS their entries (one a reader and a moved metric)
JOINED = ["live_seqs_mean.p95", "kv_bytes_per_token",
          "expert_load_max_over_mean.p95", "round_p50_ms.p95",
          "serve_program_gib.p95", "kv_step_fill_pct",
          "ragged_tile_fill_pct.p95"]
V5E = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
TOPK = 8
HF = {"model_type": "KeyeVL2", "hidden_size": 64, "intermediate_size": 96,
      "moe_intermediate_size": 32, "num_hidden_layers": 4,
      "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
      "vocab_size": 256, "num_experts": 8, "num_local_experts": 8,
      "num_experts_per_tok": 3, "norm_topk_prob": True,
      "rms_norm_eps": 1e-6, "rope_theta": 10000000,
      "rope_scaling": {"mrope_section": [2, 3, 3]},
      "sa_config": {"indexer_head_dim": 8, "indexer_num_heads": 2,
                    "topk": TOPK},
      "layer_shared_by": 2,
      "reduced": {"num_experts": {"published": 16, "run": 8,
                                  "counts": "experts", "why": "tiny"},
                  "num_local_experts": {"published": 16, "run": 8,
                                        "counts": "experts", "why": "tiny"}}}
OVERRIDES = {"hidden_size": 64, "intermediate_size": 96,
             "moe_intermediate_size": 32, "num_layers": 4, "num_heads": 4,
             "num_kv_heads": 2, "head_dim": 16, "vocab_size": 256,
             "num_experts": 16, "num_experts_per_tok": 3,
             "num_experts_held": 8, "index_topk": TOPK, "index_heads": 2,
             "index_head_dim": 8, "max_seq_len": 256,
             # the experts at full weight
             "routed_write_share": None}
ENGINE = {"max_context": 128, "max_sequences": 4, "num_blocks": 64,
          "block_size": 8, "max_tokens_per_batch": 32,
          "prefill_attn": "xla", "decode_attn": "xla"}


@pytest.fixture(scope="module")
def family():
    return spec.Bench().family(HF)


def _catalog_row():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    try:
        rows = [json.loads(ln) for ln in open(catalog)]
    except OSError:
        pytest.skip("the catalog is not on this machine")
    return next(r for r in rows if r["name"] == "Keye-VL-2.0-30B-A3B")


# ------------------------------------------------- the file and the preset
def test_the_configuration_is_the_source_but_for_what_reduced_lists():
    row = _catalog_row()
    cfg = spec.Bench().config(CONFIG)
    assert cfg["source"] == row["source_url"]
    assert cfg["model_type"] == "KeyeVL2"
    cuts = {"num_hidden_layers": (48, 12, "layers"),
            "num_experts": (128, 16, "experts"),
            "num_local_experts": (128, 16, "experts"),
            "vocab_size": (151936, 18992, "vocabulary")}
    assert {k: (c["published"], c["run"], c["counts"])
            for k, c in cfg["reduced"].items()} == cuts
    for key, value in row["config"].items():
        assert cfg[key] == (cuts[key][1] if key in cuts else value), key
    assert cfg["layer_shared_by"] == 8 and 18992 * 8 == 151936
    assert set(cfg["assumed"]) >= {
        "qk_norm", "indexer_input", "indexer_key_norm", "indexer_rotary",
        "indexer_score", "selection", "chunk_sizes", "mrope", "vision",
        "weights", "dtype", "kv_pool", "attention_form"}
    assert cfg["engine"] == {
        "max_context": 49152, "max_sequences": 8, "num_blocks": 6272,
        "block_size": 64, "max_tokens_per_batch": 768,
        "prefill_attn": "kernel", "decode_attn": "pallas"}
    assert cfg["policy"] == {"admission": "none", "preempt_policy": "requeue"}
    assert (cfg["path"], cfg["dtype"], cfg["overrides"]) == (
        "serve", "bfloat16",
        {"num_layers": 12, "num_experts_held": 16, "vocab_size": 18992})


def test_the_preset_has_the_published_widths(family):
    from deepspeedsyclsupport_tpu.models import get_config

    whole = get_config("keye-vl2-30b-a3b")
    want = family.program_widths(_catalog_row()["config"])
    assert {k: getattr(whole, k) for k in want} == want
    cfg = spec.Bench().config(CONFIG)
    cut = get_config("keye-vl2-30b-a3b", **cfg["overrides"])
    want = family.program_widths(cfg)
    assert {k: getattr(cut, k) for k in want} == want
    assert (whole.q_dim, whole.kv_dim, whole.index_topk, whole.index_heads,
            whole.index_head_dim) == (4096, 512, 2048, 16, 64)
    # ISSUE 45's arithmetic: a layer HERE 96.9 M parameters, the embedding
    # and the head 77.8 M, 2.32 GiB resident in bf16
    layer = (cut.param_count() - 2 * 18992 * 2048 - 2048) / 12
    assert layer / 1e6 == pytest.approx(96.9, abs=0.1)
    assert cut.param_count() * 2 / 2**30 == pytest.approx(2.31, abs=0.01)
    # the pool: 12 x (K and V 2,048 B + one indexer key 128 B) a token
    engine = cfg["engine"]
    slots = engine["num_blocks"] * engine["block_size"]
    per_token = 12 * (2 * cut.num_kv_heads * cut.head_dim
                      + cut.index_head_dim) * 2
    assert (per_token, slots) == (26112, 401408)
    assert per_token * slots / 2**30 == pytest.approx(9.76, abs=0.01)


def test_the_familys_counts_against_a_hand_count(family):
    a = family.arch(HF)
    assert (a["num_experts"], a["experts_held"], a["intermediate_size"],
            a["index_topk"], a["num_dense_layers"]) == (16, 8, 32, TOPK, 0)
    attn = 64 * 64 + 2 * 64 * 32 + 64 * 64
    index = 64 * (2 * 8 + 8 + 2)
    mlp = 3 * 3 * 64 * 32 + 64 * 16
    assert family.matmul_params(a) == 4 * (attn + index + mlp) + 64 * 256
    # 20 positions: the first 8 see 1..8 keys, the other 12 see topk = 8
    pairs, scored = 8 * 9 // 2 + 12 * 8, 20 * 21 // 2
    assert family.train_flops_per_token(a, 20) == 6 * family.matmul_params(
        a) + 3 * 4 * 16 * 4 * 4 * pairs / 20 + 3 * 2 * 2 * 8 * 4 * scored / 20
    # what the rooflines count, at the CELL's widths
    whole = family.arch(spec.Bench().config(CONFIG))
    assert family.selected_attention_work(whole, 1000, 50) == (
        12 * 1000 * 32 * 4 * 128, 12 * 50 * 2048)
    assert family.selected_rows_bytes(whole, 2048) == 12 * 2048 * 2048
    assert family.index_work(whole, 1000, 40000) == (
        12 * 1000 * 16 * 64 * 2, 12 * 40000 * 128)


def test_mrope_with_equal_rows_is_plain_rotary(family):
    """The reference rotates by sections over three position rows; the
    served path runs plain rotary (``models.layers.apply_rope``). On a text
    token's three equal rows they are the same rotation; on different rows
    the sections show."""
    from deepspeedsyclsupport_tpu.models.layers import apply_rope

    a = family.arch(spec.Bench().config(CONFIG))
    assert a["mrope_section"] == (16, 24, 24) and a["head_dim"] == 128
    x = jax.random.normal(jax.random.PRNGKey(0), (9, 3, 128))
    pos = jnp.asarray([0, 1, 5, 17, 300, 4095, 40000, 49151, 7])
    text = jnp.broadcast_to(pos, (3, 9))
    plain = apply_rope(x[None], pos[None], a["rope_theta"])[0]
    # float32 angles: two ways to the same frequency differ by an ulp,
    # times the position (5e-3 rad at 49 k)
    np.testing.assert_allclose(family.mrope(a, x, text), plain, atol=2e-2)
    np.testing.assert_allclose(family.mrope(a, x, text)[:5], plain[:5],
                               atol=5e-5)
    np.testing.assert_allclose(family.mrope(a, x, family.text_positions(9)),
                               apply_rope(x[None], jnp.arange(9)[None],
                                          a["rope_theta"])[0], atol=2e-5)
    # height and width rows of their own move frequencies 16-39 and 40-63
    video = text.at[1].add(3).at[2].add(11)
    moved = np.abs(np.asarray(family.mrope(a, x, video)
                              - family.mrope(a, x, text))).max((0, 1))
    assert not moved[:16].any() and not moved[64:80].any()
    assert moved[16:40].min() > 0 and moved[40:64].min() > 0


def test_the_eight_shares_add_up_to_the_uncut_layer(family):
    """One expert layer over 8 experts, cut eight ways: the routed parts the
    eight shares give (one expert each, through the PROGRAM's layer told
    which expert it holds) are the reference's uncut layer, renormalised
    over all 3 chosen whatever is held; each share alone is the reference's
    share."""
    from deepspeedsyclsupport_tpu.models import get_config
    from deepspeedsyclsupport_tpu.parallel.moe import moe_mlp_nodrop

    cfg = get_config("keye-vl2-30b-a3b", **{
        **OVERRIDES, "num_experts": 8, "num_experts_held": 1})
    d, fe, e = 64, 32, 8
    ks = jax.random.split(jax.random.PRNGKey(7), 5)
    whole = {"router": jax.random.normal(ks[0], (d, e)) * 0.3,
             "w_gate": jax.random.normal(ks[1], (e, d, fe)) * 0.2,
             "w_up": jax.random.normal(ks[2], (e, d, fe)) * 0.2,
             "w_down": jax.random.normal(ks[3], (e, fe, d)) * 0.2}
    x = jax.random.normal(ks[4], (37, d))
    live = jnp.arange(37) < 33                      # four pad rows
    part = lambda lo, hi: {  # noqa: E731
        "router": whole["router"],
        **{k: whole[k][lo:hi] for k in ("w_gate", "w_up", "w_down")}}
    a = {**family.arch(HF), "num_experts": e, "experts_held": e}
    with jax.default_matmul_precision("highest"):
        want, _ = family.experts(a, part(0, e), x)
        routed = jnp.zeros_like(x)
        for first in range(e):
            share = dataclasses.replace(cfg, first_expert_held=first)
            got, rows = moe_mlp_nodrop(part(first, first + 1), x, share, live)
            alone, _ = family.experts({**a, "first_expert_held": first},
                                      part(first, first + 1), x)
            assert np.abs(np.asarray(got - alone))[:33].max() \
                < 1e-4 * float(jnp.std(want))
            assert rows.shape == (e,) and int(rows.sum()) == 33 * 3
            routed += got
    err = np.abs(np.asarray(routed - want))[:33].max() / float(jnp.std(want))
    assert err < 1e-4, err
    gates, _ = family.router(a, whole["router"], x)
    np.testing.assert_allclose(gates.sum(-1), 1.0, atol=1e-6)


def test_index_gaps_are_the_topk_th_scores_margin(family):
    from deepspeedsyclsupport_tpu.models import build_model

    model = build_model("keye-vl2-30b-a3b", **OVERRIDES, dtype="float32")
    params = model.init_params(jax.random.PRNGKey(2))
    ids = np.random.default_rng(0).integers(0, 256, 30).astype(np.int32)
    a = family.arch(HF)
    gaps = np.asarray(family.index_gaps(a, params, ids))
    assert gaps.shape == (4, 30)
    assert (gaps[:, :TOPK] == 1.0).all()        # no more than topk seen
    assert (gaps[:, TOPK:] >= 0).all() and np.isfinite(gaps).all()
    assert (gaps[:, TOPK:] > 0).mean() > 0.5
    assert np.asarray(family.router_gaps(a, params, ids)).shape == (4, 30)


# ------------------------------------------------------------ the benchmark
def test_the_benchmark_is_sound_with_the_new_entries():
    bench = spec.Bench()
    assert bench.problems() == []
    cell = bench.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, MIX, 1)
    assert len(cell["why"]) <= 200
    assert bench._entry("configs", CONFIG)["reduced"] == [
        "num_experts", "num_hidden_layers", "num_local_experts", "vocab_size"]
    e2e = {m["name"] for m in bench.metrics_of(CELL, "end_to_end")}
    assert e2e >= {"itl_p95_ms", "setup_s"}
    reports = {m["name"] for m in bench.metrics_of(CELL, "per_layer")}
    # supersets: an entry appended later breaks nothing here
    assert reports >= {"start_to_chip_s", *NEW, *ALIASES, *JOINED}
    for m in bench.doc["per_layer"]:
        if m["name"] in (*NEW, *ALIASES, *JOINED):
            assert CELL in m["workloads"] and m["moves"] == "itl_p95_ms"
    for name, alias in ALIASES.items():
        assert json.loads(bench._find(
            "metrics", name, (".json",)).read_text()) == alias


def test_the_mix_is_the_issues_grid_and_fits_the_pool():
    from benchmark import traffic

    bench = spec.Bench()
    mix, cfg = bench.traffic(MIX), bench.config(CONFIG)["engine"]
    pairs = traffic.length_pairs(mix, mix["count"])
    assert (mix["kind"], mix["clients"]) == ("closed", 8)
    assert mix["prompt_len"] == {"dist": "uniform", "min": 16384,
                                 "max": 48384}
    assert mix["output_len"] == {"dist": "uniform", "min": 256, "max": 768}
    assert mix["clients"] == cfg["max_sequences"]
    # one window's work at the measured rate: 7-10 requests end in a window
    # (PERF.md section 2), and a cycle of the 8 callers is the whole grid
    assert mix["count"] == 8
    assert [p for p, _ in pairs] == list(range(18384, 48384, 4000))
    assert sorted(o for _, o in pairs) == list(range(288, 768, 64))
    worst = max(p + o for p, o in pairs)
    assert 22 * 2048 < worst <= 48384 + 768 == cfg["max_context"]
    # 8 callers on the longest pair there can be: 6,144 of 6,272 blocks
    assert 8 * -(-cfg["max_context"] // cfg["block_size"]) == 6144 \
        <= cfg["num_blocks"]
    # every context passes 8 times the indexer's topk and stays under 24
    assert min(p for p, _ in pairs) >= 8 * 2048
    assert max(p + o for p, o in pairs) <= 24 * 2048


# ------------------------------------------------------------ the tiny cell
@pytest.fixture(scope="module")
def tiny_cell(tmp_path_factory):
    from . import tiny

    root = tmp_path_factory.mktemp("bench")
    bench = tiny.make_root(root)
    doc = bench.doc
    cfg = {**HF, "source": "tests", "path": "serve",
           "preset": "keye-vl2-30b-a3b", "overrides": OVERRIDES,
           "dtype": "float32", "engine": ENGINE,
           "policy": {"admission": "none", "preempt_policy": "requeue"}}
    (root / "extra" / "configs" / "tiny-keye.json").write_text(
        json.dumps(cfg))
    doc["configs"].append({
        "name": "tiny-keye", "source": "tests", "why": "tiny",
        "reduced": ["num_experts", "num_local_experts"],
        "file": "extra/configs/tiny-keye.json"})
    doc["workloads"].append({"name": "tiny-keye-cell", "chips": 1,
                             "config": "tiny-keye", "why": "tiny",
                             "traffic": "tiny-closed"})
    for m in doc["end_to_end"] + doc["per_layer"]:
        if CELL in m.get("workloads", ()):
            m["workloads"].append("tiny-keye-cell")
    (root / "BENCHMARK.json").write_text(json.dumps(doc))
    bench = spec.Bench(root)
    assert bench.problems() == []
    return tiny.drive(bench, "tiny-keye-cell", seed=2**31 + 45)


def test_the_cell_runs_is_checked_and_reports_what_the_real_cell_lists(
        tiny_cell):
    obs, m = tiny_cell
    assert obs["correct"] and obs["failed"] == 0 and obs["attempted"] > 4
    bench = spec.Bench()
    # (the tiny engine attends through XLA, which takes no atoms: the tiles'
    # fill has nothing to read here; on the chip it read 98.99, PERF.md PR 48)
    no_atoms = "ragged_tile_fill_pct.p95"
    untraced = {x["name"] for x in bench.metrics_of(CELL, "per_layer")
                if x["source"] != "device_trace"} - {"start_to_chip_s",
                                                     no_atoms}
    assert untraced <= set(m), untraced - set(m)
    assert set(JOINED) - {no_atoms} <= untraced and no_atoms not in m
    assert m["serve_tok_s.p95"] > 0 and m["itl_p95_ms"] > 0
    # 4 layers x one 8-wide float32 key a token
    assert m["index_bytes_per_token"] == 4 * 8 * 4
    assert 0 < m["dsa_kept_pct"] < 100
    eng = obs["engine"]
    assert eng.allocator.free_blocks == eng.allocator.num_blocks
    stats = eng.moe_stats()
    assert list(stats["held"]) == list(range(8))


# --------------------------------------- the new readers, hand-made traces
def traced_obs(family, scopes=True, dsa=True):
    """``obs`` of a traced run at the CELL's widths: five rounds, the middle
    three traced; the second a ``decode_forward`` of 8 rows, the others a
    mixed ``ragged_forward`` (a 768-row chunk at 40 k of context beside 7
    one-token rows). On the device, per execution: one fusion under
    ``dsa_index``, the ``dsa_index_scores`` and ``dsa_select`` custom calls
    and ``dsa_prefill`` (a mixed round only), one fusion under ``dsa_rows``
    inside ``dsa_attend``, and the experts' outside all."""
    from benchmark import spans

    cfg = spec.Bench().config(CONFIG)
    offset, rounds, t = 5.0, [], 100.0
    for took in (0.150, 0.151, 0.030, 0.152, 0.153):
        rounds.append((t, t + took, 8, 0))
        t += took + 0.001
    pairs = 768 * 40000 + 768 * 769 // 2
    stages, host, modules, ops = [], [], [], []
    for i, (t0, t1, *_) in enumerate(rounds):
        mixed = i != 2
        program = "ragged_forward" if mixed else "decode_forward"
        data = {"stage": "round", "round": i, "t0": t0 + 1e-4,
                "t1": t1 - 1e-4, "launch_t": t0 + 0.0031, "program": program,
                "tokens": 775 if mixed else 8, "n_seqs": 8,
                "decode_rows": 7 if mixed else 8,
                "attn_pairs": pairs if mixed else 0,
                "dec_ctx_tokens": (7 if mixed else 8) * 30000}
        if dsa:
            data.update(sel_pairs=768 * 2048 if mixed else 0,
                        dec_sel_tokens=(7 if mixed else 8) * 2048)
        stages.append({"name": "serve/stage", "data": data})
        if 1 <= i <= 3:
            at = t0 + offset
            host += [[spans.ROUND_SPAN, at, t1 - t0],
                     [f"PjitFunction({program})", at + 0.002, 0.001]]
            steps = [("%fusion.1 = bf16[8,16,64]{2,1,0} fusion(%x)", 0.006),
                     ("%fusion.2 = bf16[8,32,128]{2,1,0} fusion(%x)", 0.009),
                     ("%fusion.9 = bf16[8,2048]{1,0} fusion(%x)", 0.005)]
            if mixed and not dsa:    # another model's ragged kernel
                steps = [("%ragged_prefill.1 = bf16[15,128,32,128]{3,2,1,0} "
                          "custom-call(%x), custom_call_target="
                          "\"tpu_custom_call\"", 0.060)] + steps
            elif mixed:
                steps = [
                    ("%dsa_index_scores.1 = f32[15,128,49152]{2,1,0} "
                     "custom-call(%x), custom_call_target=\"tpu_custom_call\"",
                     0.010),
                    ("%dsa_select.1 = s8[15,128,49152]{2,1,0} "
                     "custom-call(%x), custom_call_target=\"tpu_custom_call\"",
                     0.012),
                    ("%dsa_prefill.1 = bf16[15,128,32,128]{3,2,1,0} "
                     "custom-call(%x), custom_call_target=\"tpu_custom_call\"",
                     0.060)] + steps
            modules.append([f"jit_{program}(7)", at + 0.004,
                            sum(s for _t, s in steps)])
            start = at + 0.004
            for text, took in steps:
                ops.append([text, start, took])
                start += took

    class Compiled:
        def __init__(self, program):
            self.program = program

        def as_text(self):
            if not scopes:
                return ""
            path = f'op_name="jit({self.program})/while/body/'
            return (f'  %fusion.1 = bf16[8,16,64]{{2,1,0}} fusion(%x), '
                    f'metadata={{{path}dsa_index/dot_general"}}\n'
                    f'  %fusion.2 = bf16[8,32,128]{{2,1,0}} fusion(%x), '
                    f'metadata={{{path}dsa_attend/dsa_rows/gather"}}\n'
                    f'  %fusion.9 = bf16[8,2048]{{1,0}} fusion(%x), '
                    f'metadata={{{path}moe_experts/dot_general"}}\n')

    pool = types.SimpleNamespace(shape=(12, 401408, 4, 128),
                                 size=12 * 401408 * 4 * 128,
                                 dtype=np.dtype("float16"))
    idx = types.SimpleNamespace(shape=(12, 200704, 128),
                                size=12 * 200704 * 128,
                                dtype=np.dtype("float16"))
    engine = types.SimpleNamespace(
        compiled_programs=lambda: {p: Compiled(p) for p in (
            "ragged_forward", "decode_forward")},
        kv=types.SimpleNamespace(k=pool, v=pool, idx=idx if dsa else None),
        config=types.SimpleNamespace(max_tokens_per_batch=768))
    return {"trace": {"host": host, "devices": {"/device:TPU:0": {
                "modules": modules, "ops": ops}}},
            "trace_window": (rounds[1][0] + offset - 1e-3,
                             rounds[3][1] + offset + 1e-3),
            "window": (rounds[0][0] - 1e-3, rounds[4][1] + 1e-3),
            "rounds": rounds, "stages": stages, "engine": engine,
            "config": cfg, "peaks": V5E, "family": family}


def test_the_dsa_readers_on_two_mixed_rounds_and_a_decode_step():
    family = spec.Bench().family({"model_type": "KeyeVL2"})
    bench = spec.Bench()
    obs = traced_obs(family)
    busy = 2 * (0.010 + 0.012 + 0.060 + 0.020) + 0.020
    dsa = 2 * (0.010 + 0.012 + 0.060 + 0.015) + 0.015
    assert bench.reader("select_share_pct")(obs) == pytest.approx(
        100 * dsa / busy, rel=1e-6)
    assert bench.reader("select_pick_share_pct")(obs) == pytest.approx(
        100 * 2 * 0.012 / busy, rel=1e-6)
    # a 768-row chunk at 40 k: 768 x 2048 selected pairs, 32 heads x 512
    # FLOPs, 12 layers = 0.31 TFLOP = 1.57 ms at peak, against 60 ms of a
    # kernel that visits all 31 M pairs and masks: 2.6 %, about topk / ctx
    pairs = 768 * 40000 + 768 * 769 // 2
    ideal = 12 * 768 * 2048 * 32 * 4 * 128 / 197e12
    assert 12 * (pairs / 768) * 2048 / 819e9 < ideal       # compute-bound
    assert bench.reader("select_prefill_roofline")(obs) == pytest.approx(
        100 * ideal / 0.060, rel=1e-6)
    # one-token rows: 7, 8, 7 rows x 2048 selected x 2,048 B x 12 layers
    need = 22 * 2048 * 2048 * 12 / 819e9
    assert bench.reader("select_decode_roofline")(obs) == pytest.approx(
        100 * need / (3 * 0.009), rel=1e-6)
    # the indexer: the rows' contexts as keys + the chunks' scores
    floor = 22 * 30000 * 128 * 12 / 819e9 \
        + 2 * 12 * pairs * 16 * 64 * 2 / 197e12
    assert bench.reader("select_score_roofline")(obs) == pytest.approx(
        100 * floor / (2 * 0.016 + 0.006), rel=1e-6)
    kept = 4 * (768 + 7) * 2048 + 8 * 2048
    seen = 4 * (pairs + 7 * 30000) + 8 * 30000
    assert bench.reader("dsa_kept_pct")(obs) == pytest.approx(
        100 * kept / seen, rel=1e-6)
    assert bench.reader("index_bytes_per_token")(obs) == 12 * 64 * 2


@pytest.mark.parametrize("name", NEW)
def test_a_new_reader_reads_nothing_where_there_is_nothing(tiny_cell, name):
    """No trace (the CPU); a program without the scopes, the record's counts
    or the third pool (the parent, every model without an indexer):
    ``None``, not 0, and nothing raised."""
    family = spec.Bench().family({"model_type": "KeyeVL2"})
    bench = spec.Bench()
    obs, m = tiny_cell
    if name not in ("dsa_kept_pct", "index_bytes_per_token"):
        assert name not in m and bench.reader(name)(obs) is None
    assert bench.reader(name)({**obs, "stages": [], "rounds": [],
                               "engine": None}) is None
    parent = traced_obs(spec.Bench().family({"model_type": "olmoe"}),
                        scopes=False, dsa=False)
    parent["config"] = spec.Bench().config("olmoe-1b-7b-d10")
    assert bench.reader(name)(parent) is None
