"""The ``minicpm_sala`` configuration, its cell and its readers: the file
against the catalog row and the program's preset; the cut's arithmetic; the
family's counts against a hand count; the reference's logits unembedded on
demand; the mix; the six new readers (five with an entry) on hand-made
traces and on nothing."""
import json
import types

import numpy as np
import pytest

from benchmark import spec

CELL, CONFIG, MIX = "sala-docs-sat", "minicpm-sala-d12", "docs-64k-sat"
NEW = ["select_share_pct", "select_score_roofline", "select_prefill_roofline",
       "state_share_pct.p95", "state_chunk_p95_roofline"]
# the sixth reader ISSUE 59 wanted, and the state step's roofline, got their
# entries when PR 62 folded the list by the kind of layer (127 -> 111)
READERS = NEW + ["select_decode_roofline", "state_decode_p95_roofline"]
V5E = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
REDUCED = ["mixer_types", "num_hidden_layers"]
LS, LL = 3, 9       # sparse and lightning layers of the cut
STATE = 32 * 128 * 128 * 4          # a sequence's state in one layer: 2 MiB
CALL = ('%{name}.1 = f32[8,4]{{1,0}} custom-call(%a), '
        'custom_call_target="tpu_custom_call"')


@pytest.fixture(scope="module")
def family():
    return spec.Bench().family({"model_type": "minicpm_sala"})


def _catalog_row():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    try:
        rows = [json.loads(ln) for ln in open(catalog)]
    except OSError:
        pytest.skip("the catalog is not on this machine")
    return next(r for r in rows if r["name"] == "MiniCPM-SALA")


# ------------------------------------------------- the file and the preset
def test_the_configuration_is_the_source_with_every_width_unchanged():
    row = _catalog_row()
    cfg = spec.Bench().config(CONFIG)
    assert cfg["source"] == row["source_url"]
    assert sorted(cfg["reduced"]) == REDUCED and "layer_shared_by" not in cfg
    for key, value in row["config"].items():
        if key not in REDUCED:
            assert cfg[key] == value, key
    published = row["config"]["mixer_types"]
    assert [i for i, m in enumerate(published) if m == "minicpm4"] \
        == [0, 9, 16, 17, 22, 29, 30, 31]
    for key, (pub, run) in {"num_hidden_layers": (32, 12),
                            "mixer_types": (published,
                                            published[9:21])}.items():
        cut = cfg["reduced"][key]
        assert (cut["published"], cut["run"], cut["counts"]) \
            == (pub, run, "layers")
        assert cut["published"] == row["config"][key] and cfg[key] == run
    assert cfg["mixer_types"].count("minicpm4") == 3
    # the widths ISSUE 59 names, as published
    assert (cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"],
            cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["head_dim"], cfg["lightning_nh"], cfg["lightning_nkv"],
            cfg["lightning_head_dim"]) \
        == (4096, 16384, 73448, 32, 2, 128, 32, 32, 128)
    assert (cfg["scale_emb"], cfg["scale_depth"], cfg["mup_denominator"],
            cfg["dim_model_base"]) == (12, 1.4, 32, 256)
    assert not cfg["attn_use_rope"] and cfg["lightning_use_rope"]
    assert set(cfg["assumed"]) >= {
        "sparse", "blocks_read", "dense_len", "scores", "decay", "lightning",
        "mup", "state", "weights", "dtype", "kv_pool", "prefix_cache"}
    assert cfg["engine"] == {
        "max_context": 99072, "max_sequences": 8, "num_blocks": 12544,
        "block_size": 64, "max_tokens_per_batch": 768,
        "prefill_attn": "kernel", "decode_attn": "pallas"}
    assert cfg["policy"] == {"admission": "none",
                             "preempt_policy": "requeue"}
    assert (cfg["path"], cfg["dtype"], cfg["preset"]) \
        == ("serve", "bfloat16", "minicpm-sala")
    assert cfg["overrides"] == {"num_layers": 24,
                                "layer_pattern": "*FLFLFLFLFLFLF*F*FLFLFLF"}
    assert "three pipeline stages" in cfg["deployment"]


def test_the_preset_has_the_published_widths_and_the_cut_its_arithmetic(
        family):
    from deepspeedsyclsupport_tpu.models import get_config

    whole = get_config("minicpm-sala")
    want = family.program_widths(_catalog_row()["config"])
    assert {k: getattr(whole, k) for k in want} == want
    cfg = spec.Bench().config(CONFIG)
    cut = get_config("minicpm-sala", **cfg["overrides"])
    want = family.program_widths(cfg)
    assert {k: getattr(cut, k) for k in want} == want
    assert want["layer_pattern"] == cfg["overrides"]["layer_pattern"]
    assert (cut.num_kv_layers, cut.state_layers, cut.pattern_count("F")) \
        == (LS, LL, 12)
    # r stays the PUBLISHED depth's
    assert cut.residual_scale == whole.residual_scale \
        == pytest.approx(1.4 / 32 ** 0.5)
    # ISSUE 59's arithmetic: 3,930 M parameters = 7.32 GiB resident in bf16
    assert cut.param_count() / 1e6 == pytest.approx(3930, abs=2)
    assert cut.param_count() * 2 / 2**30 == pytest.approx(7.32, abs=0.01)
    # the state: 2 MiB a layer, 18 MiB a sequence, 162 MiB for 9 slots
    a = family.arch(cfg)
    assert family.la_state_bytes(a) == STATE == 2 * 2**20
    slots = cfg["engine"]["max_sequences"] + 1
    assert slots * LL * STATE / 2**20 == 162
    # a cached token: three layers, 2 heads of 128, K and V in bf16, and a
    # pooled key a KV head every 16 tokens
    token = LS * 2 * 128 * 2 * 2
    assert token == 3072 and LS * 2 * 128 * 2 // 16 == 96
    engine = cfg["engine"]
    kv = engine["num_blocks"] * 64 * (token + 96)
    assert engine["num_blocks"] * 64 * token / 2**30 == pytest.approx(
        2.30, abs=0.01)
    assert engine["max_context"] == 1548 * 64 == 98304 + 768
    assert 8 * 1548 <= engine["num_blocks"]
    total = cut.param_count() * 2 + slots * LL * STATE + kv
    assert total / 2**30 == pytest.approx(9.85, abs=0.02)


def test_the_familys_counts_against_a_hand_count(family):
    hf = {"model_type": "minicpm_sala", "hidden_size": 64,
          "num_hidden_layers": 4,
          "mixer_types": ["minicpm4"] + ["lightning-attn"] * 3,
          "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
          "lightning_nh": 2, "lightning_nkv": 2, "lightning_head_dim": 8,
          "vocab_size": 512, "intermediate_size": 96, "rms_norm_eps": 1e-6,
          "rope_theta": 10000, "scale_emb": 12, "scale_depth": 1.4,
          "mup_denominator": 32, "dim_model_base": 16,
          "sparse_config": {"kernel_size": 4, "kernel_stride": 2,
                            "block_size": 8, "topk": 4, "init_blocks": 1,
                            "window_size": 16, "dense_len": 72}}
    a = family.arch(hf)
    assert family.layer_pattern(hf) == "*FLFLFLF"
    assert family.layer_counts(a) == (1, 3)
    assert (a["blocks_read"], a["window"], a["residual_scale"]) \
        == (6, 2, pytest.approx(1.4 / 32 ** 0.5))
    assert family.bsa_score_flops(a) == 4 * 16 * 2
    assert family.bsa_attend_flops(a) == 2 * 16 * 4
    assert family.bsa_page_bytes(a) == 8 * 16 * 2 * 2
    assert family.bsa_pool_bytes(a) == 16 * 2
    assert family.la_step_flops(a) == 4 * 2 * 8 * 8
    assert family.la_state_bytes(a) == 2 * 8 * 8 * 4
    assert family.la_row_bytes(a) == 4 * 16 * 4
    params = (3 * 64 * 64 + 2 * 64 * 32) + 3 * 5 * 64 * 16 \
        + 4 * 3 * 64 * 96 + 64 * 512
    assert family.matmul_params(a) == params
    assert family.train_flops_per_token(a, 8) == 6 * params \
        + 3 * 4 * 16 * 4 * 4.5 + 3 * 4 * 2 * 64 * 3
    # at the cell's widths: ISSUE 59's counts
    cell = family.arch(spec.Bench().config(CONFIG))
    assert family.bsa_score_flops(cell) == 32 * 128 * 2
    assert family.bsa_attend_flops(cell) == 16 * 128 * 4
    assert family.bsa_page_bytes(cell) == 64 * 128 * 2 * 2
    assert family.bsa_pool_bytes(cell) == 256
    assert family.la_step_flops(cell) == 32 * 4 * 128 * 128
    assert (cell["blocks_read"], cell["window"], cell["dense_len"]) \
        == (96, 32, 8192)


def test_the_references_logits_are_unembedded_when_asked_for(family):
    """``Logits``: the rows a caller slices are ``h[rows] @ W`` in float32
    at the highest precision, ``np.asarray`` all of them a block of rows at
    a time, and the object passes out of a ``jit`` as the arrays do."""
    import jax
    import jax.numpy as jnp

    rng = np.random.default_rng(0)
    h = jnp.asarray(rng.standard_normal((37, 8)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((8, 20)), jnp.bfloat16)
    want = np.asarray(h, np.float64) @ np.asarray(w.astype(jnp.float32),
                                                  np.float64)
    lazy = jax.jit(lambda a, b: family.Logits(a, b))(h, w)
    assert isinstance(lazy, family.Logits)
    assert lazy.shape == (37, 20) and len(lazy) == 37
    np.testing.assert_allclose(np.asarray(lazy), want, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(lazy[5:-1]), want[5:-1], rtol=1e-5,
                               atol=1e-5)
    inside = jax.jit(lambda a, b: family.Logits(a, b)[-3:])(h, w)
    np.testing.assert_allclose(np.asarray(inside), want[-3:], rtol=1e-5,
                               atol=1e-5)
    old, family.ROW_BLOCK = family.ROW_BLOCK, 16
    try:
        np.testing.assert_allclose(np.asarray(lazy, np.float32), want,
                                   rtol=1e-5, atol=1e-5)
    finally:
        family.ROW_BLOCK = old


def test_the_benchmark_is_sound_with_the_new_entries():
    bench = spec.Bench()
    assert bench.problems() == []
    cell = bench.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, MIX, 1)
    assert len(cell["why"]) <= 200
    assert bench._entry("configs", CONFIG)["reduced"] == REDUCED
    e2e = {m["name"] for m in bench.metrics_of(CELL, "end_to_end")}
    assert e2e == {"itl_p95_ms", "setup_s"}
    reports = {m["name"] for m in bench.metrics_of(CELL, "per_layer")}
    assert reports >= {"start_to_chip_s", *NEW}
    for m in bench.doc["per_layer"]:
        if m["name"] in READERS:
            assert CELL in m["workloads"] and m["moves"] == "itl_p95_ms"
            assert m["unit"] == "%" and m["source"] == "device_trace"
    assert reports >= set(READERS)
    assert sum(w["chips"] == 4 for w in bench.doc["workloads"]) == 1
    assert len(bench.doc["per_layer"]) <= 112 and len(
        bench.doc["workloads"]) == 14


def test_the_mix_is_the_issues_grid():
    """ISSUE 59's parameters, all of them: 8 callers on lanes, a grid of 8,
    its two distributions."""
    from benchmark import traffic

    bench = spec.Bench()
    mix, cfg = bench.traffic(MIX), bench.config(CONFIG)["engine"]
    pairs = traffic.length_pairs(mix, mix["count"])
    assert (mix["kind"], mix["clients"], len(pairs), mix["order"],
            mix["trace_seconds"]) == ("closed", 8, 8, "lanes", 50)
    assert mix["prompt_len"] == {"dist": "uniform", "min": 32768,
                                 "max": 98304}
    assert mix["output_len"] == {"dist": "uniform", "min": 256, "max": 768}
    assert sorted(p for p, _ in pairs) == list(range(36864, 94209, 8192))
    assert sorted(o for _, o in pairs) == list(range(288, 737, 64))
    assert sum(p for p, _ in pairs) / 8 == 65536
    assert mix["clients"] == cfg["max_sequences"]
    assert max(p + o for p, o in pairs) <= cfg["max_context"]
    # every context is past dense_len and many times what a query reads
    assert min(p for p, _ in pairs) >= 4 * 8192
    # the lanes: the 8 in flight are always the grid
    plan = traffic.ClosedPlan(mix, 7, 1000)
    first = sorted(len(plan.take(c)["tokens"]) for c in range(8))
    assert first == sorted(p for p, _ in pairs)


# --------------------------------------- the new readers, hand-made traces
def traced_obs(family, program, rows=768, ones=7, pos=65536, score_s=0.010,
               select_s=0.004, attend_s=0.060, rows_s=0.0006, pool_s=0.0003,
               proj_s=0.012, gate_s=0.004, step_s=0.0015, chunk_s=0.009,
               mlp_s=0.030, scopes=True):
    """``obs`` of a traced run at the CELL's widths: five rounds, the middle
    three traced, each launching one ``program``: a ``ragged_forward`` of a
    ``rows - ones``-row chunk at position ``pos`` beside ``ones`` one-token
    rows, or a ``decode_forward`` of ``ones`` rows; the device's counts of
    each forward on the record AFTER its own."""
    from benchmark import spans

    cfg = spec.Bench().config(CONFIG)
    ragged = program == "ragged_forward"
    chunk = rows - ones if ragged else 0
    pieces = -(-chunk // 128)
    blocks = pos // 64 + 1
    windows = (pos + 1 - 32) // 16 + 1
    atoms = -(-chunk // 128)
    counts = {
        "bsa_rows": LS * (chunk + ones),
        "bsa_windows": LS * (chunk + ones) * windows,
        "bsa_pairs": LS * 2 * (chunk + ones) * (95 * 64 + 33),
        # an atom's union: every visible page; a row's: its 96
        "bsa_pages": LS * 2 * (atoms * blocks + ones * 96),
        "bsa_visible_blocks": LS * 2 * (atoms + ones) * blocks,
        "bsa_row_pairs": LS * 2 * ones * (95 * 64 + 33),
        "bsa_row_pages": LS * 2 * ones * 96}
    offset, rounds, t = 5.0, [], 100.0
    for took in (0.150, 0.161, 0.172, 0.183, 0.194):
        rounds.append((t, t + took, 8, 0))
        t += took + 0.001
    ops_of = [("%fusion.1 = bf16[3,12544,4,2,128]{4,3,2,1,0} fusion(%x)",
               pool_s),
              ("%fusion.2 = f32[32,128,6192]{2,1,0} fusion(%x)", score_s),
              (CALL.format(name="bsa_select"), select_s),
              (CALL.format(name="bsa_prefill"), attend_s if ragged else 0),
              (CALL.format(name="bsa_rows"), rows_s),
              ("%fusion.3 = bf16[768,4096]{1,0} fusion(%x)", proj_s),
              ("%fusion.4 = f32[768,32,128]{2,1,0} fusion(%x)", gate_s),
              (CALL.format(name="ssm_state_step"), step_s),
              ("%fusion.5 = f32[32,128,128]{2,1,0} fusion(%x)",
               chunk_s if ragged else 0),
              ("%fusion.6 = bf16[768,16384]{1,0} fusion(%x)", mlp_s)]
    stages, host, modules, ops = [], [], [], []
    for i, (t0, t1, *_) in enumerate(rounds):
        stages.append({"name": "serve/stage", "data": {
            "stage": "round", "round": i, "t0": t0 + 1e-4, "t1": t1 - 1e-4,
            "launch_t": t0 + 0.0031, "tokens": chunk + ones,
            "program": program, "n_seqs": 8, "decode_rows": ones,
            "la_rows": chunk + ones, "la_pieces": LL * (pieces + ones),
            "la_first": 0, **(counts if i else {})}})
        if 1 <= i <= 3:
            at = t0 + offset
            host += [[spans.ROUND_SPAN, at, t1 - t0],
                     [f"PjitFunction({program})", at + 0.002, 0.001]]
            modules.append([f"jit_{program}(7)", at + 0.004, 0.145])
            start = at + 0.005
            for text, took in ops_of:
                if took:
                    ops.append([text, start, took])
                    start += took

    class Compiled:
        def as_text(self):
            if not scopes:
                return ""
            path = f'op_name="jit({program})/while/body/'
            lines = (("fusion.1", "bsa_pool/scatter"),
                     ("fusion.2", "bsa_score/while/body/reduce_max"),
                     ("bsa_select.1", "bsa_select/pallas_call"),
                     ("bsa_prefill.1", "bsa_attend/pallas_call"),
                     ("bsa_rows.1", "bsa_attend/bsa_rows/pallas_call"),
                     ("fusion.3", "la_proj/dot_general"),
                     ("fusion.4", "la_gate/mul"),
                     ("ssm_state_step.1", "la_scan/la_step/pallas_call"),
                     ("fusion.5", "la_scan/while/body/la_chunk/dot_general"),
                     ("fusion.6", "dot_general"))
            return "".join(
                f'  %{name} = f32[8,4]{{1,0}} fusion(%x), '
                f'metadata={{{path}{scope}"}}\n' for name, scope in lines)

    engine = types.SimpleNamespace(
        compiled_programs=lambda: {program: Compiled()},
        kv=types.SimpleNamespace(),
        state_stats=lambda: {"bytes_per_slot": LL * STATE, "slots": 8,
                             "slots_live": 8, "dtype": "float32",
                             "layers": LL})
    return {"trace": {"host": host, "devices": {"/device:TPU:0": {
                "modules": modules, "ops": ops}}},
            "trace_window": (rounds[1][0] + offset - 1e-3,
                             rounds[3][1] + offset + 1e-3),
            "window": (rounds[0][0], rounds[-1][1]),
            "rounds": rounds, "stages": stages, "engine": engine,
            "config": cfg, "peaks": V5E, "family": family, "counts": counts,
            "sizes": (chunk, pieces, blocks, windows, atoms)}


def test_the_readers_on_a_mixed_round_at_64k(family):
    """A 761-row chunk at 64 k beside 7 one-token rows: the shares by the
    scopes and the kernels' names; each roofline by the record's counts
    (the record AFTER the forward's own) over the time under its scopes;
    given the roofline's own time each reads 100."""
    bench = spec.Bench()
    obs = traced_obs(family, "ragged_forward")
    a, n = family.arch(obs["config"]), obs["counts"]
    chunk, pieces, blocks, windows, atoms = obs["sizes"]
    bsa = 0.0003 + 0.010 + 0.004 + 0.060 + 0.0006
    la = 0.012 + 0.004 + 0.0015 + 0.009
    busy = bsa + la + 0.030
    assert bench.reader("select_share_pct")(obs) == pytest.approx(
        100 * bsa / busy, rel=1e-6)
    assert bench.reader("state_share_pct.p95")(obs) == pytest.approx(
        100 * la / busy, rel=1e-6)
    # the scores: compute-bound at these widths
    fl = n["bsa_windows"] * 32 * 128 * 2
    by = n["bsa_visible_blocks"] * 4 * 256 + n["bsa_windows"] // 4 * 2 * 4
    ideal = max(fl / 197e12, by / 819e9)
    assert ideal == fl / 197e12
    assert bench.reader("select_score_roofline")(obs) == pytest.approx(
        100 * ideal / 0.010, rel=1e-6)
    # the atoms: the selection's pairs and the union's pages, less the rows'
    fl = (n["bsa_pairs"] - n["bsa_row_pairs"]) * 16 * 128 * 4
    by = (n["bsa_pages"] - n["bsa_row_pages"]) * 64 * 128 * 2 * 2
    ideal_p = max(fl / 197e12, by / 819e9)
    got = bench.reader("select_prefill_roofline")(obs)
    assert got == pytest.approx(100 * ideal_p / 0.060, rel=1e-6)
    assert 1 < got < 100
    # the pieces of the nine lightning layers, bound by their states
    fl = LL * chunk * family.la_step_flops(a)
    by = LL * (chunk * family.la_row_bytes(a) + 2 * pieces * STATE)
    ideal_c = max(fl / 197e12, by / 819e9)
    assert bench.reader("state_chunk_p95_roofline")(obs) == pytest.approx(
        100 * ideal_c / 0.009, rel=1e-6)
    # the decode reader reads decode_forward rounds alone
    assert bench.reader("select_decode_roofline")(obs) is None
    for name, kw, want in (
            ("select_score_roofline", dict(score_s=ideal), 100.0),
            ("select_prefill_roofline", dict(attend_s=ideal_p), 100.0),
            ("state_chunk_p95_roofline", dict(chunk_s=ideal_c), 100.0)):
        at_floor = traced_obs(family, "ragged_forward", **kw)
        assert bench.reader(name)(at_floor) == pytest.approx(want, rel=1e-6)


def test_the_decode_reader_on_a_decode_round(family):
    """8 rows at 64 k: 96 pages a row and KV head and the context's pooled
    keys in three layers over the HBM bandwidth, against the time under
    ``bsa_score`` + ``bsa_select`` + ``bsa_rows``; at the floor it reads
    100; a decode round has no atom and no piece."""
    bench = spec.Bench()
    obs = traced_obs(family, "decode_forward", ones=8, score_s=0.0012,
                     select_s=0.0005, rows_s=0.0009)
    n = obs["counts"]
    assert n["bsa_pages"] == n["bsa_row_pages"] == LS * 2 * 8 * 96
    need = n["bsa_pages"] * 32768 + n["bsa_windows"] * 2 * 256
    ideal = need / 819e9
    got = bench.reader("select_decode_roofline")(obs)
    assert got == pytest.approx(100 * ideal / 0.0026, rel=1e-6)
    at_floor = traced_obs(family, "decode_forward", ones=8, score_s=ideal,
                          select_s=0.0, rows_s=0.0)
    # (the row's kernel's time left out: the floor is the three's sum)
    assert bench.reader("select_decode_roofline")(at_floor) == pytest.approx(
        100.0, rel=1e-6)
    assert bench.reader("select_prefill_roofline")(obs) is None
    assert bench.reader("state_chunk_p95_roofline")(obs) is None
    assert bench.reader("state_bytes_per_seq")(obs) == LL * STATE
    # the lightning state step (PR 62's place for it): 8 rows' 2 MiB in nine
    # layers read and written, against the time under ``la_step`` (the
    # ``ssm_state_step`` call, by its name)
    step = 2 * LL * 8 * STATE / 819e9
    assert bench.reader("state_decode_p95_roofline")(obs) == pytest.approx(
        100 * step / 0.0015, rel=1e-6)
    assert bench.reader("state_decode_p95_roofline")(traced_obs(
        family, "decode_forward", ones=8, step_s=step)) == pytest.approx(
            100.0, rel=1e-6)
    busy = 0.0003 + 0.0012 + 0.0005 + 0.0009 + 0.012 + 0.004 + 0.0015 + 0.030
    assert bench.reader("select_pick_share_pct")(obs) == pytest.approx(
        100 * 0.0005 / busy, rel=1e-6)


@pytest.mark.parametrize("name", READERS)
def test_a_new_reader_reads_nothing_where_there_is_nothing(family, name):
    """No trace (the CPU); a program without the scopes, the counters or
    ``state_stats()`` (the parent); another family: ``None``, not 0, and
    nothing raised."""
    bench = spec.Bench()
    obs = traced_obs(family, "ragged_forward")
    untraced = {**obs, "trace": None, "stages": [], "engine": None}
    assert bench.reader(name)(untraced) is None
    parent = traced_obs(family, "ragged_forward", scopes=False, score_s=0,
                        select_s=0, attend_s=0, rows_s=0, step_s=0)
    for s in parent["stages"]:
        for field in [k for k in s["data"] if k.startswith(("bsa_", "la_"))]:
            del s["data"][field]
    parent["engine"] = types.SimpleNamespace(
        compiled_programs=parent["engine"].compiled_programs,
        kv=types.SimpleNamespace())
    assert bench.reader(name)(parent) is None
    # a delta-rule model's family on the same trace: it says no selection,
    # and its state's scopes are not this program's; a family that says no
    # kind at all
    for other in ("solar_open2", "olmoe"):
        other = spec.Bench().family({"model_type": other})
        assert bench.reader(name)({**obs, "family": other}) is None
