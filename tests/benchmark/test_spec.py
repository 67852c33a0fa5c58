"""Every cell, configuration, traffic mix and metric of ``BENCHMARK.json``
is found by name and is sound; the contract's limits on names and units."""
import json

import pytest

from benchmark import spec

BENCH = spec.Bench()
DOC = BENCH.doc
CELLS = [w["name"] for w in DOC["workloads"]]
METRICS = DOC["end_to_end"] + DOC["per_layer"]


def test_the_benchmark_has_no_problems():
    assert BENCH.problems() == []


def test_top_level_keys_and_limits():
    assert set(DOC) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert DOC["command"] == ["python3", "-m", "benchmark.run"]
    assert 1 <= DOC["run_seconds"] <= 51
    assert len(json.dumps(DOC)) < 64 * 1024
    assert sum(w["chips"] == 4 for w in DOC["workloads"]) == 1
    assert all(len(w["why"]) <= 200 for w in DOC["workloads"])
    assert all(0.01 <= m["bound"] <= 0.1 for m in DOC["end_to_end"])


@pytest.mark.parametrize("cell", CELLS)
def test_cell_loads_by_name(cell):
    w = BENCH.cell(cell)
    cfg, mix = BENCH.config(w["config"]), BENCH.traffic(w["traffic"])
    assert cfg["path"] in spec.CONFIG_PATHS
    assert mix["kind"] in spec.TRAFFIC_KINDS
    # a serving path needs a serving mix, a training path training batches
    assert (cfg["path"] == "serve") == (mix["kind"] != "train-batches")
    assert w["chips"] == (cfg.get("train", {}).get("fsdp", 1))
    e2e = [m["name"] for m in BENCH.metrics_of(cell, "end_to_end")]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert BENCH.metrics_of(cell, "per_layer")


@pytest.mark.parametrize("config", [c["name"] for c in DOC["configs"]])
def test_configuration_names_its_source_cuts_and_assumptions(config):
    entry = BENCH._entry("configs", config)
    cfg = BENCH.config(config)
    assert cfg["source"] == entry["source"]
    assert sorted(cfg["reduced"]) == entry["reduced"]
    # what may be cut, and by how much, is ``spec.reduced_problems``' rule
    assert spec.reduced_problems(cfg) == []
    assert all(cut["counts"] in spec.CUTS for cut in cfg["reduced"].values())
    assert cfg["assumed"] and cfg["deployment"]


# ----------------------------------------------- what ``reduced`` may hold
# DeepSeek-V2 as the catalog has it (the numbers of its public config.json;
# no configuration of the benchmark): one leading dense layer, then 160
# routed experts a layer, 128 heads over one latent row, 102400 rows.
DEEPSEEK_V2 = {
    "source": "https://huggingface.co/deepseek-ai/DeepSeek-V2/blob/main/"
              "config.json",
    "model_type": "deepseek_v2", "first_k_dense_replace": 1,
    "hidden_size": 5120, "intermediate_size": 12288, "kv_lora_rank": 512,
    "moe_intermediate_size": 1536, "n_group": 8, "n_routed_experts": 160,
    "n_shared_experts": 2, "num_attention_heads": 128,
    "num_experts_per_tok": 6, "num_hidden_layers": 60,
    "num_key_value_heads": 128, "q_lora_rank": 1536, "qk_nope_head_dim": 128,
    "qk_rope_head_dim": 64, "topk_group": 3, "v_head_dim": 128,
    "vocab_size": 102400, "path": "serve", "assumed": {"weights": "noise"},
    "deployment": "one of the chips that share each layer of a replica"}
FAMILY_STUB = '''"""Stands where the PR that brings the share writes the
family: ``Bench.family`` asks for the four names only."""


def arch(hf):
    raise NotImplementedError


program_widths = sequence_logits = train_flops_per_token = arch
'''


def cut(cfg, shared_by=None, **cuts):
    """``cfg`` with ``cuts`` (``key=(run, counts)``) applied and listed under
    ``reduced`` beside the published values, shared by ``shared_by`` chips."""
    out = {**cfg, "reduced": {}}
    for key, (run, counts) in cuts.items():
        out["reduced"][key] = {"published": cfg[key], "run": run,
                               "counts": counts, "why": "it must fit"}
        out[key] = run
    if shared_by is not None:
        out["layer_shared_by"] = shared_by
    return out


def without(cfg, key, field):
    entry = {k: v for k, v in cfg["reduced"][key].items() if k != field}
    return {**cfg, "reduced": {**cfg["reduced"], key: entry}}


DEPTH = {"num_hidden_layers": (5, "layers")}
EXPERTS = {"n_routed_experts": (40, "experts")}
ROWS = {"vocab_size": (25600, "vocabulary")}
HEADS = {"num_attention_heads": (32, "heads"),
         "num_key_value_heads": (32, "heads")}
SHARE = cut(DEEPSEEK_V2, 4, **DEPTH, **EXPERTS, **ROWS)
# (configuration, the key and the reason its ONE sentence must name; none =
# accepted)
CUT_CASES = {
    "depth_alone": (cut(DEEPSEEK_V2, **DEPTH), None),
    "depth_with_its_dense_and_mtp_counts": (
        cut({**DEEPSEEK_V2, "first_k_dense_replace": 3,
             "num_nextn_predict_layers": 1}, **DEPTH,
            first_k_dense_replace=(1, "layers"),
            num_nextn_predict_layers=(0, "layers")), None),
    "a_quarter_of_experts_and_rows": (SHARE, None),
    "a_quarter_of_the_heads_too": (
        cut(DEEPSEEK_V2, 4, **DEPTH, **EXPERTS, **ROWS, **HEADS), None),
    "a_width_said_to_count_experts": (
        cut(DEEPSEEK_V2, 4, **DEPTH, **EXPERTS,
            moe_intermediate_size=(384, "experts")),
        ("moe_intermediate_size", "names a width")),
    "a_width_said_to_be_one": (
        cut(DEEPSEEK_V2, **DEPTH, moe_intermediate_size=(384, "width")),
        ("moe_intermediate_size", "a width is never cut")),
    "no_counts": (without(SHARE, "n_routed_experts", "counts"),
                  ("n_routed_experts", "a width is never cut")),
    "no_why": (without(SHARE, "vocab_size", "why"), ("vocab_size", "no why")),
    "a_share_of_no_stated_deployment": (
        cut(DEEPSEEK_V2, **DEPTH, **EXPERTS),
        ("n_routed_experts", "needs \"layer_shared_by\"")),
    "shared_by_three": (cut(DEEPSEEK_V2, 3, **DEPTH, **ROWS),
                        ("vocab_size", "N in (2, 4, 8, 16, 32)")),
    "not_the_nth_part": (
        cut(DEEPSEEK_V2, 4, **DEPTH, n_routed_experts=(32, "experts")),
        ("n_routed_experts", "32 x layer_shared_by 4 is not the published "
                             "160")),
    "four_experts_held": (
        cut({**DEEPSEEK_V2, "n_routed_experts": 128}, 32, **DEPTH,
            n_routed_experts=(4, "experts")),
        ("n_routed_experts", "the floor is 8")),
    "a_sixteenth_of_the_rows": (
        cut(DEEPSEEK_V2, 16, **DEPTH, vocab_size=(6400, "vocabulary")),
        ("vocab_size", "1/8 of the vocabulary")),
    "query_heads_alone": (
        cut(DEEPSEEK_V2, 4, **DEPTH, num_attention_heads=(32, "heads")),
        ("num_attention_heads", "cut together or not at all; "
                                "['num_key_value_heads'] stay whole")),
    "a_share_three_layers_after_the_dense_one": (
        cut(DEEPSEEK_V2, 4, **EXPERTS, num_hidden_layers=(4, "layers")),
        ("num_hidden_layers 4", "the 1 leading dense layers + 4")),
    "the_file_runs_another_number": (
        {**SHARE, "n_routed_experts": 20},
        ("n_routed_experts", "the file runs 20, the entry says run 40")),
    "nothing_cut": (cut(DEEPSEEK_V2, num_hidden_layers=(60, "layers")),
                    ("num_hidden_layers", "want file == run != published")),
}


def one_cell_bench(root, cfg, listed=None):
    """A ``Bench`` at ``root`` whose ``BENCHMARK.json`` has ONE cell of
    ``cfg``, written to a file of its own beside a stub of its family; the
    mix and the readers are the real ones, found under the real path."""
    for sub in ("configs", "families"):
        (root / "extra" / sub).mkdir(parents=True)
    (root / "extra" / "configs" / "share.json").write_text(json.dumps(cfg))
    (root / "extra" / "families" / "deepseek_v2.py").write_text(FAMILY_STUB)

    def here(section, names):
        return [{**m, "workloads": ["share-cell"]} for m in DOC[section]
                if m["name"] in names]

    doc = {**DOC, "paths": [str(spec.ROOT / "benchmark"), "extra"],
           "configs": [{
               "name": "share", "source": cfg["source"], "why": "a share",
               "file": "extra/configs/share.json",
               "reduced": sorted(cfg["reduced"]) if listed is None
               else listed}],
           "workloads": [{"name": "share-cell", "config": "share", "chips": 1,
                          "traffic": "chat-short-sat", "why": "a share"}],
           "end_to_end": here("end_to_end", ("serve_tok_s", "setup_s")),
           "per_layer": here("per_layer", ("live_seqs_mean",))}
    (root / "BENCHMARK.json").write_text(json.dumps(doc))
    return spec.Bench(root)


@pytest.mark.parametrize("case", CUT_CASES)
def test_reduced_takes_depth_and_a_chips_share_and_never_a_width(
        tmp_path, case):
    """The acceptance of ISSUE 32: DeepSeek-V2's cut (60 -> 5 layers, 40 of
    160 experts, 25600 of 102400 rows, each layer shared by 4, the leading
    dense layer untouched) passes, with or without a quarter of the heads;
    every other case is refused by ONE sentence that names the key and the
    reason, by ``problems()`` and again where ``benchmark.run`` loads it."""
    cfg, refused = CUT_CASES[case]
    bench = one_cell_bench(tmp_path, cfg)
    problems = bench.problems()
    if refused is None:
        assert problems == []
        assert bench.config("share")["reduced"] == cfg["reduced"]
        return
    key, reason = refused
    assert len(problems) == 1, problems
    assert problems[0].startswith("share: ")
    assert key in problems[0] and reason in problems[0]
    with pytest.raises(ValueError, match="configuration 'share'") as e:
        bench.config("share")
    assert key in str(e.value) and reason in str(e.value)


def test_the_documents_list_of_cuts_is_the_files(tmp_path):
    bench = one_cell_bench(tmp_path, SHARE, listed=["num_hidden_layers"])
    assert bench.problems() == [
        "share: BENCHMARK.json lists reduced ['num_hidden_layers'], the "
        "file's keys sorted are ['n_routed_experts', 'num_hidden_layers', "
        "'vocab_size']"]


@pytest.mark.parametrize("metric", METRICS, ids=lambda m: m["name"])
def test_metric_is_named_sourced_and_has_a_reader(metric):
    assert spec.NAME_RE.match(metric["name"])
    assert spec.UNIT_RE.match(metric["unit"])
    assert metric["source"] in spec.SOURCES
    assert callable(BENCH.reader(metric["name"]))
    allowed = {"name", "unit", "better", "source", "workloads"} | (
        {"bound"} if metric in DOC["end_to_end"] else {"layer", "moves"})
    assert set(metric) <= allowed
    if metric in DOC["end_to_end"]:
        assert metric["source"] in ("host_clock", "device_trace")


@pytest.mark.parametrize("metric", DOC["per_layer"], ids=lambda m: m["name"])
def test_what_a_layer_metric_moves_is_reported_wherever_it_is(metric):
    moved = BENCH._entry("end_to_end", metric["moves"])
    for cell in metric.get("workloads", CELLS):
        assert cell in moved.get("workloads", CELLS)


@pytest.mark.parametrize("bad", ["two words", "a,b", "a/b", "", "x" * 65,
                                 "-lead", "µs"])
def test_names_outside_the_allowed_characters_are_refused(bad):
    assert not spec.NAME_RE.match(bad)


@pytest.mark.parametrize("unit,ok", [("tokens/s", True), ("%", True),
                                     ("1/token", True), ("GiB", True),
                                     ("tokens per s", False), ("µs", False),
                                     ("", False), ("x" * 17, False)])
def test_units(unit, ok):
    assert bool(spec.UNIT_RE.match(unit)) == ok


def test_an_unknown_name_says_what_exists():
    with pytest.raises(KeyError, match="phi2-decode-sat"):
        BENCH.cell("no-such-cell")
    with pytest.raises(FileNotFoundError):
        BENCH.traffic("no-such-mix")


# ------------------------------------- the fold of PR 62, held from its data
# ``per_layer`` had one entry a reader and a moved metric, and a reader a
# KIND of layer in every family that has one (127); a reader now asks the
# cell's family what layers of the kind it has (111). New name: the names it
# took the place of. (PR 48's fold, one entry a cell -> one a reader and a
# moved metric, was held here from ``per_layer_pr47.json`` until PR 62.)
FOLDED = {
    "state_share_pct": "ssm_share_pct ret_share_pct kda_share_pct",
    "state_decode_roofline":
        "ssm_decode_roofline ret_decode_roofline kda_decode_roofline",
    "state_chunk_roofline":
        "ssm_chunk_roofline ret_chunk_roofline kda_chunk_roofline",
    "state_share_pct.p95": "la_share_pct",
    "state_chunk_p95_roofline": "la_chunk_roofline",
    "select_share_pct": "dsa_share_pct bsa_share_pct",
    "select_pick_share_pct": "dsa_select_share_pct",
    "select_score_roofline": "dsa_index_roofline bsa_score_roofline",
    "select_prefill_roofline": "dsa_prefill_roofline bsa_prefill_roofline",
    "select_decode_roofline": "dsa_decode_roofline"}
NEW_NAME = {old: new for new, olds in FOLDED.items() for old in olds.split()}
# under 4 % of the quantity beside them and the same on both sides of every
# line the ledger holds (PERF.md section 3): the entries went, their readers
# and alias files stay (``tools/bench_unlisted.py --readers`` reads them)
RETIRED = ("round_plan_ms", "round_post_ms", "round_idle_head_ms",
           "round_plan_ms.prefill", "round_post_ms.prefill",
           "round_idle_head_ms.prefill", "loop_exit_share_pct",
           "setup_engine_s")
# the one ``better`` that changed: a share of the busy time is better lower
BETTER = {"ret_share_pct": "lower"}
# the list as PR 61 left it, each entry with what its name ``resolved`` to on
# that tree. The three tests bind what the snapshot holds and nothing else:
# a cell or an entry it does not know may join or be whatever it likes
SNAPSHOT = spec.load_json(
    spec.ROOT / "tests/benchmark/data/per_layer_pr61.json")


def reads(stem, args, moves=None):
    """What an entry comes to, as something a set can hold."""
    return (stem, json.dumps(args, sort_keys=True), moves)


def test_the_table_is_the_nineteen_and_the_list_keeps_its_order():
    assert len(NEW_NAME) == 19 and len(FOLDED) == 10 and len(RETIRED) == 8
    assert len(SNAPSHOT) == 127
    was = list(dict.fromkeys(NEW_NAME.get(e["name"], e["name"])
                             for e in SNAPSHOT if e["name"] not in RETIRED))
    # the entries that stood alone keep name and place, a folded entry stands
    # where its oldest name stood; a later PR appends behind them
    assert [m["name"] for m in DOC["per_layer"]][:len(was)] == was
    assert len(was) == 127 - 8 - 9 and len(DOC["per_layer"]) <= 112


@pytest.mark.parametrize("old", [e["name"] for e in SNAPSHOT])
def test_an_old_entry_stands_or_the_table_says_what_stands_for_it(old):
    before, = [e for e in SNAPSHOT if e["name"] == old]
    if old in RETIRED:
        assert old not in {m["name"] for m in DOC["per_layer"]}
        assert BENCH.resolved(old) == (before["resolved"]["reader"],
                                       before["resolved"]["args"])
        assert callable(BENCH.reader(old))
        return
    after = BENCH._entry("per_layer", NEW_NAME.get(old, old))
    for key in ("unit", "source", "layer", "moves"):
        assert after[key] == before[key]
    assert after["better"] == BETTER.get(old, before["better"])
    assert ("workloads" in after) == ("workloads" in before)
    assert set(before.get("workloads", ())) <= set(after.get("workloads", ()))
    # in the order of the cells
    assert after.get("workloads", []) == sorted(after.get("workloads", []),
                                                key=CELLS.index)
    if old == after["name"]:
        assert BENCH.resolved(old) == (before["resolved"]["reader"],
                                       before["resolved"]["args"])
    else:
        with pytest.raises(KeyError):
            BENCH._entry("per_layer", old)
        with pytest.raises(FileNotFoundError):
            BENCH.reader(old)


@pytest.mark.parametrize("cell", sorted(
    {c for e in SNAPSHOT for c in e.get("workloads", ())}, key=CELLS.index))
def test_every_cell_reads_what_the_snapshot_read_there(cell):
    """Each quantity the snapshot reported in the cell, under its old name or
    the table's, the cell still reports, moving the same end-to-end metric,
    except the eight retired. ``>=``: joining an accepted entry is free."""
    def quantity(e):
        if e["name"] in NEW_NAME:
            return reads(*BENCH.resolved(NEW_NAME[e["name"]]), e["moves"])
        return reads(e["resolved"]["reader"], e["resolved"]["args"],
                     e["moves"])
    before = {quantity(e) for e in SNAPSHOT
              if cell in e.get("workloads", [cell])
              and e["name"] not in RETIRED}
    now = {reads(*BENCH.resolved(m["name"]), m["moves"])
           for m in BENCH.metrics_of(cell, "per_layer")}
    assert before <= now


def test_one_entry_a_reader_and_a_moved_metric():
    seen = [reads(*BENCH.resolved(m["name"]), m["moves"])
            for m in DOC["per_layer"]]
    assert len(set(seen)) == len(seen)
    # a share of a roofline is known by the END of its name, folded or not
    for m in DOC["per_layer"]:
        if BENCH.resolved(m["name"])[0].endswith("_roofline"):
            assert m["name"].endswith("_roofline") and m["unit"] == "%"


# ------------------------------------- the driver's limits, in problems()
def tiny_doc(tmp_path, keep=None):
    """``tiny.make_root``'s document cut down to its own cells (seven, one
    of them on four chips), or to ``keep`` of them: the configurations they
    use and the metrics that list them."""
    from . import tiny

    doc = tiny.make_root(tmp_path).doc
    keep = set(keep or (c[0] for c in tiny.CELLS))
    doc["workloads"] = [w for w in doc["workloads"] if w["name"] in keep]
    used = {w["config"] for w in doc["workloads"]}
    doc["configs"] = [c for c in doc["configs"] if c["name"] in used]
    for section in ("end_to_end", "per_layer"):
        for m in doc[section]:
            if "workloads" in m:
                m["workloads"] = [c for c in m["workloads"] if c in keep]
        doc[section] = [m for m in doc[section] if m.get("workloads", 1)]
    moved = {m["name"] for m in doc["end_to_end"]}
    doc["per_layer"] = [m for m in doc["per_layer"] if m["moves"] in moved]
    return doc


def more_entries(tmp_path, doc, names):
    """Entries of one reader under ``names``, each with args of its own (so
    that none is another's double), in the one cell that reader has."""
    like, = [m for m in doc["per_layer"] if m["name"] == "rounds_per_s"]
    for i, name in enumerate(names):
        (tmp_path / "extra" / "metrics" / f"{name}.json").write_text(
            json.dumps({"reader": "rounds_per_s", "args": {"i": i}}))
        doc["per_layer"].append({**like, "name": name})


def more_cells(doc, n):
    """``n`` cells more, each a twin of ``tiny-closed-cell``."""
    for i in range(n):
        doc["workloads"].append({
            "name": f"more-cell-{i}", "config": "tiny-serve", "chips": 1,
            "traffic": "tiny-closed", "why": "tiny"})
        for m in doc["end_to_end"] + doc["per_layer"]:
            if "tiny-closed-cell" in m.get("workloads", ()):
                m["workloads"].append(f"more-cell-{i}")


def four_chips(doc, cell):
    doc["workloads"] = [{**w, "chips": 4} if w["name"] == cell else w
                        for w in doc["workloads"]]


# (what is done to the tiny document, the ONE sentence's parts; none = sound)
LIMIT_CASES = {
    "the_128th_entry": (lambda tmp, d: more_entries(
        tmp, d, [f"more_{i}" for i in range(128 - len(d["per_layer"]))]),
        None),
    "a_129th_entry": (lambda tmp, d: more_entries(
        tmp, d, [f"more_{i}" for i in range(129 - len(d["per_layer"]))]),
        ("per_layer: 129 entries", "most is 128")),
    "the_24th_cell": (lambda tmp, d: more_cells(d, 24 - 7), None),
    "a_25th_cell": (lambda tmp, d: more_cells(d, 25 - 7),
                    ("workloads: 25 entries", "most is 24")),
    "a_name_of_64_letters": (lambda tmp, d: more_entries(tmp, d, ["x" * 64]),
                             None),
    "a_name_of_65_letters": (
        lambda tmp, d: more_entries(tmp, d, ["x" * 65]),
        ("per_layer: bad name 'xxxx", "at most 64 of A-Za-z0-9_.-")),
    "a_name_that_starts_with_a_dot": (
        lambda tmp, d: more_entries(tmp, d, [".hidden"]),
        ("per_layer: bad name '.hidden'", "starts with neither . nor -")),
    "a_second_four_chip_cell_among_seven": (
        lambda tmp, d: four_chips(d, "tiny-train-cell"),
        ("workloads: 2 cells of 7 ask for 4 chips",
         "['tiny-train-cell', 'tiny-zero3-cell']", "and one always may")),
    "a_second_four_chip_cell_among_eight": (
        lambda tmp, d: (more_cells(d, 1), four_chips(d, "tiny-train-cell")),
        None),
}


@pytest.mark.parametrize("case", LIMIT_CASES)
def test_the_contracts_limits_are_problems_too(tmp_path, case):
    """What the driver refuses a file for before a single run, a builder
    reads here first: each breach is ONE sentence of ``problems()``."""
    change, refused = LIMIT_CASES[case]
    doc = tiny_doc(tmp_path)
    assert len(doc["workloads"]) == 7
    change(tmp_path, doc)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(doc))
    problems = spec.Bench(tmp_path).problems()
    if refused is None:
        assert problems == []
        return
    assert len(problems) == 1, problems
    assert all(part in problems[0] for part in refused), problems


def test_one_four_chip_cell_always_may(tmp_path):
    doc = tiny_doc(tmp_path, keep=("tiny-closed-cell", "tiny-zero3-cell"))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(doc))
    assert [w["chips"] for w in doc["workloads"]] == [1, 4]
    assert spec.Bench(tmp_path).problems() == []


def test_a_double_of_an_entry_is_told_to_join_it(tmp_path):
    """What cost the list its room: a cell's own name for an accepted
    reader that moves what the accepted entry moves."""
    doc = tiny_doc(tmp_path)
    (tmp_path / "extra" / "metrics" / "rounds_per_s.mine.json").write_text(
        json.dumps({"reader": "rounds_per_s"}))
    like, = [m for m in doc["per_layer"] if m["name"] == "rounds_per_s"]
    doc["per_layer"].append({**like, "name": "rounds_per_s.mine"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(doc))
    problem, = spec.Bench(tmp_path).problems()
    assert problem.startswith("rounds_per_s.mine: the reader (rounds_per_s")
    assert "of 'rounds_per_s'" in problem and "append the cell" in problem
