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
