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
    assert sorted(cfg["reduced"]) == sorted(entry["reduced"])
    for key, cut in cfg["reduced"].items():
        assert cfg[key] == cut["run"] != cut["published"] and cut["why"]
        # a width is never cut: only depth
        assert key == "num_hidden_layers"
    assert cfg["assumed"] and cfg["deployment"]


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
