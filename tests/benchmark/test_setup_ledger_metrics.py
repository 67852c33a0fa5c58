"""The seven entries that read the program's set-up ledger (PR 51):
``setup_trace_s`` ... ``setup_warm_run_s`` through ``metrics/setup_ledger.py``
and ``train_remat_saved_gib``, on a hand-made ledger and window; and their
place in ``BENCHMARK.json``."""
import pytest

from benchmark import spec
from deepspeedsyclsupport_tpu.monitor import telemetry

BENCH = spec.Bench()
# the issue's table, in its order: name, unit, source, layer
TABLE = [
    ("setup_trace_s", "s", "program_span", "programs, traced"),
    ("setup_lower_s", "s", "program_span", "programs, lowered"),
    ("setup_compile_s", "s", "program_span", "programs, compiled or loaded"),
    ("setup_cache_miss_programs", "programs", "program_counter",
     "compile cache"),
    ("setup_engine_s", "s", "program_span", "engine build"),
    ("setup_warm_run_s", "s", "program_span", "engine warm-up"),
    ("train_remat_saved_gib", "GiB", "program_counter", "train engine")]
TRAIN_CELLS = ["mistral7b-train-1chip", "mistral7b-zero3-4chip"]
WINDOW = (100.0, 151.0)


def rec(phase, program, start, end, **kw):
    return {"kind": "compile", "t": end, "dur": end - start, "phase": phase,
            "program": program, **kw}


def span(sid, name, t0, t1, parent=None):
    return {"kind": "span", "id": sid, "name": name, "t0": t0, "t1": t1,
            "parent": parent, "fields": {}}


def remat(t, rung, saved, **kw):
    return {"kind": "decision", "t": t, "name": "remat", "rung": rung,
            "saved_bytes": saved, "auto": True, **kw}


# a set-up of 90 s before the window, and what the window and the tail do
LEDGER = [
    span(1, "engine", 10.0, 14.0), span(2, "state", 10.5, 13.5, 1),
    rec("trace", "init", 11.0, 11.5), rec("lower", "jit(init)", 11.5, 12.0),
    rec("compile", "jit(init)", 12.0, 13.0, cached=True),
    span(3, "first_step", 20.0, 60.0),
    rec("trace", "flash", 22.0, 26.0), rec("trace", "step", 21.0, 30.0),
    rec("lower", "jit(step)", 30.0, 36.0),
    rec("compile", "jit(step)", 36.0, 50.0, cached=False),
    remat(50.0, "attn+mlp", 3 * 2**29, stepped_down_from=None),
    remat(51.0, "attn", None, stepped_down_from="attn+mlp",
          saved_bytes_before=3 * 2**29),
    rec("compile", "jit(step)", 52.0, 58.0, cached=False),
    remat(59.0, "attn", 2**30),
    # after the window opened: not set-up
    rec("compile", "jit(late)", 120.0, 125.0, cached=False),
    span(4, "first_step", 160.0, 170.0),
    remat(171.0, "nothing_saveable", 0)]
WANT = {"setup_trace_s": 9.5, "setup_lower_s": 6.5, "setup_compile_s": 21.0,
        "setup_cache_miss_programs": 2,
        # engine 4 s less its tracing, lowering and load (2 s)
        "setup_engine_s": 2.0,
        # first_step 40 s less 9 + 6 + 14 + 6 of building
        "setup_warm_run_s": 5.0,
        "train_remat_saved_gib": 1.0}


@pytest.fixture
def program_with(monkeypatch):
    def fill(records):
        led = telemetry.SetupLedger()
        for r in sorted(records, key=lambda r: r.get("t", r.get("t0"))):
            led._append(dict(r))
        monkeypatch.setattr(telemetry, "setup_ledger_store", led)
    return fill


@pytest.mark.parametrize("name", [row[0] for row in TABLE])
def test_a_reader_counts_what_ended_before_the_window(name, program_with):
    program_with(LEDGER)
    read = BENCH.reader(name)
    assert read({"window": WINDOW}) == pytest.approx(WANT[name])
    # a window that opened before any of it: nothing was set-up
    early = read({"window": (5.0, 56.0)})
    assert early is None if name == "train_remat_saved_gib" else early == 0


@pytest.mark.parametrize("name", [row[0] for row in TABLE])
def test_a_program_without_a_ledger_reads_nothing(name, monkeypatch):
    """The parent commit under this PR's benchmark files."""
    monkeypatch.delattr(telemetry, "setup_summary")
    assert BENCH.reader(name)({"window": WINDOW}) is None


def test_the_seconds_are_disjoint_and_a_run_that_did_not_step_down_reads_its_rung(
        program_with):
    program_with(LEDGER[:10] + [remat(50.0, "attn+mlp", 1411 * 2**20)])
    obs = {"window": WINDOW}
    seconds = sum(BENCH.reader(name)(obs) for name, unit, *_ in TABLE
                  if unit == "s")
    # engine (4) + first_step (40): every second of both spans, once
    assert seconds == pytest.approx(44.0)
    assert BENCH.reader("train_remat_saved_gib")(obs) == pytest.approx(
        1411 / 1024)
    assert BENCH.reader("setup_cache_miss_programs")(obs) == 1


# 0.01-0.06 s of a ``setup_s`` of 8-106 s on every line the ledger holds: PR
# 62 retired the entry; the reader and the name's file stay, and read
RETIRED = "setup_engine_s"


def test_the_entries_stand_together_in_the_tables_order():
    """The last seven when PR 51 appended them, less the one PR 62 retired;
    a later PR appends behind."""
    assert BENCH.problems() == []
    table = [row for row in TABLE if row[0] != RETIRED]
    at = [m["name"] for m in BENCH.doc["per_layer"]].index(TABLE[0][0])
    last = BENCH.doc["per_layer"][at:at + 6]
    assert [(m["name"], m["unit"], m["source"], m["layer"]) for m in last] \
        == table
    assert RETIRED not in {m["name"] for m in BENCH.doc["per_layer"]}
    assert BENCH.resolved(RETIRED)[0] == "setup_ledger"
    for m in last[:5]:
        assert (m["moves"], m["better"]) == ("setup_s", "lower")
        assert "workloads" not in m        # every cell reports setup_s
        assert BENCH.resolved(m["name"])[0] == "setup_ledger"
    assert (last[5]["moves"], last[5]["better"], last[5]["workloads"]) == (
        "train_tok_s", "higher", TRAIN_CELLS)
    for cell in (w["name"] for w in BENCH.doc["workloads"]):
        names = [m["name"] for m in BENCH.metrics_of(cell, "per_layer")]
        assert set(names) >= {row[0] for row in table[:5]}
        assert ("train_remat_saved_gib" in names) == (cell in TRAIN_CELLS)
    for m in last:
        assert "mfu" not in m["name"] and not m["name"].endswith("_roofline")
