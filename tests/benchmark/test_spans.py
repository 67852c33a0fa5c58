"""``benchmark/spans.py`` and the readers of the program's ``round`` records:
the clock offset between the harness and the trace is recovered from paired
rounds and a run that does not fit is refused; each reader on the recorded
v5e trace joined with the records a session would have left beside it
(``data/decode_sat_v5e_rounds.json``); and the readers of the untraced
window on a real session at tiny size."""
import json
import types
from pathlib import Path

import pytest

from benchmark import spans, spec, trace

from . import tiny

DATA = Path(__file__).parent / "data"
BENCH = spec.Bench()


@pytest.fixture(scope="module")
def recorded():
    """``obs`` as ``benchmark.run`` leaves it after a traced run: the
    recorded trace, and beside it the harness's rounds and the session's
    records of the fixture."""
    tr = json.loads((DATA / "decode_sat_v5e.json").read_text())["trace"]
    fx = json.loads((DATA / "decode_sat_v5e_rounds.json").read_text())
    steps = [e for e in tr["host"] if e[0] == spans.ROUND_SPAN]
    kv = types.SimpleNamespace(k=types.SimpleNamespace(
        shape=(32, 9600, 32, 128), dtype=types.SimpleNamespace(itemsize=2)))
    return {"trace": tr, "rounds": [tuple(r) for r in fx["rounds"]],
            "stages": fx["stages"], "offset": fx["trace_minus_harness_s"],
            # the recorded harness opened its window 7 us into the first round
            "trace_window": (steps[0][1] - 1e-4, trace.window_of(tr)[1]),
            "window": (fx["rounds"][2][1], fx["rounds"][5][1]),
            "engine": types.SimpleNamespace(kv=kv),
            "peaks": {"hbm_bytes_per_s": 819e9}}


# ------------------------------------------------------------ the clocks
def test_a_known_clock_offset_is_recovered_from_paired_rounds(recorded):
    offset, k = spans.clock_offset(recorded["rounds"],
                                   recorded["trace"]["host"])
    assert k == 3                       # three rounds before the traced ones
    assert offset == pytest.approx(recorded["offset"], abs=1e-5)
    traced = spans.traced_rounds(recorded)
    assert [d["round"] for d in traced] == [104, 105, 106]
    steps = sorted(e for e in recorded["trace"]["host"]
                   if e[0] == spans.ROUND_SPAN)
    for d, (_name, start, dur) in zip(traced, steps):
        # acceptance: the program's record lies within 1 ms of the harness's
        # span of the same round, on the trace's clock
        assert d["t0"] == pytest.approx(start, abs=1e-3)
        assert d["t1"] == pytest.approx(start + dur, abs=1e-3)
        assert d["t0"] < d["launch_t"] < d["t1"]


def synthetic(durations, gap=0.001, start=100.0):
    rounds, t = [], start
    for d in durations:
        rounds.append((t, t + d, 4, 0))
        t += d + gap
    return rounds


def test_a_run_that_does_not_fit_is_refused():
    rounds = synthetic([0.081, 0.0805, 0.152, 0.0812, 0.079, 0.150])
    offset = -7.25
    host = [[spans.ROUND_SPAN, a + offset + 2e-6, b - a - 5e-6]
            for a, b, *_ in rounds[2:5]]
    assert spans.clock_offset(rounds, host) == (
        pytest.approx(offset, abs=1e-5), 2)
    # another run's spans: one round 2 ms longer than any run of the harness
    wrong = [list(e) for e in host]
    wrong[1][2] += 0.002
    with pytest.raises(ValueError, match="0 runs"):
        spans.clock_offset(rounds, wrong)
    # rounds as like as peas fit twice: refused, not guessed
    same = synthetic([0.08] * 6)
    with pytest.raises(ValueError, match="need exactly one"):
        spans.clock_offset(same, [[spans.ROUND_SPAN, a + 1.0, b - a]
                                  for a, b, *_ in same[:3]])
    with pytest.raises(ValueError, match="0 bench/serve_step"):
        spans.clock_offset(rounds, [])


def test_the_programs_own_launch_instants_confirm_the_offset(recorded,
                                                            capsys):
    """``launch_t`` is read by the program when its forward's dispatch
    returns: on the trace's clock it lies between the start of that round's
    forward ``PJRT_LoadedExecutable_Execute`` and 2 ms after its end
    (ISSUE 24's acceptance), in each traced round and so in their median."""
    host = recorded["trace"]["host"]
    traced = spans.traced_rounds(recorded)
    launches = sorted((e[1], e[1] + e[2]) for e in host
                      if e[0] == trace.LAUNCH)
    for d in traced:
        start, end = max(x for x in launches if x[0] <= d["launch_t"])
        assert end - start > 2e-4         # the forward's, not a slice's
        assert start <= d["launch_t"] <= end + 2e-3
    assert -1e-4 <= spans.launch_skew(traced, host) <= 2e-3
    assert spans.launch_skew([{**traced[0], "program": None}], host) is None
    # a record that times another instant than its forward's launch (here
    # the start and the end of the round) reads nothing, and says why
    for key in ("t0", "t1"):
        wrong = [{**s, "data": {**s["data"], "launch_t": s["data"][key]}}
                 for s in recorded["stages"]]
        assert spans.traced_rounds({**recorded, "stages": wrong}) is None
        assert "launch_t lies" in capsys.readouterr().err
    # ... and so does one that names a program the trace did not see
    wrong = [{**s, "data": {**s["data"], "program": "train_batch_fn"}}
             for s in recorded["stages"]]
    assert spans.traced_rounds({**recorded, "stages": wrong}) is None
    assert "no PjitFunction(train_batch_fn)" in capsys.readouterr().err


# ---------------------------------- each reader on the recorded v5e trace
def test_launches_per_round_counts_every_program_launch(recorded):
    starts = sorted(e[1] for e in recorded["trace"]["host"]
                    if e[0] == trace.LAUNCH)
    per_round = [sum(1 for s in starts if d["t0"] <= s <= d["t1"])
                 for d in spans.traced_rounds(recorded)]
    assert per_round == [103, 104, 104]
    assert BENCH.reader("launches_per_round")(recorded) == pytest.approx(
        311 / 3)
    assert BENCH.reader("launches_per_round.prefill")(recorded) \
        == pytest.approx(311 / 3)


def test_head_and_tail_idle_of_the_recorded_decode_rounds(recorded):
    """Read by hand off the trace (ISSUE 24): the host takes 14 ms to launch
    a decode round's forward and 17 ms after the forward ends to finish the
    round, with nothing on the chip."""
    module = BENCH._module("metrics", "round_idle_ms")
    idle = module.per_round(recorded)
    decode = [pair for pair, d in zip(idle, spans.traced_rounds(recorded))
              if d["program"] == "decode_forward"]
    assert len(idle) == 3 and len(decode) == 2
    for i, want in enumerate((0.014, 0.017)):
        assert sum(p[i] for p in decode) / 2 == pytest.approx(want, abs=1e-3)
    assert BENCH.reader("round_idle_head_ms")(recorded) == pytest.approx(
        14.13, abs=0.05)
    # no metric names the tail since PR 32 (0 by construction once a round
    # returns while its forward runs); the reader still takes either end
    assert module.read(recorded, end="tail") == pytest.approx(
        17.97, abs=0.05)
    with pytest.raises(FileNotFoundError):
        BENCH.reader("round_idle_tail_ms")
    # head + tail is the traced rounds' idle time: all but what the device
    # idles INSIDE its programs and between two rounds
    lo, hi = recorded["trace_window"]
    total = (hi - lo) - trace.busy_s(recorded["trace"], lo, hi)
    assert sum(h + t for h, t in idle) == pytest.approx(total, rel=0.10)


def test_paged_roofline_of_the_recorded_decode_rounds(recorded):
    """32 calls x 3520 (3552) tokens x 2 x 32 heads x 128 x 2 B = 1.85
    (1.86) GB at 819 GB/s = 2.25 (2.27) ms against the 4.54 (4.47) ms the
    32 custom calls of each decode_forward took."""
    want = 100 * (32 * (3520 + 3552) * 16384 / 819e9) / (
        0.004540834 + 0.004473471)
    assert BENCH.reader("paged_roofline")(recorded) == pytest.approx(want)
    assert 1 < want < 100


def test_window_readers_take_the_phase_groups_from_the_records(recorded):
    # the fixture's window holds rounds 104-106 (its harness rounds 3-5)
    records = spans.window_records(recorded)
    assert [d["round"] for d in records] == [104, 105, 106]
    groups = {g: BENCH.reader(f"round_{g}_ms")(recorded)
              for g in spans.GROUPS}
    mid = sorted(records, key=lambda d: d["phases"]["gather"])[1]
    assert groups["pre"] == pytest.approx(1e3 * sum(
        mid["phases"][p] for p in spans.GROUPS["pre"]))
    assert groups["launch"] == pytest.approx(1.0)
    assert groups["plan"] == pytest.approx(0.2 + 0.5 + 0.6)
    assert sorted(p for g in spans.GROUPS.values() for p in g) == sorted(
        records[0]["phases"])          # the groups partition the phases
    assert BENCH.reader("round_max_ms")(recorded) == pytest.approx(
        152.1, abs=0.1)
    # a record's time belongs to the forward the round BEFORE it launched
    # (the one it waits for): 104-106 each follow a decode_forward, and 107
    # follows 106's ragged_forward
    share = BENCH.reader("ragged_round_share_pct")
    assert share(recorded) == 0.0
    longer = {**recorded, "window": (recorded["window"][0],
                                     recorded["rounds"][6][1])}
    took = [d["t1"] - d["t0"] for d in spans.window_records(longer)]
    assert len(took) == 4
    assert share(longer) == pytest.approx(100 * took[3] / sum(took))
    assert BENCH.reader("share_ragged_rounds_pct.moe")(longer) \
        == share(longer)
    # a ring that dropped the record before the window's first charges that
    # one to no program and keeps it in the total
    kept = {**longer, "stages": [st for st in longer["stages"]
                                 if st["data"]["round"] >= 104]}
    assert share(kept) == share(longer)


def test_readers_say_so_and_read_nothing_where_records_are_missing(
        recorded, capsys):
    """A program from before the ``round`` record, and a ring that dropped
    its oldest records: ``None`` from every reader, and a line on stderr."""
    names = [m["name"] for m in BENCH.doc["per_layer"]
             if m["name"].startswith(("round_", "launches_", "ragged_round",
                                      "paged_roofline"))
             and not m["name"].startswith("round_p50_ms")]
    assert len(names) == 10
    # the six PR 62 retired (each under 4 % of a round on every line of the
    # ledger) keep their readers and their names' files, and are silent too
    names += [f"round_{g}_ms{cell}" for g in ("plan", "post", "idle_head")
              for cell in ("", ".prefill")]
    for stages in ([], recorded["stages"][4:]):
        obs = {**recorded, "stages": stages}
        for name in names:
            assert BENCH.reader(name)(obs) is None, name
            assert "no reading" in capsys.readouterr().err
    # and without a trace the trace's readers are silent, as every other
    assert BENCH.reader("paged_roofline")({**recorded, "trace": None}) is None
    assert capsys.readouterr().err == ""


# --------------------------------------------- a real session, tiny size
def test_a_tiny_closed_loop_cell_reports_the_round_metrics(tmp_path):
    bench = tiny.make_root(tmp_path)
    obs, m = tiny.drive(bench, "tiny-closed-cell", seed=5)
    assert obs["correct"]
    # two of the four groups are listed (PR 62 retired the other two's
    # entries); all four read, by the names' files
    assert {"round_pre_ms", "round_launch_ms"} <= set(m)
    groups = [bench.reader(f"round_{g}_ms")(obs) for g in spans.GROUPS]
    assert all(v >= 0 for v in groups)
    records = spans.window_records(obs)
    t0, t1 = obs["window"]
    assert len(records) == sum(1 for r in obs["rounds"] if t0 < r[1] <= t1)
    outside = []
    for d, (h0, h1, *_) in zip(
            records, [r for r in obs["rounds"] if t0 < r[1] <= t1]):
        # per record: the four groups are the round, which the harness's
        # own span of the same round brackets
        assert 1e3 * sum(d["phases"].values()) == pytest.approx(
            1e3 * (d["t1"] - d["t0"]), abs=1e-6)
        assert h0 <= d["t0"] and d["t1"] <= h1
        outside.append((h1 - h0) - (d["t1"] - d["t0"]))
    # ... closely: the record is written between the two (a busy test
    # machine may hold one round up, so the median, not each)
    assert sorted(outside)[len(outside) // 2] < 1e-3
    assert 0 < m["round_p50_ms"] <= m["round_max_ms"] * 1.001
    assert 0 < m["ragged_round_share_pct"] <= 100
    assert "launches_per_round" not in m and "paged_roofline" not in m
