"""The serving round's own spans (``serving.RoundSpans``, the ``round`` stage
record, ``reqtrace.ROUND_PHASES``): the phases partition every round, the
record's counters equal a hand count, the launch instant lies where it says,
and with ``trace_stages`` off nothing is written and nothing annotated. A
real session on the CPU sim, tiny model."""
import os
import subprocess
import sys
import time
from collections import deque

import jax
import jax.numpy as jnp
import pytest

from deepspeedsyclsupport_tpu.analysis import codelint
from deepspeedsyclsupport_tpu.inference.v2 import (
    InferenceEngineV2, ServingPolicyConfig, ServingSession)
from deepspeedsyclsupport_tpu.inference.v2.serving import RoundSpans
from deepspeedsyclsupport_tpu.inference.v2.supervisor import journal_path
from deepspeedsyclsupport_tpu.models import build_model
from deepspeedsyclsupport_tpu.monitor import reqtrace

PROMPTS = [(1, [1, 2, 3]), (2, list(range(4, 30))), (3, [7, 8, 9])]


@pytest.fixture(scope="module")
def tiny():
    model = build_model("tiny", dtype="float32")
    return model, model.init_params()


def _engine(tiny, **kw):
    model, params = tiny
    return InferenceEngineV2(model, params, dtype=jnp.float32, block_size=8,
                             max_context=64, max_tokens_per_batch=16,
                             max_sequences=4, **kw)


def _serve(eng, policy=None, budget=6):
    """Three requests to idle (the 26-token prompt takes two chunks, so the
    first rounds are mixed); the session and every record it kept."""
    sess = ServingSession(eng, policy or ServingPolicyConfig(admission="none"))
    for uid, prompt in PROMPTS:
        assert sess.submit(uid, prompt, budget) == "admitted"
    for _ in range(200):
        if sess.idle:
            break
        sess.step()
    assert sess.idle
    return sess, sess.drain_trace()


def _rounds(records):
    return [r["data"] for r in records
            if r["data"].get("stage") == "round"]


@pytest.fixture(scope="module")
def per_token_run(tiny):
    return _serve(_engine(tiny))


PATHS = {
    "per_token": lambda d: d["program"] == "decode_forward",
    "mixed": lambda d: d["program"] == "ragged_forward"
    and 0 < d["prefill_tokens"] < d["tokens"],
}


@pytest.mark.parametrize("path", sorted(PATHS))
def test_phases_partition_every_round(per_token_run, path):
    _sess, records = per_token_run
    rounds = [d for d in _rounds(records) if PATHS[path](d)]
    assert rounds, f"the drive made no {path} round"
    for d in rounds:
        assert set(d["phases"]) <= set(reqtrace.ROUND_PHASES)
        assert sum(d["phases"].values()) == pytest.approx(
            d["t1"] - d["t0"], rel=0.01)
        assert d["t0"] <= d["launch_t"] <= d["t1"]
    # a round that sampled passed through every phase of the registry
    full = [d for d in rounds if d["uids"]]
    assert full and all(
        set(d["phases"]) == set(reqtrace.ROUND_PHASES) for d in full)


def test_one_record_per_round_and_its_spans_name_it(per_token_run):
    sess, records = per_token_run
    rounds = _rounds(records)
    assert [d["round"] for d in rounds] == list(range(1, sess._round + 1))
    assert all(b["t0"] >= a["t1"] for a, b in zip(rounds, rounds[1:]))
    by_no = {d["round"]: d for d in rounds}
    emits = [r["data"] for r in records if r["name"] == "serve/emit"]
    chunks = [r["data"] for r in records
              if r["data"].get("stage") == "prefill_chunk"]
    assert emits and chunks
    for e in emits:   # the round that sampled the token lists its request
        assert e["uid"] in by_no[e["round"]]["uids"]
    for c in chunks:  # ... and a prompt chunk's round ran its forward (a
        # last chunk of ONE token counts as a decode step there, by the
        # rule the engine itself routes forwards by)
        d = by_no[c["round"]]
        assert d["program"] and d["tokens"] >= c["tokens"]
        assert c["tokens"] == 1 or d["prefill_tokens"] >= c["tokens"]
    # no record times an asynchronous dispatch as if it were the work
    assert not any("dur" in d for d in rounds + chunks)
    assert not [r for r in records
                if r["data"].get("stage") == "decode_round"]
    # the join still counts each request's rounds, now from ``round``
    traces = reqtrace.join_traces([("0", "", records)])
    assert all(tr["rounds"] == 6 for tr in traces.values())


def test_context_and_block_counters_equal_a_hand_count(tiny):
    """Three sequences of 5, 9 and 2 cached tokens in blocks of 8: the
    decode forward after their prompts reads 16 tokens of context through
    1 + 2 + 1 blocks (the 9-token one was given its second block for the
    prompt; nobody needs a new block for this token)."""
    eng = _engine(tiny)
    sess = ServingSession(eng, ServingPolicyConfig(admission="none"))
    for uid, n in [(1, 5), (2, 9), (3, 2)]:
        sess.submit(uid, list(range(1, n + 1)), 4)
    sess.step()                      # the prompts: 16 tokens, one forward
    sess.step()                      # first tokens sampled, decode forward
    first, second = _rounds(sess.drain_trace())
    assert (first["program"], first["n_seqs"], first["tokens"],
            first["prefill_tokens"], first["ctx_tokens"]) == (
        "ragged_forward", 3, 16, 16, 0)
    assert first["kv_blocks"] == 1 + 2 + 1
    assert (second["program"], second["n_seqs"], second["tokens"],
            second["prefill_tokens"]) == ("decode_forward", 3, 3, 0)
    assert second["ctx_tokens"] == 5 + 9 + 2
    assert second["kv_blocks"] == sum(
        len(eng.seqs[u].blocks) for u in (1, 2, 3)) == 4
    # just what has a reader: the join, the report's tables, the benchmark
    assert set(second) == {"uid", "stage", "round", "t0", "t1", "launch_t",
                           "program", "uids", "phases",
                           *reqtrace.FORWARD_FIELDS}
    sess.close()


@pytest.mark.parametrize("program", ["decode_forward", "ragged_forward"])
def test_the_record_counts_the_tiles_attention_ran(tiny, program):
    """Through the kernel path (interpreted, atoms of 8 rows): two short
    prompts, then a 10-token prompt while they decode, then all three
    decode. A one-token chunk is a one-row tile in either program; only a
    ``ragged_forward``'s longer chunks are atoms, and the tiles' rows hold
    the tokens."""
    bq = 8
    eng = _engine(tiny, prefill_attn="kernel_interpret",
                  decode_attn="pallas_interpret", atom_q_size=bq)
    sess = ServingSession(eng, ServingPolicyConfig(admission="none"))
    sess.submit(1, [1, 2, 3], 6)
    sess.submit(2, [4, 5, 6, 7, 8], 6)
    sess.step()                      # 3 + 5 prompt tokens: two atoms
    sess.submit(3, list(range(10, 20)), 6)
    sess.step()                      # two decode rows + 10 prompt tokens
    sess.step()                      # three decode rows
    prompts, mixed, decode = _rounds(sess.drain_trace())
    if program == "ragged_forward":
        assert [(d["program"], d["tokens"], d["decode_rows"], d["atoms"])
                for d in (prompts, mixed)] == [
            ("ragged_forward", 8, 0, 2), ("ragged_forward", 12, 2, 2)]
    else:
        assert (decode["program"], decode["tokens"], decode["decode_rows"],
                decode["atoms"]) == ("decode_forward", 3, 3, 0)
    for d in (prompts, mixed, decode):
        assert d["atoms"] * bq + d["decode_rows"] >= d["tokens"]
        assert d["decode_rows"] == d["tokens"] - d["prefill_tokens"]
    sess.close()


@pytest.mark.parametrize("case", ["dead_gap", "one", "two_calls", "decode"])
def test_the_record_counts_the_tiles_that_started_warm(tiny, case):
    """``warm_tiles``: of a round's attention tiles, those that stand
    behind a live tile of their own kernel call, so that it fetched their
    first KV step: the live tiles less one a call and one a dead gap."""
    eng = _engine(tiny, prefill_attn="kernel_interpret",
                  decode_attn="pallas_interpret", atom_q_size=8)
    sess = ServingSession(eng, ServingPolicyConfig(admission="none"))

    def step():                     # the round's record, drained
        sess.step()
        return _rounds(sess.drain_trace())[-1]

    if case == "one":               # a batch of one: its tile starts cold
        sess.submit(1, [1, 2, 3], 4)
        rec = step()
        assert (rec["atoms"], rec["warm_tiles"]) == (1, 0)
        rec = step()
        assert (rec["program"], rec["decode_rows"],
                rec["warm_tiles"]) == ("decode_forward", 1, 0)
    else:
        sess.submit(1, [1, 2, 3], 8)
        sess.submit(2, [4, 5, 6, 7, 8], 8)
        rec = step()                # two atoms of one call: the second warm
        assert (rec["atoms"], rec["warm_tiles"]) == (2, 1)
        if case == "decode":        # three rows of one call, compact
            sess.submit(3, [9, 10], 8)
            step()
            rec = step()
            assert (rec["program"], rec["decode_rows"],
                    rec["warm_tiles"]) == ("decode_forward", 3, 2)
        elif case == "two_calls":   # two rows | two atoms: one warm in each
            sess.submit(3, list(range(10, 20)), 8)
            rec = step()
            assert (rec["decode_rows"], rec["atoms"],
                    rec["warm_tiles"]) == (2, 2, 2)
        else:
            # the one-row call's grid is the batch's slots: a prompt chunk
            # BETWEEN two decoding sequences leaves its slot dead there
            from deepspeedsyclsupport_tpu.ops.paged_attention import (
                warm_tiles)
            from deepspeedsyclsupport_tpu.inference.v2.ragged import (
                SequenceDescriptor, build_ragged_batch)
            one, two = (SequenceDescriptor(uid, pending=[7], n_cached=n,
                                           blocks=[uid])
                        for uid, n in ((1, 3), (2, 5)))
            newcomer = SequenceDescriptor(3, pending=list(range(10, 20)))
            batch = build_ragged_batch(
                [(one, 1), (newcomer, 10), (two, 1)],
                16, 4, eng.config.blocks_per_seq, atom_q=8)
            assert (batch.dec_len > 0).tolist() == [True, False, True, False]
            assert warm_tiles(batch.dec_len > 0) == 0
            assert warm_tiles(batch.atom_qlen > 0) == 1
    sess.close()


@pytest.mark.parametrize("polls", [1, 50])
def test_a_round_that_begins_with_no_work_writes_no_record(tiny, monkeypatch,
                                                           polls):
    """A loop that polls ``step()`` while nothing is live or queued (the
    serving loop's pattern before the first request and between bursts)
    leaves the ring and the journal as they were, and opens no span."""
    seen = Annotations()
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", seen)
    sess = ServingSession(_engine(tiny), ServingPolicyConfig(admission="none"))
    for _ in range(polls):
        assert sess.step() == []
    assert list(sess.trace_log) == [] and seen.spans == []
    # the first round with work is recorded whole, and so is the last
    sess.submit(1, [1, 2, 3], 2)
    while not sess.idle:
        sess.step()
    done = sess._round
    for _ in range(polls):
        sess.step()
    rounds = _rounds(sess.drain_trace())
    assert [d["round"] for d in rounds] == list(range(polls + 1, done + 1))
    assert rounds[0]["program"] == "ragged_forward" and rounds[-1]["uids"]
    assert all(sum(d["phases"].values()) == pytest.approx(
        d["t1"] - d["t0"], rel=0.01) for d in rounds)
    sess.close()


def test_the_forward_programs_carry_the_names_they_are_dispatched_by(
        per_token_run):
    """What the device trace's ``XLA Modules`` line and the host's
    ``PjitFunction`` span print: ``jit_<name>``, not ``jit__unknown``."""
    sess, _records = per_token_run
    programs = sess.eng.compiled_programs()
    assert set(programs) == {"ragged_forward", "decode_forward"}
    for name, compiled in programs.items():
        assert compiled.as_text().startswith(f"HloModule jit_{name},")


class Annotations:
    """Stands in for ``jax.profiler.TraceAnnotation``: every span opened,
    with its ends on the session's clock. The engine's set-up spans
    (``dstpu/setup/*``: its constructor runs under the patch in these tests)
    are not a round's and are left out."""

    def __init__(self):
        self.spans = []

    def __call__(self, name, **kw):
        outer = self

        class Span:
            def __enter__(self):
                self.rec = [name, kw, time.perf_counter(), None]
                if not name.startswith("dstpu/setup/"):
                    outer.spans.append(self.rec)

            def __exit__(self, *exc):
                self.rec[3] = time.perf_counter()

        return Span()


def test_launch_instant_lies_inside_the_dispatch_span(tiny, monkeypatch):
    seen = Annotations()
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", seen)
    _sess, records = _serve(_engine(tiny))
    rounds = [d for d in _rounds(records) if d["program"]]
    assert rounds
    outer = {kw["round"]: (t0, t1) for name, kw, t0, t1 in seen.spans
             if name == "dstpu/serve/round"}
    assert sorted(outer) == [d["round"] for d in _rounds(records)]
    names = {name for name, *_ in seen.spans}
    assert names == {"dstpu/serve/round"} | {
        "dstpu/serve/" + p for p in reqtrace.ROUND_PHASES if p != "other"}
    for d in rounds:
        lo, hi = outer[d["round"]]
        assert d["t0"] <= lo <= d["t1"] <= hi   # the span closes on t1
        inside = [(a, b) for name, _kw, a, b in seen.spans
                  if name == "dstpu/serve/dispatch" and lo <= a and b <= hi]
        assert len(inside) == 1
        assert inside[0][0] <= d["launch_t"] <= inside[0][1]


def test_trace_stages_off_writes_nothing_and_annotates_nothing(
        tiny, monkeypatch):
    seen = Annotations()
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", seen)
    eng = _engine(tiny)
    sess, records = _serve(
        eng, ServingPolicyConfig(admission="none", trace_stages=False))
    assert records == [] and seen.spans == []
    assert sess._spans is None and eng.round_spans is None
    assert sess.stats()["trace_dropped"] == 0


def test_an_undeclared_phase_raises(tiny):
    with pytest.raises(ValueError, match="undeclared round phase 'gathr'"):
        reqtrace.check_phase("gathr")
    spans = RoundSpans(time.perf_counter)
    with spans.round(1, time.perf_counter()):
        with pytest.raises(ValueError, match="ROUND_PHASES|declared"):
            with spans.phase("dispach"):
                pass
        with spans.phase("dispatch"):
            pass
    assert set(spans.phases) == {"other", "dispatch"}
    # and an engine driven without a session times nothing
    eng = _engine(tiny)
    eng.put([1], [[1, 2, 3]])
    assert eng.round_spans is None


def test_the_lint_rule_holds_phase_literals_to_the_registry(tmp_path):
    src = ("class S:\n"
           "    def f(self):\n"
           "        with self._phase('gathr'):\n"
           "            pass\n"
           "        with self.spans.phase('gather'):\n"
           "            pass\n"
           "        with self.round_spans.phase('colect'):\n"
           "            pass\n"
           "        self.moon.phase('waxing')\n"   # somebody else's phase
           "        pipeline._phase('warmup')\n")
    path = tmp_path / "inference" / "v2" / "x.py"
    path.parent.mkdir(parents=True)
    path.write_text(src)
    found = codelint.lint_paths(str(tmp_path), relpaths=["inference/v2/x.py"],
                                rules=[codelint.UndeclaredStageName()])
    assert [(v.rule, v.line) for v in found] == [
        ("undeclared-stage-name", 3), ("undeclared-stage-name", 7)]
    assert "ROUND_PHASES" in found[0].message


def test_a_full_ring_counts_what_it_drops_and_keeps_its_rounds(tiny):
    """The stream's ring turns over; the rounds' ring, one record a round,
    still holds every round, and ``drain_trace`` hands both over as one
    stream in the order of ``t``."""
    eng = _engine(tiny)
    sess = ServingSession(eng, ServingPolicyConfig(admission="none"))
    sess.trace_log = deque(maxlen=8)
    for uid, prompt in PROMPTS:
        sess.submit(uid, prompt, 6)
    rounds = 0
    while not sess.idle:
        sess.step()
        rounds += 1
    dropped = sess.stats()["trace_dropped"]
    assert dropped > 0 and len(sess.trace_log) == 8
    assert len(sess.round_log) == rounds and not any(
        r["data"].get("stage") == "round" for r in sess.trace_log)
    # every record ever written is either still there or counted
    drained = sess.drain_trace()
    assert len(drained) == 8 + rounds and not sess.round_log
    assert [r["t"] for r in drained] == sorted(r["t"] for r in drained)
    assert [r["data"]["round"] for r in drained
            if r["data"].get("stage") == "round"] == list(range(1, rounds + 1))
    sess.round_log = deque(maxlen=2)
    sess.submit(9, PROMPTS[0][1], 6)
    while not sess.idle:
        sess.step()
    assert len(sess.round_log) == 2 and sess.trace_dropped > dropped
    sess.close()


def test_the_journal_carries_the_record_and_the_report_prints_its_phases(
        tiny, tmp_path):
    """The operator's reader: ``tools/trace_report.py --requests`` over a
    journal directory, on a node without jax."""
    jdir = str(tmp_path / "journal")
    sess, records = _serve(_engine(tiny), ServingPolicyConfig(
        admission="none", journal_path=journal_path(jdir)))
    sess.close()
    streams, _router = reqtrace.load_root(jdir)
    table = reqtrace.round_phases(streams)
    assert table["rounds"] == len(_rounds(records)) == sess._round
    assert set(table["phases"]) == set(reqtrace.ROUND_PHASES)
    assert sum(q["mean_s"] for q in table["phases"].values()) \
        == pytest.approx(table["round_s"]["mean_s"], rel=0.01)
    decode = table["programs"]["decode_forward"]
    assert decode["rounds"] >= 5 and decode["prefill_tokens"] == 0
    by_program = {}
    for d in _rounds(records):
        by_program.setdefault(d["program"], []).append(d)
    for name, ds in by_program.items():   # each column is the records' mean
        for f in reqtrace.FORWARD_FIELDS:
            assert table["programs"][name or "(nothing launched)"][f] \
                == pytest.approx(sum(d[f] for d in ds) / len(ds))
    launched = [d["launch_t"] - d["t0"] for d in _rounds(records)
                if d["launch_t"] is not None]
    assert table["launch_s"]["mean_s"] == pytest.approx(
        sum(launched) / len(launched))
    assert reqtrace.round_phases([("0", "", [])]) is None
    blocker = tmp_path / "nojax"
    blocker.mkdir()
    (blocker / "jax.py").write_text("raise ImportError('jax blocked')\n")
    repo = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    out = subprocess.run(
        [sys.executable, os.path.join(repo, "tools", "trace_report.py"),
         "--requests", jdir], env={**os.environ, "PYTHONPATH": str(blocker)},
        capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    assert f"round phases ({sess._round} round record(s)" in out.stdout
    assert "forward launched p50=" in out.stdout
    cover = [ln.split() for ln in out.stdout.splitlines()
             if ln.strip().startswith(("launched", "decode_forward"))]
    assert cover[0] == ["launched", "rounds", "seqs", "tokens", "prompt",
                        "context", "kv", "blocks", "1-row", "atoms",
                        "pairs", "1-row-ctx", "experts", "ahead",
                        "spec-rows", "step-keys", "tile-keys", "rows",
                        "warm"]
    assert int(cover[1][1]) == decode["rounds"]
    counts = [f for f in reqtrace.FORWARD_FIELDS if f != "warm_tiles"]
    assert [float(x) for x in cover[1][2:-1]] == [
        pytest.approx(decode[f], abs=0.05) for f in counts]
    # the warm tiles are printed as their share of the live tiles: all but
    # the first row of every decode call
    assert float(cover[1][-1].rstrip("%")) == pytest.approx(
        100 * decode["warm_tiles"] / decode["decode_rows"], abs=0.05)
    assert 0 < decode["warm_tiles"] < decode["decode_rows"]
    phases = [ln.split()[0] for ln in out.stdout.split("round phases")[1]
              .splitlines()[1:] if ln.startswith("    ")]
    assert {"gather", "dispatch", "collect"} <= set(phases)
