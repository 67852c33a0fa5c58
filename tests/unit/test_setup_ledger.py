"""The set-up ledger (``monitor/telemetry.py``: the listener, the store,
``setup_span`` / ``setup_decision``; ``monitor/setup_folds.py``: the folds)
and what the two engines write into it while they are built.
"""
import importlib.util
import json
import os
import subprocess
import sys
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeedsyclsupport_tpu as dstpu
from deepspeedsyclsupport_tpu.inference.v2.config import ServingPolicyConfig
from deepspeedsyclsupport_tpu.inference.v2.engine_v2 import InferenceEngineV2
from deepspeedsyclsupport_tpu.inference.v2.serving import ServingSession
from deepspeedsyclsupport_tpu.models import build_model
from deepspeedsyclsupport_tpu.monitor import setup_folds
from deepspeedsyclsupport_tpu.monitor import telemetry as tel

from .simple_model import SimpleModel, random_dataset, simple_config
from .test_remat_rungs import _engine as remat_engine

TRACE = "/jax/core/compile/jaxpr_trace_duration"
LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
COMPILE = "/jax/core/compile/backend_compile_duration"
REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


@pytest.fixture
def ledger():
    """The process's ledger, emptied (its running totals stay)."""
    tel.setup_ledger_store.reset()
    return tel.setup_ledger_store


def of_program(records, name, **match):
    return [r for r in records if r["kind"] == "compile"
            and setup_folds.program_name(r["program"]) == name
            and all(r.get(k) == v for k, v in match.items())]


# ------------------------------------------------------------- the listener
def test_the_package_installs_the_one_listener_on_import():
    import jax._src.monitoring as m

    ours = [cb for cb in m.get_event_duration_listeners()
            if getattr(cb, "__self__", None) is tel.setup_ledger_store]
    assert len(ours) == 1
    assert sum(getattr(cb, "__self__", None) is tel.setup_ledger_store
               for cb in m.get_event_listeners()) == 1
    tel.install_compile_listener()      # idempotent: still one
    assert len([cb for cb in m.get_event_duration_listeners()
                if getattr(cb, "__self__", None)
                is tel.setup_ledger_store]) == 1


def test_the_package_registers_with_jax_monitoring_in_one_place():
    package = os.path.join(REPO, "deepspeedsyclsupport_tpu")
    found = set()
    for folder, _dirs, files in os.walk(package):
        for name in files:
            if name.endswith(".py"):
                path = os.path.join(folder, name)
                with open(path) as f:
                    if "register_event" in f.read():
                        found.add(os.path.relpath(path, package))
    assert found == {os.path.join("monitor", "telemetry.py")}


def test_a_jitted_function_leaves_its_three_phases_under_its_name(ledger):
    def ledger_known_name(x):
        return jnp.sin(x) * 3 + 1

    jax.jit(ledger_known_name)(jnp.ones((7,)))
    mine = of_program(tel.setup_ledger(), "ledger_known_name")
    assert [r["phase"] for r in mine] == ["trace", "lower", "compile"]
    assert [r["program"] for r in mine] == [
        "ledger_known_name", "jit(ledger_known_name)",
        "jit(ledger_known_name)"]
    assert all(r["dur"] > 0 and r["thread"] == threading.get_ident()
               for r in mine)
    assert [r["t"] for r in mine] == sorted(r["t"] for r in mine)
    # only an executable says whether the cache held it
    assert ["cached" in r for r in mine] == [False, False, True]
    row = tel.setup_summary()["programs"]["ledger_known_name"]
    assert row["executables"] == 1 and row["trace_s"] > 0 \
        and row["lower_s"] > 0 and row["compile_s"] > 0


@pytest.fixture
def empty_cache(tmp_path):
    """jax's persistent cache in an empty directory, every program cached
    (the two thresholds as ``benchmark/run.py`` sets them)."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    before = {k: getattr(jax.config, k) for k in keys}
    jax.config.update(keys[0], str(tmp_path / "cache"))
    jax.config.update(keys[1], 0.0)
    jax.config.update(keys[2], -1)
    cc.reset_cache()
    yield tmp_path / "cache"
    for k, v in before.items():
        jax.config.update(k, v)
    cc.reset_cache()


def test_cached_is_false_on_a_first_compile_and_true_on_the_next(
        ledger, empty_cache):
    def ledger_cached_twice(x):
        return jnp.cos(x) * 5 - 2

    x = jnp.ones((11,))
    jax.jit(ledger_cached_twice)(x)
    jax.clear_caches()      # what a new process starts with: nothing traced
    jax.jit(ledger_cached_twice)(x)
    built = of_program(tel.setup_ledger(), "ledger_cached_twice",
                       phase="compile")
    assert [r["cached"] for r in built] == [False, True]
    row = tel.setup_summary()["programs"]["ledger_cached_twice"]
    assert row["executables"] == 2 and row["cache_misses"] == 1
    # it was traced and lowered both times: a load saves the compile alone
    assert len(of_program(tel.setup_ledger(), "ledger_cached_twice",
                          phase="trace")) == 2


EVENTS = [(TRACE, 0.25, "f"), (LOWER, 0.5, "jit(f)"), (COMPILE, 2.0, "jit(f)"),
          ("/jax/compilation_cache/cache_retrieval_time_sec", 0.125, None),
          ("/jax/core/other", 9.0, None),
          (TRACE, 0.0625, "g"), (COMPILE, 1.0, "jit(g)")]


def test_compile_stats_is_what_the_two_integers_were():
    """The fold before this PR: every ``/jax/core/compile*`` duration added
    up, one count a ``backend_compile`` event."""
    led = tel.SetupLedger()
    count = seconds = 0.0
    for event, dur, name in EVENTS:
        led.on_jax_event(event, dur, **({"fun_name": name} if name else {}))
        if event.startswith("/jax/core/compile"):
            count += event.endswith("backend_compile_duration")
            seconds += dur
    assert (led.executables, sum(led.phase_seconds.values())) == (
        count, seconds) == (2, 3.8125)
    assert led.phase_seconds == {"trace": 0.3125, "lower": 0.5,
                                 "compile": 3.0}
    recs = led.records()
    assert [r["phase"] for r in recs] == [
        "trace", "lower", "compile", "trace", "compile"]
    # the retrieval event since the last executable marks the next one
    assert [r.get("cached") for r in recs] == [None, None, False, None, True]


def test_compile_stats_reads_the_ledgers_totals(ledger):
    c0, s0 = tel.compile_stats()
    p0 = tel.compile_phase_seconds()
    jax.jit(lambda x: x * 7 - 3)(jnp.ones((13,)))
    c1, s1 = tel.compile_stats()
    recs = [r for r in tel.setup_ledger() if r["kind"] == "compile"]
    assert c1 - c0 == sum(r["phase"] == "compile" for r in recs) >= 1
    assert s1 - s0 == pytest.approx(sum(r["dur"] for r in recs))
    p1 = tel.compile_phase_seconds()
    for phase in setup_folds.PHASES:
        assert p1[phase] - p0[phase] == pytest.approx(
            sum(r["dur"] for r in recs if r["phase"] == phase))


def test_the_ring_is_bounded_and_the_totals_outlive_what_it_drops():
    led = tel.SetupLedger(capacity=4)
    for i in range(10):
        led.on_jax_event(COMPILE, 1.0, fun_name=f"jit(p{i})")
    assert len(led.records()) == 4 and led.dropped == 6
    assert [r["program"] for r in led.records()] == [
        f"jit(p{i})" for i in range(6, 10)]
    assert led.executables == 10 and led.phase_seconds["compile"] == 10.0
    assert setup_folds.summarize(led.records(), dropped=led.dropped)[
        "dropped"] == 6


def test_appends_from_several_threads_lose_nothing():
    led, n, workers = tel.SetupLedger(capacity=10**6), 2000, 8
    before = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=lambda: [
            led.on_jax_event(COMPILE, 0.5, fun_name="jit(p)")
            for _ in range(n)]) for _ in range(workers)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(before)
    assert led.executables == len(led.records()) == n * workers
    assert led.phase_seconds["compile"] == 0.5 * n * workers


# ----------------------------------------------------------------- the folds
def rec(phase, program, start, end, thread=1, **kw):
    return {"kind": "compile", "t": end, "dur": end - start, "phase": phase,
            "program": program, "thread": thread, **kw}


def span(sid, name, t0, t1, parent=None, thread=1, **fields):
    return {"kind": "span", "id": sid, "name": name, "t0": t0, "t1": t1,
            "parent": parent, "thread": thread, "fields": fields}


def in_order(records):
    return sorted(records, key=lambda r: r["t0"] if r["kind"] == "span"
                  else r["t"])


# ``step``'s trace holds an inner jit's and a kernel body's, its lowering
# traces a helper, and an eager op compiled while it was traced
NESTED = in_order([
    rec("trace", "mul", 1.0, 1.5), rec("trace", "body", 2.0, 4.0),
    rec("trace", "arange", 4.5, 4.6), rec("lower", "jit(arange)", 4.6, 4.7),
    rec("compile", "jit(arange)", 4.7, 5.0, cached=True),
    rec("trace", "step", 0.0, 6.0),
    rec("trace", "helper", 7.0, 7.5), rec("lower", "jit(step)", 6.0, 9.0),
    rec("compile", "jit(step)", 9.5, 19.5, cached=False),
    rec("trace", "other", 20.0, 21.0, thread=2)])


def test_a_nested_phase_is_counted_once_and_billed_to_the_outermost():
    rows = setup_folds.self_seconds(NESTED)
    assert {(r["program"], r["phase"]): (round(r["self"], 6), r["root"])
            for r in rows} == {
        ("mul", "trace"): (0.5, "step"), ("body", "trace"): (2.0, "step"),
        ("arange", "trace"): (0.1, "step"),
        ("jit(arange)", "lower"): (0.1, "step"),
        ("jit(arange)", "compile"): (0.3, "step"),
        ("step", "trace"): (3.0, "step"),
        ("helper", "trace"): (0.5, "step"),
        ("jit(step)", "lower"): (2.5, "step"),
        ("jit(step)", "compile"): (10.0, "step"),
        ("other", "trace"): (1.0, "other")}
    s = setup_folds.summarize(NESTED)
    assert s["programs"]["step"] == {
        "trace_s": pytest.approx(6.1), "lower_s": pytest.approx(2.6),
        "compile_s": pytest.approx(10.3), "executables": 2,
        "cache_misses": 1}
    # every second once: the union of the intervals
    assert s["trace_s"] + s["lower_s"] + s["compile_s"] == pytest.approx(20.0)
    assert (s["executables"], s["cache_misses"]) == (2, 1)


def test_until_keeps_what_ended_before_it():
    s = setup_folds.summarize(NESTED, until=9.0)
    assert s["executables"] == 1 and s["cache_misses"] == 0
    assert "other" not in s["programs"]
    # ``helper`` stands alone: the lowering around it had not ended
    assert s["trace_s"] + s["lower_s"] + s["compile_s"] == pytest.approx(6.5)


SPANS = in_order(NESTED + [
    span(1, "engine", -1.0, 9.5, side="serve"), span(2, "params", -0.5, 6.0, 1),
    span(3, "pool", 6.0, 9.25, 1),
    span(4, "warmup", 9.5, 30.0), span(5, "warm/f@8", 9.5, 20.0, 4),
    span(6, "open", 31.0, None),
    {"kind": "decision", "t": 9.4, "name": "remat", "rung": "attn",
     "saved_bytes": 2**30, "thread": 1}])


def test_a_spans_self_time_leaves_out_its_children_and_the_compiling_inside():
    s = setup_folds.summarize(SPANS)
    rows = {r["name"]: r for r in s["spans"]}
    assert "open" not in rows                  # not closed: not counted
    assert [rows[n]["depth"] for n in
            ("engine", "params", "pool", "warmup", "warm/f@8")] == [
        0, 1, 1, 0, 1]
    # params: 6.5 long, step's trace (6.0 with all inside it) ran in it
    assert rows["params"]["compile_s"] == pytest.approx(6.0)
    assert rows["params"]["self_s"] == pytest.approx(0.5)
    assert rows["pool"]["compile_s"] == pytest.approx(3.0)
    assert rows["pool"]["self_s"] == pytest.approx(0.25)
    # engine: 10.5 long, children 9.75, nothing compiled outside them
    assert rows["engine"]["self_s"] == pytest.approx(0.75)
    assert rows["warm/f@8"]["compile_s"] == pytest.approx(10.0)
    assert rows["warm/f@8"]["self_s"] == pytest.approx(0.5)
    assert rows["warmup"]["self_s"] == pytest.approx(10.0)
    # another thread's tracing is in nobody's span
    assert s["engine_s"] == pytest.approx(0.5 + 0.25 + 0.75)
    assert s["warm_run_s"] == pytest.approx(10.5)
    assert s["decisions"][0]["rung"] == "attn"
    # the five of seconds are disjoint: under the wall they cover
    assert (s["trace_s"] + s["lower_s"] + s["compile_s"] + s["engine_s"]
            + s["warm_run_s"]) == pytest.approx(20.0 + 1.5 + 10.5)


def test_spans_nest_by_thread_and_reach_the_recorder(ledger):
    recorder = tel.FlightRecorder()
    tel.set_active_recorder(recorder)
    try:
        with tel.setup_span("engine", side="test") as fields:
            with tel.setup_span("params"):
                jax.jit(lambda x: x + 41)(jnp.ones((3,)))
            fields["leaves"] = 3
            other = threading.Thread(
                target=lambda: tel.setup_span("elsewhere").__enter__())
            other.start()
            other.join(timeout=30)
        tel.setup_decision("remat", rung="attn", saved_bytes=7)
    finally:
        tel.set_active_recorder(None)
    spans = {r["name"]: r for r in tel.setup_ledger()
             if r["kind"] == "span"}
    assert spans["engine"]["parent"] is None
    assert spans["params"]["parent"] == spans["engine"]["id"]
    assert spans["elsewhere"]["parent"] is None      # its own thread's stack
    assert spans["engine"]["fields"] == {"side": "test", "leaves": 3}
    assert spans["engine"]["t0"] <= spans["params"]["t0"] \
        <= spans["params"]["t1"] <= spans["engine"]["t1"]
    rows = {r["name"]: r for r in tel.setup_summary()["spans"]}
    assert rows["params"]["compile_s"] > 0
    assert rows["params"]["self_s"] == pytest.approx(
        rows["params"]["dur"] - rows["params"]["compile_s"])
    written = {r["name"]: r for r in recorder.snapshot()}
    assert written["setup/params"]["kind"] == "span"
    assert written["setup/params"]["data"]["parent"] == spans["engine"]["id"]
    assert written["setup/engine"]["dur"] == pytest.approx(
        spans["engine"]["t1"] - spans["engine"]["t0"])
    assert written["setup/decision.remat"]["data"]["rung"] == "attn"
    decision, = [r for r in tel.setup_ledger() if r["kind"] == "decision"]
    assert (decision["name"], decision["saved_bytes"]) == ("remat", 7)


# ------------------------------------------------------------ serving engine
@pytest.fixture(scope="module")
def warmed():
    """The smallest test model behind an engine of two ``ragged_forward``
    shapes, warmed; the ledger as ``warmup()`` left it."""
    tel.setup_ledger_store.reset()
    model = build_model("tiny", max_seq_len=512, dtype="float32")
    eng = InferenceEngineV2(
        model, model.init_params(jax.random.PRNGKey(1)), dtype=jnp.float32,
        topology=dstpu.build_topology(dp=1, devices=jax.devices()[:1]),
        max_context=512, num_blocks=96, block_size=16,
        max_tokens_per_batch=512, max_sequences=4, prefill_attn="xla",
        decode_attn="xla")
    eng.warmup()
    return eng, tel.setup_ledger()


def test_warmup_leaves_a_span_for_each_program_and_shape_it_built(warmed):
    eng, records = warmed
    rows = setup_folds.summarize(records)["spans"]
    engine, = [r for r in rows if r["name"] == "engine"]
    assert engine["fields"] == {"side": "serve"}
    assert [r["name"] for r in rows if r.get("parent") == engine["id"]] == [
        "params", "pool"]
    warmup, = [r for r in rows if r["name"] == "warmup"]
    warm = [r["name"] for r in rows if r.get("parent") == warmup["id"]]
    assert all(n.startswith("warm/") for n in warm)
    built = {f"warm/{name}@{rows_}"
             for name, (_fn, shapes) in eng._dispatched.items()
             for rows_ in (shapes if name == "ragged_forward"
                           else [eng.config.max_sequences])}
    assert built == {"warm/ragged_forward@128", "warm/ragged_forward@512",
                     "warm/decode_forward@4"}
    assert set(warm) == built | {"warm/sample_rows@4", "warm/split_key@1"}
    # one span a forward it ran: both pool states of the largest shape and
    # of the decode step, every smaller shape once
    assert warm.count("warm/ragged_forward@512") == 2
    assert warm.count("warm/decode_forward@4") == 2
    assert warm.count("warm/ragged_forward@128") == 1
    by_name = {}
    for r in rows:
        by_name.setdefault(r["name"], []).append(r)
    # a program's first span holds its build
    for name in built:
        assert by_name[name][0]["compile_s"] > 0, name
    shapes = {d["program"]: d["rows"] for d in records
              if d["kind"] == "decision" and d["name"] == "shapes"}
    assert shapes == {"ragged_forward": [128, 512], "decode_forward": [4]}
    programs = setup_folds.summarize(records)["programs"]
    assert programs["ragged_forward"]["executables"] >= 2
    assert programs["decode_forward"]["executables"] >= 1


def test_nothing_compiles_after_warmup_while_serving(warmed):
    eng, records = warmed
    n_before = len([r for r in tel.setup_ledger() if r["kind"] == "compile"])
    assert n_before == len([r for r in records if r["kind"] == "compile"])
    sess = ServingSession(eng, ServingPolicyConfig())
    rng = np.random.default_rng(0)
    for uid, n in enumerate((30, 200, 9)):
        sess.submit(uid, rng.integers(0, 250, n).tolist(), 6)
        sess.step()
    for _ in range(64):
        if sess.idle:
            break
        sess.step()
    assert sess.idle
    after = [r for r in tel.setup_ledger() if r["kind"] == "compile"]
    # the staircase's programs (a session's first rounds at each count of
    # live sequences) are the harness's to warm: none is a forward, a
    # sampler or the key's split
    late = {setup_folds.program_name(r["program"])
            for r in after[n_before:] if r["phase"] == "compile"}
    assert not late & {"ragged_forward", "decode_forward", "_sample_rows",
                       "split_key"}, late
    sess.close()


# ----------------------------------------------------------- training engine
def test_the_first_step_is_a_span_and_the_rung_a_decision(ledger):
    engine, batch = remat_engine({})
    for _ in range(2):
        engine.train_batch(batch)
    s = tel.setup_summary()
    rows = s["spans"]
    built, = [r for r in rows if r["name"] == "engine"]
    assert built["fields"] == {"side": "train"}
    assert [r["name"] for r in rows if r.get("parent") == built["id"]] == [
        "state"]
    first, = [r for r in rows if r["name"] == "first_step"]   # the first only
    assert first["parent"] is None and first["compile_s"] > 0
    # (a second step may build the step again, for the state the first one
    # gave back: outside the span, and in the ledger all the same)
    builds = of_program(tel.setup_ledger(), "train_batch_fn", phase="compile")
    assert sum(first["t0"] < r["t"] < first["t1"] for r in builds) == 1
    assert s["programs"]["train_batch_fn"]["executables"] == len(builds)
    decision, = [d for d in s["decisions"] if d["name"] == "remat"]
    assert {k: decision[k] for k in ("rung", "saved_bytes", "auto")} == \
        engine.remat_choice
    assert first["t0"] < decision["t"] < first["t1"]
    assert s["warm_run_s"] == pytest.approx(first["self_s"])
    assert s["engine_s"] > 0


def test_a_forced_step_down_is_a_decision_beside_the_second_build(
        ledger, monkeypatch):
    """``test_remat_rungs``' fault, one step further: the step that does not
    fit was BUILT before the device refused it."""
    engine, batch = remat_engine({}, 1 << 30, monkeypatch)
    engine._train_batch_fn = engine._build_train_batch_fn()
    real = engine._train_batch_fn

    def too_big(*args):
        real.lower(*args).compile()
        raise jax.errors.JaxRuntimeError(
            "RESOURCE_EXHAUSTED: XLA:TPU compile permanent error. Ran out "
            "of memory in memory space hbm.")

    engine._train_batch_fn = too_big
    engine.train_batch(batch)
    records = tel.setup_ledger()
    builds = of_program(records, "train_batch_fn", phase="compile")
    assert len(builds) == 2
    down, noted = [r for r in records if r["kind"] == "decision"
                   and r["name"] == "remat"]
    assert down["stepped_down_from"] == "attn+mlp" and down["rung"] == "attn"
    assert down["saved_bytes"] is None and down["saved_bytes_before"] > 0
    assert builds[0]["t"] < down["t"] < builds[1]["t"] < noted["t"]
    assert "stepped_down_from" not in noted
    assert {k: noted[k] for k in ("rung", "saved_bytes", "auto")} == \
        engine.remat_choice
    assert noted["saved_bytes"] < down["saved_bytes_before"]


# ------------------------------------------ the recorder's side, the report
def test_the_recorders_compile_event_and_the_compile_family_say_the_phases(
        tmp_path, ledger):
    out = str(tmp_path / "tel")
    cfg = simple_config(telemetry={"enabled": True, "output_dir": out})
    engine, _, _, _ = dstpu.initialize(model=SimpleModel(), config=cfg)
    for batch in random_dataset(engine.train_batch_size(), n_batches=2):
        engine.train_batch(batch)
    compiled = [r for r in engine.telemetry.recorder.snapshot()
                if r["name"] == "compile/train_step"]
    assert compiled
    d = compiled[0]["data"]
    assert d["trace_s"] > 0 and d["lower_s"] > 0 and d["compile_s"] > 0
    assert compiled[0]["dur"] == pytest.approx(
        d["trace_s"] + d["lower_s"] + d["compile_s"])
    events = {n: v for n, v, _s in engine.telemetry.periodic_events(2)}
    assert events["Compile/total_s"] == pytest.approx(
        events["Compile/trace_s"] + events["Compile/lower_s"]
        + events["Compile/backend_s"])
    assert all(tel.is_declared(n) for n in events)
    engine.telemetry.close()
    path = os.path.join(out, "flightrec_rank0.jsonl")
    lines = [json.loads(line) for line in open(path)]
    dumped, = [r for r in lines if r["name"] == "setup/ledger"]
    kinds = {r["kind"] for r in dumped["data"]["records"]}
    assert kinds >= {"compile", "span"}
    assert any(r["name"] == "setup/first_step" and r["kind"] == "span"
               for r in lines)
    # the operator's reading, on a node without jax
    shim = tmp_path / "shim"
    shim.mkdir()
    (shim / "jax.py").write_text("raise ImportError('no jax on this node')")
    dump = tmp_path / "live.json"
    dump.write_text(json.dumps(tel.setup_ledger()))
    for source in (path, str(dump)):
        done = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools", "trace_report.py"),
             "--setup", source], capture_output=True, text=True, timeout=120,
            env={**os.environ, "PYTHONPATH": str(shim)})
        assert done.returncode == 0, done.stderr
        assert "set-up ledger" in done.stdout
        assert "train_batch_fn" in done.stdout
        assert "first_step" in done.stdout and "engine" in done.stdout
    empty = tmp_path / "empty.jsonl"
    empty.write_text("")
    assert subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "trace_report.py"),
         "--setup", str(empty)], capture_output=True, timeout=120,
        env={**os.environ, "PYTHONPATH": str(shim)}).returncode == 2


def test_the_folds_load_without_the_package():
    """``tools/trace_report.py`` loads the file by path: stdlib only."""
    spec = importlib.util.spec_from_file_location(
        "_alone_setup_folds", os.path.join(
            REPO, "deepspeedsyclsupport_tpu", "monitor", "setup_folds.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    text = "\n".join(mod.render(SPANS, dropped=2))
    assert "2 older records dropped" in text and "decision remat" in text
    assert "warm/f@8" in text and "step" in text
