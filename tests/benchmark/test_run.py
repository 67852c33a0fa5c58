"""The harness end to end at tiny size on the CPU, through the same path
functions the chip runs; that the command refuses to run off the chip; and
that a configuration, a traffic mix, a per-layer metric and a cell are added
by new files and new entries alone."""
import subprocess
import sys

import pytest

from benchmark import run, spec

from . import tiny


@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    return tiny.make_root(tmp_path_factory.mktemp("bench"))


def test_the_command_off_the_chip_exits_nonzero_and_prints_no_metric():
    r = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload",
         "phi2-decode-sat", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=spec.ROOT, capture_output=True, text=True, timeout=300,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin",
             "PYTHONPATH": str(spec.ROOT)})
    assert r.returncode != 0
    assert "metrics" not in r.stdout and r.stdout.strip() == ""
    assert "no CPU mode" in r.stderr


def test_an_unknown_cell_exits_nonzero_before_touching_jax():
    r = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload", "nope",
         "--seconds", "1"], cwd=spec.ROOT, capture_output=True, text=True,
        timeout=120)
    assert r.returncode != 0 and "metrics" not in r.stdout


def test_added_files_and_entries_alone_make_new_cells(bench):
    """``tiny.make_root`` edits nothing under ``benchmark/``: it adds four
    configurations (one of a new model family, with that family's reference
    and FLOP count), three mixes, one metric reader and five cells as files
    in a new directory plus entries, and the result is sound."""
    assert bench.problems() == []
    assert bench.config("tiny-serve")["preset"] == "phi-2"
    assert bench.traffic("tiny-open")["kind"] == "open-fixed-rate"
    assert callable(bench.reader("rounds_per_s"))
    assert callable(bench.reader("decode_fwd_ms"))    # a .json alias
    moe = bench.config("tiny-moe-serve")
    assert "extra/families/mixtral.py" in bench.family(moe).__file__
    names = [m["name"] for m in
             bench.metrics_of("tiny-open-cell", "per_layer")]
    assert "rounds_per_s" in names and "ragged_fwd_ms" in names


def test_closed_loop_cell_runs_and_counts_tokens_in_the_window(bench):
    obs, m = tiny.drive(bench, "tiny-closed-cell", seed=2**31 + 7)
    assert obs["correct"] and obs["failed"] == 0 and obs["attempted"] > 4
    t0, t1 = obs["window"]
    assert t1 - t0 >= 1.0
    # window edges are round returns
    ends = {r[1] for r in obs["rounds"]}
    assert t0 in ends and t1 in ends
    assert m["serve_tok_s"] > 0 and m["itl_p99_ms"] > 0
    assert m["live_seqs_mean"] == pytest.approx(4.0, abs=0.5)
    assert m["round_p50_ms"] > 0 and "rounds_per_s" not in m
    assert "dispatch_per_tok" not in m         # retired with PR 32
    # the traced metrics have nothing to read off the chip: left out
    assert "decode_fwd_ms" not in m and "serve_idle_pct" not in m


def test_closed_loop_by_lanes_gives_each_caller_its_own_walk_of_the_grid(bench):
    """``"order": "lanes"`` through ``serve.run_closed``: whichever caller
    ends first, a caller's next request is the next point of ITS walk."""
    from benchmark import traffic

    obs, m = tiny.drive(bench, "tiny-closed-cell", seed=2**31 + 48,
                        traffic="tiny-lanes")
    assert obs["correct"] and obs["failed"] == 0 and obs["attempted"] > 8
    assert m["serve_tok_s"] > 0
    mix = bench.traffic("tiny-lanes")
    pairs = traffic.length_pairs(mix, mix["count"])
    walk = [pairs[(k * 3) % 8] for k in range(8)]
    by_caller = {}
    for r in obs["requests"]:
        by_caller.setdefault(r["client"], []).append(
            (len(r["prompt"]), r["budget"]))
    assert sorted(by_caller) == [0, 1, 2, 3]
    starts = set()
    for sent in by_caller.values():
        at = walk.index(sent[0])
        starts.add(at)
        assert sent == [walk[(at + k) % 8] for k in range(len(sent))]
    assert starts == {0, 2, 4, 6}      # 4 callers evenly over 8 places


def test_a_cell_of_a_family_added_by_files_alone_runs_and_is_checked(bench):
    """Sparse experts through the serve path; ``correct`` includes the
    added family's plain reference agreeing with what the engine emitted."""
    obs, m = tiny.drive(bench, "tiny-moe-cell", seed=11)
    assert obs["correct"] and obs["failed"] == 0 and obs["attempted"] > 4
    assert m["serve_tok_s"] > 0
    assert 0.0 <= m["mixed_round_share_pct"] <= 100.0


def test_open_loop_cell_times_requests_from_their_due_time(bench):
    obs, m = tiny.drive(bench, "tiny-open-cell", seed=3)
    assert obs["correct"] and obs["failed"] == 0
    t0, t1 = obs["window"]
    due = [r for r in obs["requests"] if t0 <= r["due"] < t1]
    assert len(due) == 8                      # 8 per second for one second
    assert all(r["sent"] >= r["due"] for r in due)
    assert m["ttft_p90_ms"] > 0 and m["gen_late_max_ms"] >= 0
    assert m["queue_wait_p90_ms"] >= 0
    # the metric the temporary directory added, and an alias of a reader
    assert m["rounds_per_s"] > 0
    assert m["round_p50_ms.prefill"] > 0 and m["itl_p99_ms.prefill"] > 0
    assert m["ttft_p50_ms"] <= m["ttft_p90_ms"]


@pytest.mark.parametrize("policy, lost", [("reject", True),
                                          ("requeue", False)])
def test_a_stalled_host_loses_requests_only_where_eviction_rejects(
        bench, monkeypatch, capsys, policy, lost):
    """Half a second in which the host does nothing (the chip's machines do
    that for whole seconds) leaves 20 arrivals at once before a pool that
    holds 4: streams are evicted. Under ``reject`` they close with part of
    their output and the run is NOT correct, and says why; under
    ``requeue`` every request closes with all of it, and the reference is
    held against the whole output of a stream that was evicted."""
    from benchmark import serve

    step, stalled = serve.Loop.step, []

    def stalling_step(self):
        if len(self.requests) >= 30 and not stalled:
            stalled.append(run.CLOCK())
            run.time.sleep(0.5)
        return step(self)

    monkeypatch.setattr(serve.Loop, "step", stalling_step)
    obs, _ = tiny.drive(bench, f"tiny-{policy}-cell", seed=3)
    evicted = [r for r in obs["requests"] if r["evictions"]]
    assert stalled and evicted
    assert obs["correct"] is not lost
    assert (obs["failed"] > 0) is lost
    said = capsys.readouterr()
    assert ("NOT correct" in said.err) is lost
    if lost:
        assert "evicted" in said.err
    else:
        probe, n = serve.probe_of(obs["requests"], *obs["window"])
        assert probe["evictions"] and n == len(probe["tokens"]) > 8


def test_train_cell_loss_falls_and_mfu_reads_the_harness_own_flops(bench):
    obs, m = tiny.drive(bench, "tiny-train-cell", seed=5)
    assert obs["correct"] and obs["steps"] == obs["attempted"] > 2
    assert m["train_tok_s"] == pytest.approx(
        obs["steps"] * 4 * 128 / (obs["window"][1] - obs["window"][0]))
    assert 0 < m["train_mfu_pct"] < 100


def test_zero3_cell_matches_the_one_device_plain_reference(bench):
    obs, m = tiny.drive(bench, "tiny-zero3-cell", seed=5)
    assert obs["correct"]
    assert m["train_tok_s"] > 0


class FakeDevice:
    def __init__(self, platform, kind):
        self.platform, self.device_kind = platform, kind


@pytest.mark.parametrize("devices,why", [
    ([FakeDevice("tpu", "TPU v5 lite")] * 4, "needs 1 chip"),
    ([FakeDevice("gpu", "A100")], "no CPU mode"),
])
def test_main_refuses_the_wrong_devices(monkeypatch, capsys, devices, why):
    import jax

    monkeypatch.setattr(jax, "devices", lambda: devices)
    rc = run.main(["--workload", "phi2-decode-sat", "--seconds", "1"])
    out = capsys.readouterr()
    assert rc == 2 and why in out.err and "metrics" not in out.out


def test_main_refuses_a_device_the_peak_table_does_not_know(monkeypatch,
                                                            capsys):
    import jax

    monkeypatch.setattr(jax, "devices",
                        lambda: [FakeDevice("tpu", "TPU v9 imaginary")])
    with pytest.raises(KeyError, match="do not borrow"):
        run.main(["--workload", "phi2-decode-sat", "--seconds", "1"])
    assert "metrics" not in capsys.readouterr().out


def test_the_time_before_the_chip_is_kept_beside_setup_not_inside_it(bench):
    assert run.process_age_s() >= 0.0
    assert run._AGE_AT_IMPORT >= 0.0
    read = bench.reader("start_to_chip_s")
    assert read({"split": {"start_s": 1.0, "runtime_s": 11.5}}) == 12.5
    assert read({}) is None
    assert bench.reader("setup_s")({"setup_s": 24.0}) == 24.0


def test_hooks_keep_only_what_falls_inside_the_window():
    h = run.Hooks(traced=0, trace_s=3, trace_dir="unused")
    assert h.trace_s == 0.0
    h.t_open, h.t_close = 10.0, 20.0
    assert h.inside([(5.0, 1), (10.0, 2), (19.9, 3), (20.0, 4)]) == [
        (10.0, 2), (19.9, 3)]
    traced = run.Hooks(1, 3, "x", settle_s=2)
    assert (traced.trace_s, traced.settle_s, traced.tail_s) == (3.0, 2.0, 5.0)
    assert run.Hooks(0, 3, "x", settle_s=2).tail_s == 0.0
