"""One cell, once:

    python -m benchmark.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

Needs a TPU with exactly the cell's ``chips`` (there is no CPU mode: off
the chip this exits 2 and prints no result; the CPU rehearsal is
``tests/benchmark``, through the same functions at tiny size). Makes
weights and inputs from ``--seed``, warms the cell's own programs (set-up,
reported as ``setup_s`` with its split on an earlier line), measures for
``--seconds``, checks correctness outside the window and prints the result
as the last line of stdout. ``--trace 0`` prints the cell's end-to-end
metrics; ``--trace 1`` keeps the same window untraced, then runs the same
load for a few seconds more under the profiler, and prints the cell's
per-layer metrics, the device's busy time and the breakdown.
"""
import time

_T_IMPORT = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

from . import flops, serve, spec, trace, train, window  # noqa: E402

CLOCK = time.perf_counter
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def process_age_s():
    """Seconds since the kernel started this process (interpreter start-up
    and imports before this module are set-up too); 0.0 where /proc cannot
    say."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return max(0.0, uptime - ticks / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError):
        return 0.0


_AGE_AT_IMPORT = process_age_s()


def log(**fields):
    """An earlier line of stdout: one JSON object, never the last line."""
    print(json.dumps(fields, default=float), flush=True)


class Hooks:
    """What the load loops call at the window's edges. Holds the clock
    readings, the compile and garbage-collection watch, and (``--trace 1``)
    the profiler around the tail that follows the window."""

    def __init__(self, traced, trace_s, trace_dir, settle_s=0.0):
        self.trace_s = float(trace_s) if traced else 0.0
        # seconds of load between starting the profiler (which stalls the
        # loop) and the traced window: an open loop works off the stall's
        # backlog first. The load loops see ``tail_s`` = both.
        self.settle_s = float(settle_s) if traced else 0.0
        self.tail_s = self.trace_s + self.settle_s
        self._t_traced = None
        self.trace_dir = trace_dir
        self.t_open = self.t_close = None
        self.compiles, self.gc_pauses = [], []
        self._gc_t0 = None
        self._span = None

    # ---- watches (always on: they cost a list append per event)
    def watch(self):
        import jax.monitoring

        jax.monitoring.register_event_duration_secs_listener(
            lambda name, dur, **kw: self.compiles.append((CLOCK(), dur))
            if name == COMPILE_EVENT else None)
        gc.callbacks.append(self._on_gc)

    def _on_gc(self, phase, info):
        if phase == "start":
            self._gc_t0 = CLOCK()
        elif self._gc_t0 is not None:
            self.gc_pauses.append((self._gc_t0, CLOCK() - self._gc_t0))

    def inside(self, stamped):
        return [x for x in stamped
                if self.t_open is not None and self.t_open <= x[0]
                and (self.t_close is None or x[0] < self.t_close)]

    # ---- edges
    def window_open(self):
        self.t_open = CLOCK()

    def window_close(self):
        self.t_close = CLOCK()

    def _drain_devices(self):
        """Wait until every device has finished what it was given, so that
        the trace starts and ends between programs."""
        import jax
        import numpy as np

        for d in jax.devices():
            (jax.device_put(np.float32(0), d) + 1).block_until_ready()

    def trace_start(self):
        import jax

        shutil.rmtree(self.trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0     # the device, TraceMe spans; no
        opts.host_tracer_level = 2       # python frames (slow and huge)
        self._drain_devices()
        jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        self._t_traced = CLOCK()
        self.tick()

    def tick(self):
        """Called by the load loops once a round: opens the traced window
        when the settling time after ``trace_start`` is over."""
        import jax

        if self._t_traced is not None and self._span is None \
                and CLOCK() - self._t_traced >= self.settle_s:
            self._span = jax.profiler.TraceAnnotation(trace.WINDOW_SPAN)
            self._span.__enter__()

    def trace_stop(self):
        import jax

        self._drain_devices()
        self._span.__exit__(None, None, None)
        jax.profiler.stop_trace()

    def read_trace(self):
        files = sorted(glob.glob(os.path.join(
            self.trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
        if not files:
            raise RuntimeError(f"the profiler left no trace in "
                               f"{self.trace_dir}")
        return trace.read_xplane(files[-1])


# ------------------------------------------------------------------- paths
def run_serve(cfg, mix, args, hooks, split, obs):
    import numpy as np

    seed32 = args.seed % (2**31 - 1)
    model, engine = serve.build(cfg, obs["family"], seed32, split)
    vocab = model.config.vocab_size
    loop = serve.Loop(engine, cfg.get("policy", {}))
    t = CLOCK()
    n_before = len(hooks.compiles)
    serve.staircase(loop, vocab, np.random.default_rng(args.seed))
    split["staircase_s"] = CLOCK() - t
    n_stairs = len(hooks.compiles)
    n_warm = len(loop.requests)
    serve.freeze_garbage()
    t_ramp = CLOCK()
    runner = {"closed": serve.run_closed,
              "open-fixed-rate": serve.run_open}[mix["kind"]]
    t0, t1 = runner(loop, mix, args.seed, vocab, args.seconds, hooks)
    split["ramp_s"] = t0 - t_ramp
    obs["compiles_by_phase"] = {
        "build": n_before, "staircase": n_stairs - n_before,
        "ramp": sum(1 for c in hooks.compiles[n_stairs:] if c[0] < t0)}
    load = loop.requests[n_warm:]
    failed = [r for r in load if not window.ok(r)]
    leaked = engine.allocator.num_blocks - engine.allocator.free_blocks
    # correctness outside the window. An eviction is not a fault: under the
    # configuration's ``requeue`` policy the stream is prefilled again and
    # closes ``done`` with every token, and the reference is then held
    # against such a stream. A request that closed any other way is one.
    probe, n_probe = serve.probe_of(load, t0, t1)
    margin, agrees = (
        serve.reference_check(cfg, obs["family"], engine, probe, n_probe)
        if probe else (math.inf, False))
    obs.update(
        window=(t0, t1), requests=load, rounds=loop.rounds,
        attempted=len(load), failed=len(failed),
        correct=not failed and not leaked and agrees,
        engine=engine, stages=loop.session.drain_trace())
    if not obs["correct"]:
        print(f"benchmark.run: NOT correct: {len(failed)} of {len(load)} "
              f"requests did not close done with their budget "
              f"{sorted({str(r['closed']) for r in failed})}, {leaked} KV "
              f"blocks leaked, reference margin {margin} over {n_probe} "
              f"tokens (tolerance 0.1), {loop.evicted} evictions, "
              f"{loop.shed} shed", file=sys.stderr)
    in_win = [r for r in loop.rounds if t0 < r[1] <= t1]
    log(phase="serve", rounds=len(in_win),
        tokens=window.tokens_in_window(load, t0, t1),
        prompt_tokens_sent=sum(len(r["prompt"]) for r in load
                               if t0 <= r["sent"] < t1),
        dispatches=(in_win[-1][3] - in_win[0][3]) if in_win else 0,
        requests_due=sum(1 for r in load if t0 <= r["due"] < t1),
        finished=sum(1 for r in load if r["emits"]
                     and t0 < r["emits"][-1] <= t1 and window.ok(r)),
        live_at_close=sum(1 for r in load if r["sent"] < t1 and (
            not r["emits"] or r["emits"][-1] > t1)),
        window_s=t1 - t0, idle_s=loop.idle_s, shed=loop.shed,
        evicted=loop.evicted, leaked_blocks=leaked, failed=len(failed),
        reference_margin=margin, reference_tokens=n_probe,
        **tails(load, t0, t1))
    loop.session.close()


def tails(load, t0, t1):
    """Latency percentiles for the earlier line (a reader's view of the
    distribution around the metrics; milliseconds)."""
    gaps = window.gaps_in_window(load, t0, t1)
    ttft = window.ttfts_from_due(load, t0, t1)
    out = {f"itl_p{int(p * 100)}_ms": 1e3 * window.percentile(gaps, p)
           for p in (0.5, 0.9, 0.95, 0.99) if gaps}
    out.update({f"ttft_p{int(p * 100)}_ms": 1e3 * window.percentile(ttft, p)
                for p in (0.5, 0.9) if ttft})
    return out


def run_train(cfg, mix, args, hooks, split, obs):
    import jax
    import numpy as np

    seed32 = args.seed % (2**31 - 1)
    model, engine = train.build(cfg, obs["family"], seed32, split)
    batches = train.batches_of(mix, cfg, args.seed, model.config.vocab_size)
    t = CLOCK()
    t0, t1, steps, losses, first_batch, first_loss = train.run_steps(
        engine, batches, args.seconds, mix["warm_steps"], hooks)
    split["compile_or_load_and_warm_s"] = hooks.t_open - t
    tail = float(np.mean(losses[-5:]))
    sound = bool(np.all(np.isfinite(losses))) and tail < first_loss
    obs.update(window=(t0, t1), steps=steps, losses=losses,
               tokens_per_step=cfg["train"]["batch"] * mix["seq_len"],
               seq_len=mix["seq_len"], attempted=steps,
               failed=int(np.sum(~np.isfinite(losses))))
    if args.trace:
        # what the compiled step needs and moves, from the program's own
        # handles (a compile-cache hit, outside the window)
        obs["step_bytes"] = flops.program_bytes(engine.compiled_train_step())
        if cfg["path"] == "zero3":
            census = engine.graph_report(
                analyzers=("collectives",))["collectives"]
            obs["param_gather_bytes"] = census.classes.bytes_of(
                "param_gather")
    ref = None
    if cfg["path"] == "zero3":
        # the one-device plain reference needs device 0: release the engine
        del engine
        gc.collect()
        ref = train.reference_first_loss(cfg, obs["family"], seed32,
                                         first_batch)
        sound = sound and abs(first_loss - ref) \
            <= 4 * train.reference.BF16_EPS * abs(ref)
    obs["correct"] = sound
    log(phase="train", steps=steps, window_s=t1 - t0, first_loss=first_loss,
        window_first_loss=losses[0], window_last5_loss=tail,
        reference_first_loss=ref,
        peak_bytes=[(d.memory_stats() or {}).get("peak_bytes_in_use")
                    for d in jax.devices()])


PATHS = {"serve": run_serve, "train": run_train, "zero3": run_train}


# -------------------------------------------------------------------- main
def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench = spec.Bench()
    cell = bench.cell(args.workload)
    cfg = bench.config(cell["config"])
    mix = bench.traffic(cell["traffic"])
    family = bench.family(cfg)
    split = {"start_s": _AGE_AT_IMPORT + CLOCK() - _T_IMPORT}

    t = CLOCK()
    import jax

    devices = jax.devices()
    t_chip = CLOCK()
    split["runtime_s"] = t_chip - t        # import jax + reaching the chip
    if devices[0].platform != "tpu":
        print(f"benchmark.run: needs a TPU; jax found "
              f"{devices[0].platform!r} — there is no CPU mode",
              file=sys.stderr)
        return 2
    if len(devices) != cell["chips"]:
        print(f"benchmark.run: {cell['name']} needs {cell['chips']} chip(s), "
              f"jax sees {len(devices)}", file=sys.stderr)
        return 2
    kind = devices[0].device_kind
    peak = flops.peaks(kind)           # an unknown device raises: no default

    from deepspeedsyclsupport_tpu.utils.jax_cache import place_compile_cache

    cache = place_compile_cache()
    # the small per-round programs compile in well under a second: cache
    # them too, so that the second run of a cell finds EVERY program
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    import deepspeedsyclsupport_tpu  # noqa: F401  (timed: the program's imports)

    split["imports_s"] = CLOCK() - t_chip

    hooks = Hooks(args.trace, mix.get("trace_seconds", 3.0),
                  os.path.join(str(bench.root), ".bench_trace", cell["name"]),
                  mix.get("trace_settle_seconds", 0.0))
    hooks.watch()
    obs = {"cell": cell, "config": cfg, "traffic": mix, "family": family,
           "peaks": peak,
           "chips": cell["chips"], "device_kind": kind,
           "seconds": args.seconds, "trace": None, "split": split}
    PATHS[cfg["path"]](cfg, mix, args, hooks, split, obs)

    # set-up is counted from the instant the chip is reached: interpreter
    # start and the TPU runtime's own start-up (start_s, runtime_s on the
    # split line) took 11-17 s on ONE machine within one call, and nothing
    # in the repo can move them
    obs["setup_s"] = hooks.t_open - t_chip
    peak_bytes = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                     for d in devices)
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices), "memory_peak_bytes": peak_bytes}
    result = {"correct": bool(obs["correct"]), "attempted": obs["attempted"],
              "failed": obs["failed"]}
    if args.trace:
        obs["trace"] = tr = hooks.read_trace()
        lo, hi = trace.window_of(tr)
        obs["trace_window"] = (lo, hi)
        device.update(busy_s=trace.busy_s(tr, lo, hi), window_s=hi - lo)
        plane = sorted(tr["devices"])[0]
        result["breakdown"] = {
            "device_ops": trace.top_ops(tr, plane),
            "idle_gaps": trace.idle_gaps(tr, plane, lo, hi)}
    metrics = {}
    for m in bench.metrics_of(
            cell["name"], "per_layer" if args.trace else "end_to_end"):
        value = bench.reader(m["name"])(obs)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    log(phase="setup", cache=cache, setup_s=obs["setup_s"], split=split,
        compiles_in_window=len(hooks.inside(hooks.compiles)),
        compiles_total=len(hooks.compiles),
        compiles_by_phase=obs.get("compiles_by_phase"),
        gc_in_window=len(hooks.inside(hooks.gc_pauses)),
        gc_in_window_s=sum(p[1] for p in hooks.inside(hooks.gc_pauses)))
    print(json.dumps({**result, "metrics": metrics, "device": device}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
