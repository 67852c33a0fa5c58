"""One number of the program's set-up ledger
(``deepspeedsyclsupport_tpu.monitor.telemetry.setup_summary``), over the
records that ended before the window opened (``args: {"field": ...}``):

* ``trace_s`` / ``lower_s`` / ``compile_s`` — seconds jax spent tracing the
  programs (kernel bodies included), lowering them (Pallas bodies to Mosaic)
  and compiling them or loading them from the persistent cache, each second
  counted once (a phase nested in another is taken out of it);
* ``cache_misses`` — executables the persistent cache did not hold (0 in a
  warm run; what a cold ``setup_s`` is made of);
* ``engine_s`` — the engines' constructors (weights placed, pools and
  optimizer state allocated) less the compile records inside them;
* ``warm_run_s`` — ``warmup()`` / the first ``train_batch`` less the compile
  records inside: the first executions and the host's work around them.

The five of seconds are disjoint, so their sum is under ``setup_s``; the
rest of it is the harness's own (``imports_s``, the seeded weights, the
staircase and the ramp on the ``split`` line). None from a program without
a ledger."""


def read(obs, field):
    from deepspeedsyclsupport_tpu.monitor import telemetry

    summary = getattr(telemetry, "setup_summary", None)
    if summary is None or "window" not in obs:
        return None
    return summary(until=obs["window"][0])[field]
