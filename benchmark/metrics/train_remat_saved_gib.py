"""What the train step's checkpointed layers keep for the backward pass on
each device, in GiB: the ``saved_bytes`` of the LAST ``remat`` decision in
the program's set-up ledger that says them (``Engine._note_remat_choice``).
A run whose compiled step did not fit and that stepped down to a leaner
rung (``Engine._remat_step_down``) reads a smaller number, and pays for it
in ``train_recompute_ms``. None from a program without a ledger, or one
that took no rung."""


def read(obs):
    from deepspeedsyclsupport_tpu.monitor import telemetry

    summary = getattr(telemetry, "setup_summary", None)
    if summary is None or "window" not in obs:
        return None
    saved = [d["saved_bytes"]
             for d in summary(until=obs["window"][0])["decisions"]
             if d["name"] == "remat" and d.get("saved_bytes") is not None]
    return saved[-1] / 2**30 if saved else None
