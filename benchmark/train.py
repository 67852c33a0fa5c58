"""The ``train`` and ``zero3`` paths: ``dstpu.initialize`` ->
``engine.train_batch`` on fresh seeded batches, one per step.

A step is dispatched while the one before it still runs (the host prepares
and sends batch k+1 during step k, as a training loop does), and the
harness waits for step k's loss only after dispatching step k+1: the input
path runs but never blocks the device. The window starts at a
``block_until_ready`` and ends at the ``block_until_ready`` of the first
step that ends ``seconds`` or more later; the rate is all its steps'
tokens over all its time.
"""
import time

from . import reference, traffic

CLOCK = time.perf_counter


def build(cfg, family, seed, split):
    """Engine with fp32 master weights made on the device from ``seed`` in
    one jitted call, straight into the engine's shards; the optimizer state
    is ``dstpu.initialize``'s. ZeRO stage and mesh from the configuration's
    file."""
    import jax

    import deepspeedsyclsupport_tpu as dstpu
    from deepspeedsyclsupport_tpu.models import build_model

    from deepspeedsyclsupport_tpu.runtime import zero as zero_lib

    from .serve import check_widths, seeded_params

    t = CLOCK()
    run = cfg["train"]
    model = build_model(cfg["preset"], **cfg.get("overrides", {}))
    check_widths(cfg, family, model.config)
    fsdp = int(run.get("fsdp", 1))
    topology = dstpu.build_topology(dp=1, fsdp=fsdp,
                                    devices=jax.devices()[:fsdp])
    # the engine's own placement rule, so that it finds every leaf where
    # it wants it and copies nothing
    shardings = zero_lib.tree_param_shardings(
        jax.eval_shape(model.init_params), topology, run["zero_stage"],
        extra_rules=model.sharding_rules)
    params = seeded_params(model, seed, shardings=shardings)
    config = {
        "train_batch_size": run["batch"],
        "bf16": {"enabled": True},
        "optimizer": {"type": "adamw",
                      "params": {"lr": run["lr"], "weight_decay": 0.01}},
        "activation_checkpointing": {},     # remat on, the default policy
        "zero_optimization": {"stage": run["zero_stage"]},
        "steps_per_print": 10**9,
        "seed": seed,
    }
    engine, _, _, _ = dstpu.initialize(model=model, params=params,
                                       config=config, topology=topology)
    del params
    jax.block_until_ready(engine.params)
    split["weights_s"] = CLOCK() - t
    return model, engine


def run_steps(engine, batches, seconds, warm_steps, hooks):
    """Warm up, then measure. Returns ``(t0, t1, n_steps, losses,
    first_batch, first_loss)``: ``losses`` are the window's, one per step;
    ``first_loss`` is the loss of the very first step, on ``first_batch``,
    before any update (what the one-device reference is compared with). A
    traced run goes on for ``hooks.tail_s`` seconds after the window,
    under the profiler."""
    import jax

    first_batch = next(batches)
    first_loss = float(engine.train_batch(first_batch)["loss"])
    for _ in range(warm_steps - 1):
        jax.block_until_ready(engine.train_batch(next(batches))["loss"])

    def steps_for(duration):
        """Pipelined steps until one ends ``duration`` or more after the
        start; the step then in flight is waited for but not counted."""
        losses, ends, pending = [], [], None
        t0 = CLOCK()
        while True:
            with jax.profiler.TraceAnnotation("bench/batch_prep"):
                batch = next(batches)
            with jax.profiler.TraceAnnotation("bench/train_dispatch"):
                out = engine.train_batch(batch)["loss"]
            if pending is not None:
                with jax.profiler.TraceAnnotation("bench/train_wait"):
                    losses.append(float(pending))
                ends.append(CLOCK())
                if ends[-1] - t0 >= duration:
                    break
            pending = out
        with jax.profiler.TraceAnnotation("bench/train_wait"):
            float(out)
        return t0, ends[-1], losses

    hooks.window_open()
    t0, t1, losses = steps_for(seconds)
    hooks.window_close()
    if hooks.tail_s:
        hooks.trace_start()
        steps_for(hooks.tail_s)
        hooks.trace_stop()
    return t0, t1, len(losses), losses, first_batch, first_loss


def reference_first_loss(cfg, family, seed, first_batch):
    """Step-0 loss of the same seeded weights from the plain reference on
    ONE device: the engine rounds its fp32 master weights to bf16 inside
    the step, so the reference takes the same seeded weights cast to bf16.
    Call only after the engine has been released (device 0 is full)."""
    from deepspeedsyclsupport_tpu.models import build_model

    from .serve import seeded_params

    model = build_model(cfg["preset"], **cfg.get("overrides", {}))
    params = seeded_params(model, seed, "bfloat16")
    return reference.lm_loss(family, cfg, params, first_batch["input_ids"])


def batches_of(mix, cfg, seed, vocab):
    run = cfg["train"]
    if mix["kind"] != "train-batches":
        raise ValueError(f"{mix['name']}: a train path needs train-batches")
    return traffic.pattern_batches(seed, run["batch"], mix["seq_len"], vocab)
