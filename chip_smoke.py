#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

    python chip_smoke.py             # one TPU chip: train phase + serve phase
    python chip_smoke.py --chips 4   # four chips: ONLY the ZeRO-3 phase

Drives both main paths once through the public entry points, at the full
width of a supported model, with weights made from ``--seed``:

* train — ``dstpu.initialize`` on mistral-7b widths (depth cut to what 16
  B/param of training state leaves room for on one chip), bf16 + AdamW +
  remat + the flash kernel at sequence 2048; a few ``engine.train_batch``
  steps on fresh seeded batches; the loss must fall.
* serve — phi-2 at full depth and width in bf16 through
  ``InferenceEngineV2`` → ``warmup()`` → ``ServingSession``; requests of
  mixed prompt lengths driven to idle; every request must close with its
  full token budget, and one request's first tokens must agree with a plain
  ``jax.numpy`` greedy decode of the same params.
* ``--chips 4`` — ZeRO-3 over ``build_topology(fsdp=4)`` at a depth whose
  training state does not fit one chip, against the step-0 loss of the same
  seeded params from a plain jitted ``model.loss`` on one device.

There is no CPU mode: the first act is to require a TPU. The phase functions
return what they observed (``tests/unit/test_chip_smoke.py`` calls them at
tiny size on the CPU mesh); ``main`` adds the assertions only a chip can
meet — above all that the attention each phase ran is a ``tpu_custom_call``.
Any phase that raises ends the run non-zero. The last stdout line is
``{"ok": true, "device": {...}}`` and nothing else.
"""
import argparse
import contextlib
import json
import sys
import time

KERNEL = "tpu_custom_call"   # how a Mosaic (Pallas) kernel reads in HLO text
BF16_EPS = 2.0 ** -8


# --------------------------------------------------------------------- data
def pattern_batches(seed, n, batch, seq, vocab):
    """``n`` fresh seeded LM batches with learnable structure: every row is
    an arithmetic progression over the first ``vocab // 4`` tokens, so a few
    optimizer steps visibly lower the loss (uniform noise has nothing to
    learn, and a repeated batch would only show memorisation)."""
    import numpy as np

    rng = np.random.default_rng(seed)
    support = max(8, vocab // 4)
    for _ in range(n):
        start = rng.integers(0, support, (batch, 1))
        stride = rng.integers(1, 4, (batch, 1))
        ids = (start + stride * np.arange(seq)[None, :]) % support
        yield {"input_ids": ids.astype(np.int32)}


def peak_bytes():
    """Per-device ``peak_bytes_in_use`` (None where the backend has no
    ``memory_stats``, i.e. the CPU rehearsal)."""
    import jax

    return [(d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in jax.devices()]


@contextlib.contextmanager
def no_world_mesh():
    """Run a plain reference with no world topology installed (no sharding
    constraints, no shard_map around the attention), then put it back."""
    from deepspeedsyclsupport_tpu.comm.topology import (
        get_world_topology, reset_world_topology, set_world_topology)

    world = get_world_topology()
    reset_world_topology()
    try:
        yield
    finally:
        set_world_topology(world)


def release_device_memory(phase):
    """Collect what ``phase`` left behind before the next one needs the
    device (one process holds the chip for the whole run, so a leak is the
    next phase's OOM) and say so if arrays are still referenced."""
    import gc

    import jax

    gc.collect()
    left = sum(a.nbytes for a in jax.live_arrays())
    if left > 2**20:
        print(f"{phase}: {gb(left)} of arrays still referenced")


# -------------------------------------------------------------------- train
def train_phase(model_name, overrides, *, batch, seq, steps, seed,
                zero_stage=0, fsdp=1):
    """A few optimizer steps through ``dstpu.initialize`` /
    ``engine.train_batch``. Returns the per-step losses (each is the loss
    BEFORE that step's update), the engine and compile seconds."""
    import jax
    import numpy as np

    import deepspeedsyclsupport_tpu as dstpu
    from deepspeedsyclsupport_tpu.models import build_model

    model = build_model(model_name, **overrides)
    model.seed = seed
    topology = dstpu.build_topology(
        dp=1, fsdp=fsdp, devices=jax.devices()[:fsdp])
    config = {
        "train_batch_size": batch,
        "bf16": {"enabled": True},
        "optimizer": {"type": "adamw",
                      "params": {"lr": 3e-4, "weight_decay": 0.01}},
        "activation_checkpointing": {},     # remat on, the default policy
        "zero_optimization": {"stage": zero_stage},
        "steps_per_print": 10_000,
        "seed": seed,
    }
    batches = list(pattern_batches(seed, steps, batch, seq,
                                   model.config.vocab_size))
    engine, _, loader, _ = dstpu.initialize(
        model=model, config=config, topology=topology,
        training_data=batches)
    losses, step_s = [], []
    for b in loader:
        t0 = time.perf_counter()
        losses.append(float(engine.train_batch(b)["loss"]))  # blocks
        step_s.append(time.perf_counter() - t0)
    if not all(np.isfinite(losses)):
        raise AssertionError(f"train: non-finite loss {losses}")
    if not losses[-1] < losses[0]:
        raise AssertionError(f"train: loss did not fall: {losses}")
    return {"engine": engine, "losses": losses,
            "first_batch": batches[0],
            "compile_s": step_s[0] - min(step_s[1:]),
            "params": model.config.param_count(),
            "peak_bytes": peak_bytes()}


def fit_depth(model_name, bytes_limit, bytes_per_param=16, reserve=0.25):
    """Largest depth of ``model_name`` (widths untouched) whose
    ``bytes_per_param`` training state — fp32 params, grads and both Adam
    moments — leaves ``reserve`` of the device for activations, logits and
    the compiler's temporaries (mistral-7b on 15.75 GiB: 2 layers use 66 %,
    3 would use 87 %). The compiled step's ``memory_analysis`` is the judge
    afterwards (``main`` prints and checks it)."""
    from deepspeedsyclsupport_tpu.models import get_config

    n = 1
    while get_config(model_name, num_layers=n + 1).param_count() \
            * bytes_per_param <= (1 - reserve) * bytes_limit:
        n += 1
    return n


# -------------------------------------------------------------------- serve
def reference_greedy_margins(model_name, overrides, params, prompt, emitted):
    """Plain ``jax.numpy`` check of a greedy stream: re-run the FULL context
    through the training forward with the exact XLA attention (no paged
    pool, no kernel) and report, per emitted token, how far its reference
    logit sits below the reference argmax's (0.0 = identical choice), in
    units of that row's logit standard deviation."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deepspeedsyclsupport_tpu.models import build_model

    ref = build_model(model_name, **{**overrides, "attn_impl": "xla"})
    ids = np.zeros((1, len(prompt) + len(emitted)), np.int32)
    ids[0, :len(prompt)] = prompt
    ids[0, len(prompt):] = emitted
    with no_world_mesh():
        logits = jax.jit(ref.apply)(params, jnp.asarray(ids))[0]
    rows = np.asarray(logits[len(prompt) - 1:-1], np.float32)
    picked = rows[np.arange(len(emitted)), np.asarray(emitted)]
    return ((rows.max(-1) - picked) / rows.std(-1)).tolist(), \
        rows.argmax(-1).tolist()


def serve_phase(model_name, overrides, *, engine_config, prompt_lens,
                max_new_tokens, seed, dtype="bfloat16"):
    """Serve a handful of requests to idle through ``InferenceEngineV2`` +
    ``ServingSession``. Returns per-request outputs, the engine (for its
    compiled programs) and the reference margins of request 0."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    import deepspeedsyclsupport_tpu as dstpu
    from deepspeedsyclsupport_tpu.inference.params import (
        init_inference_params)
    from deepspeedsyclsupport_tpu.inference.v2.config import (
        ServingPolicyConfig)
    from deepspeedsyclsupport_tpu.inference.v2.engine_v2 import (
        InferenceEngineV2)
    from deepspeedsyclsupport_tpu.inference.v2.serving import ServingSession
    from deepspeedsyclsupport_tpu.models import build_model

    model = build_model(model_name, **overrides)
    model.seed = seed
    topology = dstpu.build_topology(dp=1, devices=jax.devices()[:1])
    params = init_inference_params(model, topology, jnp.dtype(dtype))
    engine = InferenceEngineV2(model, params, topology=topology,
                               dtype=dtype, seed=seed, **engine_config)
    t0 = time.perf_counter()
    engine.warmup()
    warmup_s = time.perf_counter() - t0

    rng = np.random.default_rng(seed)
    vocab = model.config.vocab_size
    prompts = [rng.integers(0, vocab, n).tolist() for n in prompt_lens]
    session = ServingSession(engine, ServingPolicyConfig(admission="none"))
    for uid, prompt in enumerate(prompts):
        session.submit(uid, prompt, max_new_tokens)
    out = {uid: [] for uid in range(len(prompts))}
    finished = {}
    rounds = 0
    t0 = time.perf_counter()
    while not session.idle:
        rounds += 1
        if rounds > 64 * (max_new_tokens + len(prompts)):
            raise AssertionError("serve: session never reached idle")
        for ev in session.step():
            if ev.kind == "token":
                out[ev.uid].extend(ev.tokens)
            elif ev.kind == "finish":
                finished[ev.uid] = ev.reason
            elif ev.kind in ("shed", "evict"):
                raise AssertionError(f"serve: request {ev.uid} {ev.kind} "
                                     f"({ev.reason}) under no load")
    serve_s = time.perf_counter() - t0
    session.close()
    for uid in out:
        if finished.get(uid) != "done" or len(out[uid]) != max_new_tokens:
            raise AssertionError(
                f"serve: request {uid} closed {finished.get(uid)!r} with "
                f"{len(out[uid])}/{max_new_tokens} tokens")
    if engine.allocator.free_blocks != engine.allocator.num_blocks:
        raise AssertionError("serve: KV blocks leaked after idle")

    margins, ref_tokens = reference_greedy_margins(
        model_name, overrides, engine.params, prompts[0], out[0])
    # a greedy stream agrees with the reference when each token IS the
    # reference argmax, or sits within rounding of it (random weights give
    # near-flat logits, so bf16 kernels may break a near-tie differently)
    # — but never far from it: a WRONG token sits ~4 std below the argmax
    tol = 0.1 if jnp.dtype(dtype) == jnp.bfloat16 else 1e-4
    if max(margins) > tol:
        raise AssertionError(
            f"serve: request 0 disagrees with the plain decode: tokens "
            f"{out[0]} vs {ref_tokens}, margins {margins} (tol {tol})")
    return {"engine": engine, "outputs": out, "finished": finished,
            "ref_tokens": ref_tokens, "margins": margins,
            "warmup_s": warmup_s, "serve_s": serve_s, "rounds": rounds,
            "dispatches": engine.host_dispatches,
            "peak_bytes": peak_bytes()}


# ------------------------------------------------------------ zero-3 (x4)
def reference_step0_loss(model_name, overrides, seed, batch, rows=2):
    """Step-0 loss of the same seeded params from a plain jitted
    ``model.loss`` on ONE device: exact XLA attention, no mesh, no engine.
    Params are generated in bf16 (the engine casts its fp32 master weights
    to bf16 inside the step — the same rounding) and the batch is walked
    ``rows`` sequences at a time so O(S^2) logits stay small."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deepspeedsyclsupport_tpu.models import build_model

    ref = build_model(model_name, **{**overrides, "attn_impl": "xla"})
    ref.seed = seed
    with no_world_mesh():
        params = jax.jit(lambda: jax.tree_util.tree_map(
            lambda x: x.astype(jnp.bfloat16), ref.init_params()))()
        loss = jax.jit(lambda p, b: ref.loss(p, b, None, train=False)[0])
        ids = batch["input_ids"]
        parts = [float(loss(params, {"input_ids": ids[i:i + rows]}))
                 for i in range(0, len(ids), rows)]
    return float(np.mean(parts))


def zero3_phase(model_name, overrides, *, batch, seq, steps, seed, fsdp):
    """ZeRO-3 over ``fsdp`` devices, then the one-device reference. Returns
    the train observations plus the census and per-device peak bytes taken
    BEFORE the reference touches device 0."""
    obs = train_phase(model_name, overrides, batch=batch, seq=seq,
                      steps=steps, seed=seed, zero_stage=3, fsdp=fsdp)
    engine = obs.pop("engine")
    obs["census"] = engine.graph_report(
        analyzers=("collectives",))["collectives"]
    obs["step_text"] = engine.compiled_train_step().as_text()
    del engine
    release_device_memory("zero3")   # device 0 takes the reference next
    obs["ref_loss"] = reference_step0_loss(
        model_name, overrides, seed, obs["first_batch"])
    if abs(obs["losses"][0] - obs["ref_loss"]) > \
            4 * BF16_EPS * abs(obs["ref_loss"]):
        raise AssertionError(
            f"zero3: step-0 loss {obs['losses'][0]} vs one-device "
            f"reference {obs['ref_loss']}")
    return obs


def kernel_operand_batches(hlo_text):
    """Leading (batch) dim of every Mosaic kernel result in ``hlo_text``."""
    import re

    return [int(m.group(1)) for m in re.finditer(
        r"= \(?[a-z0-9]+\[(\d+),[0-9,]*\][^=]*custom-call\([^\n]*"
        + KERNEL, hlo_text)]


# --------------------------------------------------------------------- main
def gb(n):
    return "n/a" if n is None else f"{n / 2**30:.2f} GiB"


def step_bytes(compiled):
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU; jax found "
              f"{devices[0].platform!r} — there is no CPU mode",
              file=sys.stderr)
        return 2
    if len(devices) != args.chips:
        print(f"chip_smoke: --chips {args.chips} but jax sees "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 2

    from deepspeedsyclsupport_tpu.utils.jax_cache import place_compile_cache

    limit = devices[0].memory_stats()["bytes_limit"]
    print(f"device: {len(devices)} x {devices[0].device_kind}, "
          f"{gb(limit)} each; compile cache: {place_compile_cache()}")

    if args.chips == 4:
        print(f"mesh order (id, coords): "
              f"{[(d.id, tuple(d.coords)) for d in devices]}")
        # ZeRO-3 shards the state four ways but not the activations: three
        # times the one-chip depth, whose state does NOT fit one chip
        depth = 3 * fit_depth("mistral-7b", limit)
        obs = zero3_phase("mistral-7b", {"num_layers": depth}, batch=8,
                          seq=2048, steps=4, seed=args.seed, fsdp=4)
        state = 16 * obs["params"]
        print(f"zero3: mistral-7b widths, depth 32 -> {depth} "
              f"({obs['params'] / 1e9:.2f} B params, {gb(state)} of state "
              f"vs {gb(limit)} per chip), compile {obs['compile_s']:.1f} s, "
              f"losses {[round(x, 4) for x in obs['losses']]}, one-device "
              f"reference {obs['ref_loss']:.4f}")
        assert state > limit, "zero3: the state fits one chip — no proof"
        peaks = obs["peak_bytes"]
        print(f"zero3: peak bytes per device {[gb(p) for p in peaks]}")
        assert max(peaks) < 1.25 * min(peaks), f"unbalanced: {peaks}"
        assert max(peaks) < 0.5 * state, "a device holds half the state"
        census = obs["census"]
        print(census.report())
        # the expectation is priced at the fp32 master dtype and at most two
        # gathers per param; the chip's compiler moves bf16 (half the bytes)
        # and re-gathers as it sees fit, so the check is a floor: every
        # sharded param gathered at least once, most grad bytes summed
        exp = census.expectation
        gathered = census.classes.bytes_of("param_gather")
        synced = census.classes.bytes_of("grad_sync")
        once = exp.param_gather_bytes // exp.notes["gathers_per_param"] // 2
        print(f"zero3: param all-gather {gb(gathered)} per step "
              f"({gathered / once:.2f} bf16 gathers of every sharded param), "
              f"grad sync {gb(synced)} "
              f"({synced / (exp.grad_sync_bytes // 2):.2f} of the bf16 "
              f"grad bytes)")
        assert gathered >= once, "a sharded param is never gathered"
        assert synced >= 0.75 * (exp.grad_sync_bytes // 2), \
            "most grad bytes are not summed across the mesh"
        batches = kernel_operand_batches(obs["step_text"])
        print(f"zero3: Mosaic kernel batch dims {sorted(set(batches))} "
              f"(global batch 8 over 4 devices)")
        assert batches and set(batches) == {8 // 4}, batches
    else:
        depth = fit_depth("mistral-7b", limit)
        obs = train_phase("mistral-7b", {"num_layers": depth}, batch=4,
                          seq=2048, steps=5, seed=args.seed)
        step = obs["engine"].compiled_train_step()
        print(f"train: mistral-7b widths, depth 32 -> {depth} "
              f"({obs['params'] / 1e9:.2f} B params), compile "
              f"{obs['compile_s']:.1f} s, step program {gb(step_bytes(step))}"
              f", losses {[round(x, 4) for x in obs['losses']]}, peak "
              f"{gb(obs['peak_bytes'][0])}")
        assert step_bytes(step) <= limit
        assert KERNEL in step.as_text(), "train: flash is not a Mosaic call"
        del obs, step
        release_device_memory("train")

        obs = serve_phase(
            "phi-2", {}, engine_config=dict(
                max_context=1024, max_sequences=8, num_blocks=96),
            prompt_lens=(17, 900, 300, 40, 130, 64), max_new_tokens=24,
            seed=args.seed)
        programs = obs["engine"].compiled_programs()
        print(f"serve: phi-2 full depth (32 layers), warmup "
              f"{obs['warmup_s']:.1f} s, {len(obs['outputs'])} requests x "
              f"24 tokens in {obs['rounds']} rounds / {obs['dispatches']} "
              f"dispatches, programs "
              f"{ {n: gb(step_bytes(c)) for n, c in programs.items()} }, "
              f"reference margins max {max(obs['margins']):.4f}, peak "
              f"{gb(obs['peak_bytes'][0])}")
        assert {"ragged_forward", "decode_forward"} <= set(programs)
        for name, compiled in programs.items():
            assert KERNEL in compiled.as_text(), \
                f"serve: {name} attention is not a Mosaic call"

    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
