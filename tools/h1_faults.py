#!/usr/bin/env python
"""Does the seeded draw of ``falconh1-chat-sat`` hold the program? One probe
of the cell's mix served on the chip by the program as it is and under each
of four planted misreadings, every one held against the float32 reference of
the RIGHT weights by ``benchmark.parity``'s error and tolerance:

    python3 tools/h1_faults.py --workload falconh1-chat-sat --seed <n>

A multiplier LEFT OUT is planted in the weights, on the one engine the cell
builds (the program that forgets ``m`` after a matrix ``W`` is the program as
it is on ``W / m``: no second compile): ``attention_out_multiplier``,
``ssm_out_multiplier``, ``key_multiplier``. The halves IN SEQUENCE (attention
behind the norm, then the Mamba mixer behind the same norm of what attention
left) is the same leaves served as a stack of ``*``, ``M`` and ``F`` layers,
each multiplier folded into the matrix it scales: a second engine. Every
served run is fed the sound run's greedy tokens, so all are held to ONE
reference forward. Exit 0: the sound program within the tolerance and every
fault beyond it. Needs the chip (exit 2 off it); ``tests/test_falcon_h1.py``
holds the same faults, and more, in float32 on the CPU."""
import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import parity, serve, spec, traffic  # noqa: E402


def forced_logits(engine, uid, prompt, tokens):
    """``parity.served_logits`` with the continuation given: the logits at
    the prompt's last position and after each of ``tokens``."""
    import numpy as np

    rows = [np.asarray(engine.put([uid], [list(prompt)])[uid], np.float32)]
    for tok in tokens:
        rows.append(np.asarray(engine.put([uid], [[tok]])[uid], np.float32))
    engine.flush([uid])
    return np.stack(rows)


def divided(params, half, leaf, by):
    """``params`` with one leaf of the hybrid stack's ``half`` divided by a
    multiplier (in float32, stored as it was)."""
    import jax.numpy as jnp

    hybrid = params["hybrid_layers"]
    w = hybrid[half][leaf]
    return {**params, "hybrid_layers": {**hybrid, half: {
        **hybrid[half],
        leaf: (w.astype(jnp.float32) / by).astype(w.dtype)}}}


def in_sequence(model, params):
    """``(model, params)`` of the same leaves as ``*MF`` layers, each
    multiplier folded into its matrix (``tests/test_falcon_h1.py``'s)."""
    import dataclasses

    import jax.numpy as jnp

    from deepspeedsyclsupport_tpu.models import build_model

    cfg, m = model.config, model.config.mup
    layers = cfg.pattern_count("H")
    seq = build_model(dataclasses.replace(
        cfg, num_layers=3 * layers, layer_pattern="*MF" * layers,
        mup={"mlp": m.mlp}))
    times = lambda w, by: (w.astype(jnp.float32) * by).astype(  # noqa: E731
        w.dtype)
    h = params["hybrid_layers"]
    a, s = h["attn"], h["mamba"]
    return seq, {
        **{k: v for k, v in params.items() if k != "hybrid_layers"},
        "attn_layers": {"attn_norm": h["norm"], "attn": {
            "wq": times(a["wq"], m.attention_in),
            "wv": times(a["wv"], m.attention_in),
            "wk": times(a["wk"], m.attention_in * m.key),
            "wo": times(a["wo"], m.attention_out)}},
        "mamba_layers": {**s, "norm": h["norm"],
                         "in_proj": times(s["in_proj"], cfg.mup_in_proj),
                         "out_proj": times(s["out_proj"], m.ssm_out)}}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--steps", type=int, default=32)
    args = ap.parse_args(argv)
    bench = spec.Bench()
    cell = bench.cell(args.workload)
    cfg, mix = bench.config(cell["config"]), bench.traffic(cell["traffic"])
    family = bench.family(cfg)

    import jax
    import numpy as np

    if jax.devices()[0].platform != "tpu":
        print("h1_faults: needs a TPU — there is no CPU mode",
              file=sys.stderr)
        return 2
    model, engine = serve.build(cfg, family, args.seed % (2**31 - 1), {})
    params, mup = engine.params, model.config.mup
    pairs = sorted(traffic.length_pairs(mix, mix["count"]))
    prompt = np.random.default_rng(args.seed).integers(
        0, model.config.vocab_size, pairs[len(pairs) // 2][0]).tolist()
    sound, tokens = parity.served_logits(engine, 0, prompt, args.steps)
    arch = family.arch(cfg)
    n = len(sound)
    want = np.asarray(jax.jit(
        lambda p, i: family.sequence_logits(arch, p, i)[-n:])(
            params, np.asarray(prompt + tokens, np.int32)), np.float32)

    def report(name, logits):
        err = parity.row_errors(logits, want)
        rec = {"program": name, "err_max": float(err.max()),
               "err_p50": float(np.median(err))}
        print(json.dumps(rec), flush=True)
        return rec

    out = [report("sound", sound)]
    for name, wrong in (
            ("attention_out_multiplier_left_out",
             divided(params, "attn", "wo", mup.attention_out)),
            ("ssm_out_multiplier_left_out",
             divided(params, "mamba", "out_proj", mup.ssm_out)),
            ("key_multiplier_left_out",
             divided(params, "attn", "wk", mup.key))):
        engine.params = wrong
        out.append(report(name, forced_logits(engine, 0, prompt, tokens)))
    del engine, wrong      # the pools' room goes to the second engine
    from deepspeedsyclsupport_tpu.inference.v2.engine_v2 import (
        InferenceEngineV2)

    import deepspeedsyclsupport_tpu as dstpu

    seq_model, seq_params = in_sequence(model, params)
    engine = InferenceEngineV2(
        seq_model, seq_params, dtype=cfg["dtype"], seed=0,
        topology=dstpu.build_topology(dp=1, devices=jax.devices()[:1]),
        **cfg["engine"])
    out.append(report("the_halves_in_sequence",
                      forced_logits(engine, 0, prompt, tokens)))
    ok = out[0]["err_max"] <= parity.TOLERANCE < min(
        r["err_max"] for r in out[1:])
    print(json.dumps({"workload": cell["name"], "seed": args.seed,
                      "tokens": len(prompt) + len(tokens), "rows": n,
                      "tolerance": parity.TOLERANCE, "programs": out,
                      "sound_within_and_every_fault_refused": bool(ok),
                      "device": jax.devices()[0].device_kind}))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
