#!/usr/bin/env python
"""``benchmark.parity``'s comparison at probes of one's own choosing, for a
cell whose mix does not hold the lengths a reviewer asks about
(``sala-docs-sat``, PR 59: a prompt under ``dense_len`` whose decode tail
crosses it, a prompt with a ragged last chunk, a decode tail behind the
longest prompt).

Usage, from the root of a checkout, on the chip::

    python tools/parity_probes.py --workload sala-docs-sat --seed 3 \\
        --probes 7936+300,40000+64,94208+256 [--tail 256]

A probe is ``<prompt tokens>+<decode steps>``. Each is fed through the
engine alone as ``benchmark.parity`` feeds its own (the prompt in chunks of
``max_tokens_per_batch``, then its own greedy tokens one at a time) and its
last ``--tail`` rows of LOGITS are held against the family's
``sequence_logits`` under ``parity.TOLERANCE``; the reference with its
weights rounded to 3 mantissa bits is held against the true one over the
FIRST probe and must come out beyond it. A family with ``selection_gaps``
(block-sparse attention) has the near-ties of its selection counted: the
(layer, row, KV group) triples whose cut-off gap lies under the served
precision's rounding, so that the served selection may differ there.
``benchmark/`` is not edited. Prints one JSON object a probe and one last
line; exit 0 iff every probe is within the tolerance and the lower
precision is refused."""
import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import parity, serve, spec  # noqa: E402


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--probes", required=True)
    ap.add_argument("--tail", type=int, default=256)
    args = ap.parse_args(argv)
    bench = spec.Bench()
    cell = bench.cell(args.workload)
    cfg = bench.config(cell["config"])
    family = bench.family(cfg)

    import jax
    import numpy as np

    if jax.devices()[0].platform != "tpu":
        print("parity_probes: needs a TPU", file=sys.stderr)
        return 2
    model, engine = serve.build(cfg, family, args.seed % (2**31 - 1), {})
    rng = np.random.default_rng(args.seed)
    served = []
    for uid, probe in enumerate(args.probes.split(",")):
        n_prompt, n_out = map(int, probe.split("+"))
        prompt = rng.integers(0, model.config.vocab_size, n_prompt).tolist()
        logits, tokens = parity.served_logits(engine, uid, prompt, n_out)
        served.append((prompt + tokens, logits[-args.tail:]))
    params = engine.params
    del engine            # the pool's room goes to the float32 reference

    arch = family.arch(cfg)
    eps = float(jax.numpy.finfo(cfg["dtype"]).eps)
    gaps_of = getattr(family, "selection_gaps", None)
    forward = jax.jit(lambda p, i, n: family.sequence_logits(arch, p, i)[-n:],
                      static_argnums=2)
    out, first_want = [], None
    for ids, logits in served:
        ids, n = np.asarray(ids, np.int32), len(logits)
        want = np.asarray(forward(params, ids, n), np.float32)
        first_want = want if first_want is None else first_want
        err = parity.row_errors(logits, want)
        rec = {"tokens": int(len(ids)), "rows": n,
               "err_max": float(err.max()),
               "err_p50": float(np.median(err)),
               "err_p99": float(np.quantile(err, 0.99)),
               "argmax_agree": float(
                   (logits.argmax(-1) == want.argmax(-1)).mean())}
        if gaps_of is not None:
            gaps = np.asarray(jax.jit(
                lambda p, i: gaps_of(arch, p, i))(params, ids))
            rec["selections"] = int(np.isfinite(gaps).sum())
            rec["near_ties"] = int((gaps < eps).sum())
            rec["near_ties_in_rows"] = int((gaps[:, -n:] < eps).sum())
        out.append(rec)
        print(json.dumps(rec), flush=True)
    worst = max(r["err_max"] for r in out)
    lower = jax.jit(lambda x: jax.lax.reduce_precision(
        x, exponent_bits=8, mantissa_bits=3), donate_argnums=0)
    params = jax.tree_util.tree_map(
        lambda x: lower(x) if jax.numpy.issubdtype(x.dtype, jax.numpy.floating)
        else x, params)
    ids, logits = served[0]
    below = float(parity.row_errors(
        np.asarray(forward(params, np.asarray(ids, np.int32), len(logits)),
                   np.float32), first_want).max())
    print(json.dumps({"workload": cell["name"], "seed": args.seed,
                      "tolerance": parity.TOLERANCE, "err_max": worst,
                      "within": bool(worst <= parity.TOLERANCE),
                      "below_err_max": below,
                      "below_refused": bool(below > parity.TOLERANCE),
                      "probes": out,
                      "device": jax.devices()[0].device_kind}))
    return 0 if worst <= parity.TOLERANCE < below else 1


if __name__ == "__main__":
    sys.exit(main())
