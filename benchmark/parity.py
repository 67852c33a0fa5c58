"""Parity of a serving cell's program with its family's plain reference, at
the configuration's own widths, outside any timed window:

    python3 -m benchmark.parity --workload <cell> --seed <n> [--tail 256]

Builds the engine as the harness does (``serve.build``: preset, width check,
seeded weights, pool, warm-up), takes three requests of the cell's mix — the
grid's shortest, median and longest prompt with the answers the grid pairs
them with — and feeds each through the engine alone: the prompt through
``put()``, which prefills it in chunks of ``max_tokens_per_batch``, then its
own greedy tokens one at a time, which decode through the cache. The LOGITS
of the prompt's last position and of every decode step (for a long request
the last ``--tail`` positions) are held against ``sequence_logits`` of the
whole sequence: logits and not tokens, because with seeded noise the largest
logit changes on rounding. The error of a row is ``max |served - reference|``
over the row's logit standard deviation.

The limit is held from both sides: the same reference with the weights
rounded to the nearest precision BELOW the served one (fp8 e4m3's 3 mantissa
bits under bf16's 7) is compared with the true reference over the first
request, and has to come out beyond the tolerance (``below_refused``): a
limit that a lower precision passed would hold nothing.

A family that routes (``router_gaps``) also has its near-ties counted: the
(layer, position) pairs where the reference's k-th and (k+1)-th router
probabilities lie within the served precision's rounding of each other, so
that the served top-k set may differ there. They are reported, not dropped.

Needs the chip, like ``benchmark.run`` (exit 2 off it);
``tests/benchmark/test_olmoe.py`` drives ``served_logits`` at tiny size on
the CPU. Prints one JSON object as its last line.
"""
import argparse
import json
import sys

from . import serve, spec, traffic

# 0.1 logit-std is the harness's own limit for `correct` (a wrong token sits
# ~4 std below the argmax); PERF.md section 6 has what a bf16 program measured
# against the float32 reference of the same bf16 weights on the v5e
TOLERANCE = 0.1


def served_logits(engine, uid, prompt, n_follow):
    """``(logits [1 + n_follow, V] float32, the greedy tokens [n_follow])``:
    the engine's logits at the prompt's last position, prefilled through
    ``put()``'s chunks, and after each of its own greedy tokens fed back one
    at a time through the decode path."""
    import numpy as np

    rows = [np.asarray(engine.put([uid], [list(prompt)])[uid], np.float32)]
    tokens = []
    for _ in range(n_follow):
        tokens.append(int(rows[-1].argmax()))
        rows.append(np.asarray(engine.put([uid], [[tokens[-1]]])[uid],
                               np.float32))
    engine.flush([uid])
    return np.stack(rows), tokens


def row_errors(served, want):
    """Per row: ``max |served - want| / std(want)``."""
    import numpy as np

    return np.abs(served - want).max(-1) / want.std(-1)


def probes(mix, vocab, seed):
    """The grid's shortest, median and longest prompt, each with the output
    length the grid pairs it with, tokens from ``seed``."""
    import numpy as np

    pairs = sorted(traffic.length_pairs(mix, mix["count"]))
    rng = np.random.default_rng(seed)
    return [(rng.integers(0, vocab, p).tolist(), int(o))
            for p, o in (pairs[0], pairs[len(pairs) // 2], pairs[-1])]


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--tail", type=int, default=256)
    args = ap.parse_args(argv)
    bench = spec.Bench()
    cell = bench.cell(args.workload)
    cfg, mix = bench.config(cell["config"]), bench.traffic(cell["traffic"])
    family = bench.family(cfg)

    import jax
    import numpy as np

    if jax.devices()[0].platform != "tpu":
        print("benchmark.parity: needs a TPU — there is no CPU mode",
              file=sys.stderr)
        return 2
    model, engine = serve.build(cfg, family, args.seed % (2**31 - 1), {})
    served = []
    for uid, (prompt, n_out) in enumerate(
            probes(mix, model.config.vocab_size, args.seed)):
        logits, tokens = served_logits(engine, uid, prompt, n_out)
        served.append((prompt + tokens, logits[-args.tail:]))
    params = engine.params
    del engine            # the pool's room goes to the float32 reference

    arch = family.arch(cfg)
    eps = float(jax.numpy.finfo(cfg["dtype"]).eps)
    gaps_of = getattr(family, "router_gaps", None)
    out = []
    for ids, logits in served:
        ids = np.asarray(ids, np.int32)
        # served row i is the logits at position len(prompt) - 1 + i, so the
        # rows kept are the sequence's last ``n`` positions
        n = len(logits)
        want = np.asarray(jax.jit(
            lambda p, i: family.sequence_logits(arch, p, i)[-n:])(
                params, ids), np.float32)
        if not out:
            first_want = want       # what the lower precision is held to
        err = row_errors(logits, want)
        rec = {"tokens": int(len(ids)), "rows": n,
               "err_max": float(err.max()),
               "err_p50": float(np.median(err)),
               "err_p99": float(np.quantile(err, 0.99)),
               "argmax_agree": float(
                   (logits.argmax(-1) == want.argmax(-1)).mean())}
        if gaps_of is not None:
            gaps = np.asarray(jax.jit(
                lambda p, i: gaps_of(arch, p, i))(params, ids))
            rec["router_positions"] = int(gaps.size)
            rec["router_near_ties"] = int((gaps < eps).sum())
            rec["router_near_ties_in_rows"] = int((gaps[:, -n:] < eps).sum())
        out.append(rec)
        print(json.dumps(rec), flush=True)
    worst = max(r["err_max"] for r in out)
    # the nearest precision below: leaf by leaf and in place (donated), so
    # that one leaf's copy is all the extra room it takes; params end here
    # (reduce_precision, not a pair of casts: the TPU compiler may keep
    # "excess precision" through a cast down and up again, and did. The
    # exponent keeps bf16's range: seeded weights of 0.02 sit in fp8's
    # subnormals, so this is MILDER than a real fp8 cast)
    lower = jax.jit(lambda x: jax.lax.reduce_precision(
        x, exponent_bits=8, mantissa_bits=3), donate_argnums=0)
    params = jax.tree_util.tree_map(
        lambda x: lower(x) if jax.numpy.issubdtype(x.dtype, jax.numpy.floating)
        else x, params)
    ids, logits = served[0]
    forward = jax.jit(lambda p, i: family.sequence_logits(arch, p, i))
    ids = np.asarray(ids, np.int32)
    below_err = float(row_errors(
        np.asarray(forward(params, ids), np.float32)[-len(logits):],
        first_want).max())
    print(json.dumps({"workload": cell["name"], "seed": args.seed,
                      "tolerance": TOLERANCE, "err_max": worst,
                      "within": bool(worst <= TOLERANCE),
                      "below_precision": "3 mantissa bits (fp8 e4m3's)",
                      "below_err_max": below_err,
                      "below_refused": bool(below_err > TOLERANCE),
                      "requests": out,
                      "device": jax.devices()[0].device_kind}))
    return 0 if worst <= TOLERANCE < below_err else 1


if __name__ == "__main__":
    sys.exit(main())
