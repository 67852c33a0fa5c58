#!/usr/bin/env python
"""``benchmark.parity``'s three probes of a sparse serving cell with the ROWS
looked at: is a probe's worst row one at which the reference's router has a
near-tie (its k-th and (k+1)-th scores within the served precision's rounding
in some layer), what do the rows WITHOUT one read, and did two checkouts feed
back the same greedy tokens at all (``ids_crc``). Where PERF.md section 6,
PR 58, takes its answer to "the worst row rose: is it a near-tie that
flipped?".

Usage, on the chip, from the root of the checkout UNDER TEST (the harness and
the program are the working directory's, so a parent's archive is measured
with this file)::

    cd _parent && python3 ../tools/parity_rows.py --workload dsv2-answers-sat \\
        --seed 7

One JSON object a probe. ``benchmark/`` is not edited."""
import argparse
import json
import os
import sys

sys.path.insert(0, os.getcwd())

WORST = 8


def rows_record(ids, n_prompt, logits, want, gaps, eps):
    """One probe's record. ``logits`` / ``want`` [n, V] the served and the
    reference's rows (the sequence's last ``n`` positions), ``gaps``
    [layers, n] the reference router's relative gaps at those positions."""
    import numpy as np

    err = np.abs(logits - want).max(-1) / want.std(-1)     # parity.row_errors
    ties = (gaps < eps).sum(0)                             # near-ties a row
    tied = ties > 0

    def stat(f, rows):
        return float(f(err[rows])) if rows.any() else None

    return {
        "tokens": int(len(ids)), "prompt": int(n_prompt), "rows": len(err),
        # two checkouts compare the same rows only where this agrees
        "ids_crc": int(np.bitwise_xor.reduce(
            np.asarray(ids, np.int64) * (np.arange(len(ids)) + 1))),
        "err_max": float(err.max()), "err_p50": float(np.median(err)),
        "rows_with_a_near_tie": int(tied.sum()),
        "err_max_tied_rows": stat(np.max, tied),
        "err_max_untied_rows": stat(np.max, ~tied),
        "err_p50_tied_rows": stat(np.median, tied),
        "err_p50_untied_rows": stat(np.median, ~tied),
        "worst_rows": [
            {"row": int(r), "err": round(float(err[r]), 4),
             "near_ties": int(ties[r]),
             "min_gap_over_eps": round(float(gaps[:, r].min() / eps), 3),
             "argmax_agrees": bool(logits[r].argmax() == want[r].argmax())}
            for r in np.argsort(-err)[:WORST]],
        "min_gap_over_eps_p50": round(float(np.median(gaps.min(0)) / eps), 3),
    }


def main(argv=None):
    from benchmark import parity, serve, spec

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
        print("parity_rows: needs a TPU, like benchmark.parity",
              file=sys.stderr)
        return 2
    model, engine = serve.build(cfg, family, args.seed % (2**31 - 1), {})
    served = []
    for uid, (prompt, n_out) in enumerate(
            parity.probes(mix, model.config.vocab_size, args.seed)):
        logits, tokens = parity.served_logits(engine, uid, prompt, n_out)
        served.append((len(prompt), prompt + tokens, logits[-args.tail:]))
    params = engine.params
    del engine            # the pool's room goes to the float32 reference
    arch = family.arch(cfg)
    eps = float(jax.numpy.finfo(cfg["dtype"]).eps)
    for n_prompt, ids, logits in served:
        ids = np.asarray(ids, np.int32)
        n = len(logits)
        want = np.asarray(jax.jit(
            lambda p, i: family.sequence_logits(arch, p, i)[-n:])(
                params, ids), np.float32)
        gaps = np.asarray(jax.jit(
            lambda p, i: family.router_gaps(arch, p, i))(params, ids))
        gaps = gaps.reshape(-1, gaps.shape[-1])[:, -n:]
        print(json.dumps(rows_record(ids, n_prompt, logits, want, gaps, eps)),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
