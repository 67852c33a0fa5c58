"""Planted faults of the sparse-attention indexer, held to the comparison
that ``benchmark.parity`` makes: a limit that a wrong program passed would
hold nothing.

    python3 -m benchmark.dsa_faults --workload keye-video-sat --seed <n>

Builds the cell's engine as ``benchmark.parity`` does and feeds it the mix's
LONGEST probe (``parity.probes``), once as the program is and once under each
fault of :data:`FAULTS`, planted in the served program alone (the reference
is the family's, untouched). Each served run is compared row for row with
the reference's forward of the tokens it emitted itself
(``parity.row_errors`` over the last ``--tail`` rows); the sound program has
to stay within ``parity.TOLERANCE`` and every fault beyond it. For the sound
run it also holds the rows' errors against the reference's own near-ties
(``router_gaps``, ``index_gaps``: in how many layers of a row's forward an
expert's or a selected key's margin lies inside the served precision's
rounding), so that what the error follows can be read off. Needs the chip
(exit 2 off it); ``tests/unit/test_sparse_index.py`` plants the same faults
at a tiny size on the CPU. Prints one JSON object as its last line.

The faults (``planted(name)`` is a context manager; build the engine inside
it, the programs are traced there):

* ``dense`` — every seen key attended in the place of the selected, in
  chunks of two tokens or more (a one-token row of the kernels' route keeps
  its selection: ``dsa.attend_rows`` selects inline);
* ``no_relu`` — the indexer's products summed without the relu;
* ``no_w`` — without the heads' weights (all ones);
* ``first`` — the first ``index_topk`` positions in the place of the best.
"""
import argparse
import contextlib
import json
import sys

FAULTS = ("dense", "no_relu", "no_w", "first")


def _patches(fault):
    """{(module, attribute): replacement} of one fault."""
    import jax
    import jax.numpy as jnp

    from deepspeedsyclsupport_tpu.inference.v2 import dsa
    from deepspeedsyclsupport_tpu.ops import sparse_index as si

    if fault == "dense":
        def select(scores, pos0, qlen, *, k, **_):
            _a, r, c = scores.shape
            return si._seen(pos0, qlen, r, c).astype(jnp.int8)
        return {(si, "select_topk"): select,
                (si, "select_topk_reference"): select}
    if fault == "first":
        def by_position(q_idx, w, k_seq, *_a, **_k):
            c = k_seq.shape[1]
            return jnp.broadcast_to(-jnp.arange(c, dtype=jnp.float32),
                                    (*q_idx.shape[:2], c))
        return {(si, "index_scores"): by_position,
                (si, "index_scores_reference"): by_position}
    if fault == "no_w":
        rows = dsa.index_rows

        def unweighted(p, y, cfg, positions):
            q_i, k_i, w = rows(p, y, cfg, positions)
            return q_i, k_i, jnp.ones_like(w)
        return {(dsa, "index_rows"): unweighted}
    if fault == "no_relu":
        def kernel(seq_ref, hi_ref, q_ref, w_ref, k_ref, out_ref, *, heads,
                   scale):
            s = jax.lax.dot_general(
                q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * w_ref[0]
            out_ref[0] = s.reshape(
                heads, out_ref.shape[1], k_ref.shape[1]).sum(0) * scale + 0.0

        def twin(q_idx, w, k_seq, tile_seq, *, scale):
            s = jnp.einsum("arhd,acd->arhc", q_idx.astype(jnp.float32),
                           k_seq[tile_seq].astype(jnp.float32))
            return (s * w.astype(jnp.float32)[..., None]).sum(2) * scale + 0.0
        return {(si, "_scores_kernel"): kernel,
                (si, "index_scores_reference"): twin}
    raise ValueError(f"unknown fault {fault!r}; have {FAULTS}")


@contextlib.contextmanager
def planted(fault):
    """The served program with ``fault`` in it (None: as it is) for what is
    TRACED inside the block."""
    patches = _patches(fault) if fault else {}
    kept = {at: getattr(*at) for at in patches}
    for (module, name), new in patches.items():
        setattr(module, name, new)
    try:
        yield
    finally:
        for (module, name), old in kept.items():
            setattr(module, name, old)


def rows_against_gaps(err, gaps, eps):
    """The rows' errors ``err`` [n] held against the reference's own margins
    ``gaps`` [L, S] (a router's k-th against its (k+1)-th probability, a
    selection's ``topk``-th against its next score, both relative): the
    share of the (layer, row) pairs inside ``eps``, how many layers a row
    has inside it, and how far the error follows that count."""
    import numpy as np

    near = np.asarray(gaps)[:, -len(err):] < eps          # [L, n]
    count = near.sum(0)
    follows = float(np.corrcoef(count, err)[0, 1]) if count.std() else None
    most = count >= np.quantile(count, 0.75)
    return {"pairs_within_eps": float(near.mean()),
            "layers_within_eps_a_row_p50": float(np.median(count)),
            "err_follows_count_r": follows,
            "err_max_most_ties": float(err[most].max()),
            "err_max_fewest_ties": float(
                err[count <= np.quantile(count, 0.25)].max())}


def main(argv=None):
    from . import parity, serve, spec

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
        print("benchmark.dsa_faults: needs a TPU — there is no CPU mode",
              file=sys.stderr)
        return 2
    arch = family.arch(cfg)
    seed32 = args.seed % (2**31 - 1)
    # the kept rows are cut inside the program: [S, V] float32 of a 47 k
    # sequence is 3.6 GB
    forward = jax.jit(
        lambda p, i: family.sequence_logits(arch, p, i)[-args.tail:])
    gaps_of = jax.jit(lambda p, i: (family.router_gaps(arch, p, i),
                                    family.index_gaps(arch, p, i)))
    out, want_of = [], {}
    for fault in (None, *FAULTS):
        with planted(fault):
            model, engine = serve.build(cfg, family, seed32, {})
            prompt, n_out = parity.probes(
                mix, model.config.vocab_size, args.seed)[-1]
            logits, tokens = parity.served_logits(engine, 0, prompt, n_out)
        params = engine.params
        del engine        # the pool's room goes to the float32 reference
        logits = logits[-args.tail:]
        ids = np.asarray(prompt + tokens, np.int32)
        if ids.tobytes() not in want_of:    # a fault emits tokens of its own
            want_of[ids.tobytes()] = np.asarray(
                forward(params, ids), np.float32)
        err = parity.row_errors(logits, want_of[ids.tobytes()])
        rec = {"fault": fault, "tokens": int(len(ids)), "rows": len(err),
               "err_max": float(err.max()), "err_p50": float(np.median(err)),
               "refused": bool(err.max() > parity.TOLERANCE)}
        if fault is None:
            eps = float(jax.numpy.finfo(cfg["dtype"]).eps)
            for name, gaps in zip(("router_gaps", "index_gaps"),
                                  gaps_of(params, ids)):
                rec[name] = rows_against_gaps(err, gaps, eps)
        del params
        out.append(rec)
        print(json.dumps(rec), flush=True)
    sound = not out[0]["refused"]
    all_refused = all(r["refused"] for r in out[1:])
    print(json.dumps({"workload": cell["name"], "seed": args.seed,
                      "tolerance": parity.TOLERANCE, "sound_within": sound,
                      "faults_refused": all_refused, "runs": out,
                      "device": jax.devices()[0].device_kind}))
    return 0 if sound and all_refused else 1


if __name__ == "__main__":
    sys.exit(main())
