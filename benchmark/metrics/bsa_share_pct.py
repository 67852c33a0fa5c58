"""The block-sparse attention's own share of the device's busy time in the
traced window: the operations under the program's ``bsa_pool`` (the pooled
keys' write), ``bsa_score`` (their gather a sequence, the scores and the
pooling to blocks), ``bsa_select`` (the selection and the one-token rows'
page tables) and ``bsa_attend`` (whatever gathers, masks and attends:
``bsa_rows``, the one-token rows' part, inside it) scopes, found by
instruction name (``benchmark/scopes.py``); its three Pallas calls are found
as kernels by their names (the TPU compiler gives a custom call no scope).
The q, k, v and output projections, the head norms and the gate are every
attention's and not counted.

Nothing to read, and ``None``: a program without the scopes (every model
but one whose attention reads blocks chosen from pooled keys; every commit
before the one that added them)."""
from benchmark import scopes, spans, trace

SCOPES = ("bsa_pool", "bsa_score", "bsa_select", "bsa_attend", "bsa_rows")
# the custom calls: ``name=`` of the selection (ops/sparse_block.py), of the
# atoms' masked kernel and of the one-token rows' (inference/v2/bsa.py)
KERNELS = (("bsa_select", "bsa_select"), ("bsa_prefill", "bsa_attend"),
           ("bsa_rows", "bsa_rows"))


def read(obs, labels=SCOPES, kernels=KERNELS):
    ops = scopes.scoped_ops(obs, labels, kernels)
    if not ops:
        return None
    lo, hi = obs["trace_window"]
    tr = obs["trace"]
    busy = trace.union_s(trace.leaf_ops(tr, sorted(tr["devices"])[0]),
                         lo, hi)
    return 100.0 * trace.union_s(ops, lo, hi) / busy if busy else None


def forwards(obs, labels, kernels=KERNELS):
    """``[(the round's record, what the device counted of ITS forward,
    {label: seconds under it inside the forward's execution}), ...]`` over
    the traced rounds that launched a forward: what the three rooflines of
    this layer are read from. The device's counts of a forward come back
    behind the NEXT round's sampled tokens, so they are the following
    record's. ``None`` without a trace, records or the scopes."""
    rounds = spans.traced_rounds(obs)
    ops = scopes.scoped_ops(obs, labels, kernels)
    if not rounds or not ops:
        return None
    after = {d["round"] - 1: d for d in spans.round_records(obs)}
    dev = spans.Device(obs["trace"])
    out = []
    for d in rounds:
        counted = after.get(d["round"], {})
        ran = d["program"] and dev.forward(d["program"], d["t0"], d["t1"])
        if not ran or "bsa_pairs" not in counted:
            continue
        took = {}
        for label, program, start, dur in ops:
            if program == d["program"] and ran[0] <= start < ran[1]:
                took[label] = took.get(label, 0.0) + dur
        out.append((d, counted, took))
    return out
