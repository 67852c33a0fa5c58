"""The share of the device's busy time in the traced window that runs under
some of the program's named scopes (``args: {"labels": [...]}``; a metric is
an alias that names them), found by instruction name (``benchmark/scopes.py``;
a Pallas call keeps the scope it was traced under). ``h1_attn``: the
attention half of a layer that has attention heads and a Mamba-2 mixer side
by side (projections, rotation, pool write, the paged kernels, the output
projection), which with ``state_share_pct`` splits such a layer;
``lm_head``: the unembedding, which one chip of a pipeline that also holds
the head pays in every step.

Nothing to read, and ``None``: no trace, or a program without the scopes
(every commit before the one that added them)."""
from benchmark import scopes, trace


def read(obs, labels):
    ops = scopes.scoped_ops(obs, tuple(labels))
    if not ops:
        return None
    lo, hi = obs["trace_window"]
    tr = obs["trace"]
    busy = trace.union_s(trace.leaf_ops(tr, sorted(tr["devices"])[0]),
                         lo, hi)
    return 100.0 * trace.union_s(ops, lo, hi) / busy if busy else None
