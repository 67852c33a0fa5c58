"""The hyper-connection streams' share of the device's busy time in the
traced window: the operations under the program's ``mhc`` scope (the norm
over a token's ``hc_mult x hidden`` stream values, the three maps, the
Sinkhorn rounds, and the three mixes: reading the streams into a sublayer,
mixing them, writing the sublayer's output back; twice a layer), found by
instruction name (``benchmark/scopes.py``). A program without the scope
gives nothing to read."""
from benchmark import scopes, trace


def read(obs):
    ops = scopes.scoped_ops(obs, ("mhc",))
    if not ops:
        return None
    lo, hi = obs["trace_window"]
    tr = obs["trace"]
    busy = trace.union_s(trace.leaf_ops(tr, sorted(tr["devices"])[0]),
                         lo, hi)
    return 100.0 * trace.union_s(ops, lo, hi) / busy if busy else None
