"""The router's share of the device's busy time in the traced window: the
operations under the program's ``moe_route`` scope alone, found by
instruction name (``benchmark/scopes.py``): the router's product and
softmax, the choice (under ``group_limited_greedy`` two top-k's, one over
the groups' best scores and one over the experts of the groups kept), the
sort of the (token, choice) rows by expert, the gather of the rows and the
count of what each expert was given. None of it is a matrix product of any
size: it is latency, and what ``moe_share_pct`` holds of it says how much of
the expert layer's time is not the experts'. A program without the scope
gives nothing to read."""
from benchmark import scopes, trace


def read(obs):
    ops = scopes.scoped_ops(obs, ("moe_route",))
    if not ops:
        return None
    lo, hi = obs["trace_window"]
    tr = obs["trace"]
    busy = trace.union_s(trace.leaf_ops(tr, sorted(tr["devices"])[0]),
                         lo, hi)
    return 100.0 * trace.union_s(ops, lo, hi) / busy if busy else None
