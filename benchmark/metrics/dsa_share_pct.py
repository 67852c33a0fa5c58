"""The sparse attention's share of the device's busy time in the traced
window: the operations under the program's ``dsa_index`` scope (the
indexer's projections, norm, rotary, pool write and scores), ``dsa_select``
(the selection) and ``dsa_attend`` (whatever gathers, masks and attends over
the selected keys), found by instruction name (``benchmark/scopes.py``),
with the three Pallas kernels found by the names they carry
(``dsa_index_scores``, ``dsa_select``, ``dsa_prefill``). ``labels`` narrows
it to some of the three (``dsa_select_share_pct``). A program without the
scopes (no indexer, or every commit before the one that added them) gives
nothing to read."""
from benchmark import scopes, trace

SCOPES = ("dsa_index", "dsa_select", "dsa_attend")
KERNELS = (("dsa_index_scores", "dsa_index"), ("dsa_select", "dsa_select"),
           ("dsa_prefill", "dsa_attend"))


def read(obs, labels=SCOPES):
    ops = scopes.scoped_ops(obs, SCOPES, KERNELS)
    if not ops:
        return None
    lo, hi = obs["trace_window"]
    tr = obs["trace"]
    busy = trace.union_s(trace.leaf_ops(tr, sorted(tr["devices"])[0]),
                         lo, hi)
    mine = [op for op in ops if op[0] in labels]
    return 100.0 * trace.union_s(mine, lo, hi) / busy if busy else None
