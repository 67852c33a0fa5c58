"""The recurrent-state layers' share of the device's busy time in the traced
window, whatever kind of state the model keeps: the operations under the
scopes the cell's family says its state layers run under (``share_scopes``
of its ``"recurrent_state"`` kind, ``benchmark/reference.py``: the
projections in and out, the convolution and its tail, the gates and norms,
the recurrence, whichever it has), found by instruction name
(``benchmark/scopes.py``); a state step that is a Pallas call is found as a
kernel by its name (``share_kernels``: the TPU compiler gives a custom call
no scope).

Nothing to read, and ``None``: a family that says no such kind (every model
without recurrent state), a program without the scopes (every commit before
the one that added them)."""
from benchmark import scopes, trace
from benchmark.reference import layer_kind


def read(obs):
    kind = layer_kind(obs["family"], "recurrent_state")
    if kind is None:
        return None
    ops = scopes.scoped_ops(obs, kind["share_scopes"],
                            kind.get("share_kernels", ()))
    if not ops:
        return None
    lo, hi = obs["trace_window"]
    tr = obs["trace"]
    busy = trace.union_s(trace.leaf_ops(tr, sorted(tr["devices"])[0]),
                         lo, hi)
    return 100.0 * trace.union_s(ops, lo, hi) / busy if busy else None
