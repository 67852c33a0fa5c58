"""The share of the device's busy time in the traced window that an
attention over SELECTED keys takes beyond a dense one's: the operations under
the scopes that the cell's family says its selection runs under (``scopes``
of its ``"selection"`` kind, ``benchmark/reference.py``: the scoring pass
with whatever it caches and gathers, the selection, and whatever gathers,
masks and attends over the selected keys), found by instruction name
(``benchmark/scopes.py``), its Pallas calls found as kernels by the names
they carry (``kernels``: the TPU compiler gives a custom call no scope).
``role`` narrows it to one of the family's three ``roles`` (``"score"``,
``"select"``, ``"attend"``: ``select_pick_share_pct`` reads ``"select"``).
The q, k, v and output projections are every attention's and not counted.

Nothing to read, and ``None``: a family that says no such kind, a program
without the scopes (every commit before the one that added them)."""
from benchmark import scopes, spans, trace
from benchmark.reference import layer_kind


def read(obs, role=None):
    kind = layer_kind(obs["family"], "selection")
    if kind is None:
        return None
    ops = scopes.scoped_ops(obs, kind["scopes"], kind["kernels"])
    if not ops:
        return None
    lo, hi = obs["trace_window"]
    tr = obs["trace"]
    busy = trace.union_s(trace.leaf_ops(tr, sorted(tr["devices"])[0]),
                         lo, hi)
    if role is not None:
        ops = [op for op in ops if op[0] in kind["roles"][role]]
    return 100.0 * trace.union_s(ops, lo, hi) / busy if busy else None


def forwards(obs, labels, kernels):
    """``[(the round's record, the FOLLOWING record, {label: seconds under it
    inside the forward's execution}), ...]`` over the traced rounds that
    launched a forward: what the three rooflines of this kind are read from.
    What the device counts of a forward comes back behind the NEXT round's
    sampled tokens, so it is the following record's (``{}`` where there is
    none); what the host counts from the chunks is the round's own. ``None``
    without a trace, records or the scopes."""
    rounds = spans.traced_rounds(obs)
    ops = scopes.scoped_ops(obs, labels, kernels)
    if not rounds or not ops:
        return None
    after = {d["round"] - 1: d for d in spans.round_records(obs)}
    dev = spans.Device(obs["trace"])
    out = []
    for d in rounds:
        ran = d["program"] and dev.forward(d["program"], d["t0"], d["t1"])
        if not ran:
            continue
        took = {}
        for label, program, start, dur in ops:
            if program == d["program"] and ran[0] <= start < ran[1]:
                took[label] = took.get(label, 0.0) + dur
        out.append((d, after.get(d["round"], {}), took))
    return out


def roofline(obs, part):
    """100 x the least seconds over the seconds taken, summed over the traced
    forwards for which the family's ``work`` of ``part`` (``"score"``,
    ``"prefill"`` or ``"rows"``) gives both; ``None`` where it gives none."""
    kind = layer_kind(obs["family"], "selection")
    if kind is None:
        return None
    say = kind[part]
    found = forwards(obs, say["scopes"], say["kernels"])
    work = found and say["work"](obs)
    if not work:
        return None
    ideal = took = 0.0
    for d, counted, seconds in found:
        both = work(d, counted, seconds)
        if both is None or not both[1]:
            continue
        ideal += both[0]
        took += both[1]
    return 100.0 * ideal / took if took else None
