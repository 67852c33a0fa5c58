"""A looped stack's decode step against its memory roofline, over the traced
``decode_forward`` rounds: the bytes the step cannot avoid reading, whoever
implements it (the family's ``decode_step_bytes``: the layers' weights once
a PASS, since a pass needs the last one's output and no read serves two;
the head once; every cached token of the live contexts, ``ctx_tokens`` of
the program's ``round`` record, at every (pass, layer) row of the pool, in
the pool's dtype) over the HBM bandwidth, against the device time of the
program's whole execution. The WHOLE step's share, not a kernel's: writes,
norms, activations and launch gaps are in the time and not in the bytes, so
it is a floor and cannot pass 100. Bound by memory: a decode row meets 2
FLOPs a weight byte.

Nothing to read, and ``None``: a family without ``decode_step_bytes``
(every model whose layers run once), records without ``passes``, a trace
without such a round."""
from benchmark import spans


def read(obs):
    step_bytes = getattr(obs.get("family"), "decode_step_bytes", None)
    rounds = spans.traced_rounds(obs)
    if step_bytes is None or not rounds:
        return None
    arch = obs["family"].arch(obs["config"])
    itemsize = obs["engine"].kv.k.dtype.itemsize
    dev = spans.Device(obs["trace"])
    ideal = took = 0.0
    for d in rounds:
        if d["program"] != "decode_forward" or not d.get("passes"):
            continue
        ran = dev.forward("decode_forward", d["t0"], d["t1"])
        if not ran:
            continue
        ideal += step_bytes(arch, d["ctx_tokens"], itemsize) \
            / obs["peaks"]["hbm_bytes_per_s"]
        took += ran[1] - ran[0]
    return 100.0 * ideal / took if took else None
