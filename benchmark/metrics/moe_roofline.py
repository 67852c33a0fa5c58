"""The grouped expert GEMMs' share of their roofline, over the traced
forwards: what the three ``ragged_dot`` of every layer cannot avoid — the
weights of each expert that had at least one live row (``moe_touched`` of the
program's ``round`` record: counted on the device, summed over layers), read
once, plus the routed rows' activations in and out — and their FLOPs, through
``flops.roofline_seconds``, against the device time of the operations under
the ``moe_experts`` scope inside each forward's execution. A floor: it
counts only experts that had a row, each once, and neither the rows' second
read nor the ``[rows, intermediate]`` in between, so it cannot pass 100.

``moe_touched`` reaches the host behind the NEXT round's sampled tokens
(``reqtrace.FORWARD_FIELDS``): a forward's count is in the record that
follows its own."""
from benchmark import flops, scopes, spans


def expert_work(arch, touched, tokens, itemsize=2):
    """``(FLOPs, bytes)`` of one forward's grouped GEMMs: ``tokens`` live
    tokens, each through ``num_experts_per_tok`` experts of three
    ``hidden x intermediate`` matrices; ``touched`` expert-layers' weights."""
    d, f = arch["hidden_size"], arch["intermediate_size"]
    rows = tokens * arch["num_experts_per_tok"]
    return (rows * 3 * 2 * d * f,
            (touched * 3 * d * f + 2 * rows * d) * itemsize)


def read(obs):
    rounds = spans.traced_rounds(obs)
    ops = scopes.scoped_ops(obs, ("moe_experts",),
                            scopes.RAGGED_DOT_KERNELS)
    if not rounds or not ops:
        return None
    arch = obs["family"].arch(obs["config"])
    touched_after = {d["round"] - 1: d.get("moe_touched", 0)
                     for d in spans.round_records(obs)}
    dev = spans.Device(obs["trace"])
    ideal = took = 0.0
    for d in rounds:
        touched = touched_after.get(d["round"], 0)
        ran = d["program"] and dev.forward(d["program"], d["t0"], d["t1"])
        if not ran or not touched:
            continue
        seconds = sum(dur for _l, program, start, dur in ops
                      if program == d["program"] and ran[0] <= start < ran[1])
        if not seconds:
            continue
        ideal += flops.roofline_seconds(
            *expert_work(arch, touched, d["tokens"]), obs["peaks"])[0]
        took += seconds
    return 100.0 * ideal / took if took else None
