"""The grouped expert GEMMs' share of their roofline, over the traced
forwards: what the three ``ragged_dot`` of every expert layer cannot avoid —
the weights of each expert that had at least one live row (``moe_touched`` of
the program's ``round`` record: counted on the device, summed over layers),
read once, plus the routed rows' activations in and out — and their FLOPs,
through ``flops.roofline_seconds``, against the device time of the operations
under the ``moe_experts`` scope inside each forward's execution. A floor: it
counts only experts that had a row, each once, and neither the rows' second
read nor the ``[rows, intermediate]`` in between, so it cannot pass 100.

The rows are those that went through the experts THIS CHIP HOLDS, summed over
the expert layers like ``moe_touched``. A program that holds every expert
need not count them: each live token brings ``num_experts_per_tok`` rows to
each expert layer. A program that holds a share of the experts
(``reduced`` cuts the key that counts them; the router keeps its width) gets
only the rows routed to its own, about ``held / experts`` of that, and must
count them on the device and put them on the record as ``moe_rows``, beside
``moe_touched`` (which then counts the held experts that had a row).

Both reach the host behind the NEXT round's sampled tokens
(``reqtrace.FORWARD_FIELDS``): a forward's counts are in the record that
follows its own."""
from benchmark import flops, scopes, spans


def expert_layers(arch):
    """Layers whose MLP is sparse experts: all but the leading dense ones."""
    return arch["num_layers"] - arch.get("num_dense_layers", 0)


def expert_work(arch, touched, rows, itemsize=2):
    """``(FLOPs, bytes)`` of one forward's grouped GEMMs: ``rows`` (token,
    expert) rows in all its expert layers, each through one expert's three
    ``hidden x intermediate`` matrices; ``touched`` expert-layers' weights."""
    d, f = arch["hidden_size"], arch["intermediate_size"]
    return (rows * 3 * 2 * d * f,
            (touched * 3 * d * f + 2 * rows * d) * itemsize)


def read(obs):
    rounds = spans.traced_rounds(obs)
    ops = scopes.scoped_ops(obs, ("moe_experts",),
                            scopes.RAGGED_DOT_KERNELS)
    if not rounds or not ops:
        return None
    arch = obs["family"].arch(obs["config"])
    rows_a_token = arch["num_experts_per_tok"] * expert_layers(arch)
    after = {d["round"] - 1: d for d in spans.round_records(obs)}
    dev = spans.Device(obs["trace"])
    ideal = took = 0.0
    for d in rounds:
        counted = after.get(d["round"], {})
        touched = counted.get("moe_touched", 0)
        ran = d["program"] and dev.forward(d["program"], d["t0"], d["t1"])
        if not ran or not touched:
            continue
        seconds = sum(dur for _l, program, start, dur in ops
                      if program == d["program"] and ran[0] <= start < ran[1])
        if not seconds:
            continue
        rows = counted.get("moe_rows", d["tokens"] * rows_a_token)
        ideal += flops.roofline_seconds(
            *expert_work(arch, touched, rows), obs["peaks"])[0]
        took += seconds
    return 100.0 * ideal / took if took else None
