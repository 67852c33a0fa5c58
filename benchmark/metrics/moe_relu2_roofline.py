"""The grouped expert GEMMs' share of their roofline where an expert is TWO
matrices (``relu(x U)^2 V``, nemotron_h), over the traced forwards: as
``moe_roofline`` in every other respect. What the two grouped products of
every ``E`` layer cannot avoid (the weights of each held expert that had at
least one live row, ``moe_touched`` of the program's ``round`` record, read
once, plus the routed rows' activations in and out) and their FLOPs, through
``flops.roofline_seconds``, against the device time of the operations under
the ``moe_experts`` scope inside each forward's execution. The work is the
family's own count (``families/<model_type>.py:expert_work``: two matrices at
the published width, whatever width the program stores), the expert layers
are the ``E`` of its pattern. ``moe_roofline`` counts three matrices an
expert and would read 1.5 x too high here, past 100. A floor: it cannot pass
100.

Nothing to read, and ``None``: a family without ``expert_work``, a program
without the scope or the counters, a trace without such a round."""
from benchmark import flops, scopes, spans


def read(obs):
    family = obs["family"]
    work = getattr(family, "expert_work", None)
    rounds = spans.traced_rounds(obs)
    ops = scopes.scoped_ops(obs, ("moe_experts",),
                            scopes.RAGGED_DOT_KERNELS)
    if work is None or not rounds or not ops:
        return None
    arch = family.arch(obs["config"])
    rows_a_token = arch["num_experts_per_tok"] * family.layer_counts(arch)["E"]
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
        ideal += flops.roofline_seconds(*work(arch, touched, rows),
                                        obs["peaks"])[0]
        took += seconds
    return 100.0 * ideal / took if took else None
