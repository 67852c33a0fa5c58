"""The indexer's share of its roofline, over the traced rounds of either
program: the one-token rows read their whole context as indexer keys
(``dec_ctx_tokens`` of the program's ``round`` record x 128 B a token and
layer) over the HBM bandwidth, PLUS the prompt chunks' scores' FLOPs
(``attn_pairs`` x 16 heads x 64 x 2 a layer) through
``flops.roofline_seconds`` (the family's ``index_work``), against the device
time under the ``dsa_index`` scope: the indexer's projections, norm, rotary,
pool write, the gather of a sequence's keys through its block table and the
scores (the ``dsa_index_scores`` custom calls among them). A floor: it
cannot pass 100.

Nothing to read, and ``None``: a family without an indexer, a program whose
records lack the two counts, a trace without such a round."""
from benchmark import flops, scopes, spans

SCOPES = ("dsa_index",)
KERNELS = (("dsa_index_scores", "dsa_index"),)


def read(obs):
    work = getattr(obs["family"], "index_work", None)
    rounds = spans.traced_rounds(obs)
    ops = scopes.scoped_ops(obs, SCOPES, KERNELS)
    if work is None or not rounds or not ops:
        return None
    arch = obs["family"].arch(obs["config"])
    dev = spans.Device(obs["trace"])
    ideal = took = 0.0
    for d in rounds:
        pairs, ctx = d.get("attn_pairs", 0), d.get("dec_ctx_tokens", 0)
        ran = d["program"] and dev.forward(d["program"], d["t0"], d["t1"])
        if not (pairs or ctx) or not ran or "sel_pairs" not in d:
            continue
        seconds = sum(dur for _l, program, start, dur in ops
                      if program == d["program"] and ran[0] <= start < ran[1])
        if not seconds:
            continue
        ops_needed, bytes_needed = work(arch, pairs, ctx)
        ideal += flops.roofline_seconds(ops_needed, 0, obs["peaks"])[0] \
            + bytes_needed / obs["peaks"]["hbm_bytes_per_s"]
        took += seconds
    return 100.0 * ideal / took if took else None
