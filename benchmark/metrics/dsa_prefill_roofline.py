"""The attention over atoms' share of its roofline under a sparse-attention
indexer, over the traced ``ragged_forward`` rounds. What the MODEL needs,
whoever computes it: for every (row, SELECTED token) pair of the prompt
chunks (``sel_pairs`` of the program's ``round`` record) and every head both
products, ``4 x head_dim`` FLOPs, in each layer; and the chunk's context
read once as K and V (the record has no per-chunk contexts, so their floor:
a chunk of n <= ``max_tokens_per_batch`` rows that covers P pairs of
``attn_pairs`` reads at least P / n rows). The family's
``selected_attention_work`` through ``flops.roofline_seconds``, over the
device time under the ``dsa_attend`` scope that is not the one-token rows'
(``dsa_rows``): the ``dsa_prefill`` custom calls and the gathers around
them. A kernel that visits EVERY cached pair of an atom and masks reads
about ``topk / context`` of 100 here, and that is the finding. A floor: it
cannot pass 100.

Nothing to read, and ``None``: a family without an indexer, a program whose
records lack ``sel_pairs``, a trace without such a round."""
from benchmark import flops, scopes, spans

SCOPES = ("dsa_attend", "dsa_rows")
KERNELS = (("dsa_prefill", "dsa_attend"),)


def read(obs):
    work = getattr(obs["family"], "selected_attention_work", None)
    rounds = spans.traced_rounds(obs)
    ops = scopes.scoped_ops(obs, SCOPES, KERNELS)
    if work is None or not rounds or not ops:
        return None
    arch = obs["family"].arch(obs["config"])
    chunk = obs["engine"].config.max_tokens_per_batch
    dev = spans.Device(obs["trace"])
    ideal = took = 0.0
    for d in rounds:
        pairs = d.get("sel_pairs")
        ran = d["program"] == "ragged_forward" and dev.forward(
            d["program"], d["t0"], d["t1"])
        if not pairs or not ran:
            continue
        seconds = sum(dur for label, program, start, dur in ops
                      if label == "dsa_attend" and program == d["program"]
                      and ran[0] <= start < ran[1])
        if not seconds:
            continue
        ideal += flops.roofline_seconds(
            *work(arch, pairs, d.get("attn_pairs", 0) / chunk),
            obs["peaks"])[0]
        took += seconds
    return 100.0 * ideal / took if took else None
