"""The ragged kernel's share of its roofline on a latent pool, over the
traced ``ragged_forward`` rounds. What the absorbed form cannot avoid: for
every (row, cached token) pair of the prompt chunks (``attn_pairs`` of the
program's ``round`` record, causal within the chunk) and every head, the
score against the whole cached row (``kv_lora_rank + qk_rope_head_dim``
values) and the value product over its latent part (``kv_lora_rank``), 2
FLOPs each, in each layer's call; and the cached rows read once a chunk.
The record has no per-chunk contexts, so the bytes are their floor: a chunk
of n <= ``max_tokens_per_batch`` rows that covers P pairs reads at least
P / n rows. Through ``flops.roofline_seconds``, over the device time of the
``ragged_prefill`` custom calls inside each forward's execution. At ~70 kFLOP
a pair and layer the bound is compute, by two orders. A floor (the row's
useful lanes, not its padding; none of a diagonal block's masked half), so
it cannot pass 100.

Nothing to read, and ``None``: a family without latent attention, a program
whose records lack ``attn_pairs``, a trace without such a round."""
from benchmark import flops, scopes, spans

KERNEL = (("ragged_prefill", "mla_prefill"),)


def prefill_work(arch, attn_pairs, max_chunk, row_bytes):
    """``(FLOPs, bytes)`` of one forward's ``ragged_prefill`` calls, all
    layers: ``attn_pairs`` (row, cached token) pairs in each."""
    d_k = arch["kv_lora_rank"] + arch["qk_rope_head_dim"]
    per_pair = arch["num_heads"] * 2 * (d_k + arch["kv_lora_rank"])
    return (arch["num_layers"] * attn_pairs * per_pair,
            arch["num_layers"] * attn_pairs / max_chunk * row_bytes)


def read(obs):
    arch = obs["family"].arch(obs["config"])
    rounds = spans.traced_rounds(obs)
    ops = scopes.scoped_ops(obs, ("mla_prefill",), KERNEL)
    if "kv_lora_rank" not in arch or not rounds or not ops:
        return None
    engine = obs["engine"]
    pool = engine.kv.k
    row_bytes = pool.shape[-1] * pool.dtype.itemsize
    dev = spans.Device(obs["trace"])
    ideal = took = 0.0
    for d in rounds:
        pairs = d.get("attn_pairs")
        ran = d["program"] == "ragged_forward" and dev.forward(
            d["program"], d["t0"], d["t1"])
        if not pairs or not ran:
            continue
        seconds = sum(dur for _l, program, start, dur in ops
                      if program == d["program"] and ran[0] <= start < ran[1])
        if not seconds:
            continue
        ideal += flops.roofline_seconds(*prefill_work(
            arch, pairs, engine.config.max_tokens_per_batch, row_bytes),
            obs["peaks"])[0]
        took += seconds
    return 100.0 * ideal / took if took else None
