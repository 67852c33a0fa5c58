"""The block scores' share of their roofline, over the traced rounds of
either program. What the MODEL needs, whoever computes it: every query
head's product with every window's pooled key that its row may see
(``bsa_windows`` of the program's ``round`` record, counted on the device and
summed over the sparse layers, x the family's ``bsa_score_flops``), the
pooled keys read once a tile (``bsa_visible_blocks`` x the windows a block x
``bsa_pool_bytes``: the record counts a tile's visible blocks a KV head) and
a block score written a (row, KV head, visible block) in float32, through
``flops.roofline_seconds``; against the device time under the ``bsa_score``
scope: the gather of a sequence's pooled keys through its block table, the
products, the softmax over a row's windows, the sum over a group's heads and
the pooling to blocks. A floor: it cannot pass 100.

Nothing to read, and ``None``: a family without the counts, records without
``bsa_windows``, a program without the scope, a trace without such a
round."""
from benchmark import flops
from benchmark.metrics import bsa_share_pct


def score_work(arch, family, windows, visible_blocks):
    """``(FLOPs, bytes)`` of the scores of forwards whose rows saw
    ``windows`` windows and whose tiles ``visible_blocks`` blocks a KV
    head."""
    per_block = arch["block"] // arch["stride"]
    return (windows * family.bsa_score_flops(arch),
            visible_blocks * per_block * family.bsa_pool_bytes(arch)
            + windows // per_block * arch["num_kv_heads"] * 4)


def read(obs):
    family = obs["family"]
    if not hasattr(family, "bsa_score_flops"):
        return None
    found = bsa_share_pct.forwards(obs, ("bsa_score",))
    if not found:
        return None
    arch = family.arch(obs["config"])
    ideal = took = 0.0
    for _d, counted, seconds in found:
        if not seconds.get("bsa_score") or not counted["bsa_windows"]:
            continue
        ideal += flops.roofline_seconds(
            *score_work(arch, family, counted["bsa_windows"],
                        counted["bsa_visible_blocks"]), obs["peaks"])[0]
        took += seconds["bsa_score"]
    return 100.0 * ideal / took if took else None
