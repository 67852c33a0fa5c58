"""The attention over atoms' share of its roofline under a selection of
blocks, over the traced ``ragged_forward`` rounds. What the MODEL needs,
whoever computes it: for every (row, KV group, ATTENDED token) of the prompt
chunks (``bsa_pairs`` less ``bsa_row_pairs`` of the program's ``round``
record: counted on the device from the selection itself, summed over the
sparse layers) the group's heads' two products (the family's
``bsa_attend_flops``), and the pages the rows of an atom chose BETWEEN them
read once a KV head (``bsa_pages`` less ``bsa_row_pages`` x
``bsa_page_bytes``: the union is what the mathematics lets a tile share),
through ``flops.roofline_seconds``; against the device time under the
``bsa_attend`` scope that is not the one-token rows' (``bsa_rows``): the
``bsa_prefill`` custom calls, the mask and the gathers around them. The count
is the selection's own, the same whatever route attends: a kernel that
visits EVERY cached page of an atom, each KV head's tile computed over both
heads, reads low, and that is the finding. A floor: it cannot pass 100.

Nothing to read, and ``None``: a family without the counts, records without
``bsa_pairs``, a program without the scope, a trace without such a round."""
from benchmark import flops
from benchmark.metrics import bsa_share_pct

SCOPES = ("bsa_attend", "bsa_rows")


def read(obs):
    family = obs["family"]
    if not hasattr(family, "bsa_attend_flops"):
        return None
    found = bsa_share_pct.forwards(obs, SCOPES)
    if not found:
        return None
    arch = family.arch(obs["config"])
    ideal = took = 0.0
    for d, counted, seconds in found:
        pairs = counted["bsa_pairs"] - counted.get("bsa_row_pairs", 0)
        pages = counted["bsa_pages"] - counted.get("bsa_row_pages", 0)
        if d["program"] != "ragged_forward" or pairs <= 0 \
                or not seconds.get("bsa_attend"):
            continue
        ideal += flops.roofline_seconds(
            pairs * family.bsa_attend_flops(arch),
            pages * family.bsa_page_bytes(arch), obs["peaks"])[0]
        took += seconds["bsa_attend"]
    return 100.0 * ideal / took if took else None
