"""The one-token rows' sparse attention's share of its memory roofline, over
the traced ``decode_forward`` rounds: the bytes a row cannot avoid reading,
the pages it selected as K and V a KV head (``bsa_pages`` of the program's
``round`` record x the family's ``bsa_page_bytes``) and its context's pooled
keys (``bsa_windows`` x KV heads x ``bsa_pool_bytes``: 32 B a token and
layer where a dense row reads 1,024), over the HBM bandwidth; against the
device time under the ``bsa_score``, ``bsa_select`` and ``bsa_rows`` scopes:
everything between the pooled keys' write and the output (as
``dsa_decode_roofline`` + ``dsa_index_roofline`` count for the token
indexer). A floor: it cannot pass 100.

Nothing to read, and ``None``: a family without the counts, records without
``bsa_pages``, a program without the scopes, a trace without such a
round."""
from benchmark.metrics import bsa_share_pct

SCOPES = ("bsa_score", "bsa_select", "bsa_rows")


def read(obs):
    family = obs["family"]
    if not hasattr(family, "bsa_page_bytes"):
        return None
    found = bsa_share_pct.forwards(obs, SCOPES)
    if not found:
        return None
    arch = family.arch(obs["config"])
    need = took = 0.0
    for d, counted, seconds in found:
        if d["program"] != "decode_forward" or not counted["bsa_pages"] \
                or not seconds:
            continue
        need += counted["bsa_pages"] * family.bsa_page_bytes(arch) \
            + counted["bsa_windows"] * arch["num_kv_heads"] \
            * family.bsa_pool_bytes(arch)
        took += sum(seconds.values())
    return 100.0 * need / obs["peaks"]["hbm_bytes_per_s"] / took \
        if took else None
