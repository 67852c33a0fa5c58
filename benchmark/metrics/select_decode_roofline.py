"""The one-token rows' attention's share of its memory roofline under a
selection of keys: the bytes a row cannot avoid reading, K and V of what it
SELECTED (and, where the family counts the scoring pass with it, its
context's scoring keys), over the HBM bandwidth, against the device time
under the scopes the family names (``rows`` of its ``"selection"`` kind,
``benchmark/reference.py``, which also says over which programs' rounds: a
token indexer's rows in EITHER program, the scores and the selection being
``select_score_roofline``'s and ``select_pick_share_pct``'s; a block
selection's in the ``decode_forward`` rounds, everything between the pooled
keys' write and the output). A floor: it cannot pass 100.

Nothing to read, and ``None``: a family that says no such kind, a program
whose records lack the counts, a trace without such a round."""
from benchmark.metrics import select_share_pct


def read(obs):
    return select_share_pct.roofline(obs, "rows")
