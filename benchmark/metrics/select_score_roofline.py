"""The scoring pass's share of its roofline under an attention over selected
keys, over the traced rounds of either program. What the MODEL needs,
whoever computes it, is the family's to count (``score`` of its
``"selection"`` kind, ``benchmark/reference.py``: a token indexer's keys read
by the one-token rows and its scores' FLOPs for the prompt chunks; a block
selection's products with the pooled keys each row may see, those keys read
once a tile and a score written a visible block), against the device time
under the scopes the family names for the pass: its projections or pooling,
the gather of a sequence's scoring keys through its block table and the
scores themselves. A floor: it cannot pass 100.

Nothing to read, and ``None``: a family that says no such kind, a program
whose records lack the counts, a trace without such a round."""
from benchmark.metrics import select_share_pct


def read(obs):
    return select_share_pct.roofline(obs, "score")
