"""The attention over atoms' share of its roofline under a selection of
keys, over the traced ``ragged_forward`` rounds. What the MODEL needs,
whoever computes it, is the family's to count (``prefill`` of its
``"selection"`` kind, ``benchmark/reference.py``): for every (row, SELECTED
or ATTENDED token) of the prompt chunks both products of every head, and the
keys and values the rows of a chunk or atom chose between them read once;
against the device time under the family's attend scope that is not the
one-token rows': the prefill custom calls, the mask and the gathers around
them. A kernel that visits EVERY cached key of an atom and masks reads about
the selected share of 100 here, and that is the finding. A floor: it cannot
pass 100.

Nothing to read, and ``None``: a family that says no such kind, a program
whose records lack the counts, a trace without such a round."""
from benchmark.metrics import select_share_pct


def read(obs):
    return select_share_pct.roofline(obs, "prefill")
