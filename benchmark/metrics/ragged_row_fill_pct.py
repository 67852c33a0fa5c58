"""How much of the rows the mixed rounds' forwards ran at held a token, over
the window's ``ragged_forward`` rounds: the forward's ``tokens`` over its
``rows``, the static row count its batch was built at (pads included), both
from the program's ``round`` record. A forward's dense work (projections,
MLP, the q gather) is paid by the row whether or not the row holds a token:
31 decoding sequences and one 96-token prompt are 127 tokens, 16.5 % of a
768-row forward and 49.6 % of a 256-row one. An engine that builds every
mixed batch at ``max_tokens_per_batch`` rows reads the mean round's tokens
over that budget; one that picks the smaller of two static shapes where it
holds the round reads higher.

Nothing to read, and ``None``: a program whose records lack ``rows`` (every
commit before the one that added it), and a window without a
``ragged_forward`` round."""
from benchmark import spans


def read(obs):
    records = [d for d in spans.window_records(obs) or ()
               if d["program"] == "ragged_forward"]
    if not records or not all(d.get("rows") for d in records):
        return None
    return 100.0 * sum(d["tokens"] for d in records) \
        / sum(d["rows"] for d in records)
