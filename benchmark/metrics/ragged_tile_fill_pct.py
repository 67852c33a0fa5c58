"""How much of the rows the attention kernels were given held a token, over
the window's ``ragged_forward`` rounds: the forward's ``tokens`` over the
rows of the tiles they ran in, ``atoms`` live tiles of ``atom_q_size`` rows
(the chunks of two tokens or more) plus ``decode_rows`` one-row tiles (the
one-token chunks), all three from the program's ``round`` record. A mixed
round of 31 decoding sequences and one 96-token prompt fills 127 of 128 + 31
rows; with every chunk in atoms it filled 127 of 32 x 128.

Nothing to read, and ``None``: a program whose records lack the two fields
(every commit before the one that added them), a window without a
``ragged_forward`` round, and an attention that takes no atoms (the fields
are there and the tiles hold fewer rows than tokens: each row cost a token
anyway)."""
from benchmark import spans


def read(obs):
    records = [d for d in spans.window_records(obs) or ()
               if d["program"] == "ragged_forward"]
    if not records or not all("atoms" in d and "decode_rows" in d
                              for d in records):
        return None
    atom_rows = obs["engine"].config.atom_q_size
    tokens = sum(d["tokens"] for d in records)
    rows = sum(d["atoms"] * atom_rows + d["decode_rows"] for d in records)
    return 100.0 * tokens / rows if rows >= tokens else None
