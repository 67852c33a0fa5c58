"""How full the chunked delta rule's pieces are: the mean rows of the pieces
of two tokens or more over the window's rounds, of a ``kda_chunk_size`` of
64. A piece costs its triangular solve and its state's read and write
whatever its rows, so a round that brings its prompt rows in many short
pieces (chunk tails, a budget that cuts a prompt just past a piece's end)
pays more a row than one whose pieces are whole.

From the program's ``round`` records: a round's pieces' rows are ``kda_rows -
decode_rows`` (a one-token chunk is a decode row), its pieces ``kda_pieces``
(summed over the delta-rule layers, a one-token chunk one piece) over
``engine.state_stats()["layers"]`` less ``decode_rows``.

Nothing to read, and ``None``: an engine without ``state_stats()`` or a
model without state, records that do not cover the window
(``spans.window_records``) or hold no ``kda_rows`` / ``kda_pieces``, a
window without a round that carried a piece."""
from benchmark import spans


def read(obs):
    stats = getattr(obs.get("engine"), "state_stats", lambda: None)()
    if not stats or not stats.get("layers") or not obs.get("window"):
        return None
    rows = pieces = 0
    for d in spans.window_records(obs) or ():
        if d.get("kda_rows") is None or d.get("kda_pieces") is None:
            continue
        ones = d.get("decode_rows", 0)
        rows += d["kda_rows"] - ones
        pieces += d["kda_pieces"] // stats["layers"] - ones
    return rows / pieces if pieces > 0 else None
