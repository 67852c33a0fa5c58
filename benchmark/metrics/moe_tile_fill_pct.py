"""How full the row tiles of the grouped expert GEMMs were, over the window's
forwards: the (token, choice) rows that went through the experts over the
rows of the tiles they were laid in. The program lays every expert's rows
out from a tile boundary in tiles of ``moe_tile_rows`` rows (static by the
forward's shape: 16 in a decode step, 32-128 in a chunk round) and counts
the tiles it visits on the device (``moe_tiles``: each expert's rows rounded
up to whole tiles, summed over layers), so an expert with 4 rows fills a
quarter of a 16-row tile and one with 97 three quarters of a 128-row one.
What the MXU multiplies beyond this share is padding.

The rows are the record's ``moe_rows`` where the program counts them (it
holds a share of the experts), else the forward's ``tokens`` x the record's
``moe_rows_a_token``. ``moe_tiles`` (and ``moe_rows``) reach the host behind
the NEXT round's sampled tokens: a forward's counts are in the record that
follows its own (``benchmark/metrics/moe_roofline.py``).

Nothing to read, and ``None``: a program whose records lack the fields
(every commit before the one whose grouped GEMM has tiles of its own), a
dense model, a window in which no counted forward ran."""
from benchmark import spans


def read(obs):
    after = {d["round"] - 1: d for d in spans.round_records(obs)}
    rows = room = 0
    for d in spans.window_records(obs) or ():
        counted = after.get(d["round"], {})
        tiles = counted.get("moe_tiles", 0) * d.get("moe_tile_rows", 0)
        if not d["program"] or not tiles:
            continue
        rows += counted.get("moe_rows",
                            d["tokens"] * d.get("moe_rows_a_token", 0))
        room += tiles
    return 100.0 * rows / room if room else None
