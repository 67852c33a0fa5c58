"""``ragged_row_fill_pct``: the reader on the recorded v5e rounds
(``data/decode_sat_v5e_rounds.json``) with the field the ``round`` record
gained, ``rows``; on records that lack it, as every commit before the one
that builds a mixed round at the smallest static shape that holds it writes
them; and on a window without a ``ragged_forward`` round."""
import copy
import json
from pathlib import Path

import pytest

from benchmark import spec

DATA = Path(__file__).parent / "data"
READ = spec.Bench().reader("ragged_row_fill_pct")


def _recorded(rows=None, programs=None):
    """The recorded rounds 104-107 (``decode_forward`` x 2, a
    ``ragged_forward`` of 127 tokens, ``decode_forward``), ``rows[i]``
    written into the window's ``i``-th record (None: the parent's records)
    and ``programs[i]`` over its program."""
    fx = json.loads((DATA / "decode_sat_v5e_rounds.json").read_text())
    stages = copy.deepcopy(fx["stages"])
    window = (fx["rounds"][2][1], fx["rounds"][6][1])
    inside = [s["data"] for s in stages if window[0] < s["data"]["t1"]]
    assert [d["program"] for d in inside] == [
        "decode_forward", "decode_forward", "ragged_forward",
        "decode_forward"] and inside[2]["tokens"] == 127
    for k, d in enumerate(inside):
        if rows is not None:
            d["rows"] = rows[k]
        if programs is not None:
            d["program"] = programs[k]
    return {"rounds": [tuple(r) for r in fx["rounds"]], "stages": stages,
            "window": window}


MIXED = ("ragged_forward",) * 4   # tokens 32, 32, 127, 32


@pytest.mark.parametrize("rows, programs, want", [
    ((32, 32, 768, 32), None, 100.0 * 127 / 768),   # every round at budget
    ((32, 32, 256, 32), None, 100.0 * 127 / 256),   # the shape that holds it
    ((256, 256, 256, 256), MIXED, 100.0 * 223 / 1024),
    ((256, 256, 384, 768), MIXED, 100.0 * 223 / 1664),   # a window of shapes
], ids=["at_the_budget", "smallest_shape", "sum_over_rounds", "three_shapes"])
def test_the_recorded_rounds(rows, programs, want):
    assert READ(_recorded(rows, programs)) == pytest.approx(want)


@pytest.mark.parametrize("case", ["no_field", "one_without", "no_records",
                                  "no_mixed_round"])
def test_nothing_to_read_is_none(case):
    obs = _recorded(None if case == "no_field" else (32, 32, 256, 32),
                    ("decode_forward",) * 4 if case == "no_mixed_round"
                    else MIXED if case == "one_without" else None)
    if case == "one_without":       # the parent's default of a field: 0
        next(s["data"] for s in obs["stages"]
             if s["data"]["tokens"] == 127)["rows"] = 0
    if case == "no_records":
        obs["stages"] = []
    assert READ(obs) is None


def test_the_metric_is_declared_for_the_cells_that_report_serve_tok_s():
    """By name, not by place: a later PR appends its entries behind it."""
    bench = spec.Bench()
    entry, = [m for m in bench.doc["per_layer"]
              if m["name"] == "ragged_row_fill_pct"]
    cells = entry.pop("workloads")
    assert entry == {
        "name": "ragged_row_fill_pct", "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "serve engine",
        "moves": "serve_tok_s"}
    # at least the four it was accepted with: a later cell may join
    assert set(cells) >= {"phi2-decode-sat", "olmoe-chat-sat",
                          "nemo3-reason-sat", "ouro-reason-sat"}
    entry["workloads"] = cells
    for cell in entry["workloads"]:
        assert "serve_tok_s" in {m["name"] for m in
                                 bench.metrics_of(cell, "end_to_end")}
