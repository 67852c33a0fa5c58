"""The two planted faults GLM-5's indexer adds to ``benchmark.dsa_faults``'
four (ISSUE 65), held to the same comparison by the same code:

    python3 tools/glm5_faults.py --workload glm5-docs-sat --seed <n>

* ``q_unnormed`` — the indexer's queries from the query latent BEFORE its
  RMSNorm (``y W_qa``) in the place of ``c_q``;
* ``rope_all`` — rotary over all of an indexer head's dims in the place of
  its leading ``index_rope_dim``.

Both are planted in ``dsa.index_rows`` of the served program alone, under
``dsa_faults``' own ``main``: the sound program has to stay within
``parity.TOLERANCE`` and each fault beyond it. Needs the chip (exit 2 off
it); ``tests/test_glm5.py`` plants all six at a tiny size on the CPU."""
import contextlib
import dataclasses
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

FAULTS = ("q_unnormed", "rope_all")


def _patches(fault):
    """{(module, attribute): replacement} of one fault."""
    from deepspeedsyclsupport_tpu.inference.v2 import dsa

    rows = dsa.index_rows
    if fault == "q_unnormed":
        def wrong(p, y, cfg, positions):
            y, _c_q = y
            return rows(p, (y, y @ p["w_qa"]), cfg, positions)
    elif fault == "rope_all":
        def wrong(p, y, cfg, positions):
            return rows(p, y, dataclasses.replace(cfg, index_rope_dim=0),
                        positions)
    else:
        raise ValueError(f"unknown fault {fault!r}; have {FAULTS}")
    return {(dsa, "index_rows"): wrong}


@contextlib.contextmanager
def _these():
    """``benchmark.dsa_faults`` with this file's faults in the place of its
    own, for the length of the block."""
    from benchmark import dsa_faults

    kept = dsa_faults.FAULTS, dsa_faults._patches
    dsa_faults.FAULTS, dsa_faults._patches = FAULTS, _patches
    try:
        yield dsa_faults
    finally:
        dsa_faults.FAULTS, dsa_faults._patches = kept


@contextlib.contextmanager
def planted(fault):
    """``dsa_faults.planted`` over this file's faults."""
    with _these() as dsa_faults, dsa_faults.planted(fault):
        yield


def main(argv=None):
    with _these() as dsa_faults:
        return dsa_faults.main(argv)


if __name__ == "__main__":
    sys.exit(main())
