"""The six planted faults of ISSUE 65 on the tiny GLM-5 of
``tests/test_glm5.py`` (float32, the CPU): ``benchmark.dsa_faults``' four
(dense attention in the selection's place, no relu, no head weights, the
first positions in the place of the best) and ``tools/glm5_faults.py``'s two
(the indexer's queries from the query latent BEFORE its RMSNorm, rotary over
all of an indexer head's dims), each planted in the served program and held
to ``benchmark.parity``'s own comparison against the reference of the RIGHT
program: beyond its limit of 0.1 logit-std, where the sound program reads
under 5e-5 (``tests/test_glm5.py``)."""
import importlib.util
from pathlib import Path

import pytest

from benchmark import dsa_faults, parity
from tests import test_glm5 as G
from tests.family_harness import family  # noqa: F401
from tests.test_glm5 import built  # noqa: F401

_spec = importlib.util.spec_from_file_location(
    "glm5_faults", Path(__file__).parents[2] / "tools" / "glm5_faults.py")
glm5_faults = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(glm5_faults)

H = G.H      # (the harness the ``family`` fixture reads)
PLANTS = [*((f, dsa_faults.planted) for f in dsa_faults.FAULTS),
          *((f, glm5_faults.planted) for f in glm5_faults.FAULTS)]


@pytest.mark.parametrize("fault, planted", PLANTS,
                         ids=[f for f, _ in PLANTS])
def test_a_planted_fault_is_refused_by_the_harness_comparison(
        built, fault, planted):
    """The 41-token prompt's prefill (chunks of 16, 16 and 9 rows at
    contexts to 5 x ``topk``) and two decode steps under the fault."""
    with planted(fault):
        served, tokens = parity.served_logits(
            G.H.engine_of(*built), 0, G.PROMPT[:41], 2)
    want = G.H.reference(built[1], G.PROMPT[:41] + tokens)[40:]
    assert parity.row_errors(served, want).max() > parity.TOLERANCE


def test_the_plants_are_lifted_again(built):
    from deepspeedsyclsupport_tpu.inference.v2 import dsa
    from deepspeedsyclsupport_tpu.ops import sparse_index

    before = (dsa.index_rows, sparse_index.select_topk, dsa_faults.FAULTS,
              dsa_faults._patches)
    for fault, planted in PLANTS:
        with planted(fault):
            pass
    assert before == (dsa.index_rows, sparse_index.select_topk,
                      dsa_faults.FAULTS, dsa_faults._patches)
    with pytest.raises(ValueError, match="unknown fault"):
        with glm5_faults.planted("dense"):
            pass
