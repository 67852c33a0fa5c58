"""A stream that ends early gives back everything it held, whatever its
cache: the check the five cache kinds' test files share (dense
``test_inference_v2``, sparse ``test_moe_serving``, the latent pool
``test_latent_serving``, recurrent state ``tests/test_nemotron_h``, a looped
stack ``test_loop_lm``), each on one engine of its own.

Two drivers of the per-token loop (``InferenceEngineV2.generate`` and a
``ServingSession``) by two early ends (an EOS mid-stream; the context cap).
The reference is the SAME engine's greedy tokens with no end but the budget,
taken while it is fresh and cut where the stream ends. Afterwards nothing is
held (no descriptor, no block, no recurrent-state slot), and three new
streams on the released rows and slots say what they said on fresh ones.
No benchmark cell sets an EOS, so these are the guard that retirement has."""
import dataclasses
from typing import List

import numpy as np
import pytest

from deepspeedsyclsupport_tpu.inference.v2 import (ServingPolicyConfig,
                                                   ServingSession)

BUDGET = 10
CASES = [(driver, end) for driver in ("generate", "session")
         for end in ("eos", "context")]
parametrize = pytest.mark.parametrize(
    "driver,end", CASES, ids=[f"{d}-{e}" for d, e in CASES])


@dataclasses.dataclass
class Family:
    eng: object
    prompts: List[List[int]]     # three short ones, decoded together
    plain: List[List[int]]       # their BUDGET greedy tokens each
    long: List[int]              # three tokens short of the context
    capped: List[int]            # the four tokens the context has room for


def family(eng) -> Family:
    """``eng``'s references, taken before anything ended on it."""
    rng = np.random.default_rng(7)
    vocab = eng.model.config.vocab_size
    prompts = [rng.integers(1, vocab, n).tolist() for n in (5, 9, 6)]
    long = rng.integers(1, vocab, eng.config.max_context - 3).tolist()
    plain = eng.generate(prompts, max_new_tokens=BUDGET)
    # a budget of four ends with the last token the context holds
    capped = eng.generate([long], max_new_tokens=4)[0]
    assert [len(p) for p in plain] == [BUDGET] * 3 and len(capped) == 4
    _holds_nothing(eng)
    return Family(eng, prompts, plain, long, capped)


def _holds_nothing(eng) -> None:
    assert not eng.seqs
    # (a stack of two attention kinds has two pools: both, whole)
    assert eng.allocator.free_blocks == eng.allocator.num_blocks
    if eng._state_free is not None:   # every slot back, none of them twice
        assert sorted(eng._state_free) == list(
            range(eng.config.max_sequences))


def _run(driver, eng, prompts, eos=None):
    """``prompts``' streams and, from a session, why each finished."""
    if driver == "generate":
        return eng.generate(prompts, max_new_tokens=BUDGET,
                            eos_token_id=eos), None
    sess = ServingSession(eng, ServingPolicyConfig(admission="none"),
                          eos_token_id=eos)
    for uid, prompt in enumerate(prompts):
        assert sess.submit(uid, prompt, BUDGET) == "admitted"
    out = [[] for _ in prompts]
    why = [None] * len(prompts)
    for _ in range(400):
        if sess.idle:
            break
        for ev in sess.step():
            assert ev.kind in ("token", "finish"), ev
            if ev.kind == "token":
                out[ev.uid].extend(ev.tokens)
            else:
                why[ev.uid] = ev.reason
    assert sess.idle
    sess.close()
    return out, why


def _cut(tokens, eos):
    return tokens[:tokens.index(eos) + 1] if eos in tokens else tokens


def check(fam: Family, driver: str, end: str) -> None:
    eng = fam.eng
    if end == "eos":
        # the token stream 0 says for the first time nearest its middle
        first = {}
        for i, tok in enumerate(fam.plain[0]):
            first.setdefault(tok, i)
        eos = min((t for t, i in first.items() if 0 < i < BUDGET - 1),
                  key=lambda t: abs(first[t] - BUDGET // 2), default=None)
        assert eos is not None, f"no new token mid-stream: {fam.plain[0]}"
        prompts, plain = fam.prompts[:2], fam.plain[:2]
        want = [_cut(p, eos) for p in plain]
        why = ["eos" if eos in p else "done" for p in plain]
    else:
        # the cap truncates the long stream and raises nothing
        eos, prompts = None, [fam.long, fam.prompts[1]]
        want, why = [fam.capped, fam.plain[1]], ["context", "done"]
    got, said = _run(driver, eng, prompts, eos)
    assert got == want and len(got[0]) < BUDGET
    assert said is None or said == why
    _holds_nothing(eng)
    # a released row, block or slot is clean
    assert _run(driver, eng, fam.prompts)[0] == fam.plain
    _holds_nothing(eng)
