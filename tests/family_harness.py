"""What the families' serving tests share: the program (``build_model`` ->
``InferenceEngineV2``) held to the family's plain reference under
``benchmark/families/`` at tiny widths, float32, on the CPU.

A family's file keeps its constants (``HF``, ``ENGINE``, ``PROMPTS``, its
tolerance), its ``built`` fixture, its planted faults and the tests that are
its own, and names ONE harness at module scope::

    H = Harness(HF, ENGINE, PROMPTS)
    from tests.family_harness import ending, engines, family   # fixtures

(its ``built`` draws the init in ONE program and moves it on the host:
``moved(jax.jit(model.init_params)())``).

What costs the time here is, in order: the reference walked op by op at a new
length a call (``reference`` runs ONE jitted walk a (configuration, length) and
keeps what it said), a device draw a leaf (``moved`` draws on the host) and an
engine a case (``engines`` hands one idle engine a set of arguments to every
case that neither plants a fault nor needs a pool of its own). ROADMAP Design
11 has the rule for a new family's file.
"""
import functools
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import parity, spec
from tests.unit import stream_ends

PAD = 48        # a reference walk's length is a multiple of this


def moved(params, key=1, by=0.2, relative=False):
    """Every leaf off its init (norm scales start at one, a gate's bias where
    a half-life put it, routed experts small: where each sits would not matter
    otherwise). ``relative``: a matrix by its own spread. Drawn on the host:
    a draw a leaf on the device is a program a shape to compile."""
    rng = np.random.default_rng(key)

    def off(x):
        step = by * rng.standard_normal(x.shape)
        if relative and x.ndim > 1:
            step = step * float(jnp.std(x))
        return x + jnp.asarray(step, x.dtype)
    return jax.tree_util.tree_map(off, params)


def assert_idle(eng):
    """No sequence, every block of every pool free, no state slot live."""
    assert not eng.seqs
    assert eng.allocator.free_blocks == eng.allocator.num_blocks
    stats = eng.state_stats()
    assert stats is None or stats["slots_live"] == 0


class Harness:
    def __init__(self, hf, engine, prompts=(), bystander=False):
        """``hf`` None: a model with no family file, for ``engine_of`` and
        ``engines`` alone. ``bystander``: ``served_errors`` has a fourth
        sequence hold the first blocks of every pool, so that a table entry
        that reads 0 reads SOMEBODY ELSE'S rows."""
        self.hf, self.engine, self.prompts = hf, engine, prompts
        self.bystander = bystander
        self._walks, self._said = {}, {}
        self.shelf = {}         # ``idle_engine``'s, by model and arguments

    @functools.cached_property
    def family(self):
        return spec.Bench().family(self.hf)

    def engine_of(self, model, params, **engine):
        import deepspeedsyclsupport_tpu as dstpu
        from deepspeedsyclsupport_tpu.inference.v2.engine_v2 import (
            InferenceEngineV2)

        return InferenceEngineV2(
            model, params,
            topology=dstpu.build_topology(dp=1, devices=jax.devices()[:1]),
            **{"dtype": "float32", **self.engine, **engine})

    def idle_engine(self, model, params, **engine):
        """The ONE engine of these arguments on this model, held to be idle
        (what ``engines`` hands out)."""
        key = (id(model), id(params), *sorted(engine.items()))
        if key not in self.shelf:
            self.shelf[key] = self.engine_of(model, params, **engine)
        assert_idle(self.shelf[key])
        return self.shelf[key]

    def reference(self, params, ids, hf=None):
        """The plain reference's logits for ``ids``, kept by (tree, sequence):
        a file's planted faults are all held against ONE forward of the right
        program. The reference is causal and takes one sequence, so every
        sequence runs zero-padded to a multiple of ``PAD`` through ONE
        compiled walk a (configuration, length) and its own rows are read
        off the front."""
        hf = hf or self.hf
        length = -(-len(ids) // PAD) * PAD
        walk = (json.dumps(hf, sort_keys=True), length)
        key = (id(params), tuple(ids), walk)
        if key not in self._said:
            if walk not in self._walks:
                arch = self.family.arch(hf)
                self._walks[walk] = jax.jit(
                    lambda p, x: self.family.sequence_logits(arch, p, x))
            padded = jnp.asarray(list(ids) + [0] * (length - len(ids)),
                                 jnp.int32)
            # (the tree is kept beside what it said: its id stays its own)
            self._said[key] = params, np.asarray(
                self._walks[walk](params, padded))[:len(ids)]
        return self._said[key][1]

    def served_errors(self, model, params, prompts=None, n_follow=6,
                      want_params=None, eng=None, **engine):
        """Worst row error of the served path over ``prompts`` (chunked
        prefill, then ``n_follow`` decode steps each) against the reference's
        forward of the whole sequence on ``want_params``. ``eng``: an idle
        engine to run on (else a new one of ``engine``)."""
        eng = eng or self.engine_of(model, params, **engine)
        if self.bystander:
            eng.put([99], [[1, 2, 3]])
        worst = 0.0
        for uid, prompt in enumerate(self.prompts if prompts is None
                                     else prompts):
            logits, tokens = parity.served_logits(eng, uid, prompt, n_follow)
            want = self.reference(want_params or params, prompt + tokens)
            worst = max(worst, float(parity.row_errors(
                logits, want[-len(logits):]).max()))
        if self.bystander:
            eng.flush([99])
        return worst


    # ------------------------- checks the recurrent-state families share
    def check_a_mixed_round_and_a_slot_reused(self, params, eng, tol):
        """Sequence A decodes while B's prompt comes in beside it (one-token
        rows and pieces in ONE forward, each from its own slot); then A is
        flushed and C takes its slot and starts from zero."""
        a, b = self.prompts
        la = [np.asarray(eng.put([1], [a])[1])]
        toks_a = [int(la[-1].argmax())]
        out = eng.put([1, 2], [[toks_a[-1]], b], drain=False)  # a mixed round
        assert 1 in out and 2 not in out
        la.append(np.asarray(out[1]))
        lb = np.asarray(eng.put([], [])[2])                   # b's last chunks
        want_a = self.reference(params, a + toks_a)
        assert parity.row_errors(np.stack(la), want_a[-2:]).max() < tol
        assert parity.row_errors(
            lb[None], self.reference(params, b)[-1:]).max() < tol
        slot = eng.seqs[1].state_slot
        assert eng.state_stats()["slots_live"] == 2
        eng.flush([1])
        assert eng.state_stats()["slots_live"] == 1
        c = [5, 9, 2, 8, 1]
        lc = np.asarray(eng.put([3], [c])[3])
        assert eng.seqs[3].state_slot == slot   # A's place, A's state in it
        assert parity.row_errors(
            lc[None], self.reference(params, c)[-1:]).max() < tol
        eng.flush([2, 3])

    def check_the_tails_kernel_is_the_xla_form(self, built, monkeypatch):
        """The one-token rows' convolution through ``conv_tail_step`` and the
        pieces' through ``conv_pieces`` (both put first in the registry,
        interpreted) against the XLA forms, engine beside engine: a prompt,
        a mixed round (A's one-token row beside B's pieces,
        ``ragged_forward``), two decode rounds (``decode_forward``,
        two rows on the sink): the same logits and the same pools in every
        slot but the sink, the first state layer's tails bit for bit (what it
        is handed has passed through no convolution; the later layers'
        through a silu whose last bit the two forms round apart: the op's own
        test holds the tails exactly, ``tests/unit/test_conv_tail.py``)."""
        import dataclasses

        from deepspeedsyclsupport_tpu.inference.v2 import model as model_v2
        from deepspeedsyclsupport_tpu.inference.v2 import (
            module_registry as reg)

        def drive():
            eng = self.engine_of(*built)
            a, b = self.prompts
            rows = [np.asarray(eng.put([1], [a])[1])]
            tok = int(rows[-1].argmax())
            rows.append(np.asarray(
                eng.put([1, 2], [[tok], b], drain=False)[1]))
            rows.append(np.asarray(eng.put([], [])[2]))
            for _ in range(2):
                out = eng.put([1, 2], [[int(rows[-2].argmax())],
                                       [int(rows[-1].argmax())]])
                rows += [np.asarray(out[1]), np.asarray(out[2])]
            assert {"ragged_forward",
                    "decode_forward"} <= set(eng._dispatched)
            return np.stack(rows), [np.asarray(p) for p in eng.kv.state]

        want, pools = drive()
        for kind, resolved in (("conv_step", model_v2._conv_step_fn),
                               ("conv_pieces", model_v2._conv_pieces_fn)):
            first = dataclasses.replace(
                reg.get_impl(kind, "pallas_interpret"), name="first",
                priority=100, auto_eligible=lambda ctx: True)
            monkeypatch.setitem(reg._REGISTRY[kind], "first", first)
            assert resolved() is first.fn
        got, pools_k = drive()
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(pools_k[0][:, :-1], pools[0][:, :-1],
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(pools_k[1][:, :, :-1],
                                   pools[1][:, :, :-1], rtol=1e-5, atol=1e-5)
        np.testing.assert_array_equal(pools_k[1][0, :, :-1],
                                      pools[1][0, :, :-1])


# ------------------------------------------- fixtures a family's file imports
@pytest.fixture(scope="module")
def family(request):
    return request.module.H.family


@pytest.fixture
def engines(request, built):
    """``engines(**engine)``: the module's ONE engine of these arguments on
    its ``built`` fixture, idle when handed out and held to be idle again
    when the case ends. For the cases that plant nothing a trace reads and
    need no pool of their own: a planted fault or a tight pool builds its
    engine (``H.engine_of``), since a compiled program keeps what it was
    traced under."""
    harness, handed = request.module.H, []

    def engine(**args):
        handed.append(harness.idle_engine(*built, **args))
        return handed[-1]
    yield engine
    for eng in handed:
        assert_idle(eng)


@pytest.fixture(scope="module")
def ending(request, built):
    """``stream_ends``' references on a fresh engine of the module's
    ``ENDING`` arguments (a small context: one of the ends is its cap)."""
    return stream_ends.family(
        request.module.H.engine_of(*built, **request.module.ENDING))
