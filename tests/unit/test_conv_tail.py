"""``ops/ssm.conv_step``: the one-token rows of the depthwise convolution
both mixers share (Mamba-2's ``xBC`` with a bias, the delta rule's q | k | v
without), the tail's kernel (``conv_tail_step``, interpreted) against the
XLA form: the results of the rows that stand at a slot of their own to
float32 rounding, their slots' tails EXACTLY, every other slot and every
other layer of the pool bit for bit. The two forwards of a model through the
kernel are ``tests/test_nemotron_h.py``'s and ``tests/test_solar_open2.py``'s,
its compile for a described v5e ``tests/unit/test_chip_compile.py``'s."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeedsyclsupport_tpu.ops import ssm

LAYERS, LAYER = 3, 1

# channels, kernel, slots beside the sink, rows, rows on the sink, the
# pool's dtype, a bias, {slots a grid step, channels a tile} (None: the
# rule's). Nemotron's form is the one with a bias, Solar's the one without;
# the rows never fill a block of slots, and the slots are no whole blocks
CASES = {
    "mamba-bf16": (384, 4, 40, 37, 3, jnp.bfloat16, True, None),
    "delta-bf16": (768, 4, 40, 37, 3, jnp.bfloat16, False, None),
    "delta-two-tiles-bf16": (512, 4, 50, 21, 4, jnp.bfloat16, False,
                             dict(slots_a_step=16, lanes=256)),
    "mamba-three-blocks-f32": (256, 4, 19, 19, 0, jnp.float32, True,
                               dict(slots_a_step=8, lanes=128)),
    "kernel-2-bf16": (256, 2, 33, 20, 2, jnp.bfloat16, True, None),
    "kernel-3-f32": (128, 3, 33, 33, 5, jnp.float32, False, None),
    "kernel-5-f32": (128, 5, 12, 7, 1, jnp.float32, True, None),
    "one-row-bf16": (128, 4, 6, 1, 0, jnp.bfloat16, False, None),
    "all-on-the-sink-bf16": (128, 4, 6, 5, 5, jnp.bfloat16, True, None),
    "lanes-no-tile-f32": (96, 4, 5, 4, 1, jnp.float32, False, None),
}


def _operands(channels, taps, slots, rows, on_sink, dtype, bias, seed=0):
    rng = np.random.default_rng(seed)
    pool = jnp.asarray(rng.normal(size=(LAYERS, taps - 1, slots + 1,
                                        channels)), dtype)
    x = jnp.asarray(rng.normal(size=(rows, channels)), dtype)
    w = jnp.asarray(rng.normal(size=(taps, channels)), jnp.float32)
    b = jnp.asarray(rng.normal(size=(channels,)), jnp.float32) \
        if bias else None
    at = rng.permutation(slots)[:rows].astype(np.int32)
    # the padding rows lie AMONG the others, as a batch's dead rows do
    at[rng.permutation(rows)[:on_sink]] = slots
    keep = rng.random(rows) > 0.3
    return x, w, b, pool, jnp.asarray(at), jnp.asarray(keep)


@pytest.mark.parametrize("case", list(CASES))
def test_the_tails_kernel_is_the_xla_form_in_place(case):
    *shape, how = CASES[case]
    x, w, b, pool, at, keep = _operands(*shape)
    slots = shape[2]
    want, pool_x = ssm.CONV_STEPS["xla"](x, w, b, pool, LAYER, at, keep)
    got, pool_k = jax.jit(
        lambda *a: ssm._conv_step_pallas(*a, interpret=True, **(how or {})),
        donate_argnums=3)(x, w, b, jnp.array(pool), LAYER, at, keep)
    own = np.asarray(at) != slots
    np.testing.assert_allclose(np.asarray(got)[own], np.asarray(want)[own],
                               rtol=2e-6, atol=2e-6)
    # a row on the sink reads zeros, and the sink keeps what it held
    assert not np.asarray(got)[~own].any()
    was, xla, kernel = (np.asarray(p.astype(jnp.float32))
                        for p in (pool, pool_x, pool_k))
    assert kernel.dtype == xla.dtype and pool_k.dtype == pool.dtype
    np.testing.assert_array_equal(kernel[:, :, :slots], xla[:, :, :slots])
    np.testing.assert_array_equal(kernel[:, :, slots], was[:, :, slots])
    idle = np.setdiff1d(np.arange(slots), np.asarray(at))
    np.testing.assert_array_equal(kernel[LAYER][:, idle],
                                  was[LAYER][:, idle])
    others = [i for i in range(LAYERS) if i != LAYER]
    np.testing.assert_array_equal(kernel[others], was[others])


def test_a_row_from_zeros_leaves_zeros_and_its_token():
    """``keep`` false: the window's older rows are zeros, in the result AND
    in the tail the row leaves, whatever the slot held."""
    x, w, b, pool, at, _ = _operands(128, 4, 8, 8, 0, jnp.bfloat16, True)
    keep = jnp.zeros((8,), bool)
    got, pool_k = ssm.CONV_STEPS["pallas_interpret"](x, w, b, pool, LAYER,
                                                     at, keep)
    np.testing.assert_allclose(
        np.asarray(got),
        np.asarray(jax.nn.silu(b + w[3] * x.astype(jnp.float32))),
        rtol=2e-6, atol=2e-6)
    tail = np.asarray(pool_k[LAYER][:, np.asarray(at)].astype(jnp.float32))
    assert not tail[:2].any()
    np.testing.assert_array_equal(tail[2], np.asarray(x, np.float32))


def test_steps_one_after_another_hold_the_window():
    """Four steps through the kernel on one slot are the convolution of the
    four tokens: each step reads the tail the step before it left."""
    rng = np.random.default_rng(4)
    channels, taps = 128, 4
    xs = jnp.asarray(rng.normal(size=(4, 1, channels)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(taps, channels)), jnp.float32)
    pool = jnp.asarray(rng.normal(size=(1, taps - 1, 3, channels)),
                       jnp.float32)
    at = jnp.asarray([1], jnp.int32)
    outs = []
    for t in range(4):
        out, pool = ssm.CONV_STEPS["pallas_interpret"](
            xs[t], w, None, pool, 0, at, jnp.asarray([t > 0]))
        outs.append(np.asarray(out[0]))
    window = np.concatenate([np.zeros((3, channels), np.float32),
                             np.asarray(xs[:, 0])])
    for t in range(4):
        want = jax.nn.silu(sum(w[j] * window[t + j] for j in range(taps)))
        np.testing.assert_allclose(outs[t], np.asarray(want), rtol=2e-6,
                                   atol=2e-6)


def test_the_platform_picks_the_form(monkeypatch):
    """``conv_step`` takes the XLA form off the TPU and the kernel on it (no
    option names one), and a caller's ``step`` goes before either."""
    x, w, b, pool, at, keep = _operands(128, 4, 6, 4, 1, jnp.float32, True)
    seen = []

    def listening(name):
        def fn(*a):
            seen.append(name)
            return ssm._conv_step_xla(*a)
        return fn

    monkeypatch.setattr(ssm, "CONV_STEPS",
                        {name: listening(name) for name in ssm.CONV_STEPS})
    ssm.conv_step(x, w, b, pool, LAYER, at, keep)
    monkeypatch.setattr(ssm, "default_impl", lambda: "pallas")
    ssm.conv_step(x, w, b, pool, LAYER, at, keep)
    ssm.conv_step(x, w, b, pool, LAYER, at, keep, listening("mine"))
    assert seen == ["xla", "pallas", "mine"]


# a dense preset and one with a state pool and no convolution, at widths
# that keep the structure
WITHOUT = {
    "phi-2": dict(intermediate_size=128, num_layers=2, num_kv_heads=4),
    "brumby-14b": dict(num_kv_heads=2, intermediate_size=96, num_layers=2,
                       retention_chunk_size=8),
}


@pytest.mark.parametrize("preset", sorted(WITHOUT))
def test_a_model_without_a_convolution_lowers_what_it_did(preset,
                                                          monkeypatch):
    """Both serving forwards of a model that has no convolution, built and
    run: the compiled text is the same whatever stands behind ``conv_step``
    (here: steps that raise, in the op's table and first in the registry),
    and names no ``conv_tail_step``: the kernel reaches the two mixers that
    call it and nothing else."""
    import dataclasses

    import deepspeedsyclsupport_tpu as dstpu
    from deepspeedsyclsupport_tpu.inference.v2 import module_registry as reg
    from deepspeedsyclsupport_tpu.inference.v2.engine_v2 import (
        InferenceEngineV2)
    from deepspeedsyclsupport_tpu.models import build_model

    def texts():
        model = build_model(preset, hidden_size=64, num_heads=4, head_dim=16,
                            vocab_size=256, max_seq_len=256, dtype="float32",
                            **WITHOUT[preset])
        model.seed = 1
        eng = InferenceEngineV2(
            model, model.init_params(), dtype="float32",
            topology=dstpu.build_topology(dp=1, devices=jax.devices()[:1]),
            max_context=64, max_sequences=4, num_blocks=32, block_size=8,
            max_tokens_per_batch=16, prefill_attn="xla", decode_attn="xla")
        logits = eng.put([1], [list(range(3, 23))])[1]
        eng.put([1], [[int(logits.argmax())]])
        # less the frames a line was traced under (a second trace's have
        # other numbers): the table at the head and each line's metadata
        return {name: re.sub(r"\nFileNames\n.*?\n\n\n|, metadata=\{[^}]*\}",
                             "", compiled.as_text(), flags=re.S)
                for name, compiled in eng.compiled_programs().items()}

    def refuses(*_a):
        raise AssertionError("no convolution in this model")

    want = texts()
    assert {"ragged_forward", "decode_forward"} <= set(want)
    monkeypatch.setattr(ssm, "CONV_STEPS",
                        {name: refuses for name in ssm.CONV_STEPS})
    monkeypatch.setitem(
        reg._REGISTRY["conv_step"], "first", dataclasses.replace(
            reg.get_impl("conv_step", "xla"), name="first", fn=refuses,
            priority=100, auto_eligible=lambda ctx: True))
    got = texts()
    assert got == want
    assert not any("conv_tail_step" in text for text in got.values())
