"""``chip_smoke.py`` off the chip: it must refuse to run, and its phase
functions must work at ``tiny`` size on the CPU mesh (the rehearsal that
finds wrong paths, arguments and control flow before chip time is spent).
Plus the two seams the smoke leans on: an accelerator that raises when its
platform is absent, and the compile cache placed from outside."""
import importlib.util
import os
import subprocess
import sys

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
SMOKE = os.path.join(REPO, "chip_smoke.py")


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SMOKE)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_off_the_chip_exits_nonzero_and_prints_no_result():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    r = subprocess.run([sys.executable, SMOKE], cwd=REPO, env=env,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    assert "no CPU mode" in r.stderr


def test_train_phase_tiny_loss_falls(smoke):
    obs = smoke.train_phase("tiny", {"attn_impl": "flash"}, batch=4, seq=128,
                            steps=4, seed=0)
    assert len(obs["losses"]) == 4
    assert obs["losses"][-1] < obs["losses"][0]
    # the handle main() reads the kernels from
    assert "dot" in obs["engine"].compiled_train_step().as_text()


def test_serve_phase_tiny_every_request_closes(smoke):
    obs = smoke.serve_phase(
        "tiny", {}, dtype="float32", engine_config=dict(
            max_context=128, max_sequences=4, max_tokens_per_batch=32,
            block_size=16, num_blocks=24, prefill_attn="kernel_interpret",
            decode_attn="pallas_interpret"),
        prompt_lens=(5, 50, 17), max_new_tokens=6, seed=0)
    assert set(obs["finished"].values()) == {"done"}
    assert all(len(t) == 6 for t in obs["outputs"].values())
    # fp32 on CPU: the paged kernels and the plain decode pick the same token
    assert obs["outputs"][0] == obs["ref_tokens"]
    # a 50-token prompt under a 32-token budget: chunked, then pure decode
    assert {"ragged_forward", "decode_forward"} <= \
        set(obs["engine"].compiled_programs())


def test_zero3_phase_tiny_matches_one_device_reference(smoke):
    obs = smoke.zero3_phase("tiny", {"attn_impl": "flash"}, batch=8, seq=128,
                            steps=3, seed=0, fsdp=4)
    assert obs["census"].ok, obs["census"].report()
    assert obs["census"].classes.bytes_of("param_gather") > 0
    assert obs["census"].classes.bytes_of("grad_sync") > 0
    assert abs(obs["losses"][0] - obs["ref_loss"]) < 0.05


def test_fit_depth_cuts_mistral_to_two_layers_on_a_v5e(smoke):
    assert smoke.fit_depth("mistral-7b", int(15.75 * 2**30)) == 2


def test_kernel_operand_batches_reads_the_per_device_batch(smoke):
    line = ('  %shard_map.25 = (bf16[2,32,2048,128]{3,2,1,0:T(8,128)(2,1)}, '
            'f32[2,32,2048,1]{3,2,1,0}) custom-call(%a, %b), '
            'custom_call_target="tpu_custom_call", operand_layout={}\n'
            '  %x = f32[8,4]{1,0} custom-call(%c), '
            'custom_call_target="AllocateBuffer"\n')
    assert smoke.kernel_operand_batches(line) == [2]


# ------------------------------------------------------ accelerator seam
def test_tpu_accelerator_raises_when_no_tpu_is_present():
    from deepspeedsyclsupport_tpu.accelerator.real_accelerator import (
        TpuAccelerator)

    assert jax.default_backend() == "cpu"
    with pytest.raises(RuntimeError, match="tpu"):
        TpuAccelerator().devices()


def test_accelerator_override_for_an_absent_tpu_raises(monkeypatch):
    from deepspeedsyclsupport_tpu.accelerator import real_accelerator as ra

    monkeypatch.setenv("DSTPU_ACCELERATOR", "tpu")
    ra.reset_accelerator()
    try:
        with pytest.raises(RuntimeError, match="tpu"):
            ra.get_accelerator().devices()
    finally:
        ra.reset_accelerator()


# ----------------------------------------------------------- compile cache
def test_compile_cache_honours_the_environment(monkeypatch, tmp_path):
    from deepspeedsyclsupport_tpu.utils import jax_cache

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert jax_cache.place_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before  # nothing set


def test_compile_cache_defaults_to_one_path_inside_the_checkout(monkeypatch):
    from deepspeedsyclsupport_tpu.utils import jax_cache

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        placed = jax_cache.place_compile_cache()
        assert placed == os.path.join(REPO, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == placed
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
