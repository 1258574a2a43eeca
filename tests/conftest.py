"""Test configuration: run the whole suite on a virtual 8-device CPU mesh.

This gives every test (including the multi-chip sharding tests) a fake
8-device backend — the fake-backend trick the reference lacks entirely
(SURVEY.md §4).

The tier-1 command exports JAX_PLATFORMS=cpu; the platform is pinned here
too (`jax.config.update`, before first backend init) so a bare `pytest`
on a machine that has a chip still never touches it. XLA_FLAGS goes in
the env because the CPU client reads it at its own init.
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

# Persistent XLA compile cache: the suite is compile-dominated (recursive
# hourglass at several configs/shapes); warm runs drop from ~10min to ~2min.
# Set as ENV VARS (jax reads both natively) rather than jax.config.update
# so every subprocess a test spawns — distributed/eval workers, the CLI
# runs, the multichip dryrun — inherits the cache with zero per-file
# plumbing. Same rule as every entry point (runtime/compile_cache.py): an
# externally placed JAX_COMPILATION_CACHE_DIR wins, else the checkout's
# build/jax_cache.
# NOTE the cache is machine-specific: XLA:CPU AOT results bake in host CPU
# features, and entries from a different box make loads fail or crash
# (observed: a stale cache from the earlier multi-core image broke the
# 4-process rendezvous) — hence gitignored, never committed.
from real_time_helmet_detection_tpu.runtime.compile_cache import (  # noqa: E402
    CACHE_ENV, DEFAULT_CACHE_DIR)

os.environ.setdefault(CACHE_ENV, DEFAULT_CACHE_DIR)
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "1.0")

import jax

jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402  (after the platform pin on purpose)


@pytest.fixture
def count_device_get():
    """The ONE `jax.device_get`-counting implementation behind every
    per-subsystem zero-extra-D2H pin (ISSUE 19 satellite) — backed by
    the transfer audit's runtime twin so the static manifest
    (analysis/transfer_manifest.json) and the dynamic pins share one
    definition of "a fetch". Usage::

        def test_x(count_device_get):
            with count_device_get() as c:
                ...  # run the loop under test
            assert c.count == n_expected   # c.calls keeps the trees

    The context restores the real `jax.device_get` on exit (even when
    the body raises), so a single test can open several independent
    counting windows."""
    from real_time_helmet_detection_tpu.analysis.transfer_audit import \
        counting_device_get
    return counting_device_get
