"""Shared test setup.

Mesh / shard_map tests need several devices; CPU-only CI hosts expose one.
Force an 8-device host platform BEFORE jax initializes its backends — but
only when the caller hasn't already pinned a device count (the dry-run entry
points force 512 themselves).  Test subprocesses (test_dist, test_dryrun,
test_checkpoint, examples/elastic_restart.py) set their own XLA_FLAGS.

Tests never use the persistent compile cache: the entry points turn it on
(``repro.launch.compile_cache``), and this switch, inherited by test
subprocesses too, keeps it off.
"""
import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"
