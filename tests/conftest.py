"""Test configuration: run on a virtual 8-device CPU mesh.

Mirrors the reference's test pattern (SURVEY §4 'fakes'): N CPU-backed jax
devices stand in for a TPU mesh; cpu(0)/cpu(1) behave as distinct devices.
Must set env before jax initializes.
"""
import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = flags + " --xla_force_host_platform_device_count=8"
os.environ.setdefault("JAX_ENABLE_X64", "0")

# The env var covers spawned subprocesses; config.update covers this
# process even when jax was imported before conftest ran.
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "nightly: slow integration tests (real short trainings with "
        "accuracy asserts — ref tests/python/train tier)")
    config.addinivalue_line(
        "markers",
        "slow: excluded from the default tier-1 run "
        "(`pytest tests/ -q -m 'not slow'`, ROADMAP.md)")


def pytest_collection_modifyitems(config, items):
    # nightly implies slow: the tier-1 gate filters on `-m 'not slow'`
    # (ROADMAP.md), so the nightly tier must carry the slow marker or
    # the default run silently includes the minutes-long trainings —
    # exactly the round-5 failure mode (default suite >> the 870 s
    # tier-1 budget). Run everything with -m "nightly or not nightly".
    import pytest

    for item in items:
        if "nightly" in item.keywords:
            item.add_marker(pytest.mark.slow)
