"""Test harness: an 8-device virtual CPU platform, so mesh/sharding
tests run hermetically without a chip. JAX reads both variables when
it first initializes a backend; chip_smoke.py is what runs on the TPU."""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()
os.environ.setdefault("JAX_ENABLE_X64", "0")
os.environ["JAX_PLATFORMS"] = "cpu"


def pytest_configure(config):
    # tier-1 runs `-m 'not slow'`; the wide chaos sweeps opt out
    config.addinivalue_line(
        "markers", "slow: wide sweeps excluded from the tier-1 gate")
