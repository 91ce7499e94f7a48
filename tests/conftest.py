"""Test harness: force an 8-device virtual CPU platform so every sharding /
multi-chip path runs in CI without TPUs (SURVEY.md §4 implication)."""

import os

# Force-set (not setdefault): tests always run on the virtual CPU mesh,
# whatever platform the launching shell selected — a chip belongs to one
# process at a time, and a test run must never be the one holding it.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402  (import after env vars take effect)
import pytest  # noqa: E402


@pytest.fixture
def key():
    return jax.random.key(0)
