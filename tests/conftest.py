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


@pytest.fixture
def interpreted_kernel(monkeypatch):
    """The grouped kernel of :mod:`apex_tpu.ops.grouped` through Pallas's
    interpreter (plain JAX: it runs on a CPU, under ``jax.checkpoint`` and
    ``jax.grad`` too)."""
    import functools
    import types

    from apex_tpu.ops import grouped
    real = grouped._megablox
    monkeypatch.setattr(grouped, "_megablox", types.SimpleNamespace(
        gmm=functools.partial(real.gmm, interpret=True),
        tgmm=functools.partial(real.tgmm, interpret=True)))


@pytest.fixture(params=["ragged", "tiled"])
def grouped_rule(request, monkeypatch, interpreted_kernel):
    """An expert layer both ways its grouped products can go.  ``tiled``:
    the layer as a program compiled for a TPU runs it at widths 512 does
    not divide, forced on this CPU at the toys' (hidden 64, experts 32): a
    best tile of 24, which divides neither, one candidate tile of 24 (64
    -> 72, 32 -> 48), the TPU's side of every platform choice and the
    kernel interpreted.  Answers the sides taken."""
    from apex_tpu.ops import grouped
    taken = []
    if request.param == "ragged":
        yield taken
        assert not taken
        return

    def choose(*args, tpu, default=None, **_others):
        taken.append("tpu")
        return tpu(*args)

    monkeypatch.setattr(grouped, "BEST", 24)
    monkeypatch.setattr(grouped, "LANES", 8)
    monkeypatch.setattr(grouped, "TILES", (24,))
    monkeypatch.setattr(jax.lax, "platform_dependent", choose)
    assert grouped.plan(64, 32, 32, "tpu") == ((24, 72), (24, 48))
    yield taken
    assert taken            # every round made went the kernel's way
