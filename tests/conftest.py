"""Test environment: force the CPU backend with 8 virtual devices so the
multi-device sharding path is exercised without TPU hardware (the strategy the
reference uses for its distributed tests is in-process simulation; ours adds a
virtual device mesh — SURVEY.md §4)."""

import os

# Must run before jax creates a backend. Force the CPU platform with 8
# virtual devices so the mesh-sharding paths are exercised deterministically
# and offline, whatever JAX_PLATFORMS says. The chip's own code (eager
# dispatch, compiled Pallas kernels) is covered by tests/test_chip_compile.py
# and tests/test_chip_smoke_stages.py here, and by chip_smoke.py on the chip.
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402


@pytest.fixture
def cpu_link():
    """CPU-scale link constants so the round planner's wire comparison (not
    the product's per-dispatch constants) decides routing; restored after."""
    from automerge_tpu.engine import dispatch
    keys = ("dispatch_fixed_s", "h2d_call_s", "d2h_call_s")
    saved = {k: dispatch._LINK[k] for k in keys}
    dispatch.calibrate(dispatch_fixed_s=1e-5, h2d_call_s=1e-6,
                       d2h_call_s=1e-5)
    yield
    dispatch.calibrate(**saved)


@pytest.fixture
def no_fused_route(monkeypatch):
    """Rounds of two and more documents take the classic route too, as
    under AMTPU_MEGABATCH=0."""
    from automerge_tpu.engine import dispatch
    monkeypatch.setattr(dispatch, "_megabatch", False)


@pytest.fixture(autouse=True)
def _reset_uuid_factory():
    yield
    import automerge_tpu as am
    am.uuid.reset()
