"""Test session setup: force JAX onto an 8-device virtual CPU mesh.

This is the JAX-idiomatic "multi-chip without a cluster" (SURVEY.md §4):
tensor-parallel and data-parallel tests shard over 8 host-platform devices,
numerics tests run on CPU, and nothing here ever needs a real TPU.
Must run before jax is imported anywhere.
"""

import os

# Hard-set (not setdefault): a machine with a chip exports
# JAX_PLATFORMS=tpu,cpu, which would run "CPU" tests on the TPU — and
# claim it from whatever else is using it.
os.environ["JAX_PLATFORMS"] = "cpu"
xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
    os.environ["XLA_FLAGS"] = (
        xla_flags + " --xla_force_host_platform_device_count=8").strip()

import asyncio  # noqa: E402
import inspect  # noqa: E402

import jax  # noqa: E402
import pytest  # noqa: E402

# This XLA CPU build runs f32 matmuls in reduced precision by default
# (observed ~5e-2 divergence vs numpy). Numerics tests need true f32.
jax.config.update("jax_default_matmul_precision", "highest")


def pytest_pyfunc_call(pyfuncitem):
    """Run ``async def`` tests with asyncio.run (pytest-asyncio not available)."""
    fn = pyfuncitem.obj
    if inspect.iscoroutinefunction(fn):
        kwargs = {name: pyfuncitem.funcargs[name]
                  for name in pyfuncitem._fixtureinfo.argnames}
        asyncio.run(fn(**kwargs))
        return True
    return None


@pytest.fixture(autouse=True)
def _fresh_metrics():
    from fasttalk_tpu.observability.events import reset_events
    from fasttalk_tpu.observability.flight import reset_flight
    from fasttalk_tpu.observability.perf import reset_perf
    from fasttalk_tpu.observability.profiler import reset_profiler
    from fasttalk_tpu.observability.slo import reset_slo
    from fasttalk_tpu.observability.trace import reset_tracer
    from fasttalk_tpu.observability.watchdog import reset_watchdog
    from fasttalk_tpu.utils.metrics import reset_metrics

    reset_metrics()
    reset_tracer()
    reset_events()
    reset_slo()
    reset_watchdog()
    reset_perf()
    reset_flight()
    reset_profiler()
    yield
    reset_metrics()
    reset_events()
    reset_slo()
    reset_watchdog()
    reset_perf()
    reset_flight()
    reset_profiler()
