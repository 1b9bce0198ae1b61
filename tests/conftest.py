"""Test config: force an 8-device virtual CPU mesh before JAX import so
multi-chip sharding tests run without TPU hardware (the driver separately
dry-runs the multichip path)."""

import os

# force CPU: a chip belongs to one process at a time, and the tests
# must never be that process
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

import pytest  # noqa: E402


def pytest_configure(config):
    # tier-1 runs `-m 'not slow'` (ROADMAP.md); scripts/ci.sh runs the
    # full suite including slow-marked tests
    config.addinivalue_line(
        "markers", "slow: heavier tests excluded from the tier-1 "
                   "budget (-m 'not slow')")


@pytest.fixture(autouse=True)
def fresh_obs():
    """Observability state is process-global (default registry, span
    tracer, flight recorder, health switch): reset it around every
    test so counters don't bleed across tests and order-dependent
    assertions can't flake."""
    from paddle_tpu.obs import comm as obs_comm
    from paddle_tpu.obs import flight as obs_flight
    from paddle_tpu.obs import health as obs_health
    from paddle_tpu.obs import mem as obs_mem
    from paddle_tpu.obs import registry as obs_registry
    from paddle_tpu.obs import tail as obs_tail
    from paddle_tpu.obs import trace as obs_trace
    from paddle_tpu.resilience import faults as r_faults

    obs_registry.reset_registry()
    obs_mem.reset()
    obs_comm.reset()
    obs_trace.disable()
    obs_trace.reset()
    r_faults.disable()
    yield
    obs_mem.reset()
    obs_comm.reset()
    obs_health.disable()
    obs_flight.uninstall()
    obs_flight.clear_host_context()
    obs_tail.uninstall()
    obs_trace.disable()
    obs_trace.reset()
    r_faults.disable()


@pytest.fixture(autouse=True)
def fresh_programs():
    """Each test gets fresh default programs/scope (the reference's tests
    run one per process; ours share a process)."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import framework
    from paddle_tpu.core import scope as scope_mod

    from paddle_tpu.v2 import layer as v2_layer

    old_main = framework.switch_main_program(framework.Program())
    old_startup = framework.switch_startup_program(framework.Program())
    old_scope = scope_mod._global_scope
    scope_mod._global_scope = scope_mod.Scope()
    v2_layer._reset_data_layers()
    yield
    framework.switch_main_program(old_main)
    framework.switch_startup_program(old_startup)
    scope_mod._global_scope = old_scope
