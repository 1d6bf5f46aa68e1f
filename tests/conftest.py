"""Test configuration: force an 8-virtual-device CPU JAX platform.

Tests must run without TPU hardware; multi-chip sharding is validated on
a virtual CPU mesh (the driver separately dry-runs the multichip path).
The env vars must be set before jax initializes its backends.
"""

import os
import sys

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

# Tests run on the CPU backend (Pallas in interpret mode, the virtual
# mesh above) even on a host with a TPU: set the platform in config,
# before any backend initializes, whatever JAX_PLATFORMS says.
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import pytest  # noqa: E402


@pytest.fixture(autouse=True, scope="module")
def _isolate_metrics_registry():
    """Fence each test module's metrics off from every other module.

    The default registry is a process-global: serve tiers observe ack
    latencies into it, gossip nodes count rounds, the fleet poller's
    SLO verdict reads whatever has accumulated. Without isolation,
    outcomes depend on module collection order (test_serve_federation
    once had to *sort after* test_obs.py). Snapshot on module entry,
    restore on exit — samples recorded inside the module vanish,
    instruments and cached references stay valid."""
    from crdt_tpu.obs.registry import default_registry

    reg = default_registry()
    snap = reg.state_snapshot()
    yield
    reg.restore_state(snap)
