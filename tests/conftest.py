"""Test configuration.

JAX tests run on a virtual 8-device CPU mesh (multi-chip sharding is validated
without TPU hardware; the driver separately dry-runs __graft_entry__ the same
way). Must be set before jax import anywhere in the test process.
"""

import os
import sys

# Forced to the CPU: a chip belongs to one process, the suite runs many.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "chaos: deterministic fault-injection tests (FaultPlan harness)",
    )
    config.addinivalue_line(
        "markers", "slow: long-running tests kept out of tier-1"
    )
    config.addinivalue_line(
        "markers",
        "metrics: metrics-plane tests (registry, exposition, scrape, "
        "timeline)",
    )
    config.addinivalue_line(
        "markers",
        "trace: distributed task tracing, subscription plane, and reactor "
        "stall-detector tests (ISSUE 8)",
    )
    config.addinivalue_line(
        "markers",
        "ingest: submit-plane tests (streaming chunked ingest, client-"
        "connection plane, lazy array materialization; ISSUE 10)",
    )
    config.addinivalue_line(
        "markers",
        "federation: sharded control plane tests (per-shard journals, "
        "lease-fenced failover, cross-shard worker lending; ISSUE 11)",
    )
    config.addinivalue_line(
        "markers",
        "planes: server threading-model tests (journal commit thread, "
        "fan-out sender pool, wire-backend ladder; ISSUE 12)",
    )
    config.addinivalue_line(
        "markers",
        "autoalloc: self-healing elasticity tests (backlog-driven "
        "autoscaling, graceful drain, crash-loop quarantine, "
        "allocation-exact restore; ISSUE 13)",
    )
    config.addinivalue_line(
        "markers",
        "sim: deterministic cluster-simulator tests (virtual-clock loop, "
        "seeded fault schedules, invariant checking; ISSUE 14)",
    )
    config.addinivalue_line(
        "markers",
        "multichip: sharded multi-device solver tests; run on the virtual "
        "8-device CPU mesh (XLA_FLAGS=--xla_force_host_platform_device_"
        "count=8, set above) so tier-1 exercises the 8-device path on "
        "CPU-only hosts",
    )
    config.addinivalue_line(
        "markers",
        "profile: continuous-profiling-plane tests (sampling profiler, "
        "per-plane CPU attribution, profile-on-stall, regression blame; "
        "ISSUE 19)",
    )
    config.addinivalue_line(
        "markers",
        "policy: weighted scheduling-objective tests (heterogeneity "
        "affinity, runtime prediction, fairness boosts; ISSUE 20)",
    )
