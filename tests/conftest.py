"""Test configuration: run everything on a virtual 8-device CPU mesh.

This is the TPU rebuild's analog of the reference's ``local[N]`` Spark test
pattern (SURVEY.md §4 "Distributed tests without a cluster"): XLA's host
platform is forced to expose 8 CPU devices, so mesh/pjit/collective logic is
exercised faithfully without TPU hardware.

The platform is pinned with ``jax.config.update`` so the suite runs on the
CPU mesh whether or not the caller exported ``JAX_PLATFORMS=cpu`` (tier-1
does) and even on a host that has a chip; XLA_FLAGS must be set before the
first backend initialisation. The on-chip suite is ``tests_tpu/``.
"""

import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402
import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: excluded from the tier-1 gate (-m 'not slow')")
    config.addinivalue_line(
        "markers",
        "chaos: seeded fault-injection chaos runs (always also slow: "
        "tier-1 filters on 'not slow')")
    config.addinivalue_line(
        "markers",
        "perf: performance microbenchmarks (latency/throughput "
        "assertions are advisory on shared CI hosts; select with "
        "-m perf)")
    config.addinivalue_line(
        "markers",
        "kvcache: prefix-aware KV-cache subsystem tests (pool/radix "
        "units + engine parity; select with -m kvcache)")
    config.addinivalue_line(
        "markers",
        "kvtier: tiered KV-cache tests (host arena / migration / "
        "handoff units + spill-reload parity; select with -m kvtier)")
    config.addinivalue_line(
        "markers",
        "failover: request-level failover / hedged dispatch / engine "
        "watchdog tests (router journal+resume parity; select with "
        "-m failover)")
    config.addinivalue_line(
        "markers",
        "kernels: Pallas/Mosaic kernel family tests (paged decode + "
        "ragged prefill interpret-mode parity vs the XLA references; "
        "select with -m kernels)")
    config.addinivalue_line(
        "markers",
        "elastic: elastic multi-host training tests (supervisor state "
        "machine, peer heartbeats, collective-hang watchdog, snapshot "
        "ring, kill-and-recover; select with -m elastic)")
    config.addinivalue_line(
        "markers",
        "analysis: static-analysis suite tests (AST passes, baseline "
        "round-trip, lockwatch witness, repo gate; select with "
        "-m analysis)")
    config.addinivalue_line(
        "markers",
        "slo: fleet telemetry plane tests (quantile sketches, metric "
        "federation, per-request SLO accounting; select with -m slo)")
    config.addinivalue_line(
        "markers",
        "mixed: unified mixed prefill+decode dispatch tests (chunked "
        "admission parity, ledger rollback, compile grid; select with "
        "-m mixed)")
    config.addinivalue_line(
        "markers",
        "fleet: elastic serving fleet tests (autoscaler, graceful "
        "drain with KV migration, provider lifecycle; select with "
        "-m fleet)")
    config.addinivalue_line(
        "markers",
        "priority: SLO-class priority scheduling / lossless preemption "
        "tests (class-ordered admission, preempt-resume parity; select "
        "with -m priority)")
    config.addinivalue_line(
        "markers",
        "timeseries: time-series plane tests (windowed store, alert "
        "engine, fleet timelines; select with -m timeseries)")
    config.addinivalue_line(
        "markers",
        "spec: self-speculative decoding tests (greedy bit-parity "
        "matrix, adaptive-k, compile grid; select with -m spec)")
    config.addinivalue_line(
        "markers",
        "api: OpenAI-compatible gateway tests (translation, SSE "
        "framing, worker/router parity; select with -m api)")


@pytest.fixture(scope="session")
def devices():
    devs = jax.devices()
    assert len(devs) == 8, f"expected 8 virtual devices, got {len(devs)}"
    return devs


@pytest.fixture()
def rng():
    return np.random.RandomState(42)
