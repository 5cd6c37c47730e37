"""Local-backend calibration for measured-mode validation (DESIGN.md §4).

Daydream's *methodology* (predict -> implement -> compare, paper §6) is
validated against whatever backend the process runs on.  This module
measures the local backend's effective matmul FLOP/s, element-wise memory
bandwidth, and (multi-host-device) collective bandwidth, producing a
:class:`repro.core.costmodel.CostModel` whose analytical durations are in local
wall-clock units.  The hardware constants for the TPU roofline path stay
untouched — calibration is only for ground-truth comparisons.
"""

from __future__ import annotations

import dataclasses
import functools
import time
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .costmodel import CollectiveModel, CostModel, MeshTopology
from .task import HardwareSpec


def _time(fn, *args, iters: int = 5, warmup: int = 2) -> float:
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t0)
    ts.sort()
    return ts[len(ts) // 2]


@functools.lru_cache(maxsize=4)
def measure_local_backend(size: int = 1024, dtype_str: str = "float32"
                          ) -> Dict[str, float]:
    """Measure matmul FLOP/s and elementwise bytes/s on the local backend."""
    dtype = jnp.dtype(dtype_str)
    key = jax.random.PRNGKey(0)
    a = jax.random.normal(key, (size, size), dtype)
    b = jax.random.normal(key, (size, size), dtype)

    mm = jax.jit(lambda x, y: x @ y)
    t_mm = _time(mm, a, b)
    flops = 2.0 * size ** 3
    flops_per_s = flops / max(t_mm, 1e-9)

    big = jax.random.normal(key, (size * size * 8,), dtype)
    ew = jax.jit(lambda x: x * 1.0001 + 0.5)
    t_ew = _time(ew, big)
    traffic = 2.0 * big.size * dtype.itemsize
    bytes_per_s = traffic / max(t_ew, 1e-9)

    return {
        "matmul_flops_per_s": flops_per_s,
        "elementwise_bytes_per_s": bytes_per_s,
        "op_overhead_s": max(_time(jax.jit(lambda x: x + 1), jnp.ones(())), 1e-7),
    }


def measure_collective_bandwidth(num_devices: Optional[int] = None,
                                 payload_mb: int = 8) -> float:
    """All-reduce bus bandwidth across the local devices (bytes/s per device)."""
    devices = jax.devices()
    n = num_devices or len(devices)
    if n < 2:
        return 8e9
    from jax.sharding import PartitionSpec as P, NamedSharding
    mesh = jax.make_mesh((n,), ("d",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    elems = payload_mb * 1024 * 1024 // 4
    x = jnp.ones((n, elems), jnp.float32)
    x = jax.device_put(x, NamedSharding(mesh, P("d", None)))
    f = jax.jit(lambda v: jnp.sum(v, axis=0),
                out_shardings=NamedSharding(mesh, P(None)))
    t = _time(f, x)
    payload = elems * 4
    # ring all-reduce equivalent: 2*(n-1)/n * payload / bw = t
    return 2 * (n - 1) / n * payload / max(t, 1e-9)


def hop_latency_from_measurement(t_small: float, payload_bytes: float,
                                 num_devices: int, bandwidth: float) -> float:
    """Per-ring-step latency implied by one tiny-payload all-reduce time.

    The ring model (``CollectiveModel.axis_time``) predicts
    ``t = 2*(n-1)/n * payload/bw + 2*(n-1)*hop``; a tiny payload makes the
    latency term dominant, so subtracting the measured-bandwidth transfer
    term and dividing by the hop count recovers ``hop`` — the collective
    analogue of deriving ``op_overhead`` from a measured no-op dispatch.
    Degenerate inputs (n < 2, negative residual from noise) fall back to the
    analytical default.
    """
    if num_devices < 2 or t_small <= 0:
        return CollectiveModel.HOP_LATENCY
    transfer = 2 * (num_devices - 1) / num_devices * payload_bytes \
        / max(bandwidth, 1e-9)
    hop = (t_small - transfer) / (2 * (num_devices - 1))
    return hop if hop > 0 else CollectiveModel.HOP_LATENCY


def measure_collective_hop_latency(num_devices: Optional[int] = None,
                                   payload_kb: int = 4,
                                   bandwidth: Optional[float] = None) -> float:
    """Measured per-ring-step latency of the local backend's collectives.

    Times a tiny (``payload_kb``) all-reduce — latency-dominated — and
    solves the ring formula for the per-hop term
    (:func:`hop_latency_from_measurement`).  This is the ROADMAP item:
    ring-leg ``HOP_LATENCY`` is calibrated against the measured local
    collective path exactly the way compute durations already are, so
    cluster ring legs land in local wall-clock units too.  Single-device
    backends return the analytical default.
    """
    devices = jax.devices()
    n = num_devices or len(devices)
    if n < 2:
        return CollectiveModel.HOP_LATENCY
    bw = bandwidth if bandwidth is not None \
        else measure_collective_bandwidth(n)
    from jax.sharding import PartitionSpec as P, NamedSharding
    mesh = jax.make_mesh((n,), ("d",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    elems = max(payload_kb * 1024 // 4, 1)
    x = jnp.ones((n, elems), jnp.float32)
    x = jax.device_put(x, NamedSharding(mesh, P("d", None)))
    f = jax.jit(lambda v: jnp.sum(v, axis=0),
                out_shardings=NamedSharding(mesh, P(None)))
    t_small = _time(f, x)
    return hop_latency_from_measurement(t_small, elems * 4, n, bw)


def calibrated_cost_model(num_devices: int = 1) -> CostModel:
    """CostModel whose constants are the *local* backend's measured rates."""
    m = measure_local_backend()
    if num_devices > 1:
        coll_bw = measure_collective_bandwidth(num_devices)
        hop = measure_collective_hop_latency(num_devices, bandwidth=coll_bw)
    else:
        coll_bw, hop = 8e9, CollectiveModel.HOP_LATENCY
    hw = HardwareSpec(
        name=jax.devices()[0].device_kind,
        peak_flops=m["matmul_flops_per_s"],
        hbm_bandwidth=m["elementwise_bytes_per_s"],
        ici_bandwidth=coll_bw,
        dcn_bandwidth=8e9,
        op_overhead=m["op_overhead_s"] * 0.25,
        host_dispatch=m["op_overhead_s"],
    )
    topo = MeshTopology({"data": num_devices}, {"data": "ici"})
    return CostModel(hw=hw, topo=topo, hop_latency=hop)
