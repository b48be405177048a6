"""One-time hardware calibration (paper §4.1.3 / §7.1.5, Table 4).

The paper found white-paper peaks diverge from effective rates (L4: 121
reported vs ~55 measured TFLOPS), so ShuntServe calibrates each device type
once with three microbenchmarks that saturate distinct resources:

  * compute-bound GEMM      -> effective FLOP/s
  * memory-bound GEMV       -> effective HBM bytes/s
  * network-bound AllReduce -> effective link bytes/s (+ latency alpha)

We run the same protocol with JAX on whatever backend is present (CPU here,
TPU in production). Per the paper, each feature is measured at multiple batch
sizes and summarized by the **median**, giving one scalar per feature that is
invariant to serving configuration.
"""

from __future__ import annotations

import dataclasses
import statistics
import time
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.hw.profiles import DeviceProfile


@dataclasses.dataclass
class CalibrationResult:
    device_name: str
    eff_flops: float
    eff_mem_bw: float
    eff_net_bps: float
    net_alpha_s: float
    wall_time_s: float
    samples: Dict[str, List[float]]

    def apply(self, dev: DeviceProfile) -> DeviceProfile:
        return dataclasses.replace(
            dev,
            flops_bf16=self.eff_flops,
            mem_bw=self.eff_mem_bw,
            intra_beta_bps=self.eff_net_bps,
            intra_alpha_s=self.net_alpha_s,
        )


def _time_fn(fn, *args, warmup: int = 1, iters: int = 3) -> float:
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters


def calibrate_gemm(sizes: Sequence[int] = (256, 512, 1024),
                   dtype=jnp.float32) -> List[float]:
    """Effective FLOP/s from square matmuls (2*m*n*k FLOPs each)."""
    rates = []
    f = jax.jit(lambda a, b: a @ b)
    for n in sizes:
        key = jax.random.PRNGKey(n)
        a = jax.random.normal(key, (n, n), dtype)
        b = jax.random.normal(key, (n, n), dtype)
        dt = _time_fn(f, a, b)
        rates.append(2.0 * n ** 3 / dt)
    return rates


def calibrate_gemv(sizes: Sequence[int] = (1024, 2048, 4096),
                   dtype=jnp.float32) -> List[float]:
    """Effective HBM bytes/s from matrix-vector products (reads n*n matrix)."""
    rates = []
    f = jax.jit(lambda a, x: a @ x)
    for n in sizes:
        key = jax.random.PRNGKey(n)
        a = jax.random.normal(key, (n, n), dtype)
        x = jax.random.normal(key, (n,), dtype)
        dt = _time_fn(f, a, x)
        rates.append(n * n * a.dtype.itemsize / dt)
    return rates


def calibrate_allreduce(sizes_bytes: Sequence[int] = (1 << 16, 1 << 20),
                        dtype=jnp.float32) -> Dict[str, float]:
    """Effective collective beta (bytes/s) and alpha (s).

    With >=2 local devices uses a real psum over a mesh; on a single device
    falls back to a copy-based bound (the collective degenerates).
    Fits (alpha, beta) by least squares over message sizes:
        t(N) = alpha + N / beta
    """
    devs = jax.devices()
    times, sizes = [], []
    if len(devs) >= 2:
        from jax.sharding import Mesh, PartitionSpec as P
        mesh = Mesh(np.array(devs), ("x",))
        for nbytes in sizes_bytes:
            n = max(1, nbytes // jnp.dtype(dtype).itemsize)
            x = jnp.ones((len(devs), n), dtype)
            f = jax.jit(
                jax.shard_map(lambda a: jax.lax.psum(a, "x"), mesh=mesh,
                              in_specs=P("x", None), out_specs=P("x", None)))
            dt = _time_fn(f, x)
            times.append(dt)
            sizes.append(nbytes)
    else:
        for nbytes in sizes_bytes:
            n = max(1, nbytes // jnp.dtype(dtype).itemsize)
            x = jnp.ones((n,), dtype)
            f = jax.jit(lambda a: a + 1.0)
            dt = _time_fn(f, x)
            times.append(dt)
            sizes.append(nbytes)
    # Least-squares fit of t = alpha + N/beta.
    A = np.stack([np.ones(len(sizes)), np.array(sizes, float)], axis=1)
    coef, *_ = np.linalg.lstsq(A, np.array(times), rcond=None)
    alpha = max(float(coef[0]), 1e-7)
    inv_beta = max(float(coef[1]), 1e-15)
    return {"alpha_s": alpha, "beta_bps": 1.0 / inv_beta}


def calibrate(device_name: str = "local",
              gemm_sizes: Sequence[int] = (256, 512, 1024),
              gemv_sizes: Sequence[int] = (1024, 2048, 4096),
              net_sizes: Sequence[int] = (1 << 16, 1 << 20),
              ) -> CalibrationResult:
    """Full calibration pass; median-summarized per the paper."""
    t0 = time.perf_counter()
    gemm = calibrate_gemm(gemm_sizes)
    gemv = calibrate_gemv(gemv_sizes)
    net = calibrate_allreduce(net_sizes)
    wall = time.perf_counter() - t0
    return CalibrationResult(
        device_name=device_name,
        eff_flops=statistics.median(gemm),
        eff_mem_bw=statistics.median(gemv),
        eff_net_bps=net["beta_bps"],
        net_alpha_s=net["alpha_s"],
        wall_time_s=wall,
        samples={"gemm_flops": gemm, "gemv_bps": gemv},
    )
