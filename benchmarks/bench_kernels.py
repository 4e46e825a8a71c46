"""Kernel microbenchmarks: µs/call for the Pallas kernels (interpret mode)
vs their jnp oracles on CPU.  These are regression numbers, not TPU
performance — TPU-side behaviour is captured by the dry-run roofline."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops


def _timeit(fn, *args, reps=5):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps * 1e6


def run() -> dict:
    rng = np.random.default_rng(0)
    out = {}

    a = jnp.asarray(rng.integers(0, 1 << 22, 16384).astype(np.int32))
    b = jnp.asarray(np.sort(rng.integers(0, 1 << 22, 65536)).astype(np.int32))
    for impl in ("ref", "pallas"):
        f = jax.jit(lambda a, b, impl=impl: ops.banded_intersect(
            a, b, 0, implementation=impl))
        out[f"banded_intersect_16k_64k_{impl}_us"] = _timeit(f, a, b)

    table = jnp.asarray(rng.normal(size=(100_000, 64)).astype(np.float32))
    ids = jnp.asarray(rng.integers(0, 100_000, (256, 39)).astype(np.int32))
    for impl in ("ref", "pallas"):
        f = jax.jit(lambda t, i, impl=impl: ops.segment_bag(
            t, i, implementation=impl))
        out[f"segment_bag_256x39_d64_{impl}_us"] = _timeit(f, table, ids)

    q = jnp.asarray(rng.normal(size=(4, 16, 128)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(4, 4096, 8, 128)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(4, 4096, 8, 128)).astype(np.float32))
    kvl = jnp.full((4,), 4096, jnp.int32)
    for impl in ("ref", "pallas"):
        f = jax.jit(lambda q, k, v, kvl, impl=impl: ops.flash_decode(
            q, k, v, kvl, implementation=impl))
        out[f"flash_decode_b4_s4k_{impl}_us"] = _timeit(f, q, k, v, kvl)
    return out


def main():
    for k, v in run().items():
        print(f"kernels.{k},{v:.1f}")


if __name__ == "__main__":
    main()
