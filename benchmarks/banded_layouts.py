"""The banded membership pass at one bucket's shape, in three ways.

`intersect` over N rows of a seed width against a constraint width, as the
bucket step runs it: the Pallas kernel in its packed layout (what
`ops.banded_intersect_rows` picks for rows of at most 1024 elements), the
same kernel forced into the tiled layout, and the jnp reference (a
vmapped `searchsorted`).  The rows hold few real keys among sentinel pads,
as the served slabs do, and a quarter of them none (inactive groups).
Times are wall-clock medians of back-to-back calls of one jitted program,
with the device synchronised after each; run it where the kernels compile
(a TPU), e.g. `python -m benchmarks.banded_layouts --rows 6144 --pa 128
--pb 128`.  Last line: JSON.
"""
from __future__ import annotations

import argparse
import json
import statistics
import time
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops

SENT = np.iinfo(np.int32).max


def rows(n: int, pa: int, pb: int, seed: int, fill: float):
    """(a, b sorted, bands): per row a geometric count of real keys
    (mean `fill` of the width) over one doc shard's key range, the rest
    sentinel; a quarter of the rows empty on the b side."""
    rng = np.random.default_rng(seed)

    def side(p):
        k = np.minimum(rng.geometric(1.0 / max(fill * p, 1.0), n), p)
        keys = rng.integers(0, 1 << 20, (n, p)).astype(np.int64)
        keys = np.where(np.arange(p)[None] < k[:, None], keys, SENT)
        return np.sort(keys, axis=1).astype(np.int32)
    a, b = side(pa), side(pb)
    b[rng.random(n) < 0.25] = SENT
    bands = np.where(rng.random(n) < 0.5, 0, 8).astype(np.int32)
    return a, b, bands


def time_calls(fn, args, reps: int) -> float:
    """Median ms per call of `fn` (compiled first)."""
    jax.block_until_ready(fn(*args))
    out = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        out.append(1e3 * (time.perf_counter() - t0))
    return statistics.median(out)


def measure(n: int, pa: int, pb: int, seed: int, fill: float,
            reps: int) -> dict:
    a, b, bands = (jnp.asarray(x) for x in rows(n, pa, pb, seed, fill))

    def call(impl):
        return jax.jit(lambda a, b, d: ops.banded_intersect_rows(
            a, b, d, implementation=impl))
    res = {"rows": n, "pa": pa, "pb": pb,
           "packed_layout": ops.packed_layout(pa, pb)}
    want = call("ref")(a, b, bands)
    res["ref_ms"] = time_calls(call("ref"), (a, b, bands), reps)
    got = call("pallas")(a, b, bands)
    res["pallas_ms"] = time_calls(call("pallas"), (a, b, bands), reps)
    with mock.patch.object(ops, "packed_layout", lambda pa, pb: False):
        tiled = call("pallas")
        got_t = tiled(a, b, bands)
        res["tiled_ms"] = time_calls(tiled, (a, b, bands), reps)
    res["exact"] = bool((got == want).all()) and bool((got_t == want).all())
    return res


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--rows", type=int, default=6144)
    ap.add_argument("--pa", type=int, default=128)
    ap.add_argument("--pb", type=int, default=128)
    ap.add_argument("--fill", type=float, default=0.03)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--reps", type=int, default=20)
    args = ap.parse_args(argv)
    res = measure(args.rows, args.pa, args.pb, args.seed, args.fill,
                  args.reps)
    res["device"] = jax.devices()[0].device_kind
    print(json.dumps(res))
    return res


if __name__ == "__main__":
    main()
