"""Compile the search path for a TPU v5e that is described, not attached.

The TPU compiler refuses what interpret mode accepts (unaligned blocks,
int64 inside a kernel, relayouts Mosaic cannot lower), so the Pallas kernels
of the served path and whole bucket steps are compiled here at served
widths, and each compiled program must hold the kernels
(`tpu_custom_call`).  Nothing runs: a compile that passes is not a chip
result.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process at a time may load the TPU library,
and every test worker imports every test file.
"""
import os
import re
from functools import partial

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.batch_executor import bucket_step_math
from repro.core.fetch_tables import batch_table_specs
from repro.kernels import ops

ROWS = 64                                   # served bucket rows (T)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    old_log_dir = os.environ.get("TPU_LOG_DIR")
    os.environ["TPU_LOG_DIR"] = "disabled"  # no compiler logs outside
    # a described chip's programs cannot be read back from the persistent
    # cache: keep them out of it
    old_cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", old_cache)
        if old_log_dir is None:
            os.environ.pop("TPU_LOG_DIR", None)
        else:
            os.environ["TPU_LOG_DIR"] = old_log_dir


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _on(sharding, tree):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree)


def _kernel_calls(fn, *args) -> int:
    return jax.jit(fn).lower(*args).compile().as_text().count(
        "tpu_custom_call")


def _kernel_names(txt: str) -> list[str]:
    """The names of a compiled program's Pallas kernels (the custom-call
    instructions, without their `.N` suffix)."""
    return [m.group(1) for m in re.finditer(
        r"%([A-Za-z_]+)(?:\.\d+)? = [^\n]*custom_call_target=\"tpu_custom_call\"",
        txt)]


BANDED = {
    "intersect": lambda a, b, d, bands: ops.banded_intersect_rows(
        a, b, bands, implementation="pallas", interpret=False),
    "min_delta": lambda a, b, d, bands: ops.banded_min_delta_rows(
        a, b, d, bands, implementation="pallas", interpret=False),
    "delta_mask": lambda a, b, d, bands: ops.banded_delta_mask_rows(
        a, b, bands, implementation="pallas", interpret=False),
}


@pytest.mark.parametrize("pa,pb", [(128, 128), (256, 2048), (2048, 8192),
                                   (512, 1024), (1024, 128)])
@pytest.mark.parametrize("kernel", sorted(BANDED))
def test_banded_kernel_compiles_for_v5e(one_chip, kernel, pa, pb):
    """The three banded row kernels at served widths: seed rows F*P0 wide
    against constraint rows F*P wide.  Rows of at most 1024 keys on both
    sides run the packed kernel, wider ones the tiled kernel alone."""
    i32 = jnp.int32
    args = _on(one_chip, (jax.ShapeDtypeStruct((ROWS, pa), i32),
                          jax.ShapeDtypeStruct((ROWS, pb), i32),
                          jax.ShapeDtypeStruct((ROWS, pb), i32),
                          jax.ShapeDtypeStruct((ROWS,), i32)))
    names = _kernel_names(
        jax.jit(BANDED[kernel]).lower(*args).compile().as_text())
    packed = max(pa, pb) <= 1024
    assert packed == ops.packed_layout(pa, pb)
    assert names == [kernel + "_packed" if packed else kernel]


@pytest.mark.parametrize("width", [128, 2048, 8192])
def test_unpack_compiles_for_v5e(one_chip, width):
    """The bit-unpack kernel over a gathered (field, row, width) slab."""
    plane = jax.ShapeDtypeStruct((3, ROWS, width), jnp.int32)
    args = _on(one_chip, (plane,) * 4)

    def unpack(w, s, wd, an):
        return ops.unpack_fields(w, s, wd, an, implementation="pallas",
                                 interpret=False)
    assert _kernel_calls(unpack, *args) == 1


# (T, G, F, P0, P, C, M): bucket shapes of the kind the engine produces
STEPS = {
    "phrase": dict(shape=(ROWS, 2, 1, 128, 2048, 4, 2), ranked=False,
                   kword=False),
    "phrase_wide": dict(shape=(128, 8, 8, 128, 2048, 4, 2), ranked=False,
                        kword=False),
    # the benchmark cell's leading bucket: 128-key rows, packed kernel
    "phrase_narrow": dict(shape=(2048, 4, 1, 128, 128, 4, 2), ranked=False,
                          kword=False),
    "ranked": dict(shape=(ROWS, 4, 2, 256, 1024, 0, 0), ranked=True,
                   kword=False),
    "kword": dict(shape=(ROWS, 4, 1, 512, 4096, 0, 0), ranked=False,
                  kword=True),
}
# the device arena of benchmarks.common.bench_world(1200, 800,
# stop_mass=0.4): packed lane words, block metadata rows, stream-3 slots
ARENA = {"lanes": ((26_800_588,), jnp.int32),
         "blk_meta": ((183_308, 5), jnp.int32),
         "near_stop": ((736_820, 20), jnp.int16)}
STEP_TEMP_LIMIT = 4 << 30      # of the chip's 16 GB, beside the arena


@pytest.mark.parametrize("kind", sorted(STEPS))
def test_bucket_step_compiles_for_v5e(one_chip, kind):
    """A whole served bucket step with the Pallas kernels: gather, unpack,
    rebase, and the banded pass of its kind (membership, min-delta scoring,
    K-word delta masks), over the benchmark world's arena.  Its temporaries
    must leave the chip room for the arena."""
    T, G, F, P0, P, C, M = STEPS[kind]["shape"]
    arena = {k: jax.ShapeDtypeStruct(*v) for k, v in ARENA.items()}
    step = partial(bucket_step_math, P0=P0, P=P, impl="pallas",
                   interpret=False, ranked=STEPS[kind]["ranked"],
                   kword=STEPS[kind]["kword"])
    compiled = jax.jit(step).lower(
        _on(one_chip, arena),
        _on(one_chip, batch_table_specs(T, G, F, C, M))).compile()
    # unpack for the seed and the constraint groups, plus the banded pass
    # in the layout the row widths select
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= 3
    banded = [n for n in _kernel_names(text) if n != "unpack"]
    packed = ops.packed_layout(F * P0, F * P)
    assert banded and all(n.endswith("_packed") == packed for n in banded)
    assert compiled.memory_analysis().temp_size_in_bytes < STEP_TEMP_LIMIT
