"""The paged append compiled for the chip, without the chip: no copy of
the pool, and the donated leaf updated in place.

``SelfAttentionLayer._stream_attend_paged`` writes a chunk's keys and
values into the ``[P, Hkv, page_size, D]`` pool through
``_paged_append``. The TPU compiler gives a scatter whose window covers
the head axis a head-minor layout of the whole leaf and copies the leaf
before and after it (PERF.md, PR 35: 16 copies of 613 MB a decode step
of olmo-hybrid-7b, 120 of 23 MB of starcoder2-3b). Only a compile for
the TPU shows that, so these cases lower the helper at the two serving
cells' shapes for a described v5e and read the compiled HLO.
"""

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from deeplearning4j_tpu.nn.conf.layers import _paged_append

#: (cell, pool leaf shape [P, Hkv, page_size, D]), 32 rows each
POOLS = [("olmo-hybrid-7b", (4993, 30, 16, 128)),
         ("starcoder2-3b", (2817, 2, 16, 128))]
ROWS = 32


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:1x1",
            chips_per_host_bounds=(1, 1, 1))
    except Exception as e:
        pytest.skip(f"no v5e:1x1 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    and cannot be read back without the chip: keep it out."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def compile_append(append, shape, t, dtype, sharding):
    hkv, d = shape[1], shape[3]

    def spec(dims, dt):
        return jax.ShapeDtypeStruct(dims, dt, sharding=sharding)

    return jax.jit(append, donate_argnums=(0,)).lower(
        spec(shape, dtype), spec((ROWS, t), jnp.int32),
        spec((ROWS, t), jnp.int32), spec((ROWS, t, hkv, d), dtype)
    ).compile().as_text()


def pool_copies(hlo, shape):
    dims = ",".join(map(str, shape))
    return re.findall(r"= \w+\[" + re.escape(dims) + r"\](?:\{[^}]*\})? "
                      r"copy\(", hlo)


@pytest.mark.parametrize("t", [1, 5])
@pytest.mark.parametrize("cell,shape", POOLS)
def test_the_append_copies_no_pool_leaf(one_chip, no_compile_cache, cell,
                                        shape, t):
    hlo = compile_append(_paged_append, shape, t, jnp.bfloat16, one_chip)
    assert pool_copies(hlo, shape) == [], cell
    # parameter 0 (the donated leaf) is the result's buffer
    assert re.search(r"input_output_alias=\{[^}]*\{\}: \(0, \{\}",
                     hlo), cell


@pytest.mark.parametrize("cell,shape", POOLS)
def test_the_int8_pool_appends_in_place_too(one_chip, no_compile_cache,
                                            cell, shape):
    hlo = compile_append(_paged_append, shape, 1, jnp.int8, one_chip)
    assert pool_copies(hlo, shape) == [], cell


def test_the_reading_sees_the_copies_of_the_head_wide_window(
        one_chip, no_compile_cache):
    """The control: the form the layer had, a [Hkv, D] window a token,
    compiles with the leaf copied before and after the scatter — so a
    clean reading above is the form's, not the regular expression's."""
    shape = POOLS[1][1]
    hlo = compile_append(
        lambda pool, page, off, rows: pool.at[page, :, off, :].set(rows),
        shape, 1, jnp.bfloat16, one_chip)
    assert len(pool_copies(hlo, shape)) == 2
