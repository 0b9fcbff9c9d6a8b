"""The paged append compiled for the chip, without the chip: no copy of
the pool, and the donated leaf updated in place.

``SelfAttentionLayer._stream_attend_paged`` writes a chunk's keys and
values into the ``[P, Hkv, page_size, D]`` pool through
``_paged_append``. The TPU compiler gives a scatter whose window covers
the head axis a head-minor layout of the whole leaf and copies the leaf
before and after it (PERF.md, PR 35: 16 copies of 613 MB a decode step
of olmo-hybrid-7b, 120 of 23 MB of starcoder2-3b). Only a compile for
the TPU shows that, so these cases lower the helper at the two serving
cells' shapes for a described v5e and read the compiled HLO. The same
holds for the selecting layer's reads of its pools: the gather of the
kept tokens and the masked decode's view of whole pages copy no pool,
and the view is not copied into another order of its axes.
"""

import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from deeplearning4j_tpu.nn.conf.layers import (_LEAF_LANES, SelfAttentionLayer,
                                                _paged_append, _paged_gather)

#: (cell, pool leaf shape [P, Hkv, page_size, D]), 32 rows each; the
#: keye configuration's keys and values, and its index key as it is kept
#: (one "head", 64 wide in a row of 128 lanes)
POOLS = [("olmo-hybrid-7b", (4993, 30, 16, 128)),
         ("starcoder2-3b", (2817, 2, 16, 128)),
         ("keye-vl-2.0-30b-a3b", (12545, 4, 16, 128)),
         ("keye-vl-2.0-30b-a3b index key", (12545, 1, 16, 128))]
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


# ------------------------------------------------- the selecting layer's
def append_then_read(pool, page, off, rows, table):
    """What a decode step of the selecting layer does with its index
    leaf: append the chunk's keys, then read a row's pages through the
    table."""
    pool = _paged_append(pool, page, off, rows)
    return pool, pool[table]


def compile_append_then_read(width, sharding):
    shape = (12545, 1, 16, width)

    def spec(dims, dt):
        return jax.ShapeDtypeStruct(dims, dt, sharding=sharding)

    return shape, jax.jit(append_then_read, donate_argnums=(0,)).lower(
        spec(shape, jnp.bfloat16), spec((16, 1), jnp.int32),
        spec((16, 1), jnp.int32), spec((16, 1, 1, width), jnp.bfloat16),
        spec((16, 784), jnp.int32)).compile().as_text()


def test_the_index_leaf_a_lane_tile_wide_is_appended_and_read_in_place(
        one_chip, no_compile_cache):
    """The 64-wide index key is kept in rows of ``_LEAF_LANES``: the
    runtime then holds the leaf row-major, and the step's append and page
    gather copy nothing pool-shaped. The control beside it: the leaf 64
    wide is held page-minor and copied there and back (PERF.md, Open
    questions: the latent layer's 64-wide leaf has that)."""
    assert _LEAF_LANES == 128
    shape, hlo = compile_append_then_read(_LEAF_LANES, one_chip)
    assert pool_copies(hlo, shape) == []
    narrow, hlo = compile_append_then_read(64, one_chip)
    assert len(pool_copies(hlo, narrow)) >= 1


def compile_gather(gather, shape, sharding):
    def spec(dims, dt):
        return jax.ShapeDtypeStruct(dims, dt, sharding=sharding)

    return jax.jit(gather).lower(
        spec(shape, jnp.bfloat16), spec((16, 1, 2048), jnp.int32),
        spec((16, 1, 2048), jnp.int32)).compile().as_text()


def test_the_selected_tokens_are_gathered_with_no_copy_of_the_pool(
        one_chip, no_compile_cache):
    """``_paged_gather`` reads the selected 2,048 tokens of 16 rows as
    rows of the leaf; indexed over page and row-in-page alone (a [Hkv, D]
    window a token) the compiler copies the whole leaf first."""
    shape = POOLS[2][1]
    assert pool_copies(compile_gather(_paged_gather, shape, one_chip),
                       shape) == []
    windowed = compile_gather(lambda pool, page, off: pool[page, :, off],
                              shape, one_chip)
    assert len(pool_copies(windowed, shape)) == 1


def compile_masked_decode(sharding, form=None):
    """The masked paged form (``_attend_paged_masked``, or ``form`` with
    its arguments) at the keye cell's shapes: 16 rows of one query, a
    784-page table (12,544 slots), topk 2,048, the K and V pools and the
    index key's."""
    layer = SelfAttentionLayer(
        n_out=2048, n_heads=32, n_kv_heads=4, head_dim=128, rope=True,
        has_bias=False, qk_norm="head", index_n_heads=16,
        index_head_dim=64, index_topk=2048, cache_length=12544,
        stream_query_block=128)
    assert layer.selected_read == "masked"
    form = form or (lambda *a: layer._attend_paged_masked(*a)[0])

    def spec(dims, dt=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dt, sharding=sharding)

    kv, ik = POOLS[2][1], POOLS[3][1]
    return layer, jax.jit(form).lower(
        spec((16, 32, 1, 128)), spec(kv), spec(kv), spec(ik),
        spec((16, 784), jnp.int32),
        (spec((16, 1, 16, 64)), spec((16, 1, 16))),
        spec((16, 1), jnp.int32)).compile().as_text()


def view_moves(hlo):
    """Copies and transposes (other than the identity) of an array of the
    mapped K or V view's size, 16 x 784 x 4 x 16 x 128 elements, in any
    order of its axes."""
    moved = []
    for dims, op, perm in re.findall(
            r"= \w+\[([\d,]+)\](?:\{[^}]*\})? (copy|transpose)\([^)]*\)"
            r"(?:, dimensions=\{([\d,]+)\})?", hlo):
        shape = tuple(map(int, dims.split(",")))
        identity = perm == ",".join(map(str, range(len(shape))))
        if math.prod(shape) == 16 * 4 * 12544 * 128 and not identity:
            moved.append((op, shape))
    return moved


def test_the_masked_decode_reads_whole_pages_with_no_copy(
        one_chip, no_compile_cache):
    """The masked form reads a page of all heads at a time, as the pool
    holds it, and scores the rows of the view as they come: no
    pool-shaped copy, and no copy or transpose of the
    [16, 784, 4, 16, 128] view. The control: the same view with its head
    axis moved forward for the prime's form (``_attend_selected``, keys
    [N, Hkv, L, D]) is copied into that order once a pool."""
    layer, hlo = compile_masked_decode(one_chip)
    for _, shape in POOLS[2:]:
        assert pool_copies(hlo, shape) == []
    assert view_moves(hlo) == []

    def head_major(pool, table):
        return jnp.moveaxis(pool[table], 2, 1).reshape(16, -1, 12544, 128)

    def moved(q, kp, vp, ip, table, idx, q_pos):
        return layer._attend_selected(
            q, head_major(kp, table), head_major(vp, table), idx,
            head_major(ip, table), q_pos)[0]

    _, control = compile_masked_decode(one_chip, moved)
    assert view_moves(control) == [("copy", (16, 784, 4, 16, 128))] * 2
