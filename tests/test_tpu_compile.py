"""The main path's kernels compile for a TPU v5e — without the chip.

Every kernel here has passed the interpret-mode suite, which says nothing
about Mosaic: casts it cannot lower, blocks that overflow the scoped VMEM.
Each test lowers one jitted engine or kernel for a described ``v5e:2x2``
device at the size and blocks the engine really picks, with
``interpret=False`` passed explicitly, and compiles it. Nothing runs.

The topology is described inside a module-scoped fixture (never at import):
only one process at a time may load the TPU library, and a test worker
that cannot skips every test of this file.
"""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from repro.core.analysis import distributed as D
from repro.core.analysis import wavefront as WF
from repro.kernels import autotune, semiring
from repro.kernels.seghist import value_histogram_pallas

# slimfly(q=41), ~100k servers: 3362 routers padded to the 128 tile
SLIMFLY_P = 3456


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure to describe skips
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", enabled)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *shapes, sharding):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def test_f32_wavefront_slimfly(one_chip):
    p, block = WF.pad_block(3362)
    assert p == SLIMFLY_P
    _compile(WF._dist_mult_fn(False, block, False),
             ((p, p), jnp.float32), sharding=one_chip)


def test_batched_wavefront(one_chip):
    p, block = WF.pad_block(1000, batched=True)
    _compile(WF._dist_mult_fn(True, block, False),
             ((4, p, p), jnp.float32), sharding=one_chip)


@pytest.mark.parametrize("case", ["single", "batched", "shared"])
def test_weighted_ecmp_loop(one_chip, case):
    # shared: the traffic engine's pass, one (p, p) graph under a 2-matrix
    # demand uploaded at its own size
    batched = case != "single"
    p, block = WF.pad_block(3362, batched=batched)
    graph = (8, p, p) if case == "batched" else (p, p)
    demand = {"single": (p, p), "batched": (8, p, p),
              "shared": (2, 3362, 3362)}[case]
    _compile(WF._ecmp_fn(batched, block, False, weighted=True,
                         shared_graph=case == "shared"),
             *[(graph, jnp.float32)] * 3, (demand, jnp.float32),
             sharding=one_chip)


def test_slack_counts_program_slimfly(one_chip):
    # the report's +1/+2 level loop at diameter 2, as slack_counts_device
    # resolves its block
    p, _ = WF.pad_block(3362)
    block = autotune.resolve("count", p, p, p)["bm"]
    assert p % block == 0
    _compile(WF._slack_fn(3362, 2, block, False),
             ((p, p), jnp.float32), ((p, p), jnp.float32), sharding=one_chip)


@pytest.mark.parametrize("p", [512, 1024])
def test_minplus_squaring_at_tuned_block(one_chip, p):
    # the throughput oracle's squaring loop at the block squaring_apsp_device
    # resolves when apsp_from_lengths passes none
    cfg = autotune.resolve("minplus", p, p, p)
    _compile(WF._squaring_fn(cfg["bm"], cfg["sub_k"], 10, False),
             ((p, p), jnp.float32), sharding=one_chip)


def test_batched_minplus_default_block(one_chip):
    cfg = autotune.resolve("batched_minplus", 1024, 1024, 1024)

    def product(a):
        return semiring.semiring_matmul_batched_pallas(
            semiring.TROPICAL, (a,), (a,), interpret=False, **cfg)

    _compile(product, ((2, 1024, 1024), jnp.float32), sharding=one_chip)


def test_packed_frontier_kernel(one_chip):
    p, block = WF.pad_block(3362)

    def step(f, a, d):
        return semiring.frontier_step_packed_pallas(
            f, a, d, bm=block, bn=block, bk=block, interpret=False)

    _compile(step, ((p, p), jnp.uint32), ((p, p), jnp.uint8),
             ((p, p), jnp.int16), sharding=one_chip)
    _compile(WF._dist_mult_fn(False, block, False, packed=True),
             ((p, p), jnp.uint8), sharding=one_chip)


def test_uint8_panel_counting(one_chip):
    # the streaming pump's product: uint32 frontier slab x uint8 panel
    bk = D.widest_divisor_block(512, D._stream_block(4096, None))
    _compile(D._panel_accumulate_fn(512, D._stream_block(4096, None), bk,
                                    False),
             ((512, 4096), jnp.float32), ((512, 4096), jnp.uint32),
             ((512, 4096), jnp.uint8), ((), jnp.int32), sharding=one_chip)


@pytest.mark.parametrize("packed", [False, True], ids=["f32", "packed"])
def test_tiled_level_at_4096_columns(one_chip, packed):
    pc = 4096
    tp, bm = D._tile_shape(512)
    bn = bk = D._stream_block(pc, None)
    cell = (jnp.uint32, jnp.uint8, jnp.int16) if packed else (jnp.float32,) * 3
    _compile(D._tile_level_fn(bm, bn, bk, False, packed),
             ((tp, pc), cell[0]), ((pc, pc), cell[1]), ((tp, pc), cell[2]),
             ((tp, pc), cell[0]), ((), jnp.int32), sharding=one_chip)


def test_tiled_level_ragged_tail_tile(one_chip):
    # slimfly(q=41) in 512-row tiles ends with a 290-row tile
    pc = SLIMFLY_P
    tp, bm = D._tile_shape(3362 % 512)
    bn = bk = D._stream_block(pc, None)
    _compile(D._tile_level_fn(bm, bn, bk, False, True),
             ((tp, pc), jnp.uint32), ((pc, pc), jnp.uint8),
             ((tp, pc), jnp.int16), ((tp, pc), jnp.uint32), ((), jnp.int32),
             sharding=one_chip)


def test_histogram(one_chip):
    # path_length_histogram's call: 65 bins over the (padded) dist matrix
    _compile(lambda x: value_histogram_pallas(x, 65, interpret=False),
             ((3584, 3584), jnp.float32), sharding=one_chip)


def test_row_sharded_wavefront_on_four_chips(topo):
    mesh = Mesh(np.array(topo.devices[:4]), (D.ROW_AXIS,))
    p, row, col = D.pad_block_sharded(3362, 4)
    fn = D._dist_mult_sharded_fn(mesh, False, row, col, False)
    x = jax.ShapeDtypeStruct((p, p), jnp.float32,
                             sharding=NamedSharding(mesh, P(None, None)))
    compiled = fn.lower(x).compile()
    assert "tpu_custom_call" in compiled.as_text()
