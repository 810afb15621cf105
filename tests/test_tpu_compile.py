"""Compiles of the served decode path for a described TPU v5e.

No chip is needed: the TPU compiler installed with JAX compiles for a
topology that is described, not attached.  This catches what interpret
mode cannot — tiling the kernel cannot take, a program that does not
fit the chip's memory, a Mosaic kernel that GSPMD would have to
partition.  The topology is described inside a fixture (never at
import), and every test stays in this one file, so only the test worker
that runs this file loads the TPU library.

Shapes are the ones ``chip_smoke.py`` serves: 8 lanes, block-table rows
of 15 pages (160-token requests, 64-token prefill chunks), a pool of
4,096 16-token pages; and the Mellum2 cell's: 16 lanes, rows of 529 pages
(8,192-token requests, 256-token chunks), 1,024-token windows.
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import ops
from repro.kernels.decode_attention import paged_flash_decode
from repro.launch import sharding as shlib
from repro.launch.mesh import make_mesh
from repro.models.transformer import abstract_params, init_paged_states
from repro.train.steps import make_paged_decode_step

LANES, ROW, PAGES, PAGE = 8, 15, 4096, 16
HBM_BYTES = 16e9


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:     # noqa: BLE001 — any failure means no TPU
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _placed(tree, sharding):
    """ShapeDtypeStructs of ``tree`` carrying ``sharding`` (one for all
    leaves, or a matching pytree of shardings)."""
    if not isinstance(sharding, (SingleDeviceSharding, NamedSharding)):
        return jax.tree_util.tree_map(
            lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
            tree, sharding)
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


def _decode_inputs(cfg, sharding):
    i32 = jnp.int32
    batch = {"tokens": jax.ShapeDtypeStruct((LANES, 1), i32),
             "block_tables": jax.ShapeDtypeStruct((LANES, ROW), i32),
             "lengths": jax.ShapeDtypeStruct((LANES,), i32)}
    return _placed(batch, sharding)


_KERNEL_SHAPES = [
    pytest.param(arch, dtype, LANES, ROW, PAGES, 1, id=f"{arch}-{dtype}")
    for arch in ("qwen2-1.5b", "starcoder2-3b")
    for dtype in ("bfloat16", "float32")
] + [
    # the benchmark's cells: chat's 149-page rows, and starcoder2-7b's
    # share of each chip on a (1, 4) mesh (9 query heads, one KV head)
    pytest.param("qwen2-1.5b", "bfloat16", 16, 149, 4096, 1,
                 id="qwen2-1.5b-chat-row"),
    pytest.param("starcoder2-7b", "bfloat16", 16, 133, 8193, 4,
                 id="starcoder2-7b-per-chip"),
]


# Mellum2's grouping (4 KV heads, 8 query heads each) with its window
_WINDOW_SHAPES = [
    pytest.param("mellum2-12b-a2.5b-d12", "bfloat16", 16, 529, 4096, 1,
                 1024, id="mellum2-window"),
]


@pytest.mark.parametrize("arch,dtype,lanes,row,pages,chips,window",
                         [pytest.param(*p.values, None, id=p.id)
                          for p in _KERNEL_SHAPES] + _WINDOW_SHAPES)
def test_paged_flash_decode_compiles(one_chip, arch, dtype, lanes, row,
                                     pages, chips, window):
    cfg = get_config(arch)
    H, KV = cfg.num_heads // chips, cfg.num_kv_heads // chips
    hd = cfg.head_dim
    args = _placed(
        (jax.ShapeDtypeStruct((lanes, H, hd), dtype),
         jax.ShapeDtypeStruct((pages, KV, PAGE, hd), dtype),
         jax.ShapeDtypeStruct((pages, KV, PAGE, hd), dtype),
         jax.ShapeDtypeStruct((lanes, row), jnp.int32),
         jax.ShapeDtypeStruct((lanes,), jnp.int32)), one_chip)
    kernel = functools.partial(paged_flash_decode, interpret=False,
                               window=window)
    compiled = jax.jit(kernel).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _layer_pools(cfg, chips=1):
    """Bytes of one layer's bf16 K and V pools on each of ``chips``."""
    return 2 * PAGES * cfg.num_kv_heads // chips * PAGE * cfg.head_dim * 2


def test_qwen2_decode_step_compiles_with_kernel(one_chip, monkeypatch):
    """The published-width qwen2-1.5b paged decode step, as the engine
    jits it, holds the Pallas kernel and fits one chip.  Its temporaries
    hold no copy of a layer's pool: the pools are written in place, in
    the layout they are kept in."""
    monkeypatch.setattr(ops, "_default_interpret", lambda: False)
    cfg = get_config("qwen2-1.5b")
    params = _placed(abstract_params(cfg), one_chip)
    states = _placed(init_paged_states(cfg, PAGES, PAGE,
                                       make=jax.ShapeDtypeStruct), one_chip)
    step = jax.jit(make_paged_decode_step(cfg), donate_argnums=2)
    compiled = step.lower(params, _decode_inputs(cfg, one_chip),
                          states).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes < HBM_BYTES
    assert mem.temp_size_in_bytes < _layer_pools(cfg)


def test_mellum2_decode_step_compiles_and_fits_one_chip(one_chip,
                                                         monkeypatch):
    """The Mellum2 cell's decode step (12 layers, every expert, 16
    lanes, 4,096 pages, 529-page rows): the windowed and the full kernel
    are both in it, weights, pools and temporaries fit 16 GB, and the
    temporaries hold no copy of a layer's pool."""
    monkeypatch.setattr(ops, "_default_interpret", lambda: False)
    cfg = get_config("mellum2-12b-a2.5b-d12")
    params = _placed(abstract_params(cfg), one_chip)
    states = _placed(init_paged_states(cfg, PAGES, PAGE,
                                       make=jax.ShapeDtypeStruct), one_chip)
    i32 = jnp.int32
    batch = _placed({"tokens": jax.ShapeDtypeStruct((16, 1), i32),
                     "block_tables": jax.ShapeDtypeStruct((16, 529), i32),
                     "lengths": jax.ShapeDtypeStruct((16,), i32)}, one_chip)
    step = jax.jit(make_paged_decode_step(cfg), donate_argnums=2)
    compiled = step.lower(params, batch, states).compile()
    assert compiled.as_text().count("tpu_custom_call") >= 2
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < HBM_BYTES
    assert mem.temp_size_in_bytes < _layer_pools(cfg)


def test_starcoder2_7b_decode_step_compiles_on_four_chips(topo,
                                                          monkeypatch):
    """starcoder2-7b over a (1, 4) mesh: the kernel runs inside
    shard_map with its KV heads split over 'model', each chip holds a
    quarter of the weights, and no chip's temporaries hold a copy of its
    share of a layer's pool."""
    monkeypatch.setattr(ops, "_default_interpret", lambda: False)
    cfg = get_config("starcoder2-7b")
    mesh = make_mesh((1, 4), ("data", "model"), devices=topo.devices)
    abstract = abstract_params(cfg)
    params = _placed(abstract, shlib.param_shardings(mesh, abstract))
    pools = init_paged_states(cfg, PAGES, PAGE, make=jax.ShapeDtypeStruct)
    states = _placed(pools, shlib.state_shardings(mesh, pools))
    batch = _decode_inputs(cfg, NamedSharding(mesh, P()))
    step = jax.jit(make_paged_decode_step(cfg), donate_argnums=2)
    with shlib.use(shlib.ShardingContext(mesh)):
        compiled = step.lower(params, batch, states).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    weight_bytes = 2 * cfg.param_count()
    mem = compiled.memory_analysis()
    per_chip = mem.argument_size_in_bytes
    assert per_chip < 0.3 * weight_bytes + 2e9, per_chip
    assert mem.temp_size_in_bytes < _layer_pools(cfg, chips=4)
