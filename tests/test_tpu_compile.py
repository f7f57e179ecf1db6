"""Compile the chip's programs for a described v5e chip, with no chip.

The TPU compiler is installed even where no TPU is attached: these tests
lower and compile, at granite-3-8b widths, the Pallas kernels, the
served decode step and the Clock2Q+ sweep for one chip of a described
``v5e:2x2`` topology.  What the chip's compiler refuses fails here.
Nothing runs, so nothing here says anything about results or times.

The topology is described inside a fixture, never while a module is
imported: only one process at a time may load the TPU library.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.core import traces
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.paged_attention.ops import paged_attention
from repro.models import transformer as T
from repro.models.model import build
from repro.tuning import sweep

GRANITE = get_config("granite-3-8b")
H, HKV, HD = GRANITE.n_heads, GRANITE.n_kv_heads, GRANITE.hd
BATCH, BLOCK, BLOCKS_PER_SEQ, POOL_BLOCKS = 8, 16, 64, 2048


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one: keep the cache off."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _shape(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    compiled = fn.lower(*args).compile()
    assert compiled is not None
    return compiled


def test_paged_attention_compiles(one_chip, no_compile_cache):
    pool = _shape(one_chip, (POOL_BLOCKS, BLOCK, HKV, HD), jnp.bfloat16)
    compiled = _compile(
        paged_attention, _shape(one_chip, (BATCH, H, HD), jnp.bfloat16),
        pool, pool, _shape(one_chip, (BATCH, BLOCKS_PER_SEQ), jnp.int32),
        _shape(one_chip, (BATCH,), jnp.int32))
    assert "tpu_custom_call" in compiled.as_text()


def test_flash_attention_compiles(one_chip, no_compile_cache):
    S = 2048
    compiled = _compile(
        flash_attention, _shape(one_chip, (1, S, H, HD), jnp.bfloat16),
        _shape(one_chip, (1, S, HKV, HD), jnp.bfloat16),
        _shape(one_chip, (1, S, HKV, HD), jnp.bfloat16))
    assert "tpu_custom_call" in compiled.as_text()


def test_served_decode_step_compiles(one_chip, no_compile_cache):
    """The engine's jitted ``forward_decode_paged`` at published widths,
    cut to 2 layers, from parameter shapes alone."""
    cfg = dataclasses.replace(GRANITE, n_layers=2)
    params = jax.tree_util.tree_map(
        lambda x: _shape(one_chip, x.shape, x.dtype),
        jax.eval_shape(build(cfg).init, jax.random.PRNGKey(0)))
    pool = _shape(one_chip, (cfg.n_layers, POOL_BLOCKS, BLOCK, HKV, HD),
                  jnp.bfloat16)
    vec = _shape(one_chip, (BATCH,), jnp.int32)
    step = jax.jit(lambda p, tk, kp, vp, bt, ln, si, so:
                   T.forward_decode_paged(cfg, p, tk, (kp, vp), bt, ln, si,
                                          so))
    compiled = _compile(
        step, params, _shape(one_chip, (BATCH, 1), jnp.int32), pool, pool,
        _shape(one_chip, (BATCH, BLOCKS_PER_SEQ), jnp.int32), vec, vec, vec)
    mem = compiled.memory_analysis()
    assert mem.output_size_in_bytes >= 2 * pool.size * 2  # both pools out


def test_served_latent_decode_step_compiles(one_chip, no_compile_cache):
    """The same for moonlight-16b-a3b's absorbed latent decode and its
    dropless expert layer (8 of 64 experts held), cut to its dense layer
    and one expert layer, at the benchmark's 64 rows of 128 blocks."""
    cfg = dataclasses.replace(get_config("moonlight-16b-a3b"), n_layers=2,
                              experts_held=8)
    params = jax.tree_util.tree_map(
        lambda x: _shape(one_chip, x.shape, x.dtype),
        jax.eval_shape(build(cfg).init, jax.random.PRNGKey(0)))
    (width,) = cfg.cache_spec()["latent"]
    pool = _shape(one_chip, (cfg.n_layers, POOL_BLOCKS, BLOCK, width),
                  jnp.bfloat16)
    vec = _shape(one_chip, (64,), jnp.int32)
    step = jax.jit(lambda p, tk, lp, bt, ln, si, so, n:
                   T.forward_decode_paged(cfg, p, tk, (lp,), bt, ln, si, so,
                                          n))
    compiled = _compile(
        step, params, _shape(one_chip, (64, 1), jnp.int32), pool,
        _shape(one_chip, (64, 128), jnp.int32), vec, vec, vec,
        _shape(one_chip, (), jnp.int32))
    assert compiled.memory_analysis().output_size_in_bytes >= pool.size * 2


def test_flush_gather_of_latent_blocks_compiles(one_chip, no_compile_cache):
    """The flusher's gather of 4 latent blocks from moonlight-16b-a3b's
    whole pool (27 layers, 8,192 blocks) keeps no copy of the pool."""
    from repro.kvcache.pool import _gather_slots
    cfg = get_config("moonlight-16b-a3b")
    pool = _shape(one_chip, (cfg.n_layers, 8192, BLOCK)
                  + cfg.cache_spec()["latent"], jnp.bfloat16)
    compiled = _compile(_gather_slots, (pool,),
                        _shape(one_chip, (4,), jnp.int32))
    assert compiled.memory_analysis().temp_size_in_bytes < 2**20


def test_flush_gather_of_kv_blocks_compiles(one_chip, no_compile_cache):
    """The same gather of 4 granite-3-8b K/V blocks (20 layers, the
    benchmark's pool size) also keeps no copy of either pool."""
    from repro.kvcache.pool import _gather_slots
    pool = _shape(one_chip, (20, 2700, BLOCK, HKV, HD), jnp.bfloat16)
    compiled = _compile(_gather_slots, (pool, pool),
                        _shape(one_chip, (4,), jnp.int32))
    assert compiled.memory_analysis().temp_size_in_bytes < 2**20


def test_sweep_grid_compiles(one_chip, no_compile_cache):
    """``grid_hit_counts`` for the fig13 Clock2Q+ grid: 2 capacities x 3
    correlation windows = 6 lanes over a SUITE-length trace."""
    n = traces.SUITE[0].n
    grid = sweep.make_grid([24, 242], (0.1, 0.3, 0.5))
    states = jax.tree_util.tree_map(
        lambda x: _shape(one_chip, x.shape, x.dtype),
        sweep.grid_init(grid, 4096))
    assert len(grid) == 6
    assert {np.shape(x)[0] for x in jax.tree_util.tree_leaves(states)} == {6}
    _compile(sweep.grid_hit_counts, "clock2q+", states,
             _shape(one_chip, (n,), jnp.int32))
