"""Real-width compiles of the serving path's attention kernels for a
described (not attached) TPU v5e chip.

Interpret mode cannot see the TPU compiler's tiling, layout and VMEM
rules; these compiles can, at no chip time. Nothing here runs a kernel.
The topology is described inside a fixture, never at import time: only
one process may load the TPU library, and every test worker imports this
module.
"""

import os

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.kernels import attention as ka

BLOCK = 16          # engine default pool block
MAX_LEN = 2048      # max_len of the chip smoke run
MAX_BATCH = 8


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def _no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but can never be read back without one: keep the cache off."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _geometry(arch):
    c = get_config(arch)
    return c.n_heads, c.n_kv_heads, c.hd


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding)
            for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile()


def _pool_shapes(nkv, d, batch):
    mb = MAX_LEN // BLOCK
    pool = (MAX_BATCH * mb + 1, BLOCK, nkv, d)
    return [(pool, jnp.bfloat16), (pool, jnp.bfloat16),
            ((batch, mb), jnp.int32)]


@pytest.mark.parametrize("arch", ["internlm2-1.8b", "qwen2-0.5b"])
def test_paged_decode_compiles(one_chip, arch):
    nh, nkv, d = _geometry(arch)

    def step(q, k, v, tbl, pos):
        return ka.decode_attention_paged(q, k, v, tbl, pos)
    compiled = _compile(step, one_chip,
                        ((MAX_BATCH, 1, nh, d), jnp.bfloat16),
                        *_pool_shapes(nkv, d, MAX_BATCH),
                        ((MAX_BATCH,), jnp.int32))
    assert "tpu_custom_call" in compiled.as_text()


def test_paged_chunk_compiles(one_chip):
    nh, nkv, d = _geometry("internlm2-1.8b")
    group, chunk = 4, 512

    def step(q, k, v, tbl, base):
        return ka.chunk_attention_paged(q, k, v, tbl, base)
    compiled = _compile(step, one_chip,
                        ((group, chunk, nh, d), jnp.bfloat16),
                        *_pool_shapes(nkv, d, group),
                        ((), jnp.int32))
    assert "tpu_custom_call" in compiled.as_text()


def test_flash_prefill_compiles(one_chip):
    nh, nkv, d = _geometry("internlm2-1.8b")
    group, seq = 4, 512

    def step(q, k, v):
        return ka.flash_attention(q, k, v, causal=True)
    compiled = _compile(step, one_chip,
                        ((group, seq, nh, d), jnp.bfloat16),
                        ((group, seq, nkv, d), jnp.bfloat16),
                        ((group, seq, nkv, d), jnp.bfloat16))
    assert "tpu_custom_call" in compiled.as_text()
