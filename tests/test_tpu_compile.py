"""Real-width compiles of the serving path's attention kernels for a
described (not attached) TPU v5e chip.

Interpret mode cannot see the TPU compiler's tiling, layout and VMEM
rules; these compiles can, at no chip time. Nothing here runs a kernel.
The topology is described inside a fixture, never at import time: only
one process may load the TPU library, and every test worker imports this
module. Each compiled kernel must carry a name that the benchmark's
roofline reader for it matches (``KERNELS`` in
``chipbench/layer_metrics``), so a renamed kernel cannot drop out of its
metric unnoticed.
"""

import dataclasses
import importlib
import math
import os
import re
import sys

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.kernels import attention as ka

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
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


def _reader_kernels(metric):
    """The kernel kinds a roofline reader of the benchmark matches."""
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    return importlib.import_module(
        f"chipbench.layer_metrics.{metric}").KERNELS


def _kernel_kinds(compiled):
    """Kinds of the program's Pallas calls: ``%decode_attention_paged.1 =
    ... custom-call(...), custom_call_target="tpu_custom_call"`` ->
    ``decode_attention_paged``, as the profiler trace names the op."""
    return {line.split(" = ")[0].strip().lstrip("%").split(".")[0]
            for line in compiled.as_text().splitlines()
            if 'custom_call_target="tpu_custom_call"' in line}


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
    assert _kernel_kinds(compiled) & set(_reader_kernels(
        "decode_attn_roofline"))


def test_paged_decode_compiles_at_benchmark_shape(one_chip):
    """The benchmark's own decode shape (internlm2-1.8b, ``max_batch`` 64,
    ``max_len`` 4096, 3600 pool blocks): the page loop's VMEM budget is
    checked where the cell runs."""
    nh, nkv, d = _geometry("internlm2-1.8b")
    batch, mb, n_blocks = 64, 4096 // BLOCK, 3600
    pool = (n_blocks, BLOCK, nkv, d)

    def step(q, k, v, tbl, pos):
        return ka.decode_attention_paged(q, k, v, tbl, pos)
    compiled = _compile(step, one_chip,
                        ((batch, 1, nh, d), jnp.bfloat16),
                        (pool, jnp.bfloat16), (pool, jnp.bfloat16),
                        ((batch, mb), jnp.int32), ((batch,), jnp.int32))
    assert _kernel_kinds(compiled) & set(_reader_kernels(
        "decode_attn_roofline"))


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
    assert _kernel_kinds(compiled) & set(_reader_kernels(
        "prefill_attn_roofline"))


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
    assert _kernel_kinds(compiled) & set(_reader_kernels(
        "prefill_attn_roofline"))


# -- the whole paged step: the layer loop moves no layer's pool ---------------

POOL_BLOCKS = 3600                  # the benchmark's pool, internlm2-1.8b
POOL_BATCH, POOL_LEN = 64, 4096
POOL_COPY_OPS = ("copy", "dynamic-slice", "dynamic-update-slice")


def _pool_sized_moves(compiled, limit):
    """Instructions of the optimised HLO that copy, slice or update-slice
    an array of ``limit`` bytes or more: by opcode, or by the name of a
    fusion (``bitcast_dynamic-update-slice_fusion.3``), as the profiler
    trace names them."""
    itemsize = {"bf16": 2, "f16": 2, "f32": 4, "s32": 4, "u32": 4}
    found = []
    for line in compiled.as_text().splitlines():
        m = re.match(r"\s*(?:ROOT )?%(\S+) = \(?(\w+)\[([\d,]*)\]\S* (\S+)\(",
                     line)
        if not m or m.group(2) not in itemsize:
            continue
        name, dtype, dims, op = m.groups()
        size = itemsize[dtype] * math.prod(int(n) for n in dims.split(",")
                                           if n)
        kinds = {op} | set(name.split(".")[0].split("_"))
        if size >= limit and kinds & set(POOL_COPY_OPS):
            found.append((name, op, f"{dtype}[{dims}]"))
    return found


@pytest.fixture
def paged_model(monkeypatch, one_chip):
    """internlm2-1.8b at its widths with 2 layers, Pallas kernels compiled
    for the chip (not interpreted), and shapes of its params and of the
    benchmark's paged cache on the described chip."""
    from repro.kernels import ops as kops
    from repro.models import build_model
    monkeypatch.setattr(kops, "_interpret", lambda: False)
    cfg = dataclasses.replace(get_config("internlm2-1.8b"), n_layers=2)
    model = build_model(cfg, use_pallas=True, remat=False)

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)
    params = on_chip(jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    cache = on_chip(jax.eval_shape(lambda: model.init_cache(
        POOL_BATCH, POOL_LEN, vector_pos=True, kv_layout="paged",
        n_blocks=POOL_BLOCKS, block_size=BLOCK)))
    layer_pool = math.prod(cache["k"].shape[1:]) * 2
    assert layer_pool == 117_964_800
    return model, params, cache, layer_pool


def test_paged_decode_step_moves_no_layer_pool(paged_model, one_chip):
    """The whole paged ``decode_step``, cache donated as the engine
    donates it: no copy, dynamic-slice or dynamic-update-slice makes an
    array of one layer's pool or more, and the decode kernel is there
    under the name its reader matches."""
    model, params, cache, layer_pool = paged_model
    tokens = jax.ShapeDtypeStruct((POOL_BATCH, 1), jnp.int32,
                                  sharding=one_chip)
    compiled = jax.jit(model.decode_step, donate_argnums=(1,)).lower(
        params, cache, tokens).compile()
    assert _pool_sized_moves(compiled, layer_pool) == []
    assert _kernel_kinds(compiled) & set(_reader_kernels(
        "decode_attn_roofline"))


def test_paged_chunk_step_moves_no_layer_pool(paged_model, one_chip):
    """The same for ``prefill_chunk`` routed into the pool through a
    block table (group 4, chunk 512), as the engine's chunk program runs
    it."""
    model, params, cache, layer_pool = paged_model
    group, chunk = 4, 512

    def chunk_step(params, cache, tokens, base, last, tbls, rem):
        return model.prefill_chunk(params, cache, tokens, base,
                                   last_pos=last, block_tbl=tbls, lens=rem)

    def arg(shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)
    rows = arg((group,))
    compiled = jax.jit(chunk_step, donate_argnums=(1,)).lower(
        params, cache, arg((group, chunk)), arg(()), rows,
        arg((group, POOL_LEN // BLOCK)), rows).compile()
    assert _pool_sized_moves(compiled, layer_pool) == []
    assert _kernel_kinds(compiled) & set(_reader_kernels(
        "prefill_attn_roofline"))
