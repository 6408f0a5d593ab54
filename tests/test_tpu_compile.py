"""Compile the Pallas kernels for a described TPU v5e (no chip attached).

The TPU compiler refuses what interpret mode accepts: blocks that break
the (8, 128) tiling rule, unaligned dynamic slices, more VMEM than a
kernel may use.  Each test lowers one kernel at real widths for one chip
of a described ``v5e:2x2`` host and checks that the compiled program
holds the Mosaic kernel.  Nothing runs, so nothing here is a timing.
"""
import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from repro.kernels import flash_attention as fa
from repro.kernels import fused_update as fu
from repro.kernels import paged_decode as pd
from repro.kernels import put_copy as pc
from repro.kernels import reduce_combine as rc
from repro.kernels import ring_attention as ra
from repro.kernels import ssd_scan as ss


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
def spec(topo):
    one_chip = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=one_chip)


@pytest.fixture(autouse=True)
def _no_compile_cache():
    # a described-topology compile cannot be read back without a chip
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield
    jax.config.update("jax_enable_compilation_cache", was)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("L", [1024, 4096])
def test_flash_attention_qwen2_widths(spec, L):
    # qwen2-0.5b: 14 query heads, 2 KV heads of 64
    q = spec((1, 14, L, 64), jnp.bfloat16)
    kv = spec((1, 2, L, 64), jnp.bfloat16)
    _compile(lambda q, k, v: fa.flash_attention(q, k, v), q, kv, kv)


def test_ring_attention_partials(spec):
    L = 1024
    q = spec((1, 14, L, 64), jnp.bfloat16)
    kv = spec((1, 2, L, 64), jnp.bfloat16)
    pos = spec((L,), jnp.int32)
    _compile(lambda q, k, v, qp, kp: ra.attn_block_partials(
        q, k, v, qp, kp, use_pallas=True, interpret=False),
        q, kv, kv, pos, pos)


def test_ssd_scan_mamba2_widths(spec):
    # mamba2-2.7b: d_inner 5120 = 80 heads of 64, state 128, chunk 128
    B, L, H, P, N = 1, 2048, 80, 64, 128
    _compile(lambda *a: ss.ssd_scan(*a, chunk=128),
             spec((B, L, H, P), jnp.bfloat16), spec((B, L, H), jnp.float32),
             spec((H,), jnp.float32), spec((B, L, 1, N), jnp.bfloat16),
             spec((B, L, 1, N), jnp.bfloat16),
             spec((B, H, P, N), jnp.float32))


def test_reduce_combine_4mib(spec):
    bufs = [spec((1024, 1024), jnp.float32)] * 3
    _compile(lambda *b: rc.reduce_combine_2d(list(b), "sum"), *bufs)


def test_put_copy_4mib(spec):
    _compile(pc.put_copy_2d, spec((1024, 1024), jnp.float32))


def test_fused_adam_update_4mib(spec):
    n = 1 << 20
    f32 = spec((n,), jnp.float32)
    scalar = spec((), jnp.float32)
    _compile(lambda g0, g1, p, m, v, wd, c1, c2: fu.fused_adam_update_2d(
        [g0, g1], p, m, v, wd, c1, c2, lr=3e-4, b1=0.9, b2=0.95, eps=1e-8,
        wd_coef=0.1, scale=4.0, out_dtype=jnp.float32),
        f32, f32, f32, f32, f32, spec((n,), jnp.int8), scalar, scalar)


# qwen2-serve-chat: 128 slots x 128 pages of 16 positions, 24 layers, two
# KV heads of 64 merged into 128 lanes, plus the null page
SLOTS, PAGES, PAGE, LAYERS = 128, 128, 16, 24
POOL_PAGES = SLOTS * PAGES + 1


def test_paged_decode_kernel_cell_widths(spec):
    pool = spec((LAYERS, POOL_PAGES, PAGE, 128), jnp.bfloat16)
    new = spec((SLOTS, 2, 64), jnp.bfloat16)
    _compile(lambda q, kn, vn, kp, vp, layer, table, pos:
             pd.paged_decode_attention(q, kn, vn, kp, vp, layer, table, pos,
                                       page_size=PAGE),
             spec((SLOTS, 14, 64), jnp.float32), new, new, pool, pool,
             spec((), jnp.int32), spec((SLOTS, PAGES), jnp.int32),
             spec((SLOTS,), jnp.int32))


def test_paged_decode_latent_kernel_cell_widths(spec):
    """The latent mode at deepseek-v3-serve-chat2k's widths: 128 slots x
    256 pages of 16, 7 layers, one latent "head" of 576 lanes padded to
    640, 128 query heads, values the first 512 lanes."""
    slots, pages = 128, 256
    pool = spec((7, slots * pages + 1, PAGE, 640), jnp.bfloat16)
    _compile(lambda q, kn, kp, layer, table, pos:
             pd.paged_decode_attention(q, kn, None, kp, None, layer, table,
                                       pos, page_size=PAGE, v_lanes=512),
             spec((slots, 128, 640), jnp.float32),
             spec((slots, 1, 640), jnp.bfloat16), pool,
             spec((), jnp.int32), spec((slots, pages), jnp.int32),
             spec((slots,), jnp.int32))


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_paged_decode_program_updates_pool_in_place(topo, monkeypatch,
                                                    program):
    """The serving engine's programs at the cell's size, decode on the
    kernel path and prefill at the 1024 bucket: the pool is donated and
    aliased to the output, and no instruction makes a copy of it or a
    layer-sized slice of it."""
    from repro.configs import get_config
    from repro.kernels import ops as kops
    from repro.launch import build
    from repro.models import layers
    from repro.serve.engine import serve_programs

    monkeypatch.setattr(layers, "_on_tpu", lambda: True)
    monkeypatch.setattr(kops, "_default_interpret", lambda: False)
    cfg = dataclasses.replace(get_config("qwen2-0.5b"), fsdp=False)
    mesh = Mesh(np.array(topo.devices[:1]).reshape(1, 1), ("data", "model"))
    prefill, decode, poolspecs = serve_programs(cfg, mesh, page_size=PAGE)
    shapes, pspecs = build.abstract_params(cfg, mesh)

    def on_mesh(s, sp):
        return jax.ShapeDtypeStruct(s.shape, s.dtype,
                                    sharding=NamedSharding(mesh, sp))
    params = jax.tree.map(on_mesh, build.global_shape(shapes, pspecs, mesh),
                          pspecs)
    pool_leaf = jax.ShapeDtypeStruct((LAYERS, POOL_PAGES, PAGE, 128),
                                     jnp.bfloat16)
    pool = jax.tree.map(lambda sp: on_mesh(pool_leaf, sp), poolspecs)

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32,
                                    sharding=NamedSharding(mesh, P()))
    with jax.set_mesh(mesh):
        if program == "decode":
            compiled = decode.lower(params, pool, i32(SLOTS, PAGES),
                                    i32(SLOTS, 1), i32(SLOTS)).compile()
        else:
            compiled = prefill.lower(params, pool, i32(1, PAGES),
                                     i32(1, 1024), i32(1, 1024),
                                     i32(1)).compile()
    text = compiled.as_text()
    assert ("tpu_custom_call" in text) == (program == "decode")
    pool_bytes = 2 * LAYERS * POOL_PAGES * PAGE * 128 * 2
    assert compiled.memory_analysis().alias_size_in_bytes >= pool_bytes
    layer_elems = POOL_PAGES * PAGE * 128
    for m in re.finditer(r"= (\w+)\[([\d,]+)\][^ ]* (\S+)\(", text):
        n = int(np.prod([int(d) for d in m.group(2).split(",")]))
        op = m.group(3)
        assert n != layer_elems, m.group(0)          # no per-layer slice
        assert not (n >= layer_elems and op.startswith("copy")), m.group(0)
