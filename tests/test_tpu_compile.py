"""Compile the serving kernels for a described TPU v5e, without a chip.

Every kernel of the serving path (``packed_matmul``, ``stream_matmul``,
``stream_attention``) is lowered and compiled at smollm-135m's published
widths for one chip of a described ``v5e:2x2`` topology, and must come
out as a Mosaic kernel (``tpu_custom_call``).  The compiler refuses here
what it would refuse on the chip: unsupported gathers, casts, block
tilings and VMEM overflows.

The topology is described inside a module-scoped fixture, never at
import, so every pytest-xdist worker collects the same tests and only
the worker given this file loads the TPU compiler.  The persistent
compilation cache is off around the compiles (an entry compiled for a
described chip cannot be read back without one).  The kernels are
called with ``interpret=False``: the backend here is the CPU, so the
test steers the mode itself.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

#: smollm-135m published widths
D_MODEL, D_FF, HEADS, KV_HEADS, HEAD_DIM = 576, 1536, 9, 3, 64
GROUP = 32
#: the engine's M tile for a batch of 4 (padded to 8 rows)
M_TILE = 8
#: chip_smoke.py's max_seq and batch
SMOKE_MAX_SEQ, SMOKE_BATCH = 64, 4


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means no TPU compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=sharding)


def _assert_mosaic(fn, *args, **kw):
    compiled = fn.lower(*args, **kw).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("k,n", [(D_MODEL, KV_HEADS * HEAD_DIM),
                                 (D_FF, D_MODEL)])
def test_packed_matmul_int4(one_chip, k, n):
    from repro.kernels.packed_matmul import packed_matmul_call
    from repro.models.quantized import _blocks

    bits = 4
    _assert_mosaic(
        packed_matmul_call,
        _spec((M_TILE, k), jnp.float32, one_chip),
        _spec((k * bits // 32, n), jnp.uint32, one_chip),
        _spec((k // GROUP, n), jnp.bfloat16, one_chip),
        bits=bits, group_size=GROUP, block_m=M_TILE, out_dtype=jnp.float32,
        interpret=False, **_blocks(k, n, GROUP))


@pytest.fixture(scope="module")
def int3_layer():
    """The int3 per-layer stream layout of smollm-135m (planning only)."""
    from repro import api
    from repro.configs import get_config
    from repro.quant import QuantSpec

    cfg = get_config("smollm-135m")
    stack = api.plan_layer_stack(cfg, QuantSpec(bits=3, group_size=GROUP),
                                 n_layers=1)
    return stack.plans[0].layout, stack.exec_program()


def test_stream_matmul_int3(one_chip, int3_layer):
    from repro.core.exec_plan import stream_matmul_tables
    from repro.kernels.stream_matmul import stream_matmul_call, window_operands
    from repro.models.quantized import _blocks

    lay, prog = int3_layer
    k, n = D_MODEL, D_FF
    tabs = stream_matmul_tables(lay, "w_up", (k, n), scales="w_up_scales",
                                group_size=GROUP, program=prog)
    blocks = _blocks(k, n, GROUP)
    ops = window_operands(tabs, blocks["block_n"])
    n_words = prog.c_max * prog.kernel.words32
    _assert_mosaic(
        stream_matmul_call,
        _spec((M_TILE, k), jnp.float32, one_chip),
        _spec((n_words,), jnp.uint32, one_chip),
        *(_spec(ops[key].shape, ops[key].dtype, one_chip)
          for key in ("w_ent", "w_rows", "s_ent", "s_rows")),
        bits=3, group_size=GROUP, n=n, block_m=M_TILE,
        out_dtype=jnp.float32, interpret=False, **blocks)


def _compile_stream_attention(sharding, bits):
    from repro.configs import get_config
    from repro.kvcache import PackedKVCache
    from repro.kvcache.kernels.stream_attention import stream_attention_call
    from repro.kvcache.layout import page_window_tables

    cfg = get_config("smollm-135m")
    kvc = PackedKVCache.create(cfg, bits=bits, page_tokens=8,
                               n_slots=SMOKE_BATCH, max_seq=SMOKE_MAX_SEQ)
    man = kvc.manifest
    tabs = page_window_tables(kvc.program(), page_tokens=8,
                              n_kv_heads=KV_HEADS, head_dim=HEAD_DIM,
                              bits=bits)
    n_ch = -(-man.c_max * man.words32 // 128)
    _assert_mosaic(
        stream_attention_call,
        _spec((SMOKE_BATCH, n_ch, man.n_pages, 128), jnp.uint32, sharding),
        _spec((SMOKE_BATCH, HEADS, 1, HEAD_DIM), jnp.bfloat16, sharding),
        _spec((SMOKE_BATCH,), jnp.int32, sharding),
        *(_spec(tabs[key].shape, np.uint32, sharding)
          for key in ("k", "k_scales", "v", "v_scales")),
        bits=bits, interpret=False)


def test_stream_attention_int3(one_chip):
    """Phase B's served KV width."""
    _compile_stream_attention(one_chip, 3)


def test_stream_attention_int8(one_chip):
    """The KV width of chip_smoke.py's end-to-end phase B check."""
    _compile_stream_attention(one_chip, 8)
