"""Ahead-of-time compiles of the served Pallas kernels for a TPU v5e.

The TPU compiler is installed with libtpu and compiles for a chip that is
described (``jax.experimental.topologies``), not attached: nothing runs,
but Mosaic refuses here what it would refuse on the chip — an int32
matmul, a block that overflows VMEM, a kernel GSPMD would have to
partition. Interpret-mode tests cannot see any of these.

Widths are the served ones: qwen2.5-14b's paged kv pool (H=40, Hkv=8,
dh=128, 16-token blocks, bf16) for a decode tick (n=1) and a prefill
chunk (n=64), on one chip and head-sharded 4 ways through the
``shard_map`` the engine uses under a mesh, at a 1024-token table and
at the served one (4096 tokens, 32 slots: a flattened grid of up to
8,192 steps, whose step arrays Mosaic keeps in SMEM); and ``wqk_score`` at
whisper-tiny's encoder shape (N=1500, D_aug=385, H=6).

The topology is described inside a module fixture — never at import —
so every pytest-xdist worker collects the same tests and only the worker
that runs this file loads libtpu.
"""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

H, HKV, DH, BS = 40, 8, 128, 16        # qwen2.5-14b attention widths
NB = 2048                              # pool blocks: 32k tokens
NBK = 64                               # table width: 1024-token context
SERVED_NBK, SERVED_SLOTS = 256, 32     # max_len 4096, the served batch
TP = 4
HBM_BYTES = 16 * 10 ** 9               # one v5e chip


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without the chip: keep the cache off
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:             # no libtpu, or no TPU support
            jax.config.update("jax_enable_compilation_cache", was)
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def tp_mesh(topo):
    return Mesh(np.asarray(topo.devices[:TP]).reshape(1, TP),
                ("data", "model"))


def _paged_operands(n, sharding, head_sharding=None, nbk=NBK, slots=8):
    """(q, KVCache pool, tables, blocks_used, qpos) shape structs for a
    decode tick (n=1, ``slots`` slots) or one prefill chunk (n=64, 1
    slot) over a table ``nbk`` blocks wide."""
    from repro.models.attention import KVCache
    B = slots if n == 1 else 1
    hs = head_sharding or sharding

    def sds(shape, dt, sh=sharding):
        return jax.ShapeDtypeStruct(shape, dt, sharding=sh)
    q = sds((B, H, n, DH), jnp.float32, hs)
    pool = KVCache(k=sds((NB, BS, HKV, DH), jnp.bfloat16, hs),
                   v=sds((NB, BS, HKV, DH), jnp.bfloat16, hs))
    return (q, pool, sds((B, nbk), jnp.int32), sds((B,), jnp.int32),
            sds((B, n), jnp.int32))


def _stream(q, pool, tables, used, qpos):
    from repro.models import attention as attn
    return attn._stream_kv(q, pool, tables, used, qpos, None,
                           scale=1 / math.sqrt(DH), softcap=0.0,
                           impl="pallas", interpret=False)


def _compile_one_chip(one_chip, n, **width):
    compiled = jax.jit(_stream).lower(
        *_paged_operands(n, one_chip, **width)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes \
        + mem.output_size_in_bytes < HBM_BYTES


def _compile_four_chips(tp_mesh, n, **width):
    from repro.launch import hlo
    from repro.sharding import act
    rep = NamedSharding(tp_mesh, P())
    args = _paged_operands(n, rep, **width)
    q, pool = args[0], args[1]
    heads = NamedSharding(tp_mesh, P(None, "model"))
    rows = NamedSharding(tp_mesh, P(None, None, "model"))
    q = jax.ShapeDtypeStruct(q.shape, q.dtype, sharding=heads)
    pool = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=rows),
        pool)
    with act.use_mesh(tp_mesh):
        compiled = jax.jit(_stream).lower(q, pool, *args[2:]).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    found = {k: v for c in hlo.collective_counts(text).values()
             for k, v in c.items()}
    assert not found, f"collectives around the sharded kernel: {found}"
    assert compiled.output_shardings.spec == P(None, "model")


@pytest.mark.parametrize("n", [1, 64])
def test_paged_kv_kernel_compiles_on_one_chip(one_chip, n):
    _compile_one_chip(one_chip, n)


@pytest.mark.parametrize("n", [1, 64])
def test_paged_kv_kernel_shard_maps_over_four_chips(tp_mesh, n):
    """Heads on "model", tables replicated: each chip runs the kernel
    on its head-slice (10 query / 2 kv heads) with no communication."""
    _compile_four_chips(tp_mesh, n)


@pytest.mark.parametrize("chips", [1, TP])
@pytest.mark.parametrize("n", [1, 64])
def test_paged_kv_kernel_compiles_at_the_served_width(one_chip, tp_mesh,
                                                      chips, n):
    """The served table (256 blocks) and batch (32 slots): the step
    arrays of the flattened grid hold 8,192 entries per tick."""
    width = dict(nbk=SERVED_NBK, slots=SERVED_SLOTS)
    if chips == 1:
        _compile_one_chip(one_chip, n, **width)
    else:
        _compile_four_chips(tp_mesh, n, **width)


@pytest.mark.parametrize("shape", [(1, 1500, 385, 6), (4, 448, 385, 6)],
                         ids=["whisper-encoder", "whisper-decoder-batch"])
def test_wqk_score_compiles(one_chip, shape):
    """The fused W8A8 score kernel: int8 weight-stationary pass, float32
    second pass (Mosaic takes no int32×int32 matmul)."""
    from repro.kernels.wqk_score import ops
    B, N, D, heads = shape

    def sds(s):
        return jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
    compiled = jax.jit(ops.scores).lower(
        sds((B, N, D)), sds((B, N, D)), sds((heads, D, D))).compile()
    assert "tpu_custom_call" in compiled.as_text()
