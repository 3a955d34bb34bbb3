"""Compile rehearsals for a TPU v5e that is described, not attached.

The kernels of the served path are lowered and compiled by the TPU
compiler at qwen2-7b widths (B 8, 28 query / 4 KV heads, head_dim 128,
page_size 16): what the chip's compiler would refuse fails here, at no
chip time. Nothing runs, so these say nothing about results or speed.

The topology is described inside a module fixture (never at import, in
``skipif`` or in ``parametrize``): only one process at a time may load
the TPU library, and it keeps it until it exits. The persistent
compilation cache is off around these compiles — a TPU program written
there cannot be read back without a chip.
"""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops
from repro.kernels.flash_attention import flash_attention
from repro.kernels.paged_decode_attention import (
    paged_decode_attention_kernel, paged_window_attention_kernel)
from repro.sharding.api import ShardCtx

B, HQ, HKV, DH, PS, P_SLOT, N_PAGES = 8, 28, 4, 128, 16, 9, 73


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means: cannot describe
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _paged_args(sh, q_shape, pool_sh=None, n_pg=P_SLOT):
    rows = q_shape[0]
    pool = (max(N_PAGES, (rows - 1) * n_pg + 1), HKV, PS, DH)
    return (_sds(q_shape, jnp.bfloat16, sh),
            _sds(pool, jnp.bfloat16, pool_sh or sh),
            _sds(pool, jnp.bfloat16, pool_sh or sh),
            _sds((rows, n_pg), jnp.int32, sh), _sds((rows,), jnp.int32, sh))


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


def test_paged_decode_kernel_compiles_for_v5e(one_chip):
    args = _paged_args(one_chip, (B, HQ, DH))
    _assert_kernel(jax.jit(paged_decode_attention_kernel)
                   .lower(*args).compile())


@pytest.mark.parametrize("rows,n_pg", [(17, 96), (9, 130)])
def test_paged_decode_kernel_compiles_at_deepest_buckets(one_chip, rows,
                                                         n_pg):
    """The chat and longdoc cells' deepest kv buckets (1536 and 2080
    entries) over their max_batch + 1 rows: 32 pages a grid step, the
    last block of the 130-page table holding two."""
    args = _paged_args(one_chip, (rows, HQ, DH), n_pg=n_pg)
    _assert_kernel(jax.jit(paged_decode_attention_kernel)
                   .lower(*args).compile())


def test_paged_window_kernel_compiles_for_v5e(one_chip):
    """W = 4: the speculative-verify / prefix tail-prefill dispatch."""
    args = _paged_args(one_chip, (B, 4, HQ, DH))
    _assert_kernel(jax.jit(paged_window_attention_kernel)
                   .lower(*args).compile())


def test_flash_prefill_kernel_compiles_for_v5e(one_chip):
    """(B, H, S, dh) kernel layout: a 512-token prefill, 28/4 heads."""
    q = _sds((2, HQ, 512, DH), jnp.bfloat16, one_chip)
    kv = _sds((2, HKV, 512, DH), jnp.bfloat16, one_chip)
    _assert_kernel(jax.jit(flash_attention).lower(q, kv, kv).compile())


def _compile_on_mesh(topo, monkeypatch, replicas, tp, n_pg):
    """(replicas, tp) serving meshes: the pool split over KV heads, the
    batch rows over replicas, the kernel run per shard under shard_map, as
    the serving runtime commits them."""
    mesh = Mesh(np.array(topo.devices).reshape(replicas, tp),
                ("data", "model"))
    ctx = ShardCtx(mesh)
    rows = NamedSharding(mesh, P("data"))
    pool_sh = NamedSharding(mesh, P(None, "model"))
    args = _paged_args(rows, (B, 1, HQ, DH), pool_sh, n_pg=n_pg)
    # the ops layer asks the process's own backend (the CPU here) whether
    # to interpret; this compile is for the described TPU
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    monkeypatch.setattr(ops, "resolved_mode", lambda: "pallas")

    def step(q, kp, vp, pages, lengths):
        return ops.decode_attention_paged(q, kp, vp, pages, lengths,
                                          kv_bucket=n_pg * PS,
                                          page_size=PS, ctx=ctx)

    compiled = jax.jit(step).lower(*args).compile()
    _assert_kernel(compiled)
    # each chip attends its own rows over its own KV heads: nothing is
    # gathered, neither the pool nor the queries
    assert "all-gather" not in compiled.as_text()


@pytest.mark.parametrize("replicas,tp", [(1, 4), (2, 2)])
def test_paged_kernel_compiles_on_4_chip_mesh(topo, monkeypatch, replicas,
                                              tp):
    _compile_on_mesh(topo, monkeypatch, replicas, tp, P_SLOT)


@pytest.mark.parametrize("replicas,tp", [(1, 4), (2, 2)])
def test_paged_kernel_compiles_on_4_chip_mesh_deepest_bucket(
        topo, monkeypatch, replicas, tp):
    """The chat cell's deepest bucket on a mesh: each shard's page is
    smaller (1 or 2 KV heads), so it covers more pages a grid step."""
    _compile_on_mesh(topo, monkeypatch, replicas, tp, 96)
