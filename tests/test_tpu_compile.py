"""The main path's Pallas kernels compile for a TPU v5e chip.

Interpret mode cannot see what the Mosaic lowering refuses (block shapes off
the (8, 128) tiling, too much VMEM), so each kernel is compiled here with
``interpret=False`` for a *described* v5e chip — no chip needed — and must
come out as a ``tpu_custom_call``.  Shapes are the real ones: qwen2-0.5b's
prefill projections (m = 128 prompt tokens, d 896, d_ff 4864), a multi-tile
SUMMA local block, and attention at seq 1024 with head dim 64.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library at a time, and a test file that loads
it while being collected breaks collection on every other xdist worker.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops as kops
from repro.kernels.abft_matmul import (abft_matmul_acc_pallas,
                                      abft_matmul_pallas)
from repro.kernels.flash_attention import _flash_call, flash_attention_pallas


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without the chip: keep the cache out of it
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compiled_text(fn, *structs):
    return jax.jit(fn).lower(*structs).compile().as_text()


@pytest.mark.parametrize("k, n", [
    (896, 896 + 2),      # q/o projection + 2 checksum columns
    (896, 4864 + 2),     # gate/up projection
    (4864, 896 + 2),     # down projection
])
def test_oneshot_kernel_at_prefill_projection(one_chip, k, n):
    m, dtype = 128, jnp.bfloat16
    plan = kops.pick_blocks(m, k, n, in_dtype=dtype, out_bytes=4)
    assert plan is not None and plan.waste <= 0.25, plan
    S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    text = _compiled_text(
        lambda a, b, wm, wn: abft_matmul_pallas(
            a, b, wm, wn, bm=plan.bm, bn=plan.bn, bk=plan.bk,
            out_dtype=jnp.float32, interpret=False),
        S((plan.pm, plan.pk), dtype), S((plan.pk, plan.pn), dtype),
        S((kops.KERNEL_F, plan.pm), jnp.float32),
        S((plan.pn, kops.KERNEL_F), jnp.float32))
    assert "tpu_custom_call" in text


def test_accumulate_kernel_at_multi_tile_summa_block(one_chip):
    # the SUMMA local update: exactly tiled, several tiles along m AND n
    m = k = n = 2048
    plan = kops.pick_blocks(m, k, n, carry=True, require_exact=True)
    assert plan is not None and plan.grid[0] > 1 and plan.grid[1] > 1, plan
    f = kops.KERNEL_F
    mt, nt = m // plan.bm, n // plan.bn
    S = lambda shape: jax.ShapeDtypeStruct(shape, jnp.float32,
                                           sharding=one_chip)
    text = _compiled_text(
        lambda a, b, c, ccol, crow, wm, wn: abft_matmul_acc_pallas(
            a, b, c, ccol, crow, wm, wn, bm=plan.bm, bn=plan.bn,
            bk=plan.bk, interpret=False),
        S((m, k)), S((k, n)), S((m, n)), S((mt, f, n)), S((nt, m, f)),
        S((f, m)), S((n, f)))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("checked", [False, True])
def test_flash_kernel_at_seq_1024(one_chip, checked):
    bh, seq, d = 14, 1024, 64
    S = jax.ShapeDtypeStruct((bh, seq, d), jnp.bfloat16, sharding=one_chip)
    if checked:
        # flash_attention_checked reads the stats on the host: compile the
        # kernel call it makes
        fn = lambda q, k, v: _flash_call(
            q, k, v, scale=d ** -0.5, causal=True, window=None,
            softcap=None, bq=256, bk=256, interpret=False, checksum=True,
            inject=None)
    else:
        fn = lambda q, k, v: flash_attention_pallas(
            q, k, v, scale=d ** -0.5, interpret=False)
    assert "tpu_custom_call" in _compiled_text(fn, S, S, S)
