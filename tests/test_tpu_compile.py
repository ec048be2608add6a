"""The main path's Pallas kernels compile for a TPU v5e chip.

Interpret mode cannot see what the Mosaic lowering refuses (block shapes off
the (8, 128) tiling, too much VMEM), so each kernel is compiled here with
``interpret=False`` for a *described* v5e chip — no chip needed — and must
come out as a ``tpu_custom_call``.  Shapes are the real ones: qwen2-0.5b's
prefill projections (m = 128 prompt tokens, d 896, d_ff 4864), a multi-tile
SUMMA local block, attention at seq 1024 with head dim 64, and the grouped
expert products of the qwen3-moe-30b-a3b.train-8k cell.  That cell's whole
train step is compiled too, and must fit one v5e beside its state.

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
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without the chip: keep the cache out of it
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


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


# qwen3-moe-30b-a3b.train-8k: 2 x 8192 tokens, top-8, 16 held experts; the
# dropless row buffer of the grouped products at its worst case
MOE_TOKENS, MOE_TOP_K, MOE_HELD = 2 * 8192, 8, 16
MOE_ROWS = -(-(MOE_TOKENS * MOE_TOP_K + MOE_HELD * (kops.GROUPED_BM - 1))
             // kops.GROUPED_BM) * kops.GROUPED_BM


@pytest.mark.parametrize("pas", ["forward", "dx", "dw"])
@pytest.mark.parametrize("k, n", [
    (2048, 2 * 768 + 2),    # gate and up in one product, 2 checksum columns
    (768, 2048 + 2),        # down
])
def test_grouped_kernels_at_the_moe_cell(one_chip, k, n, pas):
    S = lambda shape, dt=jnp.int32: jax.ShapeDtypeStruct(shape, dt,
                                                         sharding=one_chip)
    m, g = MOE_ROWS, MOE_HELD
    lay = kops.GroupedLayout(S((g,)), S((g,)), S((g,)),
                             S((m // kops.GROUPED_BM,)), S((1,)))
    f32, bf16 = jnp.float32, jnp.bfloat16
    if pas == "forward":
        fn = lambda lay, a, b, wn: kops._gmm(lay, a, b, wn, f32, False,
                                             bf16)
        args = (S((m, k), bf16), S((g, k, n), f32), S((n, 2), f32))
    elif pas == "dx":
        fn = lambda lay, a, b: kops._gmm(lay, a, b, None, f32, False, bf16)
        args = (S((m, n), bf16), S((g, n, k), f32))
    else:
        fn = lambda lay, a, d: kops._gmm_dw(lay, a, d, g, f32, False)
        args = (S((m, k), bf16), S((m, n), bf16))
    assert "tpu_custom_call" in _compiled_text(fn, lay, *args)


def test_moe_cell_train_step_fits_one_v5e(topo, monkeypatch):
    """The cell's whole step (`bench/program_moe.py` config, the
    workload's step options) compiled for a v5e: its state and its
    temporaries stay under 15 GiB, the evidence for batch 2."""
    import json
    from pathlib import Path

    from bench.program_moe import model_config
    from repro.configs.base import ShapeConfig
    from repro.dist.sharding import make_mesh
    from repro.train.optimizer import AdamWConfig
    from repro.train.step import StepOptions, build_train_step, init_state
    monkeypatch.setattr(kops, "on_tpu", lambda: True)
    bench = Path(__file__).resolve().parents[1] / "bench"
    conf = json.loads((bench / "configs" / "qwen3-moe-30b-a3b.json")
                      .read_text())
    work = json.loads((bench / "workloads" /
                       "qwen3-moe-30b-a3b.train-8k.json").read_text())
    mix = json.loads((bench / "traffic" / "train-8k.json").read_text())
    cfg = model_config(conf)
    mesh = make_mesh((1, 1), ("data", "model"), devices=topo.devices[:1])
    shape = ShapeConfig("bench", mix["seq_len"], mix["batch"], "train")
    opts = StepOptions(**work["step"])
    step, in_sh, out_sh = build_train_step(
        cfg, mesh, shape, AdamWConfig(**work["adamw"]), opts)
    state = jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        jax.eval_shape(lambda k: init_state(k, cfg, opts),
                       jax.random.PRNGKey(0)), in_sh[0])
    batch = {k: jax.ShapeDtypeStruct((mix["batch"], mix["seq_len"]),
                                     jnp.int32, sharding=in_sh[1][k])
             for k in ("tokens", "labels")}
    compiled = jax.jit(step, in_shardings=in_sh, out_shardings=out_sh,
                       donate_argnums=(0,)).lower(state, batch).compile()
    mem = compiled.memory_analysis()
    state_gib = mem.argument_size_in_bytes / 2**30
    temps_gib = mem.temp_size_in_bytes / 2**30
    print(f"moe cell step: state {state_gib:.2f} GiB, temporaries "
          f"{temps_gib:.2f} GiB")
    assert "abft_grouped_matmul_pallas" in compiled.as_text()
    assert state_gib + temps_gib < 15.0, (state_gib, temps_gib)


@pytest.mark.parametrize("program", ["decode_write", "prefill_write", "zero"])
def test_paged_kv_programs_update_the_pools_in_place(one_chip, program):
    """chat-open's page programs on its pools (32 slots x 2560 tokens,
    pages of 16, qwen2-0.5b's 24 layers of 2 x 64 K/V features): the
    donated pools are updated in place, with no copy of a pool in the
    optimised program (a relayout of the pools costs about 1 GB of copies
    each)."""
    import re

    from repro.serve.paged_kv import serve_kv_write, serve_kv_zero
    S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    pool = (1 + 32 * 2560 // 16, 24, 16, 128)
    pools = (S(pool, jnp.bfloat16), S(pool, jnp.bfloat16))
    i32 = jnp.int32
    if program == "zero":
        low = serve_kv_zero.lower(pools, S((160,), i32))
    else:
        n, src, pages = ((32, (24, 32, 1, 2, 64), 32)
                         if program == "decode_write" else
                         (512, (24, 1, 2560, 2, 64), 33))
        low = serve_kv_write.lower(pools, (S(src, jnp.bfloat16),) * 2,
                                   S((4, n), i32), S((pages,), i32))
    compiled = low.compile()
    shape = "bf16[" + ",".join(map(str, pool)) + "]"
    copies = [line for line in compiled.as_text().splitlines()
              if re.search(r"= \S+ copy(-start)?\(", line) and shape in line]
    assert not copies, copies[:2]
    alias = compiled.memory_analysis().alias_size_in_bytes
    assert alias >= 2 * 2 * 24 * (1 + 32 * 160) * 16 * 128


@pytest.mark.parametrize("program", ["read", "nonzero"])
def test_scrub_programs_read_the_pools_without_a_copy(one_chip, program):
    """The scrub's programs on chat-open's pools: a read of
    ``SCRUB_PAGES`` pages and the free pages' zero check take no copy of
    a pool, and bring home only the pages or one flag a page."""
    import re

    from repro.serve.paged_kv import (SCRUB_PAGES, serve_kv_nonzero,
                                      serve_kv_read)
    S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    pool = (1 + 32 * 2560 // 16, 24, 16, 128)
    pools = (S(pool, jnp.bfloat16), S(pool, jnp.bfloat16))
    if program == "read":
        low = serve_kv_read.lower(pools, S((SCRUB_PAGES,), jnp.int32))
        out = (SCRUB_PAGES,) + pool[1:]
    else:
        low = serve_kv_nonzero.lower(pools)
        out = pool[:1]
    assert [o.shape for o in low.out_info] == [out, out]
    compiled = low.compile()
    shape = "bf16[" + ",".join(map(str, pool)) + "]"
    copies = [line for line in compiled.as_text().splitlines()
              if re.search(r"= \S+ copy(-start)?\(", line) and shape in line]
    assert not copies, copies[:2]
