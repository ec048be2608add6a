import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this proves the distribution config is coherent without real
hardware: `jax.jit(step).lower(**ShapeDtypeStructs).compile()` must succeed
on the production meshes, and the compiled artifact yields
  * memory_analysis()  — per-device bytes (proves it fits),
  * cost_analysis()    — per-device HLO FLOPs / bytes accessed,
  * the collective schedule (parsed from HLO) for the roofline terms.

Results are written as JSON under experiments/dryrun/ and assembled into
EXPERIMENTS.md tables by benchmarks/roofline.py.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2-0.5b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] [--quick]
"""
import argparse
import dataclasses
import json
import re
import time
import traceback
from pathlib import Path

import jax

from repro.configs.base import SHAPES, get_config, list_configs, valid_cells
from repro.launch import compile_cache
from repro.launch.mesh import make_production_mesh
from repro.train.step import (StepOptions, build_prefill_step,
                              build_serve_step, build_train_step, init_state,
                              make_inputs)

COLLECTIVE_RE = re.compile(
    r"\b(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?(?:\.\d+)?\s*=\s*([^\s]+)")
SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s64": 8, "u64": 8,
    "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1, "pred": 1,
    "f8e4m3fn": 1, "f8e5m2": 1, "c64": 8, "c128": 16,
}


def collective_bytes(hlo_text: str):
    """Sum output-operand sizes of every collective op in the compiled HLO."""
    totals = {}
    for m in re.finditer(
            r"=\s*((?:\([^)]*\)|[a-z0-9_\[\],{} ]+?))\s*"
            r"(all-gather|all-reduce|reduce-scatter|all-to-all|"
            r"collective-permute)(?:-start)?\(", hlo_text):
        type_str, op = m.group(1), m.group(2)
        size = 0
        for dt, dims in SHAPE_RE.findall(type_str):
            if dt not in _DTYPE_BYTES:
                continue
            n = 1
            for d in dims.split(","):
                if d:
                    n *= int(d)
            size += n * _DTYPE_BYTES[dt]
        totals[op] = totals.get(op, 0) + size
    return totals


def dryrun_cell(arch: str, shape_name: str, mesh, *, opts=None, verbose=True,
                extra_tag="", cfg_overrides=None):
    """Lower + compile one cell; returns the roofline-input record."""
    cfg = get_config(arch)
    if cfg_overrides:
        cfg = cfg.scaled(**cfg_overrides)
    shape = SHAPES[shape_name]
    if opts is None:
        opts = StepOptions()
    if shape.kind == "train" and opts.microbatches == 1:
        # grad-accumulate so per-microbatch activations fit HBM
        opts = dataclasses.replace(opts, microbatches=8)
    t0 = time.time()
    with jax.set_mesh(mesh):
        if shape.kind == "train":
            fn, in_sh, out_sh = build_train_step(cfg, mesh, shape, opts=opts)
            state_shapes = jax.eval_shape(
                lambda k: init_state(k, cfg, opts, mesh), jax.random.PRNGKey(0))
            args = (state_shapes, make_inputs(cfg, shape))
        elif shape.kind == "prefill":
            fn, in_sh, out_sh = build_prefill_step(cfg, mesh, shape, opts=opts)
            from repro.models import transformer as tf
            params_shapes = jax.eval_shape(
                lambda k: tf.init_params(k, cfg), jax.random.PRNGKey(0))
            cache_shapes = jax.eval_shape(
                lambda: tf.init_cache(cfg, shape.global_batch, shape.seq_len))
            args = (params_shapes, make_inputs(cfg, shape), cache_shapes)
        else:
            fn, in_sh, out_sh = build_serve_step(cfg, mesh, shape, opts=opts)
            from repro.models import transformer as tf
            params_shapes = jax.eval_shape(
                lambda k: tf.init_params(k, cfg), jax.random.PRNGKey(0))
            cache_shapes = jax.eval_shape(
                lambda: tf.init_cache(cfg, shape.global_batch, shape.seq_len))
            args = (params_shapes, make_inputs(cfg, shape), cache_shapes)

        # donate the state/cache so memory_analysis reflects the steady-state
        # aliased buffers (as the real train/serve loops run)
        donate = (0,) if shape.kind == "train" else (2,)
        lowered = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh,
                          donate_argnums=donate).lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        hlo = compiled.as_text()
        coll = collective_bytes(hlo)
        # trip-count-aware accounting (XLA cost_analysis counts loop bodies
        # once — see hlo_accounting; these are the roofline inputs)
        from repro.launch.hlo_accounting import account
        acct = account(hlo)

        grad_wire = None
        if shape.kind == "train":
            # gradient-reduction wire accounting: fp32 ring all-reduce vs
            # the int8-EF exchange (`dist.collectives.ef_psum_tree`,
            # wire="int8").  Analytic, not compiled — the int8 collectives
            # are opt-in (see `ef_psum_tree`), but the wire bytes are a
            # pure function of the param tree and the DP extent, so the 4x
            # shows up in the roofline tables either way.
            from repro.dist import sharding as shd
            from repro.dist.collectives import ef_wire_bytes
            from repro.models import transformer as tf
            ndp = 1
            for a in shd.dp_axes(mesh):
                ndp *= mesh.shape[a]
            pshapes = jax.eval_shape(lambda k: tf.init_params(k, cfg),
                                     jax.random.PRNGKey(0))
            grad_wire = ef_wire_bytes(pshapes, ndp)

    n_dev = 1
    for v in mesh.shape.values():
        n_dev *= v
    record = {
        "arch": arch,
        "shape": shape_name,
        "mesh": dict(mesh.shape),
        "devices": n_dev,
        "tag": extra_tag,
        "flops_per_device": acct["flops"],
        "bytes_accessed_per_device": acct["bytes"],
        "collective_bytes_per_device": acct["collective_bytes"],
        "xla_cost_analysis": {  # raw (loop bodies counted once) for reference
            "flops": cost.get("flops", 0.0),
            "bytes_accessed": cost.get("bytes accessed", 0.0),
            "collective_bytes_once": coll,
        },
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
        },
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
    }
    if grad_wire is not None:
        record["grad_wire"] = grad_wire
    if verbose:
        peak = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
                + mem.output_size_in_bytes - mem.alias_size_in_bytes)
        print(f"[dryrun] {arch} x {shape_name} x {tuple(mesh.shape.values())}"
              f" OK  flops/dev={record['flops_per_device']:.3e}"
              f" mem/dev={peak/2**30:.2f}GiB"
              f" coll={sum(coll.values())/2**20:.1f}MiB"
              f" (lower {t_lower:.1f}s compile {t_compile:.1f}s)")
        print("  memory_analysis:", mem)
    return record


def main():
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--meshes", default="pod1",
                    help="comma list: pod1 (16x16) and/or pod2 (2x16x16)")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--abft", default="off",
                    help="ABFT mode for the protected variant (off|checksum|verify)")
    args = ap.parse_args()

    outdir = Path(args.out)
    outdir.mkdir(parents=True, exist_ok=True)
    mesh_tags = args.meshes.split(",")
    meshes = [(t, make_production_mesh(multi_pod=(t == "pod2")))
              for t in mesh_tags]

    if args.all:
        cells = [(a, s) for a in list_configs() for s in valid_cells(a)]
    else:
        assert args.arch and args.shape, "--arch and --shape (or --all)"
        cells = [(args.arch, args.shape)]

    opts = StepOptions(abft_mode=args.abft)
    failures = []
    for mesh_tag, mesh in meshes:
        for arch, shape in cells:
            tag = f"{arch}__{shape}__{mesh_tag}" + (
                f"__abft-{args.abft}" if args.abft != "off" else "")
            path = outdir / f"{tag}.json"
            try:
                rec = dryrun_cell(arch, shape, mesh, opts=opts, extra_tag=mesh_tag)
                path.write_text(json.dumps(rec, indent=1))
            except Exception as e:  # noqa
                failures.append((tag, repr(e)))
                print(f"[dryrun] {tag} FAILED: {e}")
                traceback.print_exc()
    if failures:
        print(f"\n{len(failures)} FAILURES:")
        for t, e in failures:
            print(" ", t, e)
        raise SystemExit(1)
    print(f"\nall {len(cells) * len(meshes)} cells compiled OK")


if __name__ == "__main__":
    main()
