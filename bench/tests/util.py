"""A tiny copy of the benchmark for CPU tests.

`tiny_bench` copies ``bench/`` and ``BENCHMARK.json`` into a temporary
checkout and adds cells at a size a test run can hold, built from the same
drivers, references and comparison as the real cells.  `run_cell` drives a
whole run of such a cell through `bench.harness.run` on the CPU: the test,
not the program, steps past the harness's look for a chip.
"""
from __future__ import annotations

import io
import json
import shutil
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
REPO = BENCH.parent

TINY_MODEL = dict(hidden_size=64, intermediate_size=128, num_hidden_layers=2,
                  num_attention_heads=4, num_key_value_heads=2,
                  vocab_size=512, torch_dtype="bfloat16")

# cell -> (real cell it stands for, workload, config, traffic)
TINY = {
    "tiny.chat": ("qwen2-0.5b.chat-open", {
        "config": "tiny-qwen2-f32", "traffic": "tiny-chat", "chips": 1,
        "driver": "serve_paged",
        "engine": {"slots": 4, "max_len": 96, "page_size": 16,
                   "chunk_prefill": 32, "prefix_cache": True,
                   "abft_mode": "verify", "abft_reduce": "correct",
                   "scrub_every": 16},
        "check_tokens": 300,
        # a float32 program at this size serves the reference's argmax
        # (widest gap 0 on every seed read); the int8 control serves
        # other tokens at gaps of 9e-4 to 5e-2, an altered token 0.5
        "limits": {"logit_gap": 5e-4}},
        {"kind": "open_loop", "rate_per_s": 12.0, "shape_seed": 3,
         "system_prompt_len": 16,
         "prompt": {"lo": 24, "hi": 64, "zipf_a": 1.5, "scale": 8},
         "output": {"lo": 4, "hi": 24, "zipf_a": 1.5, "scale": 4}}),
    "tiny.train": ("qwen2-0.5b.train-2k",
                   # bf16 at this size: sound runs read loss 1.2e-4, grad
                   # 3.4e-3, change 1.0e-2 at most; the int8 control and
                   # each fault read above these limits in one number
                   {"limits": {"loss_gap": 5e-4, "grad1_gap": 0.02,
                               "change_gap": 0.05}},
                   {"kind": "batches", "seq_len": 32, "batch": 2,
                    "zipf_a": 1.2}),
}


def tiny_bench(tmp: Path) -> Path:
    """A checkout under ``tmp`` holding the benchmark plus the tiny cells;
    returns its ``bench`` directory."""
    bench = tmp / "bench"
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns(
        "__pycache__", "tests"))
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    conf = json.loads((BENCH / "configs" / "qwen2-0.5b.json").read_text())
    conf.update(TINY_MODEL)
    (bench / "configs" / "tiny-qwen2.json").write_text(json.dumps(conf))
    conf["torch_dtype"] = "float32"
    (bench / "configs" / "tiny-qwen2-f32.json").write_text(json.dumps(conf))
    for name, (real, work, mix) in TINY.items():
        if "config" not in work:
            base = json.loads(
                (BENCH / "workloads" / f"{real}.json").read_text())
            base["config"] = "tiny-qwen2"
            work = dict(base, **work)
        work["traffic"] = f"traffic-{name}"
        (bench / "workloads" / f"{name}.json").write_text(json.dumps(work))
        (bench / "traffic" / f"traffic-{name}.json").write_text(
            json.dumps(mix))
        for m in spec["end_to_end"] + spec["per_layer"]:
            if real in m.get("workloads", ()):
                m["workloads"].append(name)
    (tmp / "BENCHMARK.json").write_text(json.dumps(spec))
    return bench


def run_cell(bench: Path, name: str, seed: int = 1, seconds: float = 1.5,
             trace: bool = False, hook=None) -> dict:
    """One run of a cell on the CPU; ``hook(driver_module, driver)`` plants
    a control or a fault.  Returns the result line."""
    import jax
    from bench import harness
    real_module = harness.driver_module

    class Planted:
        def __init__(self, mod):
            self.mod = mod

        def Driver(self, *a):            # noqa: N802 (the module's name)
            drv = self.mod.Driver(*a)
            if hook is not None:
                hook(self.mod, drv)
            return drv

    out = io.StringIO()
    saved = (harness.enable_compile_cache, harness.driver_module,
             harness.load_peaks)
    real_peaks = harness.load_peaks
    harness.enable_compile_cache = lambda: "off"
    harness.driver_module = lambda cell: Planted(real_module(cell))
    # a CPU has no entry in the table of peaks: borrow the v5e's so that a
    # traced run's readers run (their numbers mean nothing here)
    harness.load_peaks = lambda kind, b: real_peaks("TPU v5 lite", b)
    try:
        rc = harness.run(name, seed, seconds, trace, bench=bench,
                         find=lambda n: jax.devices()[:n], out=out)
    finally:
        (harness.enable_compile_cache, harness.driver_module,
         harness.load_peaks) = saved
    assert rc == 0
    return json.loads(out.getvalue().strip().splitlines()[-1])


def control(mod, drv):
    mod.control(drv)


def fault(name):
    return lambda mod, drv: mod.FAULTS[name](drv)
