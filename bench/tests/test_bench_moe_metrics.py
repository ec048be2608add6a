"""The MoE cell's per-layer readers on synthetic traces and stats."""
import json

import pytest

from bench import harness, trace
from bench.flops_moe import MoEDecoder
from bench.metrics_context import Context
from bench.tests.util import BENCH

PEAKS = harness.load_peaks("TPU v5 lite", BENCH)
CELL = "qwen3-moe-30b-a3b.train-8k"

# kernel ops named by their HLO text, as a v5e trace names them
GROUPED = ("%abft_grouped_matmul_pallas.3 = bf16[135168,1664]{1,0} "
           "custom-call(s32[528]{0} %tg, s32[1]{0} %nu, "
           "bf16[135168,2048]{1,0} %a, f32[16,2048,1664]{2,1,0} %b), "
           "custom_call_target=tpu_custom_call")
GROUPED_DW = ("%abft_grouped_matmul_pallas_dw.1 = f32[16,2048,1664]{2,1,0} "
              "custom-call(s32[528]{0} %tg, s32[1]{0} %nu, "
              "bf16[135168,2048]{1,0} %a, bf16[135168,1664]{1,0} %g), "
              "custom_call_target=tpu_custom_call")
DENSE = ("%abft_matmul_pallas.7 = f32[16384,4864]{1,0} custom-call("
         "f32[16384,2048]{1,0} %a, f32[2048,4864]{1,0} %b, f32[2,16384]{1,0} "
         "%wm, f32[4864,2]{1,0} %wn), custom_call_target=tpu_custom_call")


def _reader(name):
    cell = harness.load_cell(CELL, BENCH)
    return cell, harness.metric_reader(cell, name)


def _summary(ops):
    """A one-device trace over a 1 s window holding ``ops`` (name,
    seconds), back to back."""
    t, out = 0, []
    for name, s in ops:
        out.append(trace.Op(name, t, t + int(s * 1e9), trace._kind(name)))
        t += int(s * 1e9)
    dev = trace.Device("/device:TPU:0", out, [], t, [])
    return trace.Summary(window=(0, 10**9), devices=[dev], spans=[])


STATS = {"steps": 10, "batch": 2, "seq": 8192, "window_s": 20.0,
         "moe_assignments": 10 * 6 * 16384.0,
         "moe_rows_computed": 10 * 6 * 17408.0}


def test_pattern_matches_the_grouped_kernels_and_not_the_dense_one():
    mod = harness.load_module(
        BENCH / "metrics" / "expert_gemm_roofline.moe.py", "m")
    assert mod.KERNEL.match(GROUPED) and mod.KERNEL.match(GROUPED_DW)
    assert not mod.KERNEL.match(DENSE)
    dense = harness.load_module(
        BENCH / "metrics" / "abft_matmul_roofline.train.py", "d")
    assert dense.KERNEL.match(DENSE)
    assert not dense.KERNEL.match(GROUPED)
    assert not dense.KERNEL.match(GROUPED_DW)


def test_expert_gemm_roofline_counts_every_pass_over_the_kernels_time():
    cell, read = _reader("expert_gemm_roofline.moe")
    tr = _summary([(GROUPED, 0.3), (GROUPED_DW, 0.1), (DENSE, 0.5)])
    ctx = Context(cell=cell, stats=STATS, trace=tr, peaks=PEAKS)
    dims = MoEDecoder.from_config(cell.config)
    t_min = 10 * dims.expert_min_seconds(2, 8192, 197e12, 819e9)
    assert read(ctx) == pytest.approx(100 * t_min / 0.4)
    # four passes of two products, each bound by compute or memory
    assert len(dims.expert_passes(2, 8192)) == 8
    assert read(Context(cell=cell, stats=STATS, trace=_summary([(DENSE, .5)]),
                        peaks=PEAKS)) is None
    assert read(Context(cell=cell, stats=STATS, trace=None,
                        peaks=PEAKS)) is None


def test_train_mfu_moe_reads_the_bench_flop_count():
    cell, read = _reader("train_mfu.moe")
    ctx = Context(cell=cell, stats=STATS, trace=None, peaks=PEAKS)
    flops = MoEDecoder.from_config(cell.config).train_flops(2, 8192)
    assert read(ctx) == pytest.approx(100 * 10 * flops / 20.0 / 197e12)
    assert read(Context(cell=cell, stats={"steps": 0}, trace=None,
                        peaks=PEAKS)) is None


def test_expert_gemm_pad_share_from_the_counters():
    cell, read = _reader("expert_gemm_pad_share.moe")
    ctx = Context(cell=cell, stats=STATS, trace=None, peaks=PEAKS)
    assert read(ctx) == pytest.approx(100 * (1 - 16384 / 17408))
    # a program without the counters: nothing to read
    assert read(Context(cell=cell, stats={"steps": 3}, trace=None,
                        peaks=PEAKS)) is None


def test_the_cell_reports_its_metrics():
    cell = harness.load_cell(CELL, BENCH)
    assert [m["name"] for m in cell.end_to_end] == ["setup_s",
                                                    "train_tokens_per_s"]
    assert {m["name"] for m in cell.per_layer} == {
        "train_mfu.moe", "expert_gemm_roofline.moe",
        "expert_gemm_pad_share.moe", "device_idle_share.train",
        "abft_matmul_roofline.train"}
    spec = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    conf = next(c for c in spec["configs"] if c["name"] == cell.config[
        "program_config"])
    assert sorted(conf["reduced"]) == sorted(cell.config["reduced"])
