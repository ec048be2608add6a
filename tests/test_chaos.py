"""repro.chaos: taxonomy, surface registry, classification, campaigns.

Fast tests cover the pure logic (spec validation, adapters, seeded
sampling, the outcome classifier, the straggler EWMA, the registry).  The
slow tests run REAL single-device campaigns through the live workloads —
the satellite requirements verbatim: a fault into an unprotected surface
must classify as `missed` (not crash, not silently pass) and a clean
sweep must report zero detections.  The multi-pod specs (pod_loss,
slow_pod demotion) run in an 8-host-device subprocess, conftest keeping
the main process at 1 device.
"""
import os
import subprocess
import sys
from pathlib import Path

import pytest

from repro.chaos.campaign import CampaignRunner, classify
from repro.chaos.faults import (FaultSpace, FaultSpec, ensure_registered,
                                flip_bit, get_surface, scatter_delta,
                                uncovered_surfaces)
from repro.ft.runtime import StragglerDetector


# ---------------------------------------------------------------------------
# taxonomy + registry (fast)
# ---------------------------------------------------------------------------


def test_registry_has_every_protection_domain():
    reg = ensure_registered()
    protected = {n for n, s in reg.items() if s.protected}
    assert {"dist.collectives/abft_psum", "kernels.ops/acc_state",
            "ckpt.diskless/shards", "ft.runtime/topology",
            "serve.engine/logits_reduce",
            # the surfaces PR 6 retired from the ledger
            "kernels.flash_attention", "models.layers/layernorm",
            "models.layers/embedding_gather", "state.params_at_rest",
            "state.opt_state_at_rest",
            "serve.engine/kv_cache_at_rest"} <= protected
    for name in protected:
        assert reg[name].detector, name    # a protected domain names its
        #                                    detector or it is a lie


def test_uncovered_ledger_is_retired():
    """The tentpole: the ledger is EMPTY.  Every blind spot it used to
    name — flash attention state, the norm/gather paths, the *_at_rest
    DRAM surfaces — now registers protected with a live detector.  The
    ledger itself survives as a tripwire for future unprotected
    registrations."""
    ensure_registered()
    assert uncovered_surfaces() == []


def test_uncovered_surfaces_self_registers(monkeypatch):
    """Regression (stale-ledger bug): `uncovered_surfaces()` must call
    `ensure_registered()` itself — a report generated before any workload
    import must not see a stale subset of the registry."""
    from repro.chaos import faults
    called = []
    orig = faults.ensure_registered
    monkeypatch.setattr(faults, "ensure_registered",
                        lambda: called.append(True) or orig())
    faults.uncovered_surfaces()
    assert called


def test_registry_upgrade_and_conflict_semantics():
    """Regression (registry downgrade bug): double registration must not
    be last-write-wins.  A protected registration always beats an
    unprotected placeholder regardless of import order; a same-level
    conflict between different owners raises."""
    from repro.chaos.faults import _REGISTRY, register_surface
    name = "test.registry/upgrade"
    try:
        register_surface(name, owner="mod.a", protected=False,
                         note="placeholder")
        # upgrade by the protecting module wins, whatever imported first
        register_surface(name, owner="mod.b", protected=True,
                         promise="tolerance", detector="checksum")
        assert _REGISTRY[name].protected
        assert _REGISTRY[name].owner == "mod.b"
        # the stale placeholder importing later can NOT downgrade it back
        survivor = register_surface(name, owner="mod.a", protected=False,
                                    note="placeholder")
        assert survivor.protected and _REGISTRY[name].protected
        # same-level re-registration by a different owner is a wiring bug
        with pytest.raises(ValueError, match="wiring bug"):
            register_surface(name, owner="mod.c", protected=True,
                             promise="tolerance", detector="other")
        # a module re-registering its OWN surface (reload) replaces it
        register_surface(name, owner="mod.b", protected=True,
                         promise="tolerance", detector="checksum v2")
        assert _REGISTRY[name].detector == "checksum v2"
    finally:
        _REGISTRY.pop(name, None)


def test_registry_unprotected_conflict_raises():
    from repro.chaos.faults import _REGISTRY, register_surface
    name = "test.registry/placeholder"
    try:
        register_surface(name, owner="mod.a", protected=False, note="a")
        with pytest.raises(ValueError, match="wiring bug"):
            register_surface(name, owner="mod.b", protected=False,
                             note="b")
    finally:
        _REGISTRY.pop(name, None)


def test_fault_spec_validates_and_resolves_surface():
    s = FaultSpec(kind="sdc_collective", workload="serve")
    assert s.surface == "serve.engine/logits_reduce"
    assert FaultSpec(kind="sdc_collective", workload="train").surface \
        == "dist.collectives/abft_psum"
    with pytest.raises(ValueError):
        FaultSpec(kind="nope", workload="train")
    with pytest.raises(ValueError):
        FaultSpec(kind="dram_kv_cache", workload="train")  # serve-only


def test_spec_adapters_reach_existing_plans():
    s = FaultSpec(kind="sdc_collective", workload="train", step=3, shard=1,
                  delta=-2e3)
    assert s.sdc_plan().events == ((3, 1, -2e3),)
    f = FaultSpec(kind="shard_loss", workload="train", step=5, shard=2)
    assert f.failure_plan().events == ((5, 2),)
    with pytest.raises(ValueError):
        s.failure_plan()


def test_fault_space_default_spans_the_matrix():
    space = FaultSpace.default()
    kinds = {s.kind for s in space}
    assert len(kinds) >= 6                       # acceptance: >= 6 classes
    workloads = {s.workload for s in space}
    assert workloads == {"train", "serve", "solver"}
    # both pod-loss rungs drilled
    assert {s.variant for s in space if s.kind == "pod_loss"
            and s.workload == "train"} == {"diskless", "disk"}
    # the default space carries the committed episode campaign
    assert space.episodes
    assert {ep.workload for ep in space.episodes} \
        == {"train", "serve", "solver"}


def test_fault_space_cartesian_and_seeded_sample():
    space = FaultSpace.cartesian(steps=(1, 2), deltas=(1e3,))
    # kind-validity filtered: no serve-side shard_loss etc.
    assert all(s.workload in ("train", "serve") for s in space)
    assert any(s.kind == "dram_kv_cache" and s.workload == "serve"
               for s in space)
    sub = space.sample(5, seed=7)
    assert len(sub) == 5
    assert sub.specs == space.sample(5, seed=7).specs   # deterministic
    assert sub.specs != space.sample(5, seed=8).specs


def test_flip_bit_and_scatter_delta_primitives():
    import jax.numpy as jnp
    import numpy as np
    x = jnp.ones((4, 4), jnp.float32)
    y = flip_bit(x, 5, bit=30)
    assert np.asarray(y).flat[5] != 1.0
    assert (np.asarray(y) == 1.0).sum() == 15
    assert np.asarray(flip_bit(y, 5, bit=30)).flat[5] == 1.0  # involution
    d = np.asarray(scatter_delta(4, 2, -3.5))
    assert d.tolist() == [0.0, 0.0, -3.5, 0.0]


def test_ft_failures_backcompat_reexports():
    from repro.chaos import faults as cf
    from repro.ft import failures as ff
    assert ff.flip_bit is cf.flip_bit
    assert ff.SDCPlan is cf.SDCPlan
    assert ff.SDCInjector is cf.SDCInjector
    assert ff.FailurePlan is cf.FailurePlan
    assert ff.FailureInjector is cf.FailureInjector


# ---------------------------------------------------------------------------
# outcome classification (pure; the satellite's truth table)
# ---------------------------------------------------------------------------


def test_classify_truth_table():
    # fault into an UNPROTECTED surface, nothing fires -> missed
    assert classify(injected=True, detected=False, corrected=False,
                    end_state="diverged", promise="none") == "missed"
    # protected, detected + repaired within promise -> corrected
    assert classify(injected=True, detected=True, corrected=True,
                    end_state="bit_identical",
                    promise="bit_identity") == "corrected"
    assert classify(injected=True, detected=True, corrected=True,
                    end_state="within_tol", promise="tolerance") \
        == "corrected"
    # a repair that broke its promise degrades to detected
    assert classify(injected=True, detected=True, corrected=True,
                    end_state="diverged", promise="tolerance") == "detected"
    assert classify(injected=True, detected=True, corrected=True,
                    end_state="within_tol", promise="bit_identity") \
        == "detected"
    # detect-only (kernel checksum-state flip) -> detected
    assert classify(injected=True, detected=True, corrected=False,
                    end_state="bit_identical", promise="tolerance") \
        == "detected"
    # clean sweeps
    assert classify(injected=False, detected=False, corrected=False,
                    end_state="bit_identical", promise="none") == "clean"
    assert classify(injected=False, detected=True, corrected=False,
                    end_state="bit_identical", promise="none") \
        == "false_alarm"


# ---------------------------------------------------------------------------
# straggler EWMA detector (fast, no compile)
# ---------------------------------------------------------------------------


def test_straggler_detector_trips_on_persistent_laggard():
    det = StragglerDetector(4, threshold=2.0, alpha=0.5, warmup=3)
    for i in range(2):
        assert det.observe([0.1, 0.1, 0.1, 0.1]) is None  # warming up
    walls = [0.1, 0.1, 0.35, 0.1]
    tripped = None
    for _ in range(4):
        tripped = det.observe(walls)
        if tripped is not None:
            break
    assert tripped == 2


def test_straggler_detector_ignores_uniform_slowness_and_hiccups():
    det = StragglerDetector(4, threshold=2.0, alpha=0.5, warmup=3)
    # everyone slow together: never trips (median scales too)
    for w in (0.1, 0.2, 0.4, 0.8):
        assert det.observe([w] * 4) is None
    det2 = StragglerDetector(4, threshold=3.0, alpha=0.3, warmup=3)
    for _ in range(5):
        assert det2.observe([0.1, 0.1, 0.1, 0.1]) is None
    # one-off hiccup on pod 1, EWMA-smoothed away
    assert det2.observe([0.1, 0.5, 0.1, 0.1]) is None
    assert det2.observe([0.1, 0.1, 0.1, 0.1]) is None


def test_straggler_detector_single_pod_never_trips():
    det = StragglerDetector(1, threshold=2.0, warmup=1)
    for _ in range(5):
        assert det.observe([9.9]) is None


# ---------------------------------------------------------------------------
# live single-device campaigns (slow)
# ---------------------------------------------------------------------------


def _runner(specs, name="t", **train_kw):
    from repro.chaos.campaign import TrainConfig
    return CampaignRunner(FaultSpace(name, tuple(specs)),
                          train=TrainConfig(steps=4, **train_kw))


@pytest.mark.slow
def test_dram_faults_corrected_by_scrubber():
    """The faults the ledger used to report as honestly `missed` are now
    caught by the at-rest scrubber: checksum-on-write at the diskless
    encode, verify-on-read before the next step, snapshot rollback on a
    trip — never missed, and the scrub clean sweep shows no false
    alarms."""
    res = _runner([
        FaultSpec(kind="dram_params", workload="train", step=1, bit=30),
        FaultSpec(kind="dram_opt_state", workload="train", step=2, bit=29),
    ]).run(workloads=("train",))
    for ev in [r for r in res.results if r.kind.startswith("dram")]:
        assert ev.outcome == "corrected", (ev.name, ev.outcome, ev.note)
        assert ev.protected and ev.rung == "scrub:diskless"
        assert ev.end_state in ("bit_identical", "within_tol")
        assert ev.recovery_latency_s is not None
    (sweep,) = [r for r in res.results
                if r.kind == "clean_sweep"
                and r.surface == "state.params_at_rest"]
    assert sweep.outcome == "clean"
    d = res.to_dict()
    assert d["summary"]["missed_anywhere"] == []
    assert d["summary"]["false_alarms"] == []
    assert d["uncovered_surfaces"] == []   # the ledger stays retired


def test_flash_and_layer_detectors_fire():
    """Every newly protected kernel/layer surface fires its detector
    under its campaign drill and repairs within its promise — corrected,
    never missed.  (Handlers invoked directly: no golden train compile.)"""
    for spec in (
        FaultSpec(kind="flash_state_flip", workload="train", step=1),
        FaultSpec(kind="flash_state_flip", workload="train", step=1,
                  variant="l"),
        FaultSpec(kind="norm_corruption", workload="train", step=2),
        FaultSpec(kind="gather_corruption", workload="train", step=2),
    ):
        ev = _runner([spec])._run_spec(spec)
        assert ev.outcome == "corrected", (spec.kind, ev.outcome, ev.note)
        assert ev.detected and ev.corrected and ev.protected
        assert ev.rung in ("flash:recompute_tile", "recompute")


@pytest.mark.slow
def test_invariant_checks_wire_through_train_step():
    """StepOptions.invariant_checks threads the layer invariants through
    the jitted forward and surfaces their AND as metrics["inv_ok"] — 1.0
    on a clean step, composing with microbatches + remat."""
    import jax
    import jax.numpy as jnp
    from repro.configs.base import ShapeConfig, smoke_config
    from repro.data.pipeline import DataConfig, synthetic_batch
    from repro.train.optimizer import AdamWConfig
    from repro.train.step import StepOptions, build_train_step, init_state
    from repro.dist.sharding import make_mesh

    mesh = make_mesh((1, 1), ("data", "model"))
    cfg = smoke_config("qwen2-0.5b")
    shape = ShapeConfig("t", 32, 4, "train")
    opts = StepOptions(microbatches=2, remat=True, invariant_checks=True)
    with jax.set_mesh(mesh):
        fn, in_sh, out_sh = build_train_step(
            cfg, mesh, shape, AdamWConfig(lr=1e-3, total_steps=4), opts)
        jit_fn = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh)
        state = jax.device_put(init_state(jax.random.PRNGKey(0), cfg, opts),
                               in_sh[0])
        batch = jax.device_put(
            {k: jnp.asarray(v) for k, v in
             synthetic_batch(DataConfig(cfg.vocab_size, 32, 4), 0).items()},
            in_sh[1])
        _, m = jit_fn(state, batch)
        assert float(m["inv_ok"]) == 1.0, dict(m)


def test_invariant_checks_reject_deferred_grad_reduce():
    """The invariant flag rides the standard grad path; combining it with
    defer_grad_reduce is a wiring error and must fail loudly."""
    import jax
    from repro.configs.base import ShapeConfig, smoke_config
    from repro.train.optimizer import AdamWConfig
    from repro.train.step import StepOptions, build_train_step
    from repro.dist.sharding import make_mesh

    mesh = make_mesh((1, 1), ("data", "model"))
    with pytest.raises(ValueError, match="invariant_checks"):
        build_train_step(
            smoke_config("qwen2-0.5b"), mesh, ShapeConfig("t", 32, 4,
                                                          "train"),
            AdamWConfig(lr=1e-3, total_steps=4),
            StepOptions(invariant_checks=True, defer_grad_reduce=True))


@pytest.mark.slow
def test_serve_scrubber_repairs_kv_and_params():
    """Serve-side at-rest protection: a KV-cache flip is located to its
    slot and rebuilt by the erasure solve; a params flip is restored from
    the origin copy — both with the emitted token stream bit-identical to
    the clean run."""
    res = _runner([
        FaultSpec(kind="dram_kv_cache", workload="serve", step=2, bit=30),
        FaultSpec(kind="dram_params", workload="serve", step=0, bit=30),
    ]).run(workloads=("serve",))
    by = {r.name: r for r in res.results if r.spec is not None}
    kv = by["serve:dram_kv_cache:s2"]
    assert kv.outcome == "corrected" and kv.rung == "scrub:kv_repair"
    assert kv.end_state == "bit_identical"
    pp = by["serve:dram_params:s0"]
    assert pp.outcome == "corrected" and pp.rung == "scrub:restore"
    assert pp.end_state == "bit_identical"
    sweeps = [r for r in res.results if r.kind == "clean_sweep"
              and r.surface == "serve.engine/kv_cache_at_rest"]
    assert sweeps and all(s.outcome == "clean" for s in sweeps)


@pytest.mark.slow
def test_clean_sweep_reports_zero_detections():
    """Satellite requirement verbatim: a clean sweep (no injections at
    all) must report zero detections — the false-alarm regression."""
    res = _runner([]).run()
    assert res.results, "clean sweeps must still run"
    for r in res.results:
        assert r.kind == "clean_sweep"
        assert r.outcome == "clean", r
        assert not r.detected
    d = res.to_dict()
    assert d["summary"]["false_alarms"] == []
    assert d["summary"]["by_outcome"]["clean"] == len(res.results)


@pytest.mark.slow
def test_protected_sdc_corrected_on_both_workloads():
    res = _runner([
        FaultSpec(kind="sdc_collective", workload="train", step=2,
                  shard=0, delta=1e4),
        FaultSpec(kind="sdc_collective", workload="serve", step=1,
                  shard=0, delta=1e4),
    ]).run()
    by = {r.name: r for r in res.results if r.spec is not None}
    tr = by["train:sdc_collective:s2"]
    assert tr.outcome == "corrected" and tr.rung == "abft_inflight"
    assert tr.max_abs_diff is not None and tr.max_abs_diff < 1e-2
    sv = by["serve:sdc_collective:s1"]
    assert sv.outcome == "corrected" and sv.rung == "abft_inflight"
    assert sv.end_state == "bit_identical"   # token stream promise
    assert sv.recovery_latency_s is not None


def test_kernel_checksum_state_flip_is_detect_only():
    """A flip in the CARRIED CHECKSUM STATE (not the data) must be
    detected but NOT repaired — repairing off a corrupted checksum would
    corrupt healthy data — and the data must pass through bit-identical.
    (Handler invoked directly: the kernel drill needs no golden compile.)"""
    spec = FaultSpec(kind="checksum_state_flip", workload="train", step=1,
                     bit=30)
    ev = _runner([spec])._run_spec(spec)
    assert ev.outcome == "detected"
    assert ev.detected and not ev.corrected
    assert ev.end_state == "bit_identical"


@pytest.mark.slow
def test_shard_loss_recovers_through_diskless_rung():
    res = _runner([FaultSpec(kind="shard_loss", workload="train", step=2,
                             shard=0)]).run(workloads=("train",))
    (ev,) = [r for r in res.results if r.kind == "shard_loss"]
    assert ev.outcome == "corrected"
    assert ev.rung == "diskless"
    assert ev.recovery_latency_s is not None and ev.recovery_latency_s > 0
    assert ev.end_state in ("bit_identical", "within_tol")


# ---------------------------------------------------------------------------
# multi-pod campaign: pod loss (both rungs) + slow-pod demotion (subprocess)
# ---------------------------------------------------------------------------

POD_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
from repro.chaos.campaign import CampaignRunner, TrainConfig
from repro.chaos.faults import FaultSpace, FaultSpec

space = FaultSpace("pods", (
    FaultSpec(kind="pod_loss", workload="train", step=3,
              variant="diskless"),
    FaultSpec(kind="pod_loss", workload="train", step=3, variant="disk",
              seed=1),
    FaultSpec(kind="slow_pod", workload="train", step=1, delay_s=0.05),
))
res = CampaignRunner(space, train=TrainConfig(steps=6)).run(
    workloads=("train",))
by = {r.name: r for r in res.results if r.spec is not None}

dl = by["train:pod_loss:s3:diskless"]
assert dl.outcome == "corrected", dl
assert dl.rung == "elastic:diskless", dl
assert dl.recovery_latency_s is not None and dl.recovery_latency_s > 0

dk = by["train:pod_loss:s3:disk:seed1"]
assert dk.outcome == "corrected", dk
assert dk.rung == "elastic:disk", dk

sp = by["train:slow_pod:s1"]
assert sp.outcome == "corrected", sp        # EWMA tripped AND demoted
assert sp.rung is not None and sp.rung.startswith("demote:"), sp
assert "EWMA tripped" in sp.note, sp

summ = res.to_dict()["summary"]
assert summ["missed_in_protected_domains"] == [], summ
assert summ["false_alarms"] == [], summ
assert summ["by_outcome"]["skipped"] == 0, summ
print("CHAOS_POD_CAMPAIGN_OK")
"""


@pytest.mark.slow
def test_multi_pod_campaign_subprocess():
    env = dict(os.environ)
    env["PYTHONPATH"] = str(Path(__file__).parent.parent / "src") + (
        os.pathsep + env["PYTHONPATH"] if "PYTHONPATH" in env else "")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", POD_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=1200)
    assert out.returncode == 0, f"stdout:\n{out.stdout}\nstderr:\n{out.stderr}"
    assert "CHAOS_POD_CAMPAIGN_OK" in out.stdout
