"""repro.chaos — declarative fault campaigns over every protection domain.

The paper's claim is *systematic* fault tolerance, not one corrected flip:
for each fault class x protected surface, what fraction is detected,
corrected, missed, or falsely alarmed, and at what recovery cost.  This
package turns the repo's point drills (SDC mid-collective, shard loss in
SUMMA, pod kill in the train CLI) into one queryable surface:

  * `chaos.faults`   — the `FaultSpec` taxonomy + the protection-surface
    registry (domains register themselves; unprotected surfaces are an
    honest ledger, not a silent skip), plus the injector implementations
    (`SDCPlan`/`SDCInjector`/`FailurePlan`/`FailureInjector`, re-exported
    by `repro.ft.failures` for back-compat) and the single `flip_bit` /
    `scatter_delta` injection primitives.
  * `chaos.campaign` — `CampaignRunner` sweeps a `FaultSpace` over an
    `ElasticRuntime` train loop and a drilled `PagedServeEngine` decode,
    classifying every event against a clean golden run.
  * `chaos.report`   — the coverage-matrix artifact (JSON + markdown)
    with the uncovered-surface ledger.

`chaos.faults` is dependency-light (jax/numpy only) so protection-domain
modules can register their surfaces at import time; the heavyweight
campaign/report modules load lazily to keep that edge acyclic.
"""
from repro.chaos.faults import (FailureInjector, FailurePlan, FaultSpace,
                                FaultSpec, SDCInjector, SDCPlan, Surface,
                                ensure_registered, flip_bit, get_surface,
                                register_surface, scatter_delta, surfaces,
                                uncovered_surfaces)

__all__ = [
    "CampaignRunner", "CampaignResult", "FailureInjector", "FailurePlan",
    "FaultSpace", "FaultSpec", "SDCInjector", "SDCPlan", "Surface",
    "ensure_registered", "flip_bit", "get_surface", "register_surface",
    "scatter_delta", "surfaces", "uncovered_surfaces",
]

_LAZY = {"CampaignRunner": "repro.chaos.campaign",
         "CampaignResult": "repro.chaos.campaign"}


def __getattr__(name):
    # campaign imports ft.runtime / serve.engine which import ft.failures
    # which re-exports from chaos.faults — eager import here would cycle
    if name in _LAZY:
        import importlib
        return getattr(importlib.import_module(_LAZY[name]), name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
