"""What a per-layer metric reader is given.

A reader is ``bench/metrics/<metric>.py`` with ``read(ctx) -> float | None``.
It reads the driver's stats of the traced window (``ctx.stats``), the
reduced device trace (``ctx.trace``, a `bench.trace.Summary`) and the
chip's peaks, and returns None when it finds nothing to read: the harness
then leaves the metric out of the result line.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional

from bench.flops import DenseDecoder


@dataclasses.dataclass
class Context:
    cell: object                 # bench.harness.Cell
    stats: Dict
    trace: Optional[object]      # bench.trace.Summary
    peaks: Dict
    metric: str = ""             # the metric being read
    notes: List[str] = dataclasses.field(default_factory=list)

    @property
    def decoder(self) -> DenseDecoder:
        return DenseDecoder.from_config(self.cell.config)

    @property
    def peak_flops(self) -> float:
        return float(self.peaks["bf16_flops_per_s"])

    @property
    def peak_bw(self) -> float:
        return float(self.peaks["hbm_bytes_per_s"])
