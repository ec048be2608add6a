"""Operation and byte counts of a mixture-of-experts decoder on one chip's
share of the experts, from its published sizes alone.

The same conventions as `bench.flops` (a multiply-add is two operations,
bytes are each operand read once and each result written once).  Nothing
here reads the program or its routing: the chip's expert work is the
expected share, ``tokens x top_k x experts_held / n_experts`` assignments,
whatever the router did in a given step.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Tuple

from bench import flops

CHECKSUMS = 2
"""Checksum columns of the ABFT encoding (``StepOptions.abft_f``)."""


@dataclasses.dataclass(frozen=True)
class MoEDecoder:
    """The sizes of a pre-norm GQA decoder whose every layer is attention
    plus a routed MoE (Qwen3-MoE style), holding ``experts_held`` of the
    router's ``n_experts`` on this chip."""
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    moe_dff: int
    n_experts: int              # the router's outputs (published count)
    experts_held: int           # held on this chip
    expert_offset: int
    top_k: int
    vocab: int                  # this chip's slice

    @classmethod
    def from_config(cls, c: Dict) -> "MoEDecoder":
        """From the benchmark's configuration file: published keys, with
        ``num_experts`` the experts held and ``published`` the uncut
        counts."""
        d = int(c["hidden_size"])
        h = int(c["num_attention_heads"])
        return cls(layers=int(c["num_hidden_layers"]), d_model=d, heads=h,
                   kv_heads=int(c["num_key_value_heads"]),
                   head_dim=int(c.get("head_dim") or d // h),
                   moe_dff=int(c["moe_intermediate_size"]),
                   n_experts=int(c["published"]["num_experts"]),
                   experts_held=int(c["num_experts"]),
                   expert_offset=int(c.get("expert_offset", 0)),
                   top_k=int(c["num_experts_per_tok"]),
                   vocab=int(c["vocab_size"]))

    def attention_params(self) -> int:
        """Weights of one layer's q, k, v and o projections."""
        q = self.heads * self.head_dim
        kv = self.kv_heads * self.head_dim
        return self.d_model * (q + 2 * kv) + q * self.d_model

    def expert_share(self) -> float:
        """Expected assignments this chip's experts take per token."""
        return self.top_k * self.experts_held / self.n_experts

    def expert_products(self) -> Dict[str, Tuple[int, int]]:
        """(in, out) of each expert product as the step runs it: gate and
        up in one product, then down."""
        return {"gate_up": (self.d_model, 2 * self.moe_dff),
                "down": (self.moe_dff, self.d_model)}

    def forward_parts(self, batch: int, seq: int) -> Dict[str, float]:
        """Forward FLOPs of ``batch`` sequences of ``seq`` tokens, by part:
        attention (projections and causal scores), router, expert (the
        chip's share) and head (over the vocabulary slice)."""
        n = batch * seq
        pos_sum = seq * (seq + 1) // 2
        scores = 4 * pos_sum * self.heads * self.head_dim * batch
        per_assign = 2 * sum(i * o for i, o in
                             self.expert_products().values())
        return {
            "attention": self.layers * (2 * self.attention_params() * n
                                        + scores),
            "router": self.layers * 2 * self.d_model * self.n_experts * n,
            "expert": self.layers * per_assign * n * self.expert_share(),
            "head": 2 * self.d_model * self.vocab * n,
        }

    def train_flops(self, batch: int, seq: int) -> float:
        """Forward plus backward (twice the forward) of one step, with no
        credit for recomputation."""
        return 3 * sum(self.forward_parts(batch, seq).values())

    def expert_passes(self, batch: int, seq: int, in_bytes: int = 2,
                      w_bytes: int = 4, out_bytes: int = 4,
                      f: int = CHECKSUMS) -> List[Tuple[str, float, float]]:
        """(name, FLOPs, bytes) of each grouped expert product a training
        step runs, per layer summed over the layers: the forward and its
        recomputation (the products carry the ``f`` checksum columns and
        the epilogue's residual), dX and dW.  Rows are the chip's expected
        share of the assignments; weights are read once per pass."""
        a = batch * seq * self.expert_share()
        out = []
        for name, (k, n) in self.expert_products().items():
            w = self.experts_held * k * n
            fwd = (flops.gemm_flops(a, k, n + f) + 2 * a * (n + f) * f,
                   a * k * in_bytes + w * w_bytes + a * (n + f) * out_bytes
                   + self.experts_held * k * f * w_bytes)
            dx = (flops.gemm_flops(a, n, k),
                  a * n * out_bytes + w * w_bytes + a * k * in_bytes)
            dw = (flops.gemm_flops(k, a, n),
                  a * k * in_bytes + a * n * out_bytes + w * w_bytes)
            for pas, (fl, by) in (("forward", fwd), ("recompute", fwd),
                                  ("dx", dx), ("dw", dw)):
                out.append((f"{name}.{pas}", self.layers * fl,
                            self.layers * by))
        return out

    def expert_min_seconds(self, batch: int, seq: int, peak_flops: float,
                           peak_bw: float, passes=None) -> float:
        """Least time of a step's grouped expert products (those of
        ``passes``, default all): each the larger of its FLOPs at the peak
        and its bytes at the bandwidth."""
        t = 0.0
        for name, fl, by in self.expert_passes(batch, seq):
            if passes is None or name.split(".")[1] in passes:
                t += flops.roofline_seconds(fl, by, peak_flops, peak_bw)[0]
        return t
