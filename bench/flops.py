"""Operation and byte counts from shapes: the benchmark's own arithmetic.

Every count here follows from the published sizes of a configuration and
from the shapes of a call, never from the program under test, so a change to
the program cannot change what a roofline or an MFU is measured against.

FLOPs count a multiply-add as two operations.  Bytes are the algorithmic
minimum (each operand read once, each result written once), which gives the
least time a call could take; a kernel that re-reads its operands moves more.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Tuple


@dataclasses.dataclass(frozen=True)
class DenseDecoder:
    """The sizes of a dense pre-norm GQA decoder (Qwen2-style)."""
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int

    @classmethod
    def from_config(cls, c: Dict) -> "DenseDecoder":
        """From a Hugging Face style config.json dict."""
        d = int(c["hidden_size"])
        h = int(c["num_attention_heads"])
        return cls(layers=int(c["num_hidden_layers"]), d_model=d, heads=h,
                   kv_heads=int(c["num_key_value_heads"]),
                   head_dim=int(c.get("head_dim") or d // h),
                   d_ff=int(c["intermediate_size"]),
                   vocab=int(c["vocab_size"]))

    def projections(self) -> Dict[str, Tuple[int, int]]:
        """(in, out) of every per-layer projection."""
        q = self.heads * self.head_dim
        kv = self.kv_heads * self.head_dim
        return {"q": (self.d_model, q), "k": (self.d_model, kv),
                "v": (self.d_model, kv), "o": (q, self.d_model),
                "gate": (self.d_model, self.d_ff),
                "up": (self.d_model, self.d_ff),
                "down": (self.d_ff, self.d_model)}

    def matmul_params(self) -> int:
        """Weights that multiply each token: every layer's projections plus
        the (tied) unembedding.  The embedding gather is not a matmul."""
        per_layer = sum(i * o for i, o in self.projections().values())
        return self.layers * per_layer + self.d_model * self.vocab

    def attention_flops(self, position: int) -> int:
        """Forward score and value FLOPs of one query at 0-based
        ``position`` under a causal mask (it attends to position + 1 keys),
        over all layers."""
        return 4 * (position + 1) * self.heads * self.head_dim * self.layers

    def forward_flops(self, start: int, end: int) -> int:
        """Forward FLOPs of the tokens at positions [start, end) of one
        sequence whose earlier positions are already cached."""
        n = end - start
        # sum over p in [start, end) of (p + 1)
        pos_sum = (end * (end + 1) - start * (start + 1)) // 2
        return (2 * self.matmul_params() * n
                + 4 * pos_sum * self.heads * self.head_dim * self.layers)

    def train_flops(self, batch: int, seq: int) -> int:
        """Forward plus backward (twice the forward) of one step, with no
        credit for recomputation."""
        return 3 * batch * self.forward_flops(0, seq)


def gemm_flops(m: int, k: int, n: int) -> int:
    return 2 * m * k * n


def gemm_bytes(m: int, k: int, n: int, in_bytes: int, out_bytes: int,
               accumulate: bool = False) -> int:
    """A and B read once, C written once (and read once when accumulated)."""
    c = m * n * out_bytes * (2 if accumulate else 1)
    return (m * k + k * n) * in_bytes + c


def checksum_epilogue_flops(m: int, n: int, f: int) -> int:
    """The fused kernels' dual checksums: W_m @ C and C @ W_n."""
    return 2 * 2 * f * m * n


def roofline_seconds(flops: float, nbytes: float, peak_flops: float,
                     peak_bw: float) -> Tuple[float, str]:
    """Least time of a call, and which bound sets it."""
    t_c, t_m = flops / peak_flops, nbytes / peak_bw
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
