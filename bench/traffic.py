"""Open-loop request schedules on the wall clock, drawn from a seed.

Users arrive independently, so requests are due on a schedule whatever the
server does: a stall delays every later request's first token, and each
request is timed from when it was due, not from when it was sent.

Every seed gets the same work.  The shape of a mix (the lengths of prompts
and outputs, and the gaps between arrivals, in their order) is fixed by the
mix's own ``shape_seed``; the run's seed draws the token ids.  So runs with
different seeds serve requests of the same sizes on the same schedule, and
differ in what the prompts say.  (With the dozen requests a window of the
chat cell holds, letting the seed reorder them moved the TTFT tail by half
from seed to seed, while two runs of one seed agreed within a few
percent.)

A window holds few requests, so its sizes and gaps are not drawn at
random, which would let one draw hold three times the share of long
answers that the law gives: the n lengths are the law's mid-quantiles
(i + 1/2)/n, read off a large sample, and the n gaps the exponential's;
``shape_seed`` draws their order.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List

import numpy as np


def zipf_len(rng, a: float, lo: int, hi: int, scale: int = 1) -> int:
    """Zipf-tailed length in [lo, hi]: most requests short, a heavy tail of
    long ones (copied from the program's ``serve/traffic.py``; ``scale``
    stretches the tail in steps of ``scale`` tokens)."""
    return min(lo + scale * (int(rng.zipf(a)) - 1), hi)


@dataclasses.dataclass(frozen=True)
class Item:
    rid: int
    due_s: float                 # seconds after the window opens
    prompt: tuple
    max_new: int


SAMPLE = 20000
"""Draws from which a length law's quantiles are read."""


def _mid_quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def _lengths(rng, spec: Dict, n: int) -> List[int]:
    """The n mid-quantiles of the Zipf-tailed law of ``spec``, in an order
    drawn from ``rng``."""
    big = np.sort([zipf_len(rng, spec["zipf_a"], spec["lo"], spec["hi"],
                            spec.get("scale", 1)) for _ in range(SAMPLE)])
    picks = big[(_mid_quantiles(n) * SAMPLE).astype(int)]
    return [int(x) for x in rng.permutation(picks)]


def _gaps(rng, rate: float, n: int) -> np.ndarray:
    """The n mid-quantiles of the exponential law of Poisson inter-arrival
    gaps at ``rate``, in an order drawn from ``rng``."""
    return rng.permutation(-np.log1p(-_mid_quantiles(n)) / rate)


def n_requests(mix: Dict, seconds: float) -> int:
    return max(1, int(round(mix["rate_per_s"] * seconds)))


def schedule(mix: Dict, seed: int, seconds: float, vocab: int) -> List[Item]:
    """The requests due in a window of ``seconds``, in due order.

    ``mix`` keys: ``rate_per_s`` (mean Poisson arrival rate), ``shape_seed``,
    ``system_prompt_len`` (tokens every prompt starts with), ``prompt`` and
    ``output`` (each ``{"lo", "hi", "zipf_a", "scale"}``; prompt lengths
    include the system prompt).  The gaps are exponential, rescaled so
    that the last request is due inside the window.
    """
    n = n_requests(mix, seconds)
    shape = np.random.RandomState(int(mix["shape_seed"]))
    gaps = _gaps(shape, mix["rate_per_s"], n)
    plens = _lengths(shape, mix["prompt"], n)
    outs = _lengths(shape, mix["output"], n)

    due = np.cumsum(gaps) * (seconds / (gaps.sum() * (1.0 + 1.0 / n)))
    rng = np.random.default_rng(seed)
    sys_len = int(mix.get("system_prompt_len", 0))
    system = rng.integers(0, vocab, sys_len).tolist()
    items = []
    for rid in range(n):
        plen = max(plens[rid], sys_len + 1)
        body = rng.integers(0, vocab, plen - sys_len).tolist()
        items.append(Item(rid=rid, due_s=float(due[rid]),
                          prompt=tuple(system + body),
                          max_new=int(outs[rid])))
    return items


def batch_tokens(seed: int, step: int, batch: int, seq: int, vocab: int,
                 zipf_a: float):
    """Training batch ``step`` of a seeded Zipf token stream: tokens and
    next-token labels, [batch, seq] int32 each (the program's
    ``data.synthetic_batch`` arithmetic, seeded by any integer)."""
    rng = np.random.default_rng([seed, step])
    raw = rng.zipf(zipf_a, size=(batch, seq + 1))
    toks = ((raw - 1) % vocab).astype(np.int32)
    return toks[:, :-1], toks[:, 1:]
