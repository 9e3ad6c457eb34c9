"""Request and batch generation from a seed: the one general generator
that every traffic file (``bench/workloads/<cell>.json``) parameterises.

``poisson_requests`` follows the program's ``serve/trace.py::
poisson_trace`` (Philox-seeded exponential gaps, log-uniform prompt
lengths, uniform output lengths, first arrival at t=0), copied here so
that a change to the program cannot move the yardstick, and stratified:
the *set* of gaps and lengths is fixed by the traffic file alone (evenly
spaced quantiles of each distribution) and the seed only chooses their
order and the token ids, so every seed offers the same work in another
order.
"""
from __future__ import annotations

import dataclasses
from typing import List

import numpy as np

# Above 2**63 - 1 numpy's Philox key would overflow; seeds are folded.
_SEED_MASK = (1 << 63) - 1


def rng_for(seed: int, *stream: int) -> np.random.Generator:
    """Independent Philox stream per (seed, stream...)."""
    counter = [0] * (4 - len(stream)) + [int(s) for s in stream]
    return np.random.Generator(np.random.Philox(
        key=int(seed) & _SEED_MASK, counter=counter))


@dataclasses.dataclass(frozen=True)
class Request:
    due_s: float                 # scheduled arrival, from the window start
    prompt: np.ndarray           # [L] int32
    max_new_tokens: int


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def poisson_requests(n: int, *, rate: float, vocab_size: int,
                     prompt_len_range, gen_len_range, seed: int,
                     stream: int = 0) -> List[Request]:
    rng = rng_for(seed, 1, stream)
    lo, hi = prompt_len_range
    glo, ghi = gen_len_range
    u = _quantiles(n)
    gaps = rng.permutation(-np.log1p(-u) / rate)
    plens = rng.permutation(np.exp(
        np.log(lo) + u * (np.log(hi + 1) - np.log(lo))))
    glens = rng.permutation(np.floor(glo + u * (ghi - glo + 1)))
    arrivals = np.cumsum(gaps) - gaps[0]            # first request at t=0
    plens = plens.astype(int).clip(lo, hi)
    glens = np.asarray(glens, int).clip(glo, ghi)
    return [Request(due_s=float(arrivals[i]),
                    prompt=rng.integers(0, vocab_size, size=int(plens[i]),
                                        dtype=np.int32),
                    max_new_tokens=int(glens[i]))
            for i in range(n)]


def token_batch(seed: int, step: int, batch: int, seq: int,
                vocab_size: int) -> dict:
    """Training rows for one step: ``batch`` rows of ``seq + 1`` uniform
    token ids, split into inputs and next-token labels. Every step and
    every row differ."""
    x = rng_for(seed, 2, step).integers(0, vocab_size,
                                        size=(batch, seq + 1),
                                        dtype=np.int32)
    return {"tokens": x[:, :-1], "labels": np.ascontiguousarray(x[:, 1:])}
