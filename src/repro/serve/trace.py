"""Synthetic request traces for serving benchmarks and the CLI.

Poisson arrivals (exponential inter-arrival gaps at ``rate`` req/s) with
log-uniform-ish mixed prompt/generation lengths — deterministic in the
seed, so benchmark runs are reproducible.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import List

import numpy as np

__all__ = ["TraceEntry", "dense_greedy_reference", "poisson_trace",
           "replay", "run_poisson"]


@functools.lru_cache(maxsize=None)
def _jitted_decode_step():
    import jax

    from repro.models import lm

    return jax.jit(lm.decode_step, static_argnums=3)


def dense_greedy_reference(params, cfg, prompt, max_new: int):
    """Golden reference: dense-cache sequential prefill + greedy decode,
    one request at a time (the legacy serve loop). The token-exactness
    oracle the paged / continuously-batched / preemptive engine is
    checked against in tests and the overload benchmark. The decode
    step is jitted (one compile per cache length), the prefill runs
    once per request op by op."""
    import jax.numpy as jnp

    from repro.models import lm

    toks = np.asarray(prompt)[None, :]
    logits, cache = lm.prefill(params, {"tokens": toks}, cfg,
                               max_len=len(prompt) + max_new,
                               dtype=jnp.float32)
    out = [int(np.argmax(np.asarray(logits[0, -1])))]
    step = _jitted_decode_step()
    for _ in range(max_new - 1):
        lg, cache = step(params, cache,
                         np.asarray([[out[-1]]], np.int32), cfg)
        out.append(int(np.argmax(np.asarray(lg[0]))))
    return out


@dataclasses.dataclass(frozen=True)
class TraceEntry:
    arrival_s: float
    prompt: np.ndarray                 # [L] int32
    max_new_tokens: int


def poisson_trace(num_requests: int, *, rate: float, vocab_size: int,
                  prompt_len_range=(8, 96), gen_len_range=(4, 48),
                  seed: int = 0) -> List[TraceEntry]:
    rng = np.random.Generator(np.random.Philox(key=seed))
    gaps = rng.exponential(1.0 / rate, size=num_requests)
    arrivals = np.cumsum(gaps) - gaps[0]          # first request at t=0
    lo, hi = prompt_len_range
    plens = np.exp(rng.uniform(np.log(lo), np.log(hi + 1),
                               size=num_requests)).astype(int).clip(lo, hi)
    glo, ghi = gen_len_range
    glens = rng.integers(glo, ghi + 1, size=num_requests)
    return [TraceEntry(arrival_s=float(arrivals[i]),
                       prompt=rng.integers(0, vocab_size, size=int(plens[i]),
                                           dtype=np.int32),
                       max_new_tokens=int(glens[i]))
            for i in range(num_requests)]


def run_poisson(cfg, options, *, requests: int, rate: float,
                prompt_max: int, gen_max: int, seed: int = 0,
                eos_id=None, time_scale: float = 1.0, sampling=None,
                params=None, on_engine=None, gen_min: int = 2):
    """Build an Engine for ``cfg``/``options``, replay a Poisson trace
    through it, and return ``(engine, wall_s)`` — the shared body of the
    serving CLI and ``benchmarks/serving.py``. ``sampling`` (a
    :class:`repro.serve.sampling.SamplingParams`) applies to every
    request; ``params`` reuses an existing parameter tree (so two engines
    can be compared on identical weights); ``on_engine(engine)`` runs
    after warmup but before the replay — the hook the CLI uses to attach
    the live ``/metrics`` exporter to the engine's gauge refresher.
    Generation budgets are drawn from ``[gen_min, gen_max]``."""
    import time

    from repro.serve.engine import Engine

    engine = Engine(cfg, params, options=options)
    engine.warmup()        # steady-state numbers, not XLA compile time
    if on_engine is not None:
        on_engine(engine)
    trace = poisson_trace(requests, rate=rate, vocab_size=cfg.vocab_size,
                          prompt_len_range=(4, prompt_max),
                          gen_len_range=(gen_min, gen_max), seed=seed)
    t0 = time.perf_counter()
    replay(engine, trace, eos_id=eos_id, time_scale=time_scale,
           sampling=sampling)
    return engine, time.perf_counter() - t0


def replay(engine, trace: List[TraceEntry], *, eos_id=None,
           time_scale: float = 1.0, sampling=None):
    """Drive ``engine`` through ``trace`` in wall-clock time (arrival
    offsets multiplied by ``time_scale``; 0 submits everything up front).
    Returns the list of submitted Requests (done when this returns)."""
    import time

    t0 = time.perf_counter()
    pending = list(trace)
    requests = []
    kw = {} if sampling is None else {"sampling": sampling}
    while pending or engine.has_work:
        now = time.perf_counter() - t0
        while pending and pending[0].arrival_s * time_scale <= now:
            e = pending.pop(0)
            # latency clocks start at the *scheduled* arrival, so
            # queueing delay accrued while the engine was mid-step is
            # part of the reported percentiles
            requests.append(engine.submit(
                e.prompt, max_new_tokens=e.max_new_tokens, eos_id=eos_id,
                arrival_s=t0 + e.arrival_s * time_scale, **kw))
        if engine.has_work:
            engine.step()
        elif pending:
            # idle until the next scheduled arrival, in one sleep — the
            # 0.05 s cap keeps very long gaps responsive to wall-clock
            # drift without degenerating into a 1 kHz busy-poll
            time.sleep(max(0.0, min(
                0.05, pending[0].arrival_s * time_scale - now)))
    return requests
