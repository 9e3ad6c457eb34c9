"""Sampling for the serving engine: temperature / top-k / top-p + stops.

The decode and prefill step bodies call :func:`sample_tokens` *inside*
jit with per-slot parameter arrays (temperature, top-k, top-p, seed,
position), so the one-compile invariant holds: changing a request's
sampling settings changes traced array *values*, never shapes, and the
whole continuous batch — greedy and sampled slots mixed — runs through
one program. ``temperature <= 0`` means greedy (exact ``argmax``, the
golden-test reference path).

The program holds two branches and picks one per call on device
(``lax.switch`` over the whole batch, outside the per-row ``vmap``,
where a ``cond`` would run every branch), from the values of the
temperatures (:func:`sample_path`, which the engine's host-side
``repro_sample_path_total`` counter shares):

- ``greedy``: every row has ``temperature <= 0``; each row's float32
  ``argmax``, no sort, no softmax, no random bits;
- ``sorted``: some row samples; every row sorts its vocabulary, and a
  sampled row's keep set is decided by rank (``top_k <= 0`` and
  ``top_p`` outside (0, 1) keep the whole vocabulary), a greedy row
  takes its ``argmax``.

A greedy row gets the same token in both branches.

Determinism: each sampled token's PRNG key is
``fold_in(PRNGKey(seed), position)`` where ``position`` is the index of
the token being generated — a pure function of the request, independent
of batch composition, slot assignment or preemption history. The same
request with the same seed emits the same tokens whether it runs alone,
continuously batched with others, or preempted and resumed mid-stream.

Stop sequences are matched host-side against the output suffix
(:func:`stop_hit`), like the ``eos_id`` / ``max_new_tokens`` stops, so
jitted step shapes stay static.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

NEG_INF = -1.0e30
# sample_tokens' branches, by the index sample_path gives
SAMPLE_PATHS = ("greedy", "sorted")

__all__ = ["SAMPLE_PATHS", "SamplingParams", "normalize_stops",
           "sample_path", "sample_tokens", "stop_hit"]


@dataclasses.dataclass(frozen=True)
class SamplingParams:
    """Per-request sampling knobs.

    ``temperature <= 0`` selects greedy decoding (the default); ``top_k
    <= 0`` and ``top_p`` outside (0, 1) disable the respective filter.
    ``seed`` names the request's private PRNG stream.
    """
    temperature: float = 0.0
    top_k: int = 0
    top_p: float = 1.0
    seed: int = 0

    @property
    def greedy(self) -> bool:
        return self.temperature <= 0.0


def sample_path(temperature):
    """Index into :data:`SAMPLE_PATHS` of the branch a batch takes.

    Takes the per-slot temperatures as numpy (the engine's host count)
    or jax (the device branch) alike: 0 when every row is greedy, 1 when
    some row samples."""
    return (~(temperature <= 0.0)).any().astype(np.int32)


def _greedy_row(logits, *_):
    """One slot of an all-greedy batch: its float32 argmax."""
    return jnp.argmax(logits.astype(jnp.float32)).astype(jnp.int32)


def _keep(scaled, top_k, top_p):
    """One row's keep set in the sorted branch: the ``top_k`` highest
    ranks within the smallest prefix whose mass reaches ``top_p``."""
    v = scaled.shape[-1]
    # rank in the descending sort, ties broken by vocab index (argsort
    # is stable): the keep set is decided by rank, never by comparing
    # against a threshold *value* — a value cut keeps every entry tied
    # at the k-th logit, so top_k=1 over equal logits was not argmax
    sort_idx = jnp.argsort(-scaled)
    rank = jnp.zeros((v,), jnp.int32).at[sort_idx].set(
        jnp.arange(v, dtype=jnp.int32))
    order = scaled[sort_idx]                             # descending
    # top-k width (0 => keep all)
    k_eff = jnp.clip(jnp.where(top_k <= 0, v, top_k), 1, v)
    # top-p (nucleus) width: smallest prefix with mass >= top_p
    p_eff = jnp.where((top_p <= 0.0) | (top_p >= 1.0), 1.0, top_p)
    probs = jax.nn.softmax(order)
    below = jnp.cumsum(probs) - probs                    # mass before each
    n_keep = jnp.maximum(jnp.sum(below < p_eff), 1)
    # filter off keeps every token: in float32 the cumulative mass
    # passes 1 before the tail, and below < 1 would drop the tail
    n_keep = jnp.where(p_eff >= 1.0, v, n_keep)
    return rank < jnp.minimum(k_eff, n_keep)


def _sorted_row(logits, temperature, top_k, top_p, seed, position):
    """One slot: masked top-k/top-p categorical sample (or argmax)."""
    lg = logits.astype(jnp.float32)
    greedy = temperature <= 0.0
    scaled = lg / jnp.where(greedy, 1.0, temperature)
    masked = jnp.where(_keep(scaled, top_k, top_p), scaled, NEG_INF)
    key = jax.random.fold_in(jax.random.PRNGKey(seed), position)
    sampled = jax.random.categorical(key, masked)
    return jnp.where(greedy, jnp.argmax(lg), sampled).astype(jnp.int32)


# built once: lax.switch caches its traced branches by function, so an
# eager call outside jit then compiles the conditional once, not per call
_BRANCHES = (jax.vmap(_greedy_row), jax.vmap(_sorted_row))


def sample_tokens(logits, temperature, top_k, top_p, seed, position):
    """Sample one token per slot, jit-stable.

    logits ``[S, V]``; all other args ``[S]`` (float32 temperature/top_p,
    int32 top_k/seed/position). Rows are independent: a slot's token
    depends only on its own logits and sampling state, so batch
    composition cannot perturb it. The branch (:func:`sample_path`) is
    chosen on device for the whole batch. Returns int32 ``[S]``.
    """
    args = (logits, temperature.astype(jnp.float32),
            top_k.astype(jnp.int32), top_p.astype(jnp.float32),
            seed.astype(jnp.int32), position.astype(jnp.int32))
    with jax.named_scope("sample"):
        return jax.lax.switch(sample_path(args[1]), _BRANCHES, *args)


def normalize_stops(stop) -> Tuple[Tuple[int, ...], ...]:
    """Canonicalize stop sequences: tuple of non-empty int tuples."""
    if not stop:
        return ()
    out = []
    for s in stop:
        s = (int(s),) if isinstance(s, int) else tuple(int(t) for t in s)
        if s:
            out.append(s)
    return tuple(out)


def stop_hit(output: Sequence[int],
             stop: Sequence[Sequence[int]]) -> Optional[Tuple[int, ...]]:
    """The stop sequence the output now ends with, or None."""
    for s in stop:
        n = len(s)
        if n and len(output) >= n and tuple(output[-n:]) == tuple(s):
            return tuple(s)
    return None
