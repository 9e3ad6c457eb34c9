"""Serving engine (repro.serve): golden-token equivalence vs the dense
sequential loop, paged gather/scatter correctness, KV page accounting,
admission control, stop conditions and hw-spec resolution."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import CPU_HOST, TPU_V5E, resolve_hw
from repro.models import kv_cache, lm
from repro.models.api import serving_support
from repro.serve import (Engine, EngineOptions, RequestState,
                         dense_greedy_reference as ref_decode)

PROMPT_LENS = (13, 29, 7, 21, 5)
MAX_NEW = (6, 4, 8, 5, 7)


def _cfg(name):
    cfg = get_config(name).reduced()
    moe = cfg.moe
    if moe is not None:
        # generous capacity => no dropped tokens => the MoE layer is a
        # per-token function and chunked prefill is exact (the invariant
        # the golden test relies on)
        moe = dataclasses.replace(moe, capacity_factor=8.0)
    return dataclasses.replace(cfg, compute_dtype="float32", moe=moe)


@pytest.fixture(scope="module", params=["llama3-8b", "moe-gpt3-s"])
def setup(request):
    cfg = _cfg(request.param)
    params = lm.init(cfg, jax.random.PRNGKey(0))
    rng = np.random.Generator(np.random.Philox(key=7))
    prompts = [rng.integers(0, cfg.vocab_size, size=n, dtype=np.int32)
               for n in PROMPT_LENS]
    refs = [ref_decode(params, cfg, p, m)
            for p, m in zip(prompts, MAX_NEW)]
    return cfg, params, prompts, refs


def _engine(cfg, params, **over):
    kw = dict(page_size=4, max_slots=3, max_seq_len=64, chunk=16,
              min_bucket=8)
    kw.update(over)
    return Engine(cfg, params, options=EngineOptions(**kw))


# ---------------------------------------------------------------------------
# Golden-token equivalence (the tentpole invariant)
# ---------------------------------------------------------------------------

def test_golden_token_equivalence(setup):
    """Continuous batching + paged KV + chunked prefill emits exactly the
    greedy tokens of the dense sequential loop — under slot pressure, so
    slots (and their pages) are reused across requests."""
    cfg, params, prompts, refs = setup
    eng = _engine(cfg, params)
    assert eng.kv.max_slots < len(prompts)      # force queueing + reuse
    for p, m in zip(prompts, MAX_NEW):
        eng.submit(p, max_new_tokens=m, arrival_s=0.0)
    eng.run_until_idle()
    outs = [r.output for r in sorted(eng.done, key=lambda r: r.rid)]
    assert outs == refs
    # every request covered >1 prefill bucket across the mixed lengths
    assert len(eng.adaptive.resolutions) >= 2
    if cfg.moe is not None:
        for bucket, (n, strat) in eng.adaptive.resolutions.items():
            assert n >= 1 and strat in ("none", "s1", "s2", "s3", "s4")


def test_golden_token_equivalence_windowed():
    """Sliding-window layers (gemma3 5:1 local:global) through the paged
    path: the position-contiguous gathered view + window masking must
    match the dense ring-buffer reference, including after the sequence
    length passes the window."""
    cfg = _cfg("gemma3-12b")
    assert cfg.attn.window > 0
    params = lm.init(cfg, jax.random.PRNGKey(0))
    rng = np.random.Generator(np.random.Philox(key=11))
    prompts = [rng.integers(0, cfg.vocab_size, size=n, dtype=np.int32)
               for n in (29, 9)]
    max_new = (6, 5)
    assert max(len(p) + m for p, m in zip(prompts, max_new)) \
        > cfg.attn.window                        # ring wraps in the ref
    refs = [ref_decode(params, cfg, p, m)
            for p, m in zip(prompts, max_new)]
    eng = _engine(cfg, params, max_slots=2)
    for p, m in zip(prompts, max_new):
        eng.submit(p, max_new_tokens=m, arrival_s=0.0)
    eng.run_until_idle()
    outs = [r.output for r in sorted(eng.done, key=lambda r: r.rid)]
    assert outs == refs


def test_eos_early_exit_and_length_stop(setup):
    cfg, params, prompts, refs = setup
    eng = _engine(cfg, params)
    # eos = the reference's second token => engine must stop right there
    r_eos = eng.submit(prompts[0], max_new_tokens=MAX_NEW[0],
                       eos_id=refs[0][1])
    r_len = eng.submit(prompts[2], max_new_tokens=3)
    eng.run_until_idle()
    assert r_eos.output == refs[0][:2] and r_eos.finish_reason == "eos"
    assert r_len.output == refs[2][:3] and r_len.finish_reason == "length"
    assert r_eos.state == RequestState.DONE


def test_streaming_callbacks(setup):
    cfg, params, prompts, refs = setup
    eng = _engine(cfg, params)
    streamed, done = [], []
    eng.submit(prompts[2], max_new_tokens=4,
               on_token=lambda t, r: streamed.append(t),
               on_done=lambda r: done.append(r.rid))
    eng.run_until_idle()
    assert streamed == refs[2][:4]
    assert done == [0]


# ---------------------------------------------------------------------------
# Paged primitives
# ---------------------------------------------------------------------------

def test_scatter_gather_roundtrip():
    rng = np.random.Generator(np.random.Philox(key=3))
    pool = jnp.zeros((8, 4, 2, 5), jnp.float32)     # 8 pages of 4 slots
    pt = jnp.asarray([[3, 1, 6, 0], [2, 5, 7, 0]], jnp.int32)
    pos = jnp.asarray([[0, 1, 5], [4, 6, 7]], jnp.int32)
    vals = jnp.asarray(rng.standard_normal((2, 3, 2, 5)), jnp.float32)
    pool = kv_cache.scatter_pages(pool, pt, pos, vals)
    out = kv_cache.gather_pages(pool, pt)            # [2, 16, 2, 5]
    for b in range(2):
        for i in range(3):
            np.testing.assert_array_equal(out[b, int(pos[b, i])],
                                          vals[b, i])


def test_scatter_masked_writes_hit_sink_page_only():
    pool = jnp.zeros((4, 2, 1, 1), jnp.float32)
    pt = jnp.asarray([[2, 3]], jnp.int32)
    pos = jnp.asarray([[0, 1, 2, 3]], jnp.int32)
    vals = jnp.ones((1, 4, 1, 1), jnp.float32)
    valid = jnp.asarray([[True, True, False, False]])
    new = kv_cache.scatter_pages(pool, pt, pos, vals, valid)
    assert float(new[2].sum()) == 2.0               # real writes
    assert float(new[3].sum()) == 0.0               # masked out
    assert float(new[1].sum()) == 0.0
    # positions past the table also land in the sink, never clamp into
    # the last real page
    far = kv_cache.scatter_pages(pool, pt, jnp.asarray([[99]]),
                                 jnp.ones((1, 1, 1, 1)))
    assert float(far[3].sum()) == 0.0


def test_serving_support_assigns_cache_kinds():
    """One central capability query: every mixer mix maps to a cache
    kind, refusals come with a stable reason."""
    assert serving_support(_cfg("llama3-8b")) == ("paged", "")
    assert serving_support(
        get_config("deepseek-v2-lite-16b").reduced()) == ("paged", "")
    assert serving_support(
        get_config("xlstm-1.3b").reduced()) == ("constant", "")
    assert serving_support(
        get_config("jamba-1.5-large-398b").reduced()) == ("composite", "")
    for name in ("whisper-medium", "qwen2-vl-2b"):
        kind, why = serving_support(get_config(name).reduced())
        assert kind is None and why


# ---------------------------------------------------------------------------
# KV accounting (cache_bytes exercised against real buffers)
# ---------------------------------------------------------------------------

def test_cache_bytes_matches_buffer_sizes():
    cfg = _cfg("llama3-8b")
    dense = lm.init_cache(cfg, batch=2, max_len=32, dtype=jnp.float32)
    leaves = jax.tree_util.tree_leaves(dense["layers"])
    assert kv_cache.cache_bytes(dense["layers"]) == \
        sum(x.size * x.dtype.itemsize for x in leaves)
    pools = lm.init_paged_cache(cfg, num_pages=10, page_size=4,
                                dtype=jnp.float32)
    leaves = jax.tree_util.tree_leaves(pools)
    assert kv_cache.cache_bytes(pools) == \
        sum(x.size * x.dtype.itemsize for x in leaves) > 0


def test_engine_surfaces_kv_metrics(setup):
    cfg, params, prompts, _ = setup
    eng = _engine(cfg, params)
    eng.submit(prompts[2], max_new_tokens=3)
    eng.step()
    leaves = jax.tree_util.tree_leaves(eng.kv.pools)
    assert eng.stats()["cache_bytes"] == \
        sum(x.size * x.dtype.itemsize for x in leaves)
    used = eng.obs.registry.gauge("repro_kv_used_bytes")
    assert used.value > 0                           # pages reserved
    eng.run_until_idle()
    eng.stats()                                     # refreshes the gauges
    assert used.value == 0                          # all pages returned
    assert eng.stats()["peak_kv_used_bytes"] > 0


# ---------------------------------------------------------------------------
# Scheduler / admission
# ---------------------------------------------------------------------------

def test_admission_by_page_budget(setup):
    """The conservative admission-blocking baseline (preempt="never"):
    a request's whole budget is reserved up front, so a too-small pool
    queues instead of preempting."""
    cfg, params, prompts, refs = setup
    # pool so small only one request fits at a time: budget 13+6=19 tokens
    # -> 5 pages; pool has 6 real pages
    eng = _engine(cfg, params, num_pages=7, max_slots=3, preempt="never")
    r0 = eng.submit(prompts[0], max_new_tokens=MAX_NEW[0], arrival_s=0.0)
    r2 = eng.submit(prompts[2], max_new_tokens=MAX_NEW[2], arrival_s=0.0)
    eng.step()
    # second request must still be queued — not enough free pages
    assert r0.state != RequestState.QUEUED
    assert r2.state == RequestState.QUEUED
    eng.run_until_idle()
    assert [r0.output, r2.output] == [refs[0], refs[2]]
    assert eng.kv.free_pages == eng.kv.num_pages - 1
    assert eng.kv.peak_used_pages <= 6


def test_oversized_request_rejected(setup):
    cfg, params, prompts, _ = setup
    eng = _engine(cfg, params, max_seq_len=16)
    with pytest.raises(ValueError, match="exceeds engine capacity"):
        eng.submit(np.arange(20, dtype=np.int32) % cfg.vocab_size,
                   max_new_tokens=8)


# ---------------------------------------------------------------------------
# Trace replay pacing
# ---------------------------------------------------------------------------

def test_replay_idles_in_few_sleeps(setup, monkeypatch):
    """An idle gap before the next scheduled arrival is covered by a
    handful of capped sleeps, not a 1 kHz busy-poll (regression: the
    old 1 ms fixed sleep burned a core for the whole gap)."""
    import time as _time

    from repro.serve import TraceEntry, replay

    cfg, params, prompts, refs = setup
    eng = _engine(cfg, params)
    trace = [TraceEntry(0.0, prompts[0], MAX_NEW[0]),
             TraceEntry(0.4, prompts[2], MAX_NEW[2])]
    calls = []
    real_sleep = _time.sleep

    def counting_sleep(s):
        calls.append(s)
        real_sleep(s)

    monkeypatch.setattr(_time, "sleep", counting_sleep)
    replay(eng, trace)
    monkeypatch.undo()
    outs = [r.output for r in sorted(eng.done, key=lambda r: r.rid)]
    assert outs == [refs[0], refs[2]]
    # the ~0.4s gap needs ~8 sleeps at the 0.05s cap; the old busy-poll
    # took ~400. Generous headroom for engine-work jitter:
    assert len(calls) <= 40, f"{len(calls)} sleeps — busy-polling again?"
    assert all(s <= 0.05 + 1e-9 for s in calls)


# ---------------------------------------------------------------------------
# HW spec resolution (--hw flag / auto-detect)
# ---------------------------------------------------------------------------

def test_resolve_hw():
    assert resolve_hw("tpu-v5e") is TPU_V5E
    assert resolve_hw("cpu-host") is CPU_HOST
    # tests force the CPU backend (conftest), so auto must detect it
    assert resolve_hw("auto") is CPU_HOST
    with pytest.raises(KeyError):
        resolve_hw("abacus-9000")


def test_resolve_hw_keys_on_device_kind():
    """auto detection reads ``device_kind``: a v5e reports "TPU v5 lite";
    a kind with no spec is refused rather than planned as a v5e."""
    from repro.core.types import hw_for_device_kind
    assert hw_for_device_kind("TPU v5 lite") is TPU_V5E
    assert hw_for_device_kind("cpu") is CPU_HOST
    for kind in ("TPU v4", "TPU v6 lite", "abacus"):
        with pytest.raises(ValueError, match="no HardwareSpec"):
            hw_for_device_kind(kind)
