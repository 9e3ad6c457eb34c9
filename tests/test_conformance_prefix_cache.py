"""Serving conformance, prefix_cache axis: cross-request page sharing vs
the prefix-off baseline.

``prefix_cache='on'`` x kv_sharding x preempt on the 8-device mesh: a
warm resubmission wave stays bit-identical to the dense golden loop,
hits the published prefixes, passes the allocator's refcount audit,
keeps every dp hit shard-local, and compiles exactly what the prefix-off
legs of the core matrix compile (rerun in the same subprocess, so this
file needs no core-matrix subprocess of its own). Shared setup:
``conformance_harness.py``.
"""
import pytest

from conformance_harness import (COMMON, KV_SHARDINGS, LENS, MAX_NEW,
                                 PREEMPTS, STORM_PAGES)
from mesh_harness import run_mesh_script

_PREFIX_SCRIPT = COMMON + r"""
def run_prefix(**over):
    kw = dict(page_size=4, max_slots=4, max_seq_len=64, chunk=16,
              min_bucket=8, devices=8, kv_sharding=%(kv)r,
              prefix_cache='on')
    kw.update(over)
    eng = Engine(cfg, params, options=EngineOptions(**kw))
    eng.warmup()
    waves, hits = [], []
    for wave in range(2):
        rs = [eng.submit(p, max_new_tokens=m, arrival_s=0.0)
              for p, m in zip(prompts, max_new)]
        eng.run_until_idle()
        waves.append([r.output for r in rs])
        hits.append(eng.stats()['prefix_hits'])
    return eng, waves, hits

out = {}
for mode in ('never', 'recompute', 'offload'):
    eng, waves, hits = run_prefix(
        preempt=mode, num_pages=(0 if mode == 'never' else %(pages)d))
    kv, s = eng.kv, eng.stats()
    kv.check_integrity()        # raises -> subprocess fails the leg
    # every trie page must live on the shard of its root: a dp hit can
    # only ever bind pages of the shard the request was placed on
    local = True
    for sh in range(kv.n_shards):
        stack = [kv._trie_roots[sh]]
        while stack:
            node = stack.pop()
            stack.extend(node.children.values())
            if node.page >= 0 and kv.shard_of_page(node.page) != sh:
                local = False
    out[mode] = {
        'token_exact': waves[0] == refs and waves[1] == refs,
        'cold_hits': hits[0], 'warm_hits': hits[1] - hits[0],
        'hit_tokens': s['prefix_hit_tokens'],
        'cow_copies': s['prefix_cow_copies'],
        'preempts': (eng.preempts['recompute']
                     + eng.preempts['offload']),
        'kv_shards': kv.n_shards, 'shard_local': local,
        'decode_traces': s['decode_traces'],
        'prefill_traces': s['prefill_traces'],
        'prefill_compiles': s['prefill_compiles'],
        'buckets': len(eng.adaptive.resolutions),
    }
    # the prefix-off leg exactly as the core matrix runs it
    out['off_' + mode] = report(*run_engine(
        preempt=mode, num_pages=(0 if mode == 'never' else %(pages)d)))
print(json.dumps(out))
"""

_prefix_cache_results = {}


def _prefix_matrix(kv_sharding: str) -> dict:
    if kv_sharding not in _prefix_cache_results:
        _prefix_cache_results[kv_sharding] = run_mesh_script(
            _PREFIX_SCRIPT % {"kv": kv_sharding, "lens": LENS,
                              "max_new": MAX_NEW,
                              "pages": STORM_PAGES},
            timeout=1800)
    return _prefix_cache_results[kv_sharding]


@pytest.mark.parametrize("kv_sharding", KV_SHARDINGS)
@pytest.mark.parametrize("preempt", PREEMPTS)
@pytest.mark.slow
def test_prefix_cache_matrix_token_exact(preempt, kv_sharding):
    """prefix_cache='on' x kv_sharding x preempt on the 8-device mesh:
    the standard trace plus a warm resubmission wave stays bit-identical
    to the dense golden loop (so to the prefix-off legs), the warm wave
    actually hits the published prefixes, and the allocator passes the
    full refcount-conservation audit after both waves."""
    r = _prefix_matrix(kv_sharding)[preempt]
    assert r["token_exact"]
    assert r["shard_local"]
    if preempt == "never":
        # worst-case pool: nothing evicts and nothing diverges mid-page
        # (full-reserve hits are page-aligned), so resubmissions hit —
        # all of them replicated; under dp the cache-aware placement is
        # a hint, and a request whose prefix shard has no free slot
        # falls back to the other shard and misses (observed: 4/5)
        assert r["cow_copies"] == 0
        floor = len(LENS) if r["kv_shards"] == 1 else len(LENS) - 2
        assert r["warm_hits"] >= floor
        assert r["hit_tokens"] > 0
    elif preempt == "recompute":
        # recompute resumes re-prefill prompts whose prefixes were
        # published at prefill-end, so the storm itself produces hits
        assert r["warm_hits"] >= 1
        assert r["preempts"] > 0
    else:
        # offload: restores bypass prefill entirely (no hit path) and
        # the tight storm pool evicts trie entries as fast as retires
        # publish them — hits are possible but not guaranteed; the leg
        # pins exactness + conservation under sharing, not hit rate
        assert r["preempts"] > 0


@pytest.mark.parametrize("kv_sharding", KV_SHARDINGS)
@pytest.mark.slow
def test_prefix_cache_jit_counts_match_off_leg(kv_sharding):
    """Prefix caching must not perturb compiled shapes: jit trace and
    compile counters on the prefix-on legs equal the prefix-off matrix
    legs (``run_engine`` of the core matrix, in the same subprocess) —
    skipped prefill only shortens chunk loops over the same
    warmup-swept buckets, it never introduces a new traced body."""
    res = _prefix_matrix(kv_sharding)
    for mode in PREEMPTS:
        on, off = res[mode], res["off_" + mode]
        assert off["token_exact"]
        for k in ("decode_traces", "prefill_traces",
                  "prefill_compiles", "buckets"):
            assert on[k] == off[k], f"{mode}/{k}: {on[k]} != {off[k]}"
        assert on["decode_traces"] == 1


@pytest.mark.slow
def test_prefix_cache_dp_hits_stay_shard_local():
    """dp-sharded pools: the trie is per shard and every hit binds only
    pages of the request's own shard (cross-shard sharing would read
    pages a device does not hold)."""
    res = _prefix_matrix("dp")
    for mode in PREEMPTS:
        assert res[mode]["kv_shards"] == 2
        assert res[mode]["shard_local"]
