"""Serving conformance, arch axis: every StateCache kind x every preempt
mode.

The **arch axis** extends the matrix over the StateCache kinds: plain
attention (h2o-danube, sliding window), pure mamba, pure xLSTM, MLA
latent paging (deepseek-v2-lite) and the jamba attn+mamba composite —
each × every preempt mode on the 8-device mesh, with preemption storms
*forced* via ``EngineOptions.storm_every`` (a constant-state cache
holds O(1) bytes per slot and never runs dry on its own; forcing makes
the storm legs uniform across kinds while the core moe-gpt3-s matrix
keeps pinning the organic pool-dry path). Drain checks are protocol-
level (``used_bytes == 0`` / ``free_units`` full / nothing parked) so
they hold for every cache kind. The compile-count regression is
asserted here too, so the one-trace invariant holds for recurrent state
threading (slot-sliced prefill writes, frozen inactive decode slots).
Shared setup: ``conformance_harness.py``.
"""
import pytest

from conformance_harness import (ARCH_AXIS, ARCH_KIND, ARCH_LENS,
                                 ARCH_MAX_NEW, ARCH_SETUP, ARCH_STORM_EVERY,
                                 PREEMPTS, check_combo)
from mesh_harness import run_mesh_script


_ARCH_SCRIPT = ARCH_SETUP + r"""
import json

def run_engine(mode):
    kw = dict(page_size=4, max_slots=2, max_seq_len=64, chunk=16,
              min_bucket=8, devices=8, kv_sharding=%(kv)r, preempt=mode)
    if mode != 'never':
        kw['storm_every'] = %(storm)d
    eng = Engine(cfg, params, options=EngineOptions(**kw))
    eng.warmup()
    full = eng.kv.free_units                 # fresh-cache capacity
    for p, m in zip(prompts, max_new):
        eng.submit(p, max_new_tokens=m, arrival_s=0.0)
    eng.run_until_idle()
    outs = [r.output for r in sorted(eng.done, key=lambda r: r.rid)]
    kv, s = eng.kv, eng.stats()
    return {
        'cache_kind': eng.cache_kind,
        'token_exact': outs == refs,
        'preempt_recompute': eng.preempts['recompute'],
        'preempt_offload': eng.preempts['offload'],
        'swap_out': s['swap_out_bytes'], 'swap_in': s['swap_in_bytes'],
        # protocol-level drain: holds for paged, constant and composite
        'drained': kv.used_bytes == 0 and kv.free_units == full,
        'offloaded_left': kv.offloaded_count,
        'decode_traces': s['decode_traces'],
        'prefill_traces': s['prefill_traces'],
        'prefill_compiles': s['prefill_compiles'],
    }

out = {}
for mode in ('never', 'recompute', 'offload'):
    out[mode] = run_engine(mode)
print(json.dumps(out))
"""

_arch_cache = {}


def _arch_matrix(arch: str, kv_sharding: str = "replicated") -> dict:
    """One subprocess per (arch, kv_sharding) computes all preempt
    modes, amortizing jax init + model init + golden refs."""
    key = (arch, kv_sharding)
    if key not in _arch_cache:
        _arch_cache[key] = run_mesh_script(
            _ARCH_SCRIPT % {"arch": arch, "kv": kv_sharding,
                            "lens": ARCH_LENS, "max_new": ARCH_MAX_NEW,
                            "storm": ARCH_STORM_EVERY},
            timeout=1800)
    return _arch_cache[key]


@pytest.mark.parametrize("arch", ARCH_AXIS)
@pytest.mark.parametrize("preempt", PREEMPTS)
@pytest.mark.slow
def test_arch_matrix_token_exact(preempt, arch):
    """Every cache kind x preempt mode on the 8-device mesh: greedy
    decode is token-exact vs the dense golden loop, through forced
    recompute/offload preemption storms, and the cache drains back to
    its fresh capacity (slots, pages and host snapshots all returned)."""
    r = _arch_matrix(arch)[preempt]
    assert r["cache_kind"] == ARCH_KIND[arch]
    check_combo(r, preempt)


def test_arch_axis_covers_every_cache_kind():
    """The axis spans all three StateCache kinds and the full 5 x 3
    grid is asserted (no skips on this axis)."""
    assert sorted(set(ARCH_KIND.values())) == \
        ["composite", "constant", "paged"]
    assert len(ARCH_AXIS) * len(PREEMPTS) == 15


@pytest.mark.parametrize("arch", ARCH_AXIS)
@pytest.mark.slow
def test_arch_steady_state_compiles_once(arch):
    """Compile-count regression extended across cache kinds: recurrent
    state threading (slot-sliced prefill writes, frozen inactive decode
    slots, constant-state dummy page tables) must not add jit cache
    entries — one decode trace, one trace per prefill bucket, in every
    preempt mode."""
    res = _arch_matrix(arch)
    for mode in PREEMPTS:
        r = res[mode]
        assert r["decode_traces"] == 1, \
            f"{arch}/{mode}: decode compiled {r['decode_traces']}x"
        assert r["prefill_traces"] == r["prefill_compiles"], \
            f"{arch}/{mode}"


@pytest.mark.slow
def test_constant_state_dp_sharded_leg():
    """Slot-sharded constant-state cache over the mesh data axis: xlstm
    with kv_sharding='dp' (dense model => dp spans all 8 devices) stays
    token-exact through forced storms, with host snapshots pinned to a
    sticky shard across offload/restore."""
    res = _arch_matrix("xlstm-1.3b", "dp")
    for mode in PREEMPTS:
        check_combo(res[mode], mode)
