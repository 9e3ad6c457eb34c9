"""Serving conformance, attn_kernel axis: fused Pallas paged decode vs
the gather baseline.

The **attn_kernel axis** runs the moe-gpt3-s storm legs twice per
``kv_sharding`` — once on the legacy ``gather_pages`` + dense-attention
baseline, once on the fused Pallas paged-decode kernel
(``EngineOptions.attn_kernel``) — and pins them token-exact against the
dense golden loop *and* jit-counter-identical against each other: the
kernel selection is trace-static, so switching it may not add a single
trace or compile. The dp leg additionally lowers the compiled decode
program and asserts the Pallas HLO contains **no all-gather of the page
pool and zero wide (rank >= 4) f32 collectives** — the cross-shard KV
traffic XLA emits for the sharded ``gather_pages`` path (masked gather
+ rank-5 f32 all-reduce per attention layer) must be gone, because the
kernel reads pages shard-locally under ``shard_map``. The gather leg is
asserted to still carry that traffic, so the assertion keeps teeth.
The same kernel x storm cross runs over the MLA latent (deepseek) and
composite (jamba) paged paths. Shared setup: ``conformance_harness.py``.
"""
import pytest

from conformance_harness import (ARCH_KIND, ARCH_LENS, ARCH_MAX_NEW,
                                 ARCH_SETUP, ARCH_STORM_EVERY, COMMON,
                                 KV_SHARDINGS, LENS, MAX_NEW, STORM_PAGES,
                                 check_combo)
from mesh_harness import run_mesh_script

_KERNEL_SCRIPT = COMMON + r"""
import re

out = {}
for kern in ('gather', 'pallas'):
    leg = {}
    for mode in ('recompute', 'offload'):
        eng, outs = run_engine(preempt=mode, num_pages=%(pages)d,
                               attn_kernel=kern)
        leg[mode] = report(eng, outs)
    if %(kv)r == 'dp':
        # the live engine's compiled decode program, and its collectives
        # — after the reports, since lowering re-traces the decode body
        kvc = eng.kv
        hlo = eng.compiled_decode_text()
        coll = [l for l in hlo.splitlines()
                if re.search(r'(all-gather|all-reduce|all-to-all|'
                             r'collective-permute|reduce-scatter)\(', l)]
        leg['collectives'] = len(coll)
        # XLA implements the sharded-pool gather as masked local gather
        # + a wide f32 all-reduce over the gathered-KV extent (rank 5),
        # not a literal pool all-gather — count both signatures
        leg['f32_wide_collectives'] = sum(
            1 for l in coll if re.search(r'f32\[\d+(,\d+){3,}\]', l))
        leg['pool_all_gathers'] = sum(
            1 for l in coll if 'all-gather' in l
            and ',%%d,' %% kvc.num_pages
            in l.replace('[', ',').replace(']', ','))
    out[kern] = leg
print(json.dumps(out))
"""

_kernel_cache = {}


def _kernel_matrix(kv_sharding: str) -> dict:
    """One subprocess per kv_sharding runs both attn kernels through
    both storm modes (4 engine runs + golden refs + one HLO lowering)."""
    if kv_sharding not in _kernel_cache:
        _kernel_cache[kv_sharding] = run_mesh_script(
            _KERNEL_SCRIPT % {"kv": kv_sharding, "lens": LENS,
                              "max_new": MAX_NEW,
                              "pages": STORM_PAGES},
            timeout=1800)
    return _kernel_cache[kv_sharding]


@pytest.mark.parametrize("kv_sharding", KV_SHARDINGS)
@pytest.mark.parametrize("kern", ("gather", "pallas"))
@pytest.mark.slow
def test_attn_kernel_matrix_token_exact(kern, kv_sharding):
    """Both attention kernels emit exactly the dense golden loop's
    greedy tokens through recompute AND offload preemption storms, at
    both KV shardings, and drain their allocators — so the fused kernel
    is token-for-token interchangeable with the gather baseline."""
    res = _kernel_matrix(kv_sharding)[kern]
    for mode in ("recompute", "offload"):
        check_combo(res[mode], mode)


@pytest.mark.parametrize("kv_sharding", KV_SHARDINGS)
@pytest.mark.slow
def test_attn_kernel_compile_counts_pinned(kv_sharding):
    """attn_kernel is trace-static: per kv_sharding, the Pallas legs'
    decode/prefill trace and compile counts equal the gather legs'
    exactly (and decode still compiles once) — selecting the kernel
    cannot churn the jit caches."""
    res = _kernel_matrix(kv_sharding)
    for mode in ("recompute", "offload"):
        g, p = res["gather"][mode], res["pallas"][mode]
        for k in ("decode_traces", "prefill_traces",
                  "prefill_compiles", "buckets"):
            assert g[k] == p[k], f"{mode}/{k}: {g[k]} != {p[k]}"
        assert p["decode_traces"] == 1, mode


@pytest.mark.slow
def test_attn_kernel_dp_hlo_shard_local():
    """The dp-leg decode HLO: the Pallas kernel reads pages shard-local
    under shard_map, so its program contains no all-gather of the page
    pool and zero wide (rank >= 4) f32 collectives; the gather leg must
    still carry that cross-shard KV traffic (teeth — if XLA ever
    optimizes it away, the baseline changed and this pin should be
    revisited, not the kernel)."""
    res = _kernel_matrix("dp")
    g, p = res["gather"], res["pallas"]
    assert p["pool_all_gathers"] == 0
    assert p["f32_wide_collectives"] == 0
    assert g["f32_wide_collectives"] > 0
    assert p["collectives"] < g["collectives"]


# -- attn_kernel x arch: the MLA latent and composite paged paths ------------

_ARCH_KERNEL_SCRIPT = ARCH_SETUP + r"""
import json

def run_engine(kern, mode):
    eng = Engine(cfg, params, options=EngineOptions(
        page_size=4, max_slots=2, max_seq_len=64, chunk=16,
        min_bucket=8, devices=8, kv_sharding=%(kv)r, preempt=mode,
        storm_every=%(storm)d, attn_kernel=kern))
    eng.warmup()
    for p, m in zip(prompts, max_new):
        eng.submit(p, max_new_tokens=m, arrival_s=0.0)
    eng.run_until_idle()
    outs = [r.output for r in sorted(eng.done, key=lambda r: r.rid)]
    s = eng.stats()
    return outs, {
        'cache_kind': eng.cache_kind,
        'token_exact': outs == refs,
        'preempts': eng.preempts['recompute'] + eng.preempts['offload'],
        'decode_traces': s['decode_traces'],
        'prefill_traces': s['prefill_traces'],
        'prefill_compiles': s['prefill_compiles'],
    }

out = {}
for mode in ('recompute', 'offload'):
    legs = {}
    for kern in ('gather', 'pallas'):
        toks, rep = run_engine(kern, mode)
        legs[kern] = toks
        out[f'{kern}_{mode}'] = rep
    out[f'tokens_equal_{mode}'] = legs['gather'] == legs['pallas']
print(json.dumps(out))
"""

# deepseek pins the MLA compressed-latent kernel path, jamba the
# composite (paged attn + constant mamba) path; the shardings are split
# across them so the arch x kernel axis touches both layouts without
# doubling the subprocess count (the moe-gpt3-s kernel matrix above
# already runs the full kernel x kv_sharding x storm cross)
ARCH_KERNEL_AXIS = (("deepseek-v2-lite-16b", "dp"),
                    ("jamba-1.5-large-398b", "replicated"))

_arch_kernel_cache = {}


def _arch_kernel_matrix(arch: str, kv_sharding: str) -> dict:
    key = (arch, kv_sharding)
    if key not in _arch_kernel_cache:
        _arch_kernel_cache[key] = run_mesh_script(
            _ARCH_KERNEL_SCRIPT % {"arch": arch, "kv": kv_sharding,
                                   "lens": ARCH_LENS,
                                   "max_new": ARCH_MAX_NEW,
                                   "storm": ARCH_STORM_EVERY},
            timeout=1800)
    return _arch_kernel_cache[key]


@pytest.mark.parametrize("arch,kv_sharding", ARCH_KERNEL_AXIS)
@pytest.mark.slow
def test_attn_kernel_archs_token_exact(arch, kv_sharding):
    """MLA latent decode (deepseek) and the composite jamba cache run
    the fused kernel through forced recompute/offload storms on the
    8-device mesh: both kernels token-exact vs the dense golden loop
    and bit-identical to each other, with identical jit counters."""
    res = _arch_kernel_matrix(arch, kv_sharding)
    for mode in ("recompute", "offload"):
        assert res[f"tokens_equal_{mode}"], mode
        for kern in ("gather", "pallas"):
            r = res[f"{kern}_{mode}"]
            assert r["cache_kind"] == ARCH_KIND[arch]
            assert r["token_exact"], f"{kern}/{mode}"
            assert r["preempts"] > 0, f"{kern}/{mode}: storm never fired"
        g, p = res[f"gather_{mode}"], res[f"pallas_{mode}"]
        for k in ("decode_traces", "prefill_traces", "prefill_compiles"):
            assert g[k] == p[k], f"{mode}/{k}"
        assert p["decode_traces"] == 1
