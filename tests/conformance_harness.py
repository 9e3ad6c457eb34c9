"""Shared setup of the serving conformance tiers.

The conformance tiers are split over four test files so that pytest-xdist
(``--dist loadfile``) spreads their 8-device subprocess legs over its
workers:

* ``test_serving_conformance.py`` — the core preempt x devices x
  kv_sharding matrix, its jit-compile-count regression and the
  telemetry leg;
* ``test_conformance_attn_kernel.py`` — the attn_kernel axis (Pallas
  paged decode vs the gather baseline), moe-gpt3-s and the MLA /
  composite archs;
* ``test_conformance_arch.py`` — the arch axis (every StateCache kind x
  every preempt mode);
* ``test_conformance_prefix_cache.py`` — the prefix_cache axis.

This module holds what they share: the golden setup (model, prompts,
dense references) as ONE source block that both the subprocess
templates and the in-process single-device leg execute, the arch setup
block, and the per-combo checks.
"""
PREEMPTS = ("never", "recompute", "offload")
DEVICES = (1, 8)
KV_SHARDINGS = ("replicated", "dp")

# decode-heavy budgets (10..14 pages at page_size 4) over a 30-page pool
# (replicated: 29 usable; dp=2: 14 usable per shard): growth overcommits
# both layouts, so recompute/offload storms fire per shard
LENS = (13, 29, 7, 21, 5)
MAX_NEW = (26, 24, 28, 25, 27)
STORM_PAGES = 30

# the golden setup (model, prompts, dense references) is ONE source
# block: the subprocess template embeds it and the in-process
# single-device fixture exec()s the very same string, so the
# devices=1 and devices=8 legs of the matrix can never drift onto
# different models or workloads
GOLDEN_SETUP = r"""
import dataclasses
import jax
import numpy as np
from repro.configs import get_config
from repro.models import lm
from repro.serve import Engine, EngineOptions, dense_greedy_reference

cfg = get_config('moe-gpt3-s').reduced()
cfg = dataclasses.replace(
    cfg, compute_dtype='float32',
    moe=dataclasses.replace(cfg.moe, capacity_factor=8.0))
params = lm.init(cfg, jax.random.PRNGKey(0))
rng = np.random.Generator(np.random.Philox(key=7))
lens, max_new = %(lens)r, %(max_new)r
prompts = [rng.integers(0, cfg.vocab_size, size=n, dtype=np.int32)
           for n in lens]
refs = [dense_greedy_reference(params, cfg, p, m)
        for p, m in zip(prompts, max_new)]
"""

COMMON = GOLDEN_SETUP + r"""
import json

def run_engine(**over):
    kw = dict(page_size=4, max_slots=4, max_seq_len=64, chunk=16,
              min_bucket=8, devices=8, kv_sharding=%(kv)r)
    kw.update(over)
    eng = Engine(cfg, params, options=EngineOptions(**kw))
    eng.warmup()
    for p, m in zip(prompts, max_new):
        eng.submit(p, max_new_tokens=m, arrival_s=0.0)
    eng.run_until_idle()
    outs = [r.output for r in sorted(eng.done, key=lambda r: r.rid)]
    return eng, outs

def report(eng, outs):
    kv, s = eng.kv, eng.stats()
    return {
        'token_exact': outs == refs,
        'preempt_recompute': eng.preempts['recompute'],
        'preempt_offload': eng.preempts['offload'],
        'swap_out': s['swap_out_bytes'], 'swap_in': s['swap_in_bytes'],
        'kv_shards': kv.n_shards,
        'drained': all(
            sorted(kv._free_by_shard[sh]) == list(
                range(sh * kv.pages_per_shard + 1,
                      (sh + 1) * kv.pages_per_shard))
            for sh in range(kv.n_shards)),
        'offloaded_left': kv.offloaded_count,
        'decode_traces': s['decode_traces'],
        'prefill_traces': s['prefill_traces'],
        'prefill_compiles': s['prefill_compiles'],
        'buckets': len(eng.adaptive.resolutions),
        'sticky': all(r.kv_shard in range(kv.n_shards)
                      for r in eng.done),
    }
"""

def check_combo(r: dict, preempt: str) -> None:
    assert r["token_exact"]
    assert r["drained"] and r["offloaded_left"] == 0
    if preempt == "never":
        assert r["preempt_recompute"] == 0 and r["preempt_offload"] == 0
    elif preempt == "recompute":
        assert r["preempt_recompute"] > 0 and r["preempt_offload"] == 0
        assert r["swap_out"] == 0
    else:
        assert r["preempt_offload"] > 0 and r["preempt_recompute"] == 0
        assert r["swap_out"] > 0 and r["swap_in"] == r["swap_out"]


# one leg per cache geometry the StateCache protocol serves:
#   plain-attn  h2o-danube-1.8b       paged      sliding-window attention
#   mamba       synthetic pure-mamba  constant   conv window + SSM state
#   xlstm       xlstm-1.3b            constant   mLSTM matrix + sLSTM cell
#   mla         deepseek-v2-lite-16b  paged      compressed c_kv latents
#   jamba       jamba-1.5-large-398b  composite  paged attn + constant mamba
ARCH_KIND = {
    "h2o-danube-1.8b": "paged",
    "pure-mamba": "constant",
    "xlstm-1.3b": "constant",
    "deepseek-v2-lite-16b": "paged",
    "jamba-1.5-large-398b": "composite",
}
ARCH_AXIS = tuple(sorted(ARCH_KIND))

ARCH_LENS = (13, 7, 21)
ARCH_MAX_NEW = (10, 12, 9)
# constant-state slots hold O(1) bytes and never run dry, so the storm
# legs force preemption on a fixed step cadence instead of starving the
# pool — uniform across cache kinds (the core moe-gpt3-s matrix keeps
# the organic pool-dry path pinned)
ARCH_STORM_EVERY = 7

ARCH_SETUP = r"""
import dataclasses
import jax
import numpy as np
from repro.configs import get_config
from repro.models import lm
from repro.serve import Engine, EngineOptions, dense_greedy_reference

def _golden(name):
    cfg = get_config(name).reduced()
    moe = cfg.moe
    if moe is not None:
        moe = dataclasses.replace(moe, capacity_factor=8.0)
    return dataclasses.replace(cfg, compute_dtype='float32', moe=moe)

arch = %(arch)r
if arch == 'pure-mamba':
    # no registry entry is mixer-pure mamba; synthesize one from jamba's
    # mamba geometry so the constant-kind path is pinned without an
    # attention layer anywhere in the stack
    cfg = dataclasses.replace(_golden('jamba-1.5-large-398b'),
                              block_pattern=('mamba',), moe=None)
else:
    cfg = _golden(arch)
params = lm.init(cfg, jax.random.PRNGKey(0))
rng = np.random.Generator(np.random.Philox(key=7))
lens, max_new = %(lens)r, %(max_new)r
prompts = [rng.integers(0, cfg.vocab_size, size=n, dtype=np.int32)
           for n in lens]
refs = [dense_greedy_reference(params, cfg, p, m)
        for p, m in zip(prompts, max_new)]
"""
