"""Serving conformance matrix + jit-compile-count regression.

The matrix pins the tentpole contract of the serving engine across its
whole configuration surface: **greedy decode is token-exact vs the
single-device dense golden loop** for every
``preempt x devices x kv_sharding`` combination —

    preempt     ∈ {never, recompute, offload}
    devices     ∈ {1, 8}
    kv_sharding ∈ {replicated, dp}

— skipping only the structurally undefined combos (``kv_sharding="dp"``
on one device has no mesh data axis to shard over; the engine refuses
it, see ``test_kv_sharding_dp_requires_a_mesh``). The preemptive combos
run over constrained pools so the storms actually fire; "never" runs
blocking admission over an ample pool. Multi-device combos run through
``tests/mesh_harness.py``; one subprocess per ``kv_sharding`` computes
all three preempt modes (amortizing jax init + golden refs) and the
parametrized tests assert their slice.

The arch, attn_kernel and prefix_cache axes of the matrix live in
``test_conformance_arch.py``, ``test_conformance_attn_kernel.py`` and
``test_conformance_prefix_cache.py``; the setup they share is in
``conformance_harness.py``.

The compile-count regression pins the PR 4 one-committed-placement
gotcha under the DP-KV layout: every step input must enter jit with one
committed sharding (``Engine._put`` / ``_put_slots`` / the cache's
``device_*`` buffers) and step outputs must be pinned back to the
pool layout (``pin_pools``) — otherwise the jit caches churn on
sharding mismatches. Steady state must compile the decode body exactly
once and each reachable prefill bucket exactly once, counted by the
engine's own trace counters (``decode_traces`` / ``prefill_traces`` —
the jitted bodies increment them only while tracing). A **telemetry
leg** replays the recompute storm with the ``repro.obs`` span tracer
live and must match the telemetry-off leg's jit counters exactly —
instrumentation runs at trace time only, so telemetry on/off cannot
change compiled HLO (see ``docs/observability.md``).
"""
import pytest

from conformance_harness import (COMMON, DEVICES, GOLDEN_SETUP,
                                 KV_SHARDINGS, LENS, MAX_NEW, PREEMPTS,
                                 STORM_PAGES, check_combo)
from mesh_harness import run_mesh_script

_MATRIX_SCRIPT = COMMON + r"""
out = {}
for mode in ('never', 'recompute', 'offload'):
    eng, outs = run_engine(
        preempt=mode, num_pages=(0 if mode == 'never' else %(pages)d))
    out[mode] = report(eng, outs)

# telemetry leg: the recompute storm again with the span tracer live —
# instrumentation inside the jitted bodies runs at trace time only, so
# the jit trace/compile counters must not move by a single trace
from repro.obs import Recorder, Tracer
obs = Recorder(tracer=Tracer())
eng, outs = run_engine(preempt='recompute', num_pages=%(pages)d, obs=obs)
out['telemetry'] = report(eng, outs)
out['telemetry']['trace_events'] = len(obs.tracer.export()['traceEvents'])
print(json.dumps(out))
"""

_matrix_cache = {}


def _matrix(kv_sharding: str) -> dict:
    """One subprocess per kv_sharding computes all preempt modes.
    Three engine runs + golden refs per subprocess is ~1.5x the PR 4
    storm script, hence the raised timeout."""
    if kv_sharding not in _matrix_cache:
        _matrix_cache[kv_sharding] = run_mesh_script(
            _MATRIX_SCRIPT % {"kv": kv_sharding, "lens": LENS,
                             "max_new": MAX_NEW, "pages": STORM_PAGES},
            timeout=1800)
    return _matrix_cache[kv_sharding]


# -- single-device leg (in-process) -----------------------------------------

@pytest.fixture(scope="module")
def single_device_setup():
    """exec() the exact setup source the subprocess template embeds —
    one block, two legs, zero drift."""
    ns: dict = {}
    exec(GOLDEN_SETUP % {"lens": LENS, "max_new": MAX_NEW}, ns)
    return ns["cfg"], ns["params"], ns["prompts"], ns["refs"]


def _run_single(setup, preempt: str) -> dict:
    from repro.serve import Engine, EngineOptions
    cfg, params, prompts, refs = setup
    eng = Engine(cfg, params, options=EngineOptions(
        page_size=4, max_slots=4, max_seq_len=64, chunk=16, min_bucket=8,
        preempt=preempt,
        num_pages=(0 if preempt == "never" else STORM_PAGES)))
    for p, m in zip(prompts, MAX_NEW):
        eng.submit(p, max_new_tokens=m, arrival_s=0.0)
    eng.run_until_idle()
    outs = [r.output for r in sorted(eng.done, key=lambda r: r.rid)]
    return {
        "token_exact": outs == refs,
        "preempt_recompute": eng.preempts["recompute"],
        "preempt_offload": eng.preempts["offload"],
        "swap_out": eng.kv.swap_out_bytes,
        "swap_in": eng.kv.swap_in_bytes,
        "drained": sorted(eng.kv._free) == list(
            range(1, eng.kv.num_pages)),
        "offloaded_left": eng.kv.offloaded_count,
    }


# -- the matrix --------------------------------------------------------------

@pytest.mark.parametrize("kv_sharding", KV_SHARDINGS)
@pytest.mark.parametrize("devices", DEVICES)
@pytest.mark.parametrize("preempt", PREEMPTS)
@pytest.mark.slow
def test_conformance_matrix_token_exact(preempt, devices, kv_sharding,
                                        single_device_setup):
    """Every defined (preempt, devices, kv_sharding) combo emits exactly
    the dense golden loop's greedy tokens and drains its allocator."""
    if devices == 1 and kv_sharding == "dp":
        pytest.skip("structurally undefined: a single device has no "
                    "mesh data axis to shard the KV pools over")
    if devices == 1:
        r = _run_single(single_device_setup, preempt)
    else:
        r = _matrix(kv_sharding)[preempt]
    check_combo(r, preempt)


def test_matrix_covers_every_defined_combo():
    """The skip rule above is the ONLY hole: 3 x 2 x 2 = 12 combos, 3
    structurally undefined, 9 asserted."""
    defined = [(p, d, k) for p in PREEMPTS for d in DEVICES
               for k in KV_SHARDINGS if not (d == 1 and k == "dp")]
    assert len(defined) == 9


# -- jit-compile-count regression (one-committed-placement gotcha) -----------

@pytest.mark.slow
def test_dp_sharded_steady_state_compiles_once():
    """Mixed prefill/decode run with kv_sharding='dp': the decode body
    traces exactly once and each reachable prefill bucket exactly once —
    a second trace of any body means a step input arrived with a new
    committed sharding (the PR 4 jit-cache-churn gotcha, now with three
    input layouts in play: page-sharded pools, slot-sharded decode
    batch, replicated prefill rows)."""
    res = _matrix("dp")
    for mode in PREEMPTS:
        r = res[mode]
        assert r["kv_shards"] == 2                 # dp=2 x ep=4 mesh
        assert r["decode_traces"] == 1, \
            f"{mode}: decode compiled {r['decode_traces']}x"
        # every prefill jit traced exactly once...
        assert r["prefill_traces"] == r["prefill_compiles"], mode
        # ...and warmup's bucket sweep covered everything reachable (no
        # new compiles appeared mid-run, through preemption resumes
        # included)
        assert r["prefill_compiles"] == r["buckets"], mode


@pytest.mark.slow
def test_replicated_steady_state_compiles_once():
    """Same invariant for the replicated layout (the PR 4 baseline)."""
    res = _matrix("replicated")
    for mode in PREEMPTS:
        assert res[mode]["decode_traces"] == 1, mode
        assert res[mode]["prefill_traces"] == \
            res[mode]["prefill_compiles"], mode


@pytest.mark.slow
@pytest.mark.parametrize("kv_sharding", KV_SHARDINGS)
def test_telemetry_adds_zero_jit_traces(kv_sharding):
    """The span-tracer-on leg replays the recompute storm with every
    span/instant live (engine steps, request lifecycle, jit.trace
    instants inside the jitted bodies): jit trace and compile counts
    must be identical to the telemetry-off recompute leg — tracer calls
    inside jitted Python run at trace time only and can never change
    compiled HLO — and the run stays token-exact with a non-empty
    exported trace."""
    res = _matrix(kv_sharding)
    off, on = res["recompute"], res["telemetry"]
    assert on["token_exact"]
    assert on["drained"] and on["preempt_recompute"] > 0
    for k in ("decode_traces", "prefill_traces", "prefill_compiles",
              "buckets"):
        assert on[k] == off[k], f"{k}: {on[k]} != {off[k]}"
    assert on["trace_events"] > 0
