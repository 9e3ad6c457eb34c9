"""Sampling suite (repro.serve.sampling): masked top-k/top-p kernel
semantics, the two on-device branches against a row-wise reference,
per-seed determinism across batch compositions (and through
preemption), stop sequences, and the one-compile invariant."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mesh_harness import run_mesh_script
from repro.configs import get_config
from repro.models import lm
from repro.serve import Engine, EngineOptions, SamplingParams, sample_tokens
from repro.serve.sampling import (NEG_INF, SAMPLE_PATHS, _keep,
                                  sample_path, stop_hit)


def _sample(logits, *, temp=1.0, top_k=0, top_p=1.0, seed=0, pos=0):
    n = logits.shape[0]
    arr = lambda v, dt: jnp.full((n,), v, dt)
    return np.asarray(sample_tokens(
        jnp.asarray(logits), arr(temp, jnp.float32), arr(top_k, jnp.int32),
        arr(top_p, jnp.float32), arr(seed, jnp.int32),
        arr(pos, jnp.int32)))


# ---------------------------------------------------------------------------
# Kernel semantics
# ---------------------------------------------------------------------------

def test_greedy_is_exact_argmax():
    rng = np.random.Generator(np.random.Philox(key=1))
    lg = rng.standard_normal((5, 37)).astype(np.float32)
    assert (_sample(lg, temp=0.0) == lg.argmax(-1)).all()


def test_top_k_one_is_argmax_at_any_temperature():
    rng = np.random.Generator(np.random.Philox(key=2))
    lg = rng.standard_normal((4, 50)).astype(np.float32)
    assert (_sample(lg, temp=5.0, top_k=1) == lg.argmax(-1)).all()


def test_top_k_strict_under_ties():
    """Tied logits at the k-th rank: the keep set is decided by sort
    rank (stable argsort -> lowest vocab index wins), never by a value
    threshold that would admit every tied entry. Regression: top_k=1
    over exact ties used to sample among all of them."""
    lg = np.array([[1.0, 2.0, 2.0, 0.0]], np.float32)
    for p in range(32):
        assert _sample(lg, temp=1.0, top_k=1, pos=p)[0] == 1
    # k=2 over a 3-way tie keeps exactly the two lowest tied indices
    lg3 = np.array([[0.0, 5.0, 5.0, 5.0, -1.0]], np.float32)
    seen = {int(_sample(lg3, temp=2.0, top_k=2, pos=p)[0])
            for p in range(64)}
    assert seen == {1, 2}


def test_tiny_top_p_is_argmax():
    rng = np.random.Generator(np.random.Philox(key=3))
    lg = rng.standard_normal((4, 50)).astype(np.float32)
    assert (_sample(lg, temp=2.0, top_p=1e-6) == lg.argmax(-1)).all()


def test_top_k_restricts_support():
    rng = np.random.Generator(np.random.Philox(key=4))
    lg = rng.standard_normal((1, 64)).astype(np.float32)
    top5 = set(np.argsort(lg[0])[::-1][:5].tolist())
    seen = {int(_sample(lg, temp=3.0, top_k=5, pos=p)[0])
            for p in range(50)}
    assert seen <= top5 and len(seen) > 1


def test_top_p_restricts_support():
    # 3 dominant logits carry ~all the mass; nucleus 0.9 keeps only them
    lg = np.full((1, 16), -10.0, np.float32)
    lg[0, [3, 7, 11]] = [5.0, 5.2, 4.8]
    seen = {int(_sample(lg, temp=1.0, top_p=0.9, pos=p)[0])
            for p in range(40)}
    assert seen <= {3, 7, 11} and len(seen) > 1


def test_same_seed_same_position_same_token_rows_independent():
    rng = np.random.Generator(np.random.Philox(key=5))
    lg = rng.standard_normal((3, 40)).astype(np.float32)
    a = _sample(lg, temp=1.0, seed=9, pos=4)
    b = _sample(lg, temp=1.0, seed=9, pos=4)
    assert (a == b).all()
    # a row's sample is unchanged when its neighbours' logits change
    lg2 = lg.copy()
    lg2[0] = rng.standard_normal(40)
    c = _sample(lg2, temp=1.0, seed=9, pos=4)
    assert (c[1:] == a[1:]).all()


# ---------------------------------------------------------------------------
# The two branches against a row-wise reference
# ---------------------------------------------------------------------------

V = 50304                                  # moe-gpt3-s's padded vocabulary


def _filtered_row_before(logits, temperature, top_k, top_p, seed,
                         position):
    """The one-branch sampler's row before the two branches, kept
    verbatim as the reference of a filtered row (where it is right)."""
    v = logits.shape[-1]
    lg = logits.astype(jnp.float32)
    greedy = temperature <= 0.0
    scaled = lg / jnp.where(greedy, 1.0, temperature)
    sort_idx = jnp.argsort(-scaled)
    rank = jnp.zeros((v,), jnp.int32).at[sort_idx].set(
        jnp.arange(v, dtype=jnp.int32))
    order = scaled[sort_idx]
    k_eff = jnp.clip(jnp.where(top_k <= 0, v, top_k), 1, v)
    p_eff = jnp.where((top_p <= 0.0) | (top_p >= 1.0), 1.0, top_p)
    probs = jax.nn.softmax(order)
    below = jnp.cumsum(probs) - probs
    n_keep = jnp.maximum(jnp.sum(below < p_eff), 1)
    masked = jnp.where(rank < jnp.minimum(k_eff, n_keep), scaled, NEG_INF)
    key = jax.random.fold_in(jax.random.PRNGKey(seed), position)
    sampled = jax.random.categorical(key, masked)
    return jnp.where(greedy, jnp.argmax(lg), sampled).astype(jnp.int32)


def _reference_row(lg, temp, top_k, top_p, seed, pos):
    """The documented semantics of one row: greedy is the float32
    argmax, an unfiltered sampled row a categorical draw over the whole
    scaled vocabulary from its own key, a filtered row the sorted keep
    set's draw."""
    if temp <= 0.0:
        return int(np.argmax(lg.astype(np.float32)))
    if top_k > 0 or 0.0 < top_p < 1.0:
        return int(_filtered_row_before(
            jnp.asarray(lg), jnp.float32(temp), jnp.int32(top_k),
            jnp.float32(top_p), jnp.int32(seed), jnp.int32(pos)))
    key = jax.random.fold_in(jax.random.PRNGKey(seed), pos)
    return int(jax.random.categorical(
        key, jnp.asarray(lg, jnp.float32) / jnp.float32(temp)))


# rows of (temperature, top_k, top_p); the case's expected branch
BRANCH_CASES = {
    "greedy": ([(0.0, 0, 1.0), (0.0, 5, 0.5), (-1.0, 0, 0.0),
                (0.0, 1, 1.0)], "greedy"),
    "unfiltered": ([(0.7, 0, 1.0), (0.0, 0, 1.0), (1.3, 0, 0.0),
                    (2.0, -3, 1.5), (0.0, 7, 0.3)], "sorted"),
    "sorted": ([(1.0, 5, 1.0), (0.8, 0, 0.9), (1.5, 40, 0.95),
                (1.0, 1, 0.5)], "sorted"),
    "mixed": ([(0.0, 0, 1.0), (0.9, 0, 1.0), (1.0, 8, 1.0),
               (0.0, 3, 0.9), (1.2, 0, 0.8), (0.6, 0, 0.0)], "sorted"),
}


@pytest.mark.parametrize("case", list(BRANCH_CASES))
def test_branch_matches_rowwise_reference(case):
    rows, path = BRANCH_CASES[case]
    n = len(rows)
    temp = np.array([r[0] for r in rows], np.float32)
    top_k = np.array([r[1] for r in rows], np.int32)
    top_p = np.array([r[2] for r in rows], np.float32)
    assert SAMPLE_PATHS[sample_path(temp)] == path
    fn = jax.jit(sample_tokens)
    rng = np.random.Generator(np.random.Philox(key=21))
    for seed, pos in ((0, 0), (7, 13), (2 ** 31 - 1, 4095)):
        lg = (rng.standard_normal((n, V)) * 3).astype(np.float32)
        # exact ties at the maximum: argmax takes the lowest index
        top = lg.max(-1) + 1.0
        lg[:, 101] = top
        lg[:, 5] = top
        seeds = np.full((n,), seed, np.int32) + np.arange(n, dtype=np.int32)
        poss = np.full((n,), pos, np.int32)
        got = np.asarray(fn(jnp.asarray(lg), jnp.asarray(temp),
                            jnp.asarray(top_k), jnp.asarray(top_p),
                            jnp.asarray(seeds), jnp.asarray(poss)))
        want = [_reference_row(lg[i], temp[i], top_k[i], top_p[i],
                               seeds[i], poss[i]) for i in range(n)]
        assert got.tolist() == want, (seed, pos)
        greedy = temp <= 0.0
        assert (got[greedy] == 5).all()            # argmax, bit for bit


def test_top_p_one_keeps_the_whole_vocabulary():
    """``top_p = 1`` is "filter off": the sorted branch keeps all of a
    row whose float32 cumulative mass passes 1 before its tail (the
    one-branch sampler kept 49,328 of this row's 50,304 tokens), and
    such a row, alone or batched with a top-k row, gets the categorical
    draw over its whole vocabulary from its own key."""
    row = jax.random.normal(jax.random.PRNGKey(0), (V,)) * 3
    kept = int(_keep(row, jnp.int32(0), jnp.float32(1.0)).sum())
    assert kept == V
    probs = jax.nn.softmax(jnp.sort(row)[::-1])
    assert float(jnp.cumsum(probs)[-2]) >= 1.0     # the mass passes 1
    other = jax.random.normal(jax.random.PRNGKey(1), (V,)) * 3
    batch = jnp.stack([row, other])
    temp = jnp.ones((2,), jnp.float32)
    top_k = jnp.array([0, 5], jnp.int32)
    top_p = jnp.ones((2,), jnp.float32)
    assert SAMPLE_PATHS[sample_path(temp)] == "sorted"
    fn = jax.jit(sample_tokens)
    for pos in range(8):
        seed = jnp.full((2,), 3, jnp.int32)
        p = jnp.full((2,), pos, jnp.int32)
        batched = int(fn(batch, temp, top_k, top_p, seed, p)[0])
        alone = int(fn(row[None], temp[:1], top_k[:1], top_p[:1],
                       seed[:1], p[:1])[0])
        whole = int(jax.random.categorical(
            jax.random.fold_in(jax.random.PRNGKey(3), pos), row))
        assert batched == alone == whole, pos


SHARDED_SCRIPT = r"""
import json
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from repro.serve.sampling import SAMPLE_PATHS, sample_path, sample_tokens

mesh = Mesh(np.array(jax.devices()), ("data",))
slots = NamedSharding(mesh, P("data"))
rng = np.random.Generator(np.random.Philox(key=5))
out = {}
for case, (rows, path) in %(cases)r.items():
    rows = (rows * 8)[:8]                # 8 slots, 2 on each device
    args = [np.array([r[i] for r in rows], dt) for i, dt in
            ((0, np.float32), (1, np.int32), (2, np.float32))]
    args += [np.arange(8, dtype=np.int32) + 11, np.full((8,), 9, np.int32)]
    lg = (rng.standard_normal((8, %(v)d)) * 3).astype(np.float32)
    want = np.asarray(jax.jit(sample_tokens)(lg, *args))
    put = [jax.device_put(lg, NamedSharding(mesh, P("data", None)))]
    put += [jax.device_put(a, slots) for a in args]
    fn = jax.jit(sample_tokens)
    got = np.asarray(fn(*put))
    hlo = fn.lower(*put).compile().as_text()
    out[case] = [SAMPLE_PATHS[sample_path(args[0])] == path,
                 got.tolist() == want.tolist(), "all-reduce" in hlo]
print(json.dumps(out))
"""


@pytest.mark.slow
def test_branches_sharded_over_slots_match_unsharded():
    """Slots sharded over devices, as the engine's DP-KV decode batch
    is: the branch predicate reduces across the shards (an all-reduce)
    and each branch gives the unsharded batch's tokens."""
    res = run_mesh_script(SHARDED_SCRIPT % {"cases": BRANCH_CASES, "v": V},
                          devices=4)
    assert res == {case: [True, True, True] for case in BRANCH_CASES}


def test_stop_hit_matches_suffix_only():
    assert stop_hit([1, 2, 3], [(2, 3)]) == (2, 3)
    assert stop_hit([1, 2, 3], [(1, 2)]) is None
    assert stop_hit([3], [(3,), (1, 3)]) == (3,)
    assert stop_hit([], [(3,)]) is None


# ---------------------------------------------------------------------------
# Engine integration
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def setup():
    cfg = dataclasses.replace(get_config("llama3-8b").reduced(),
                              compute_dtype="float32")
    params = lm.init(cfg, jax.random.PRNGKey(0))
    rng = np.random.Generator(np.random.Philox(key=11))
    prompts = [rng.integers(0, cfg.vocab_size, size=n, dtype=np.int32)
               for n in (11, 19, 7)]
    return cfg, params, prompts


def _engine(cfg, params, **over):
    kw = dict(page_size=4, max_slots=3, max_seq_len=64, chunk=16,
              min_bucket=8)
    kw.update(over)
    return Engine(cfg, params, options=EngineOptions(**kw))


SP = SamplingParams(temperature=0.8, top_k=8, top_p=0.95, seed=7)


def test_sampling_deterministic_across_batch_compositions(setup):
    """The same request + seed emits identical tokens whether it runs
    alone, continuously batched with other requests, or preempted and
    resumed mid-stream — the key serving-determinism guarantee."""
    cfg, params, prompts = setup
    alone = _engine(cfg, params)
    r = alone.submit(prompts[0], max_new_tokens=8, sampling=SP)
    alone.run_until_idle()
    want = list(r.output)
    assert len(want) == 8

    batched = _engine(cfg, params)
    r2 = batched.submit(prompts[0], max_new_tokens=8, sampling=SP)
    batched.submit(prompts[1], max_new_tokens=6)        # greedy neighbour
    batched.submit(prompts[2], max_new_tokens=7,
                   sampling=SamplingParams(temperature=1.3, seed=99))
    batched.run_until_idle()
    assert r2.output == want

    stormy = _engine(cfg, params, num_pages=10, preempt="recompute")
    r3 = stormy.submit(prompts[0], max_new_tokens=8, sampling=SP)
    stormy.submit(prompts[1], max_new_tokens=6)
    stormy.submit(prompts[2], max_new_tokens=7,
                  sampling=SamplingParams(temperature=1.3, seed=99))
    stormy.run_until_idle()
    assert stormy.preempts["recompute"] > 0
    assert r3.output == want


def test_seed_changes_sampled_stream(setup):
    cfg, params, prompts = setup
    eng = _engine(cfg, params)
    a = eng.submit(prompts[0], max_new_tokens=8,
                   sampling=dataclasses.replace(SP, seed=1))
    b = eng.submit(prompts[0], max_new_tokens=8,
                   sampling=dataclasses.replace(SP, seed=2))
    eng.run_until_idle()
    assert a.output != b.output


def _path_counts(eng):
    snap = eng.obs.registry.snapshot().get("repro_sample_path_total", {})
    return {k: v for k, v in snap.items() if v}


def test_greedy_run_counts_only_greedy_path(setup):
    cfg, params, prompts = setup
    eng = _engine(cfg, params)
    for p in prompts:
        eng.submit(p, max_new_tokens=5)
    eng.run_until_idle()
    counts = _path_counts(eng)
    assert list(counts) == ['path="greedy"']
    steps = eng.obs.registry.snapshot()["repro_engine_steps_total"]
    served = steps['kind="decode"'] + steps['kind="prefill"']
    assert counts['path="greedy"'] >= served > 0


def test_one_compile_across_sampling_settings(setup):
    """Changing sampling parameters must not re-jit: decode is one
    program, prefill one per bucket, regardless of settings. Run alone,
    each setting counts its sampler branch (the host's count of the
    device's choice)."""
    cfg, params, prompts = setup
    eng = _engine(cfg, params)
    eng.warmup()
    compiles, decode_traces = eng.prefill_rejits, eng.decode_traces
    settings = [(SamplingParams(), "greedy"),
                (SamplingParams(temperature=0.5, seed=3), "sorted"),
                (SamplingParams(temperature=1.0, top_k=4), "sorted"),
                (SamplingParams(temperature=1.0, top_p=0.5), "sorted")]
    for i, (sp, path) in enumerate(settings):
        before = _path_counts(eng)
        eng.submit(prompts[i % 3], max_new_tokens=4, sampling=sp)
        eng.run_until_idle()
        grew = {k for k, v in _path_counts(eng).items()
                if v > before.get(k, 0)}
        assert grew == {f'path="{path}"'}, (sp, grew)
    for i, (sp, _) in enumerate(settings):
        eng.submit(prompts[i % 3], max_new_tokens=4, sampling=sp)
    eng.run_until_idle()
    assert eng.prefill_rejits == compiles
    assert eng.decode_traces == decode_traces


def test_stop_sequence_stops_and_streams(setup):
    cfg, params, prompts = setup
    ref_eng = _engine(cfg, params)
    r = ref_eng.submit(prompts[0], max_new_tokens=6)
    ref_eng.run_until_idle()
    ref = list(r.output)

    eng = _engine(cfg, params)
    streamed = []
    r2 = eng.submit(prompts[0], max_new_tokens=6, stop=[ref[1:3], [12345]],
                    on_token=lambda t, _r: streamed.append(t))
    eng.run_until_idle()
    assert r2.output == ref[:3]                 # stopped at the match
    assert r2.finish_reason == "stop"
    assert streamed == r2.output
    assert r2.token_times == sorted(r2.token_times)
    assert len(r2.itl_s) == len(r2.output) - 1
