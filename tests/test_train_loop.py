"""Training runtime: loss goes down, fault tolerance works."""
import dataclasses

import jax
import jax.numpy as jnp
import pytest

from repro.ckpt import Checkpointer
from repro.configs import get_config
from repro.data import SyntheticTokens
from repro.runtime import TrainOptions, init_state, make_train_step, train


@pytest.fixture(scope="module")
def tiny_cfg():
    base = get_config("moe-gpt3-s").reduced()
    return dataclasses.replace(
        base, num_layers=2, compute_dtype="float32",
        moe=dataclasses.replace(base.moe, num_partitions=2,
                                memory_reuse_strategy="s4"))


def test_loss_decreases(tiny_cfg):
    ds = SyntheticTokens(tiny_cfg, batch=8, seq=32, seed=0)
    opts = TrainOptions(lr=3e-3, warmup=5, total_steps=60)
    state, hist = train(tiny_cfg, steps=60, batch_source=ds, opts=opts)
    first = sum(h["loss"] for h in hist[:5]) / 5
    last = sum(h["loss"] for h in hist[-5:]) / 5
    assert last < first - 0.2, (first, last)
    assert int(state["step"]) == 60


def test_checkpoint_restart_resumes_exactly(tiny_cfg, tmp_path):
    ds = SyntheticTokens(tiny_cfg, batch=8, seq=32, seed=0)
    opts = TrainOptions(lr=1e-3, warmup=5, total_steps=30)
    ck = Checkpointer(str(tmp_path), keep=3)
    # run 20 steps, checkpoint every 10
    state, _ = train(tiny_cfg, steps=20, batch_source=ds, opts=opts,
                     checkpointer=ck, ckpt_every=10)
    ck.wait()
    assert 20 in ck.list_steps()
    # "crash": new loop restores from latest and continues to 25
    class _Ck(Checkpointer):
        def restore_latest(self, abstract=None, like=None, shardings=None):
            out = super().restore_latest(like=_like(), shardings=None)
            return out
    def _like():
        from repro.runtime.train_loop import init_state
        return init_state(tiny_cfg, jax.random.PRNGKey(0), opts)
    ck2 = _Ck(str(tmp_path), keep=3)
    state2, hist2 = train(tiny_cfg, steps=25, batch_source=ds, opts=opts,
                          checkpointer=ck2, ckpt_every=100)
    assert hist2[0]["step"] == 20           # resumed, not restarted
    assert int(state2["step"]) == 25


def test_grad_compression_trains(tiny_cfg):
    ds = SyntheticTokens(tiny_cfg, batch=8, seq=32, seed=0)
    opts = TrainOptions(lr=3e-3, warmup=5, total_steps=40,
                        compress_grads=True)
    state, hist = train(tiny_cfg, steps=40, batch_source=ds, opts=opts)
    assert "grad_err" in state
    first = sum(h["loss"] for h in hist[:5]) / 5
    last = sum(h["loss"] for h in hist[-5:]) / 5
    assert last < first


def test_failed_step_without_checkpoint_raises_its_own_error(
        tiny_cfg, monkeypatch):
    """No checkpoint to roll back to: the step's own error surfaces at
    once — a retry would re-call a step whose state was donated."""
    from repro.runtime import train_loop
    calls = []

    def failing_step(cfg, opts, dist=None):
        real = make_train_step(cfg, opts, dist)

        def step(state, batch):
            calls.append(1)
            if len(calls) == 2:
                raise RuntimeError("step blew up")
            return real(state, batch)
        return step

    monkeypatch.setattr(train_loop, "make_train_step", failing_step)
    ds = SyntheticTokens(tiny_cfg, batch=4, seq=16, seed=0)
    with pytest.raises(RuntimeError, match="step blew up"):
        train(tiny_cfg, steps=4, batch_source=ds, jit=False,
              adaptive=False, max_retries=2)
    assert len(calls) == 2


def test_compile_cache_dir_rule(monkeypatch):
    """JAX_COMPILATION_CACHE_DIR set: used as is, nothing set in code;
    unset: a fixed, git-ignored directory inside the checkout."""
    import os
    from repro.launch import compile_cache

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    assert compile_cache.enable_compile_cache() == "/elsewhere/cache"
    assert jax.config.jax_compilation_cache_dir == before
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    try:
        path = compile_cache.enable_compile_cache()
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert path == os.path.join(root, ".jax_cache")
    with open(os.path.join(root, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split(), "cache dir not ignored"


def test_grad_accum_matches_full_batch(tiny_cfg):
    """2 microbatches of 4 == 1 batch of 8 (same grads, fp32)."""
    ds = SyntheticTokens(tiny_cfg, batch=8, seq=32, seed=0)
    batch = {k: jnp.asarray(v) for k, v in ds.batch_at(0).items()}
    s1 = init_state(tiny_cfg, jax.random.PRNGKey(0), TrainOptions())
    s2 = init_state(tiny_cfg, jax.random.PRNGKey(0), TrainOptions())
    step1 = make_train_step(tiny_cfg, TrainOptions(lr=1e-3))
    step2 = make_train_step(tiny_cfg, TrainOptions(lr=1e-3, grad_accum=2))
    o1, m1 = step1(s1, batch)
    o2, m2 = step2(s2, batch)
    assert m1["loss"] == pytest.approx(float(m2["loss"]), rel=2e-2)
    w1 = jax.tree_util.tree_leaves(o1["params"])[0]
    w2 = jax.tree_util.tree_leaves(o2["params"])[0]
    assert jnp.allclose(w1, w2, atol=1e-4)


def test_train_step_publishes_its_scopes_and_the_profiler_moves_no_loss(
        tiny_cfg, tmp_path, monkeypatch):
    """After its first call the jitted step puts its device scopes into
    the scope table, from jit's caches (no compile); a profiler session
    around training changes no loss."""
    from repro.obs import scopes
    from repro.runtime import train_loop

    published = []
    publish = train_loop._ScopedStep._publish

    def counted(self, args):
        compiles = []
        jax.monitoring.register_event_duration_secs_listener(
            lambda event, duration, **_: compiles.append(event)
            if event == "/jax/core/compile/backend_compile_duration"
            else None)
        publish(self, args)
        published.append(list(compiles))

    monkeypatch.setattr(train_loop._ScopedStep, "_publish", counted)
    ds = SyntheticTokens(tiny_cfg, batch=4, seq=16, seed=0)
    opts = TrainOptions(lr=3e-3, warmup=1, total_steps=10)
    _, off = train(tiny_cfg, steps=3, batch_source=ds, opts=opts,
                   adaptive=False)
    with jax.profiler.trace(str(tmp_path)):
        _, on = train(tiny_cfg, steps=3, batch_source=ds, opts=opts,
                      adaptive=False)
    assert [h["loss"] for h in on] == [h["loss"] for h in off]
    assert published == [[], []]               # once per step object
    ops = scopes.table()["jit_train_step"]
    assert {"optimizer", "lm_head", "attention", "moe.route",
            "moe.dispatch", "moe.expert_ffn", "moe.combine"} <= \
        set(ops.values())
