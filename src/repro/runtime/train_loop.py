"""Training runtime: step construction + fault-tolerant host loop.

Step semantics:
* bf16 compute / fp32 params (+ optimizer-dependent state);
* optional int8 gradient compression with error feedback (cross-pod);
* MoE aux losses folded into the objective by the model's loss_fn.

Fault tolerance (DESIGN §8): the loop checkpoints every
``ckpt_every`` steps (async, atomic), rolls a failed step back to the
latest checkpoint (at most ``max_retries`` times in a row — the live
state was donated to the failed step, so it is never re-used), raises
the step's own error when there is no checkpoint, and emits heartbeats
a cluster monitor can watch for stragglers. The data pipeline is
seekable, so restart resumes at the exact batch.

Placement: with a ``dist`` mesh, parameters and optimizer state are
created already sharded (``state_shardings``: the logical-axis rules of
``distributed.sharding`` — experts over the expert axis, FSDP over the
data axes) and every step is jitted with those shardings in and out,
so no device ever holds the unsharded state.

Online adaptation (paper §III-C Algorithm 1 + §III-E): when the config
leaves ``num_partitions == 0`` or ``memory_reuse_strategy ==
"adaptive"``, an :class:`AdaptiveController` resolves the concrete
(n, strategy) at runtime — on every batch-shape change and, optionally,
every ``retune_every`` steps — through a persistent
``selector.Resolver``, and re-jits only when the resolved
(n, strategy, batch_shape) key is new. Revisited configurations hit the
compiled-step cache and are free.
"""
from __future__ import annotations

import contextlib
import dataclasses
import logging
import time
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.core.selector import Resolver
from repro.core.types import TPU_V5E, HardwareSpec, Strategy
from repro.obs import Recorder, scopes
from repro.distributed import sharding as shd
from repro.distributed.compression import compress_with_feedback
from repro.models.api import get_model
from repro.optim import get_optimizer, lr_schedule, state_shardings

log = logging.getLogger("repro.runtime")


@dataclasses.dataclass
class TrainOptions:
    lr: float = 3e-4
    warmup: int = 100
    total_steps: int = 10_000
    compress_grads: bool = False
    grad_accum: int = 1              # microbatching (PP-free memory lever)
    use_kernel: bool = False


def init_state(cfg: ArchConfig, key, opts: TrainOptions,
               shardings=None) -> Dict[str, Any]:
    """Fresh train state. ``shardings`` (a :func:`train_state_shardings`
    tree) builds every leaf directly in its sharded placement."""
    def build(key):
        model = get_model(cfg)
        params = model.init(cfg, key)
        opt_mod, ocfg = get_optimizer(cfg.optimizer, opts.lr)
        state = {"params": params, "opt": opt_mod.init(params, ocfg),
                 "step": jnp.zeros((), jnp.int32)}
        if opts.compress_grads:
            state["grad_err"] = jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
        return state
    if shardings is None:
        return build(key)
    return jax.jit(build, out_shardings=shardings)(key)


def train_state_shardings(cfg: ArchConfig, opts: TrainOptions, mesh
                          ) -> Dict[str, Any]:
    """NamedSharding tree of the train state on ``mesh``: parameters by
    the "train" logical-axis rules, optimizer moments wherever their
    parameter shard lives, the step counter replicated."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    model = get_model(cfg)
    pshard = shd.param_shardings(cfg, shd.make_rules(mesh, "train"), model)
    opt_mod, ocfg = get_optimizer(cfg.optimizer, opts.lr)
    out = {"params": pshard,
           "opt": state_shardings(opt_mod, ocfg, model.abstract_params(cfg),
                                  pshard, mesh),
           "step": NamedSharding(mesh, P())}
    if opts.compress_grads:
        out["grad_err"] = pshard
    return out


def _batch_shardings(mesh, batch) -> Dict[str, Any]:
    """Batch dim over the data axes (when it divides), the rest whole."""
    rules = shd.make_rules(mesh, "train")
    return {k: rules.sharding_for(v.shape,
                                  ("batch",) + (None,) * (v.ndim - 1), k)
            for k, v in batch.items()}


def _jit_step(fn, shardings, donate: bool):
    """jit a train step; with ``shardings`` the state enters and leaves
    in its sharded placement (metrics are replicated scalars)."""
    kw = {"donate_argnums": (0,)} if donate else {}
    if shardings is not None:
        kw.update(in_shardings=(shardings, None),
                  out_shardings=(shardings, None))
    return _ScopedStep(jax.jit(fn, **kw))


class _ScopedStep:
    """A jitted train step that puts its program's device scopes into
    the process's scope table (``repro.obs.scopes``) after its first
    call. Lowering with that call's arguments (donated ones included)
    hits jit's caches: nothing is traced or compiled again."""

    def __init__(self, jitted):
        self.jitted = jitted
        self._published = False

    def __call__(self, *args):
        out = self.jitted(*args)
        if not self._published:
            self._published = True
            self._publish(args)
        return out

    def _publish(self, args) -> None:
        scopes.register_compiled(self.jitted.lower(*args).compile())


def abstract_state(cfg: ArchConfig, opts: TrainOptions) -> Dict[str, Any]:
    model = get_model(cfg)
    aparams = model.abstract_params(cfg)
    opt_mod, ocfg = get_optimizer(cfg.optimizer, opts.lr)
    state = {"params": aparams,
             "opt": opt_mod.abstract_state(aparams, ocfg),
             "step": jax.ShapeDtypeStruct((), jnp.int32)}
    if opts.compress_grads:
        state["grad_err"] = jax.tree_util.tree_map(
            lambda p: jax.ShapeDtypeStruct(p.shape, jnp.float32), aparams)
    return state


def make_train_step(cfg: ArchConfig, opts: TrainOptions, dist=None
                    ) -> Callable:
    model = get_model(cfg)
    opt_mod, ocfg = get_optimizer(cfg.optimizer, opts.lr)

    def loss_of(params, batch):
        return model.loss_fn(params, batch, cfg, dist=dist,
                             use_kernel=opts.use_kernel)

    def train_step(state, batch):
        if opts.grad_accum > 1:
            def micro(carry, mb):
                (l, m), g = jax.value_and_grad(loss_of, has_aux=True)(
                    state["params"], mb)
                acc_g, acc_m = carry
                acc_g = jax.tree_util.tree_map(jnp.add, acc_g, g)
                acc_m = jax.tree_util.tree_map(jnp.add, acc_m, m)
                return (acc_g, acc_m), None
            zeros_g = jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, jnp.float32), state["params"])
            mbs = jax.tree_util.tree_map(
                lambda x: x.reshape((opts.grad_accum,
                                     x.shape[0] // opts.grad_accum)
                                    + x.shape[1:]), batch)
            # zero metric carry from the model's actual metrics pytree
            # (loss_fn implementations differ in their metric keys)
            mb0 = jax.tree_util.tree_map(lambda x: x[0], mbs)
            _, m_shapes = jax.eval_shape(loss_of, state["params"], mb0)
            zeros_m = jax.tree_util.tree_map(
                lambda s: jnp.zeros(s.shape, s.dtype), m_shapes)
            (grads, metrics), _ = jax.lax.scan(micro, (zeros_g, zeros_m),
                                               mbs)
            grads = jax.tree_util.tree_map(
                lambda g: g / opts.grad_accum, grads)
            metrics = jax.tree_util.tree_map(
                lambda m: m / opts.grad_accum, metrics)
        else:
            (loss, metrics), grads = jax.value_and_grad(
                loss_of, has_aux=True)(state["params"], batch)

        new_state = dict(state)
        if opts.compress_grads:
            grads, new_err = compress_with_feedback(grads,
                                                    state.get("grad_err"))
            new_state["grad_err"] = new_err

        scale = lr_schedule(state["step"], warmup=opts.warmup,
                            total=opts.total_steps)
        params, opt = opt_mod.update(grads, state["opt"], state["params"],
                                     ocfg, lr_scale=scale)
        new_state.update(params=params, opt=opt, step=state["step"] + 1)
        gnorm = jax.tree_util.tree_reduce(
            lambda a, g: a + jnp.sum(g.astype(jnp.float32) ** 2), grads,
            jnp.zeros((), jnp.float32))
        metrics = dict(metrics, grad_norm=jnp.sqrt(gnorm), lr_scale=scale)
        return new_state, metrics

    return train_step


# ---------------------------------------------------------------------------
# Online adaptive (n, strategy) controller
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class AdaptiveOptions:
    """Knobs of the online controller (paper §III-C + §III-E).

    retune_every == 0 retunes only on batch-shape change; k > 0 also
    re-runs ``resolve`` every k steps (workload drift without shape
    drift — e.g. interference from a colocated job).
    ``measure``: "wallclock" times a few compiled candidate steps on the
    attached accelerator, "simulate" uses the analytic pipeline
    simulator, "auto" picks wallclock iff a non-CPU backend is attached.
    """
    retune_every: int = 0
    ep_size: int = 1
    dp: int = 1
    hw: HardwareSpec = TPU_V5E
    measure: str = "auto"            # auto | wallclock | simulate
    measure_fn: Optional[Callable[[int, int, Strategy], float]] = None
    measure_steps: int = 2
    allow_offload: Optional[bool] = None
    candidates: Optional[Sequence[int]] = None
    cache_size: int = 32             # LRU bound on kept compiled steps
    obs: Optional["Recorder"] = None  # telemetry recorder shared with
                                      # the resolver (None = private
                                      # metrics-only recorder)


class AdaptiveController:
    """Feedback loop between the granularity searcher, the perf model and
    the step function: resolves (n, strategy) online and keeps a
    compiled-step cache keyed by (n, strategy, batch_shape) so that
    re-jit happens at most once per distinct configuration.
    """

    def __init__(self, cfg: ArchConfig, opts: TrainOptions, dist=None,
                 aopts: Optional[AdaptiveOptions] = None, *,
                 jit: bool = True):
        if cfg.moe is None or not cfg.moe.pipeline:
            # with pipeline=False every candidate n lowers to the same
            # n=1 program — the granularity search would be meaningless
            raise ValueError("AdaptiveController needs a pipelined MoE "
                             "config (cfg.moe with pipeline=True)")
        self.cfg = cfg
        self.opts = opts
        self.dist = dist
        self.jit = jit
        # (n, strategy) never changes the state's shapes, so one
        # placement serves every candidate step
        self.shardings = (train_state_shardings(cfg, opts, dist.mesh)
                          if dist is not None else None)
        self.aopts = aopts or AdaptiveOptions()
        if dist is not None:
            # derive the EP/DP extents from the live mesh unless the
            # caller set them: a 1-wide default under an 8-way EP mesh
            # would resolve (n, strategy) for the wrong workload
            if self.aopts.ep_size == 1:
                self.aopts = dataclasses.replace(self.aopts,
                                                 ep_size=dist.ep_size)
            if self.aopts.dp == 1:
                self.aopts = dataclasses.replace(self.aopts,
                                                 dp=dist.dp_size)
        measure_fn = self.aopts.measure_fn
        if measure_fn is None:
            mode = self.aopts.measure
            if mode == "auto":
                mode = ("wallclock" if jax.default_backend() != "cpu"
                        else "simulate")
            if mode == "wallclock":
                measure_fn = self._wallclock_measure
        self.obs = (self.aopts.obs if self.aopts.obs is not None
                    else Recorder())
        self.resolver = Resolver(cfg, ep_size=self.aopts.ep_size,
                                 hw=self.aopts.hw, measure_fn=measure_fn,
                                 dp=self.aopts.dp,
                                 allow_offload=self.aopts.allow_offload,
                                 candidates=self.aopts.candidates,
                                 obs=self.obs)
        self._step_cache: Dict[Tuple, Callable] = {}
        self._measure_cache: Dict[Tuple, Callable] = {}
        self._probe = None               # (state, batch) for wallclock
        self._last_shape = None
        self._last_retune = None
        self._last_refresh = None
        self.current: Optional[Tuple[int, str]] = None
        self.rejit_count = 0
        self.retune_count = 0

    def _cache_get(self, cache: Dict[Tuple, Callable], key: Tuple):
        """LRU: dicts iterate in insertion order; re-insert on hit."""
        fn = cache.pop(key, None)
        if fn is not None:
            cache[key] = fn
        return fn

    def _cache_put(self, cache: Dict[Tuple, Callable], key: Tuple, fn):
        cache[key] = fn
        while len(cache) > max(1, self.aopts.cache_size):
            cache.pop(next(iter(cache)))

    @staticmethod
    def _shape_key(batch) -> Tuple:
        return tuple(sorted((k, tuple(v.shape), str(v.dtype))
                            for k, v in batch.items()))

    @staticmethod
    def _local_tokens(batch) -> int:
        x = batch["tokens"] if "tokens" in batch else \
            next(iter(batch.values()))
        return int(x.shape[0]) * (int(x.shape[1]) if x.ndim > 1 else 1)

    def _cfg_with(self, n: int, strategy: str) -> ArchConfig:
        return dataclasses.replace(
            self.cfg, moe=dataclasses.replace(
                self.cfg.moe, num_partitions=n,
                memory_reuse_strategy=strategy))

    def _wallclock_measure(self, b: int, n: int,
                           strategy: Strategy) -> float:
        """Algorithm 1's measure function on real hardware: time a few
        compiled steps of candidate n against the live (state, batch).
        ``b`` equals the probe batch's token count by construction (the
        searcher is always queried at the current batch size)."""
        state, batch = self._probe
        # compiled candidates are cached across retunes (a periodic
        # refresh re-times them; only the timing is stale, not the
        # executable). The winner is still compiled once more with
        # donation for the step cache — the price of donating there.
        key = (n, strategy.value, self._shape_key(batch))
        fn = self._cache_get(self._measure_cache, key)
        if fn is None:
            fn = make_train_step(self._cfg_with(n, strategy.value),
                                 self.opts, self.dist)
            if self.jit:                 # no donation: state is reused
                fn = _jit_step(fn, self.shardings, donate=False)
            self._cache_put(self._measure_cache, key, fn)
        out = fn(state, batch)
        jax.block_until_ready(out)       # compile + warm up
        reps = max(1, self.aopts.measure_steps)
        t0 = time.perf_counter()
        for _ in range(reps):
            out = fn(state, batch)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / reps

    def step_fn(self, state, batch, step: int
                ) -> Tuple[Callable, Dict[str, Any]]:
        """Step function + controller metrics for this batch."""
        shape = self._shape_key(batch)
        shape_changed = self.current is None or shape != self._last_shape
        # timer fires on its own clock, independent of shape churn — a
        # cyclic-shape trace must not starve drift re-measurement
        timer = (self.aopts.retune_every > 0
                 and self._last_refresh is not None
                 and step - self._last_refresh >= self.aopts.retune_every)
        info: Dict[str, Any] = {}
        if shape_changed or timer:
            t0 = time.perf_counter()
            self._probe = (state, batch)
            # a timer-triggered retune re-measures (refresh): cached
            # timings are exactly what workload drift invalidates
            rcfg = self.resolver.resolve(self._local_tokens(batch),
                                         refresh=timer)
            self._probe = None
            resolved = (rcfg.moe.num_partitions,
                        rcfg.moe.memory_reuse_strategy)
            if resolved != self.current:
                log.info("adaptive retune @%d: (n, strategy) %s -> %s",
                         step, self.current, resolved)
            self.current = resolved
            self._last_shape = shape
            self._last_retune = step
            if timer or self._last_refresh is None:
                self._last_refresh = step
            self.retune_count += 1
            info["retune_time_s"] = time.perf_counter() - t0
        n, strategy = self.current
        key = (n, strategy, shape)
        fn = self._cache_get(self._step_cache, key)
        if fn is None:
            fn = make_train_step(self._cfg_with(n, strategy), self.opts,
                                 self.dist)
            if self.jit:
                fn = _jit_step(fn, self.shardings, donate=True)
            self._cache_put(self._step_cache, key, fn)
            self.rejit_count += 1
        info.update(n=n, strategy=strategy)
        return fn, info


# ---------------------------------------------------------------------------
# Fault-tolerant host loop
# ---------------------------------------------------------------------------

def train(cfg: ArchConfig, *, steps: int, batch_source,
          opts: Optional[TrainOptions] = None, dist=None,
          checkpointer=None, ckpt_every: int = 100, max_retries: int = 2,
          heartbeat: Optional[Callable[[int, Dict], None]] = None,
          state=None, jit: bool = True, adaptive=None):
    """Run ``steps`` training steps with checkpoint/restart semantics.

    ``batch_source.batch_at(step)`` must be deterministic (seekable).
    ``adaptive`` selects the online (n, strategy) controller: ``None``
    auto-enables it when cfg.moe still carries adaptive placeholders
    (``num_partitions == 0`` or ``memory_reuse_strategy ==
    "adaptive"``); pass ``False`` to force the static path, an
    :class:`AdaptiveOptions` to tune it, or a pre-built
    :class:`AdaptiveController` (benchmarks/tests inspect its counters).
    With ``dist`` (and ``jit``) the state is created and kept sharded
    over ``dist.mesh`` (:func:`train_state_shardings`) and the loop runs
    under that mesh.
    Returns (final_state, history list of metric dicts).
    """
    opts = opts or TrainOptions()
    controller = None
    if isinstance(adaptive, AdaptiveController):
        controller = adaptive
    elif adaptive is None:
        if cfg.moe is not None and cfg.moe.pipeline and (
                cfg.moe.num_partitions == 0
                or cfg.moe.memory_reuse_strategy == "adaptive"):
            controller = AdaptiveController(cfg, opts, dist, jit=jit)
    elif adaptive:
        aopts = adaptive if isinstance(adaptive, AdaptiveOptions) else None
        controller = AdaptiveController(cfg, opts, dist, aopts, jit=jit)

    shardings = None
    if dist is not None and jit:
        shardings = (controller.shardings if controller is not None
                     else train_state_shardings(cfg, opts, dist.mesh))
    mesh_scope = (jax.set_mesh(dist.mesh) if dist is not None
                  else contextlib.nullcontext())
    with mesh_scope:
        if state is None:
            state = init_state(cfg, jax.random.PRNGKey(0), opts, shardings)
        start = 0
        if checkpointer is not None:
            restored = checkpointer.restore_latest(abstract=None)
            if restored is not None:
                state, start = restored["state"], int(restored["step"])
                if shardings is not None:
                    state = jax.device_put(state, shardings)
                log.info("restored checkpoint at step %d", start)

        if controller is None:
            step_fn = make_train_step(cfg, opts, dist)
            if jit:
                step_fn = _jit_step(step_fn, shardings, donate=True)

        history = []
        step = start
        failures = 0                       # consecutive failed steps
        while step < steps:
            batch = {k: jnp.asarray(v)
                     for k, v in batch_source.batch_at(step).items()}
            if shardings is not None:
                batch = jax.device_put(batch, _batch_shardings(dist.mesh,
                                                               batch))
            try:
                ainfo = {}
                if controller is not None:
                    step_fn, ainfo = controller.step_fn(state, batch, step)
                t0 = time.perf_counter()
                state, metrics = step_fn(state, batch)
                metrics = {k: float(v) for k, v in metrics.items()}
                metrics["step_time_s"] = time.perf_counter() - t0
                metrics.update(ainfo)
            except Exception:
                # the failed call may have consumed the donated state,
                # so the only safe retry starts from a checkpoint;
                # without one the step's own error is the one to raise
                failures += 1
                log.exception("step %d failed (%d in a row)", step, failures)
                restored = (checkpointer.restore_latest(abstract=None)
                            if checkpointer is not None else None)
                if restored is None or failures > max_retries:
                    raise
                state, step = restored["state"], int(restored["step"])
                if shardings is not None:
                    state = jax.device_put(state, shardings)
                log.warning("rolled back to step %d", step)
                continue
            failures = 0
            history.append({"step": step, **metrics})
            if heartbeat is not None:
                heartbeat(step, metrics)
            step += 1
            if checkpointer is not None and step % ckpt_every == 0:
                checkpointer.save(step, state)
        if checkpointer is not None:
            checkpointer.save(steps, state, block=True)
        return state, history
