"""Training cells: the step that ``repro.runtime.train`` jits, driven by
a seeded stream of token rows.

Set-up builds one object, the compiled step with its state, as
``train()`` builds it (``make_train_step`` jitted with the state's
shardings and donation; on a mesh the state is created sharded), on
weights made from the seed, at the (n, strategy) the program's resolver
picks for the cell's tokens per chip. It drives that object through
steps 0-3 (step 0 compiles) and reads what the check needs: each step's
loss, the first gradient as the optimizer holds it after step 0, and the
parameters after step 2. The window then runs the same object, one step
after another, each on fresh rows, syncing on the step's metrics as
``train()`` does. Once the window has closed and the state is freed,
``bench/reference.py`` repeats the first three steps.
"""
from __future__ import annotations

import contextlib
import functools
import gc
import math
import time

import numpy as np

from bench import reference, traffic, weights
from bench.util import check, peak_bytes, span

WARM_STEPS = 4          # steps 0-3 run in set-up
GRAD_FLOOR = 1e-3       # leaves whose reference gradient is under this
                        # share of the median leaf's move by round-off


def _setup(ctx):
    import jax
    import jax.numpy as jnp

    from repro.core import resolve, resolve_hw
    from repro.distributed.context import DistContext
    from repro.launch.mesh import dp_axes, make_host_mesh
    from repro.models.api import get_model
    from repro.optim import get_optimizer
    from repro.runtime import train_loop as tl

    from bench import spec

    t = ctx.cell["params"]
    cfg = spec.program_config(ctx.conf)
    data, model = t["mesh"]["data"], t["mesh"]["model"]
    dist = None
    if data * model > 1:
        mesh = make_host_mesh(data, model)
        dist = DistContext(mesh=mesh, dp_axes=dp_axes(mesh),
                           ep_axis="model", tp_axis="model")
    ep, dp = (dist.ep_size, dist.dp_size) if dist else (1, 1)
    local_tokens = (t["batch"] // dp) * (t["seq"] // ep)
    rcfg = resolve(cfg, local_tokens=local_tokens, ep_size=ep,
                   hw=resolve_hw("auto"), dp=dp)
    opts = tl.TrainOptions(lr=t["optimizer"]["lr"],
                           warmup=t["optimizer"]["warmup"],
                           total_steps=t["optimizer"]["total_steps"])
    opt_mod, ocfg = get_optimizer(cfg.optimizer, opts.lr)
    for k in ("b1", "b2", "eps", "weight_decay"):
        if getattr(ocfg, k) != t["optimizer"][k]:
            raise SystemExit(f"optimizer {k}: program {getattr(ocfg, k)} "
                             f"!= cell {t['optimizer'][k]}")
    shardings = (tl.train_state_shardings(rcfg, opts, dist.mesh)
                 if dist else None)
    params = weights.make(ctx.model, ctx.seed,
                          shardings["params"] if shardings else None)
    weights.check_matches(params, get_model(cfg).abstract_params(cfg))
    state = jax.jit(
        lambda p: {"params": p, "opt": opt_mod.init(p, ocfg),
                   "step": jnp.zeros((), jnp.int32)},
        out_shardings=shardings, donate_argnums=0)(params)
    del params
    step_fn = tl._jit_step(tl.make_train_step(rcfg, opts, dist), shardings,
                           donate=True)
    n = rcfg.moe.num_partitions or 4
    n = max(1, min(n, local_tokens))
    while local_tokens % n:
        n -= 1
    info = {"dist": dist, "ep": ep, "n": n, "local_tokens": local_tokens,
            "ocfg": ocfg}
    ctx.devices = (list(dist.mesh.devices.flat) if dist
                   else [jax.devices()[0]])
    return state, step_fn, info


def _feed(ctx, info, step):
    import jax
    import jax.numpy as jnp

    from repro.runtime import train_loop as tl

    t = ctx.cell["params"]
    batch = {k: jnp.asarray(v) for k, v in traffic.token_batch(
        ctx.seed, step, t["batch"], t["seq"],
        ctx.model["vocab_size"]).items()}
    if info["dist"] is not None:
        batch = jax.device_put(batch, tl._batch_shardings(
            info["dist"].mesh, batch))
    return batch


def _state_bytes_per_device(state) -> dict:
    import jax

    out = {}
    for leaf in jax.tree_util.tree_leaves(state):
        for sh in leaf.addressable_shards:
            out[sh.device] = out.get(sh.device, 0) + sh.data.nbytes
    return out


def _rows_per_block(batch, seq, ep, group):
    """Fewest rows whose tokens on each chip fill whole dispatch
    groups."""
    per_row = seq // ep
    rows = group // math.gcd(group, per_row)
    if batch % rows:
        raise ValueError(f"{batch} rows do not split into blocks of whole "
                         f"dispatch groups ({group} tokens on each of "
                         f"{ep} chips)")
    return rows


def run(ctx, control: bool = False) -> bool:
    import jax

    t = ctx.cell["params"]
    state, step_fn, info = _setup(ctx)
    b1 = info["ocfg"].b1
    scope = (jax.set_mesh(info["dist"].mesh) if info["dist"]
             else contextlib.nullcontext())
    losses, grad_norms, params3 = [], None, None
    with scope:
        for step in range(WARM_STEPS):
            state, met = step_fn(state, _feed(ctx, info, step))
            losses.append(float(met["loss"]))
            if step == 0:
                # AdamW's first moment after one step is (1 - b1) * g
                grad_norms = {k: v / (1 - b1) for k, v in
                              reference.leaf_norms(state["opt"]["m"]).items()}
            if step == 2:
                params3 = jax.device_get(state["params"])
        tokens_per_step = t["batch"] * t["seq"]
        t0 = ctx.start_window()
        step, done = WARM_STEPS, 0
        while True:
            with span("bench.batch_upload"):
                batch = _feed(ctx, info, step)
            with span("bench.train_step"):
                state, met = step_fn(state, batch)
            with span("bench.metrics_sync"):
                loss = float(met["loss"])
            step, done = step + 1, done + 1
            if not math.isfinite(loss):
                ctx.failed += 1
            if time.perf_counter() - t0 >= ctx.seconds:
                break
        t1 = time.perf_counter()
        ctx.end_window(t1)
    ctx.attempted = done
    ctx.read_memory_peak()
    state_bytes = _state_bytes_per_device(state)
    beyond = max(peak_bytes(d) - state_bytes.get(d, 0)
                 for d in ctx.devices)
    ctx.e2e = {"train_tokens_per_s": done * tokens_per_step / (t1 - t0),
               "peak_hbm_gib": ctx.memory_peak_bytes / 2 ** 30,
               "setup_s": t0 - ctx.t_start}
    ctx.layer = {"tokens_per_s": ctx.e2e["train_tokens_per_s"],
                 "model": ctx.model, "seq": t["seq"],
                 "chips": len(ctx.devices),
                 "kind": ctx.devices[0].device_kind,
                 "beyond_state_bytes": beyond}
    del state, met, batch, step_fn
    gc.collect()
    return verify(ctx, info, losses[:3], grad_norms, params3, control)


def _gap(prog: dict, ref: dict, keys) -> tuple:
    """Worst leaf's |prog - ref| over max(ref leaf, median ref leaf)."""
    med = float(np.median([ref[k] for k in keys]))
    worst, name = 0.0, None
    for k in keys:
        g = abs(prog[k] - ref[k]) / max(ref[k], med, 1e-30)
        if g > worst:
            worst, name = g, k
    return worst, name


def readings(losses, grads, deltas, ref_losses, ref_grads, ref_deltas):
    """The three compared numbers of a run against the reference."""
    med = float(np.median(list(ref_grads.values())))
    moved = [k for k, v in ref_grads.items() if v >= GRAD_FLOOR * med]
    return {"loss_gap": max(abs(a - b) for a, b in zip(losses,
                                                        ref_losses)),
            "grad_gap": _gap(grads, ref_grads, list(ref_grads)),
            "delta_gap": _gap(deltas, ref_deltas, moved)}


def _delta_norms(p3, p0) -> dict:
    """Per-leaf norm of ``p3 - p0``; ``p3`` may live on the host."""
    import jax

    from bench.weights import flatten

    f3, f0 = flatten(p3), flatten(p0)
    return {k: float(_diff_norm(jax.device_put(f3[k], f0[k].sharding),
                                f0[k])) for k in f0}


@functools.lru_cache(maxsize=None)
def _diff_norm_fn():
    import jax
    import jax.numpy as jnp

    return jax.jit(lambda a, b: jnp.sqrt(jnp.sum(jnp.square(a - b))))


def _diff_norm(a, b):
    return _diff_norm_fn()(a, b)


def verify(ctx, info, losses, grad_norms, params3, control) -> bool:
    t = ctx.cell["params"]
    m = ctx.model
    group = info["local_tokens"] // info["n"]
    rows = _rows_per_block(t["batch"], t["seq"], info["ep"], group)
    batches = [traffic.token_batch(ctx.seed, s, t["batch"], t["seq"],
                                   m["vocab_size"]) for s in range(3)]
    rsh = (reference.expert_shardings(m, ctx.devices) if info["ep"] > 1
           else None)
    out = {}
    for prec in ("f32", "fp8") if control else ("f32",):
        ref_l, ref_g, ref_p3 = reference.train_readings(
            weights.make(m, ctx.seed, rsh), batches, m, t["optimizer"],
            group_tokens=group, ep=info["ep"], rows_per_block=rows,
            prec=prec)
        p0 = weights.make(m, ctx.seed, rsh)
        out[prec] = (ref_l, ref_g, _delta_norms(ref_p3, p0))
        del ref_p3, p0
        gc.collect()
    p0 = weights.make(m, ctx.seed, rsh)
    deltas = _delta_norms(params3, p0)
    del p0
    ref_l, ref_g, ref_d = out["f32"]
    r = readings(losses, grad_norms, deltas, ref_l, ref_g, ref_d)
    ctx.readings = {"program": r}
    if control:
        ctx.readings["control"] = readings(*out["fp8"], ref_l, ref_g, ref_d)
    lim = ctx.cell["limits"]
    ok = check(ctx, "loss_gap", r["loss_gap"], lim["loss_gap"])
    ok &= check(ctx, "grad_gap", r["grad_gap"][0], lim["grad_gap"])
    ok &= check(ctx, "delta_gap", r["delta_gap"][0], lim["delta_gap"])
    return ok
