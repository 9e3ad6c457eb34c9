"""Plain reference of the MoE decoder family, in straightforward
``jax.numpy``. It imports nothing of the program and reads only the
configuration file's ``model`` object and weights that ``bench/weights.py``
makes from the seed.

The model: learned token and position embeddings; per layer a pre-norm
causal multi-head attention block and a pre-norm FFN block, the FFN of
every ``moe_period``-th layer (at ``moe_offset``) a top-k mixture of
``num_experts`` GELU experts with Switch capacity (``capacity_factor``,
rounded up to ``capacity_multiple``) and overflow dropped, the others a
dense GELU FFN; a final LayerNorm and an untied LM head. The loss is the
mean next-token cross entropy plus, per MoE layer, the Switch
load-balance loss and the router z-loss, each averaged over the
layer's dispatch groups.

``prec`` selects the arithmetic: ``"f32"`` runs every product in float32
at ``Precision.HIGHEST``; ``"fp8"`` rounds both operands of every product
to float8 (e4m3, per-tensor scaled) first and their gradients to e5m2,
accumulating in float32. That is the control: the reference one
precision step below the configuration's bfloat16.
"""
from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np

F8 = jnp.float8_e4m3fn


def _round(x, dtype):
    """Round ``x`` to ``dtype`` under a per-tensor scale that maps its
    largest magnitude to the format's largest finite value."""
    top = float(jnp.finfo(dtype).max)
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / top
    return (x / scale).astype(dtype).astype(jnp.float32) * scale


@jax.custom_vjp
def _fp8(x):
    return _round(x, F8)


def _fp8_fwd(x):
    return _fp8(x), None


def _fp8_bwd(_, g):
    # gradients travel in e5m2, the wider-range fp8 format, as fp8
    # training does
    return (_round(g, jnp.float8_e5m2),)


_fp8.defvjp(_fp8_fwd, _fp8_bwd)


def _dot(spec, a, b, prec):
    if prec == "fp8":
        # fp8 values are exact in bf16, so one bf16 pass (the default
        # precision) multiplies them exactly, accumulating in float32
        return jnp.einsum(spec, _fp8(a.astype(jnp.float32)),
                          _fp8(b.astype(jnp.float32)))
    return jnp.einsum(spec, a.astype(jnp.float32), b.astype(jnp.float32),
                      precision=jax.lax.Precision.HIGHEST)


def _layernorm(x, p, eps):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def _gelu(x):
    return 0.5 * x * (1 + jnp.tanh(math.sqrt(2 / math.pi)
                                   * (x + 0.044715 * x ** 3)))


def capacity(m: dict, group_tokens: int) -> int:
    cap = max(1, math.ceil(group_tokens * m["top_k"] * m["capacity_factor"]
                           / m["num_experts"]))
    mult = m.get("capacity_multiple", 1)
    return -(-cap // mult) * mult


def _moe(x, p, m, prec, group_tokens, dropless):
    """x [N, D] in dispatch-group order -> (y [N, D], aux [G], z [G])."""
    n, d = x.shape
    e, k = m["num_experts"], m["top_k"]
    g = group_tokens
    xg = x.reshape(n // g, g, d)
    logits = _dot("gtd,de->gte", xg, p["router"]["w_gate"], prec)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_i = jax.lax.top_k(probs, k)
    if k > 1:
        top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)
    cap = g * k if dropless else capacity(m, g)
    # route j of token t is the (t*k + j)-th claim on its expert; a claim
    # beyond the expert's capacity is dropped
    ids = top_i.reshape(n // g, g * k)
    claims = jax.nn.one_hot(ids, e, dtype=jnp.int32)        # [G,gk,E]
    pos = jnp.take_along_axis(jnp.cumsum(claims, axis=1) - claims,
                              ids[..., None], -1)[..., 0]   # [G,gk]
    keep = pos < cap
    dest = jnp.where(keep, ids * cap + pos, e * cap)        # overflow row
    grp = jnp.arange(n // g)[:, None]
    src = jnp.repeat(xg, k, axis=1)                         # [G,gk,D]
    buf = jnp.zeros((n // g, e * cap + 1, d), x.dtype)
    buf = buf.at[grp, dest].add(src)[:, :-1].reshape(n // g, e, cap, d)
    h = _gelu(_dot("gecd,edf->gecf", buf, p["experts"]["w_up"], prec))
    out = _dot("gecf,efd->gecd", h, p["experts"]["w_down"], prec)
    out = jnp.concatenate([out.reshape(n // g, e * cap, d),
                           jnp.zeros((n // g, 1, d), out.dtype)], 1)
    routed = out[grp, dest] * (keep * top_p.reshape(n // g, g * k)
                               )[..., None]
    y = routed.reshape(n // g, g, k, d).sum(2)
    f_e = jax.nn.one_hot(top_i[..., 0], e).mean(1)          # [G,E]
    p_e = probs.mean(1)
    aux = e * (f_e * p_e).sum(-1) * m["aux_loss_weight"]
    z = (jax.scipy.special.logsumexp(logits, -1) ** 2).mean(-1) \
        * m["z_loss_weight"]
    return y.reshape(n, d), aux, z


def forward(params, tokens, m: dict, prec: str = "f32", *,
            group_tokens: int = 0, ep: int = 1, dropless: bool = False,
            remat: bool = False):
    """tokens [B, T] -> (final hidden [B, T, D] float32, aux, z), where aux and z
    hold one entry per dispatch group of every MoE layer. A dispatch group
    is ``group_tokens`` consecutive tokens of the batch flattened row by
    row; with ``ep > 1`` the rows are first cut into ``ep`` equal
    sequence slices, slice ``q`` of every row forming the tokens of chip
    ``q`` (sequence-parallel expert parallelism), and groups are taken
    within each chip's tokens. ``group_tokens=0`` makes every row one
    group."""
    b, t = tokens.shape
    d, hd, eps = m["d_model"], m["head_dim"], m["norm_eps"]
    emb = params["embed"]
    x = emb["tok"][tokens] + emb["pos"][jnp.arange(t)][None]
    mask = jnp.tril(jnp.ones((t, t), bool))
    per = m["moe_period"]

    def layer(x, lp, moe):
        a = _layernorm(x, lp["mixer_norm"], eps)
        q = _dot("btd,dhe->bthe", a, lp["mixer"]["w_q"], prec)
        k = _dot("btd,dhe->bthe", a, lp["mixer"]["w_k"], prec)
        v = _dot("btd,dhe->bthe", a, lp["mixer"]["w_v"], prec)
        s = _dot("bqhe,bkhe->bhqk", q, k, prec) * hd ** -0.5
        s = jnp.where(mask, s, -jnp.inf)
        o = _dot("bhqk,bkhe->bqhe", jax.nn.softmax(s, -1), v, prec)
        x = x + _dot("bqhe,hed->bqd", o, lp["mixer"]["w_o"], prec)
        a = _layernorm(x, lp["ffn_norm"], eps)
        if not moe:
            f = _gelu(_dot("btd,df->btf", a, lp["ffn"]["w_up"], prec))
            return x + _dot("btf,fd->btd", f, lp["ffn"]["w_down"], prec), \
                None, None
        xs = a.reshape(b, ep, t // ep, d).transpose(1, 0, 2, 3)
        y, aux, z = _moe(xs.reshape(b * t, d), lp["moe"], m, prec,
                         group_tokens or t, dropless)
        y = y.reshape(ep, b, t // ep, d).transpose(1, 0, 2, 3)
        return x + y.reshape(b, t, d), aux, z

    auxes, zs = [], []
    for i in range(m["num_layers"]):
        lp = jax.tree_util.tree_map(
            lambda a: a[i // per], params["periods"][f"l{i % per}"])
        moe = i % per == m["moe_offset"]
        fn = functools.partial(layer, moe=moe)
        if remat:
            fn = jax.checkpoint(fn)
        x, aux, z = fn(x, lp)
        if moe:
            auxes.append(aux)
            zs.append(z)
    x = _layernorm(x, params["final_norm"], eps)
    return x, jnp.stack(auxes), jnp.stack(zs)


def head(params, x, prec):
    return _dot("btd,dv->btv", x, params["embed"]["head"], prec)


# ---------------------------------------------------------------------------
# Serving: teacher-forced logits over prompt + served tokens
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _logits_fn(mjson: str, prec: str):
    m = json.loads(mjson)
    return jax.jit(lambda p, toks: head(p, forward(
        p, toks, m, prec, dropless=True)[0], prec)[0])


def served_gaps(params, seqs, m: dict, pad_to: int, control: bool = False):
    """For each ``(prompt, served)`` pair: the widest gap by which a
    served token's reference logit lies below the reference's best at
    its position. With ``control``, also the gap of the token the fp8
    control would put first at each of those positions. Returns
    ``(program_gaps, control_gaps)`` (the latter empty without
    ``control``)."""
    mj = json.dumps(m, sort_keys=True)
    ref = _logits_fn(mj, "f32")
    low = _logits_fn(mj, "fp8") if control else None
    gaps, cgaps = [], []
    for prompt, served in seqs:
        p, g = len(prompt), len(served)
        toks = np.zeros((1, pad_to), np.int32)
        seq = np.concatenate([prompt, served[:-1]]).astype(np.int32)
        toks[0, :seq.size] = seq
        rows = np.asarray(ref(params, toks))[p - 1:p - 1 + g]
        best = rows.max(-1)
        gaps.append(float((best - rows[np.arange(g), served]).max()))
        if control:
            crow = np.asarray(low(params, toks))[p - 1:p - 1 + g]
            pick = crow.argmax(-1)
            cgaps.append(float((best - rows[np.arange(g), pick]).max()))
    return gaps, cgaps


# ---------------------------------------------------------------------------
# Training: three AdamW steps, computed in blocks of rows
# ---------------------------------------------------------------------------

def lr_scale(step: int, warmup: int, total: int, min_ratio: float) -> float:
    """Linear warm-up from 0 at step 0, then cosine decay to
    ``min_ratio``."""
    warm = min(step / max(warmup, 1), 1.0)
    prog = min(max((step - warmup) / max(total - warmup, 1), 0.0), 1.0)
    return warm * (min_ratio + (1 - min_ratio) * 0.5
                   * (1 + math.cos(math.pi * prog)))


def _block_loss(params, tokens, labels, m, prec, group_tokens, ep,
                n_tokens, n_groups):
    x, aux, z = forward(params, tokens, m, prec, group_tokens=group_tokens,
                        ep=ep, remat=True)

    # the head and its cross entropy one row at a time, recomputed in the
    # backward pass, so that no [B, T, V] logits are ever held
    @jax.checkpoint
    def row_nll(xr, lr):
        logits = head(params, xr[None], prec)[0]
        lse = jax.scipy.special.logsumexp(logits, -1)
        return (lse - jnp.take_along_axis(logits, lr[:, None], -1)[:, 0]
                ).sum()

    nll = jax.lax.map(lambda a: row_nll(*a), (x, labels)).sum()
    return nll / n_tokens + (aux.sum() + z.sum()) / n_groups


@functools.lru_cache(maxsize=None)
def _grad_fns(mjson, prec, group_tokens, ep, n_tokens, n_groups):
    m = json.loads(mjson)
    loss = functools.partial(_block_loss, m=m, prec=prec,
                             group_tokens=group_tokens, ep=ep,
                             n_tokens=n_tokens, n_groups=n_groups)

    def acc(carry, params, tokens, labels):
        l, g = jax.value_and_grad(loss)(params, tokens, labels)
        lsum, gsum = carry
        return lsum + l, jax.tree_util.tree_map(jnp.add, gsum, g)

    def adamw(params, mom, vel, grads, count, lr, b1, b2, eps, wd):
        b1c, b2c = 1 - b1 ** count, 1 - b2 ** count

        def one(p, mo, ve, g):
            mo = b1 * mo + (1 - b1) * g
            ve = b2 * ve + (1 - b2) * g * g
            upd = (mo / b1c) / (jnp.sqrt(ve / b2c) + eps) + wd * p
            return p - lr * upd, mo, ve
        out = jax.tree_util.tree_map(one, params, mom, vel, grads)
        pick = lambda i: jax.tree_util.tree_map(  # noqa: E731
            lambda o: o[i], out, is_leaf=lambda o: isinstance(o, tuple))
        return pick(0), pick(1), pick(2)

    return (jax.jit(jax.value_and_grad(loss)),
            jax.jit(acc, donate_argnums=0),
            jax.jit(adamw, donate_argnums=(0, 1, 2)))


@jax.jit
def _norms(flat):
    return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
            for k, v in flat.items()}


def leaf_norms(tree) -> dict:
    """Per-leaf Euclidean norms, keyed by the leaf's path."""
    from bench.weights import flatten
    return {k: float(v) for k, v in _norms(flatten(tree)).items()}


def _zeros_like(tree):
    """float32 zeros placed like ``tree``'s leaves."""
    return jax.jit(lambda t: jax.tree_util.tree_map(
        lambda a: jnp.zeros(a.shape, jnp.float32), t),
        out_shardings=jax.tree_util.tree_map(lambda a: a.sharding, tree)
    )(tree)


def expert_shardings(m: dict, devices):
    """Placement for the reference's state on several chips: every
    expert leaf split over the chips along its expert axis, the rest
    whole on each (the arithmetic does not change, only where it runs)."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from bench.weights import layout, nest

    mesh = Mesh(np.asarray(devices), ("e",))
    return nest({k: NamedSharding(mesh, P(None, "e") if "/experts/" in k
                                  else P())
                 for k in layout(m)})


def train_readings(params, batches, m: dict, opt: dict, *,
                   group_tokens: int, ep: int = 1, rows_per_block: int,
                   prec: str = "f32", steps: int = 3):
    """Run ``steps`` AdamW steps from ``params`` (consumed) on
    ``batches`` (host dicts of ``tokens``/``labels``). Returns
    ``(losses, first_grad_norms, params_after)``; the gradient norms are
    per leaf, of the step-0 gradient."""
    b, t = batches[0]["tokens"].shape
    n_tokens = b * t
    n_groups = n_tokens // group_tokens
    mj = json.dumps(m, sort_keys=True)
    first, acc, adamw = _grad_fns(mj, prec, group_tokens, ep, n_tokens,
                                  n_groups)
    zeros = _zeros_like
    mom, vel = zeros(params), zeros(params)
    losses, gnorms = [], None
    for step in range(steps):
        # the first block's gradient starts the sum: no zero tree held
        # beside it
        rows = [slice(r, r + rows_per_block)
                for r in range(0, b, rows_per_block)]
        carry = first(params, batches[step]["tokens"][rows[0]],
                      batches[step]["labels"][rows[0]])
        for sl in rows[1:]:
            carry = acc(carry, params, batches[step]["tokens"][sl],
                        batches[step]["labels"][sl])
        loss, grads = carry
        losses.append(float(loss))
        if step == 0:
            gnorms = leaf_norms(grads)
        lr = opt["lr"] * lr_scale(step, opt["warmup"], opt["total_steps"],
                                  opt["min_lr_ratio"])
        params, mom, vel = adamw(params, mom, vel, grads,
                                 float(step + 1), lr, opt["b1"], opt["b2"],
                                 opt["eps"], opt["weight_decay"])
        del grads, carry
    return losses, gnorms, params
