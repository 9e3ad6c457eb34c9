"""Seeded weights of the MoE decoder family, made on the device.

The benchmark, not the program, makes the weights: one jitted call from
the seed builds every leaf in the type it is served in (float32, as the
configuration states). The layout is the harness's own description of the
family; ``bench/drivers`` check it leaf by leaf against the program's
parameter tree before handing it over, and ``bench/reference.py`` reads
the same description. Layers are stacked per period position: a leaf of
period position ``l<i>`` has a leading axis of ``num_layers / period``.
"""
from __future__ import annotations

import math
from typing import Dict, Tuple

import numpy as np

# init kinds: ("normal", std) | ("ones",) | ("zeros",)
Leaf = Tuple[Tuple[int, ...], tuple]


def layout(m: dict) -> Dict[str, Leaf]:
    """Flat ``{"a/b/c": (shape, init)}`` of every parameter."""
    d, h, hd = m["d_model"], m["num_heads"], m["head_dim"]
    kvh = m.get("num_kv_heads", h)
    e, fe, f, v = m["num_experts"], m["d_expert"], m["d_ff"], m["vocab_size"]
    p = m["moe_period"]
    assert m["num_layers"] % p == 0, (m["num_layers"], p)
    n = m["num_layers"] // p

    def normal(fan_in):
        return ("normal", 1.0 / math.sqrt(fan_in))

    out = {
        "embed/tok": ((v, d), ("normal", 1.0)),
        "embed/pos": ((m["max_position"], d), ("normal", 0.02)),
        "embed/head": ((d, v), normal(d)),
        "final_norm/scale": ((d,), ("ones",)),
        "final_norm/bias": ((d,), ("zeros",)),
    }
    for i in range(p):
        pre = f"periods/l{i}/"
        moe = i % m["moe_period"] == m["moe_offset"]
        for norm in ("mixer_norm", "ffn_norm"):
            out[pre + norm + "/scale"] = ((n, d), ("ones",))
            out[pre + norm + "/bias"] = ((n, d), ("zeros",))
        out[pre + "mixer/w_q"] = ((n, d, h, hd), normal(d))
        out[pre + "mixer/w_k"] = ((n, d, kvh, hd), normal(d))
        out[pre + "mixer/w_v"] = ((n, d, kvh, hd), normal(d))
        out[pre + "mixer/w_o"] = ((n, h, hd, d), normal(h * hd))
        if moe:
            out[pre + "moe/router/w_gate"] = ((n, d, e), ("normal", 0.02))
            out[pre + "moe/experts/w_up"] = ((n, e, d, fe), normal(d))
            out[pre + "moe/experts/w_down"] = ((n, e, fe, d), normal(fe))
        else:
            out[pre + "ffn/w_up"] = ((n, d, f), normal(d))
            out[pre + "ffn/w_down"] = ((n, f, d), normal(f))
    return out


def nest(flat: dict) -> dict:
    tree: dict = {}
    for path, val in flat.items():
        node = tree
        *heads, last = path.split("/")
        for k in heads:
            node = node.setdefault(k, {})
        node[last] = val
    return tree


def flatten(tree: dict, prefix: str = "") -> dict:
    out = {}
    for k, v in tree.items():
        if isinstance(v, dict):
            out.update(flatten(v, prefix + k + "/"))
        else:
            out[prefix + k] = v
    return out


def base_key(seed: int):
    import jax

    words = np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(words, impl="threefry2x32")


def make(m: dict, seed: int, shardings=None):
    """The nested parameter tree, float32, built in one jitted call.
    ``shardings`` (a nested tree like the result) places every leaf as it
    is created."""
    import jax
    import jax.numpy as jnp

    lay = layout(m)
    names = sorted(lay)

    def build(key):
        flat = {}
        for i, name in enumerate(names):
            shape, init = lay[name]
            if init[0] == "ones":
                flat[name] = jnp.ones(shape, jnp.float32)
            elif init[0] == "zeros":
                flat[name] = jnp.zeros(shape, jnp.float32)
            else:
                k = jax.random.fold_in(key, i)
                flat[name] = init[1] * jax.random.normal(k, shape,
                                                         jnp.float32)
        return nest(flat)

    return jax.jit(build, out_shardings=shardings)(base_key(seed))


def check_matches(tree, abstract_tree) -> None:
    """Raise unless ``tree`` has exactly the program's leaves, shapes and
    dtypes."""
    got = {k: (tuple(v.shape), str(v.dtype)) for k, v in flatten(tree).items()}
    want = {k: (tuple(v.shape), str(v.dtype))
            for k, v in flatten(abstract_tree).items()}
    if got != want:
        diff = sorted(set(got.items()) ^ set(want.items()))
        raise ValueError(f"parameter layout differs from the program's: "
                         f"{diff[:6]}")
