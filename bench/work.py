"""Operations and bytes the algorithm needs, counted from shapes.

These counts are the algorithm's, not the implementation's: they do not
change when a kernel reads fewer pages, skips a cast or recomputes an
activation, so a faster implementation never moves the yardstick.

``m`` is the ``model`` object of a configuration file
(``bench/configs/<name>.json``).
"""
from __future__ import annotations


def n_moe_layers(m: dict) -> int:
    return sum(1 for i in range(m["num_layers"])
               if i % m["moe_period"] == m["moe_offset"])


def matmul_params_per_token(m: dict) -> int:
    """Weights one token multiplies through: attention, the dense FFNs,
    the router, its ``top_k`` experts and the LM head (embedding
    look-ups are not multiplications)."""
    d, h, hd = m["d_model"], m["num_heads"], m["head_dim"]
    n_moe = n_moe_layers(m)
    n_dense = m["num_layers"] - n_moe
    attn = 4 * d * h * hd * m["num_layers"]
    dense = 2 * d * m["d_ff"] * n_dense
    moe = (d * m["num_experts"]
           + m["top_k"] * 2 * d * m["d_expert"]) * n_moe
    return attn + dense + moe + d * m["vocab_size"]


def attn_fwd_flops(m: dict, keys: int) -> int:
    """Forward attention FLOPs of one query over ``keys`` cached keys,
    all layers: QK^T and PV, 2 FLOPs per multiply-add each."""
    return 4 * m["num_layers"] * m["num_heads"] * m["head_dim"] * keys


def prefill_flops(m: dict, pos0: int, c: int) -> int:
    """A prefill chunk of ``c`` tokens starting at position ``pos0``:
    token ``pos0 + i`` attends ``pos0 + i + 1`` keys."""
    keys = c * pos0 + c * (c + 1) // 2
    return 2 * matmul_params_per_token(m) * c + attn_fwd_flops(m, keys)


def train_flops_per_token(m: dict, seq: int) -> float:
    """Forward + backward (3x forward) per token of a causal sequence of
    ``seq`` tokens; the mean query attends ``(seq + 1) / 2`` keys.
    Recomputation is not counted."""
    return (6 * matmul_params_per_token(m)
            + 3 * attn_fwd_flops(m, 1) * (seq + 1) / 2)


def paged_decode_attention(m: dict, keys: int, slots: int,
                           kv_bytes: int = 2) -> tuple:
    """(FLOPs, bytes) of decode attention over all layers for one step in
    which ``slots`` queries attend ``keys`` live keys in total: each live
    key's K and V row is read once per layer, the queries are read and
    the outputs written once per layer."""
    L, h, hd, kvh = (m["num_layers"], m["num_heads"], m["head_dim"],
                     m.get("num_kv_heads", m["num_heads"]))
    flops = attn_fwd_flops(m, keys)
    kv = keys * L * 2 * kvh * hd * kv_bytes
    qo = slots * L * 2 * h * hd * kv_bytes
    return flops, kv + qo
