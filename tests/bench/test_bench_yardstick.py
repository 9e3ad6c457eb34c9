"""The generator, the work counts and the peak table."""
from __future__ import annotations

import numpy as np
import pytest

import tiny
from bench import peaks, traffic, work


def _reqs(seed):
    return traffic.poisson_requests(
        40, rate=4.0, vocab_size=50304, prompt_len_range=(16, 256),
        gen_len_range=(16, 128), seed=seed)


def test_generator_is_deterministic_in_the_seed():
    big = 2 ** 31 + 12345
    a, b = _reqs(big), _reqs(big)
    assert [r.due_s for r in a] == [r.due_s for r in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    c = _reqs(big + 1)
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in c]


def test_every_seed_offers_the_same_work():
    a, c = _reqs(7), _reqs(8)
    assert sorted(len(r.prompt) for r in a) == \
        sorted(len(r.prompt) for r in c)
    assert sorted(r.max_new_tokens for r in a) == \
        sorted(r.max_new_tokens for r in c)
    # the same gaps in another order: the spans differ only by which gap
    # falls before the first arrival
    assert sorted(np.diff([r.due_s for r in a]))[:-1] == pytest.approx(
        sorted(np.diff([r.due_s for r in c]))[:-1], abs=2.0)
    assert a[0].due_s == 0.0
    for r in a:
        assert 16 <= len(r.prompt) <= 256 and 16 <= r.max_new_tokens <= 128


def test_generator_offers_the_rate_and_ranges():
    r = traffic.poisson_requests(200, rate=5.0, vocab_size=100,
                                 prompt_len_range=(8, 96),
                                 gen_len_range=(4, 48), seed=3)
    gaps = np.diff([x.due_s for x in r])
    assert 0.17 < gaps.mean() < 0.23
    assert {len(x.prompt) for x in r} <= set(range(8, 97))
    assert min(len(x.prompt) for x in r) == 8
    assert max(x.max_new_tokens for x in r) == 48
    assert all(x.prompt.max() < 100 for x in r)


def test_training_rows_all_differ():
    b0 = traffic.token_batch(5, 0, 8, 64, 50304)
    b1 = traffic.token_batch(5, 1, 8, 64, 50304)
    rows = np.concatenate([b0["tokens"], b1["tokens"]])
    assert len({r.tobytes() for r in rows}) == 16
    assert np.array_equal(b0["labels"][:, :-1], b0["tokens"][:, 1:])
    assert np.array_equal(b0["tokens"],
                          traffic.token_batch(5, 0, 8, 64, 50304)["tokens"])


def test_work_counts_by_hand():
    m = dict(tiny.MODEL)             # L=2, D=64, H=4, hd=16, F=128,
    # E=4, Fe=32, k=1, V=256; layer 1 is the MoE layer
    attn = 4 * 64 * 4 * 16 * 2                    # qkvo, 2 layers
    dense = 2 * 64 * 128                           # layer 0
    moe = 64 * 4 + 2 * 64 * 32                     # router + 1 expert
    assert work.matmul_params_per_token(m) == attn + dense + moe + 64 * 256
    assert work.attn_fwd_flops(m, 10) == 4 * 2 * 4 * 16 * 10
    n = work.matmul_params_per_token(m)
    # chunk of 3 tokens at position 5 attends 6 + 7 + 8 keys
    assert work.prefill_flops(m, 5, 3) == 2 * n * 3 + 4 * 2 * 64 * 21
    assert work.train_flops_per_token(m, 7) == 6 * n + 3 * 4 * 2 * 64 * 4
    flops, byts = work.paged_decode_attention(m, keys=100, slots=3)
    assert flops == 4 * 2 * 64 * 100
    assert byts == 100 * 2 * 2 * 4 * 16 * 2 + 3 * 2 * 2 * 64 * 2


def test_peaks_table():
    assert peaks.peaks("TPU v5 lite")["flops_bf16"] == 197e12
    assert peaks.peaks("TPU v5e")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(ValueError):
        peaks.peaks("TPU v9 imaginary")
