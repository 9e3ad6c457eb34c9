"""The serving check, driven end to end at a tiny size on the CPU: a
sound run is correct, the fp8 control is not, and a token altered where
it is produced makes ``correct`` false."""
from __future__ import annotations

import numpy as np

import tiny
from bench import spec
from bench.drivers import serve


def _ctx(monkeypatch, seed):
    monkeypatch.setattr(spec, "program_config", tiny.program_config)
    ctx = tiny.context("serve.gpt3s.chat", seed=seed, seconds=1.5,
                       rate=6.0, prompt_len=[4, 40], output_len=[4, 12])
    ctx.cell["engine"] = dict(ctx.cell["engine"], max_slots=4,
                              max_seq_len=64, chunk=16)
    return ctx


def test_sound_run_is_correct_and_the_control_is_not(monkeypatch):
    ctx = _ctx(monkeypatch, seed=2 ** 31 + 5)
    assert serve.run(ctx, control=True)
    assert ctx.attempted == 9 and ctx.failed == 0
    assert ctx.layer["sample_tokens"] > 0
    assert max(ctx.control_gaps) > ctx.cell["limits"]["served_gap"]
    assert set(ctx.e2e) == {"itl_p95_ms", "peak_hbm_gib", "setup_s"}


def test_altered_token_is_caught(monkeypatch):
    from repro.serve import request

    emit = request.Request.emit

    def altered(self, token, now):      # every token off by one id
        return emit(self, (int(token) + 1) % tiny.MODEL["vocab_size"], now)

    monkeypatch.setattr(request.Request, "emit", altered)
    ctx = _ctx(monkeypatch, seed=9)
    assert not serve.run(ctx)
    value, limit = ctx.checks["served_gap"]
    assert value > limit


def test_sample_takes_the_longest_and_enough_tokens():
    class R:
        def __init__(self, n):
            self.output, self.prompt = [1] * n, np.zeros(5, np.int32)
            from repro.serve.request import RequestState
            self.state = RequestState.DONE

    reqs = {i: R(n) for i, n in enumerate([10, 200, 40, 90, 120, 30])}
    ctx = type("C", (), {"seed": 4})()
    picked = serve.sample(ctx, {"counted": reqs}, 6)
    assert len(picked[0][1]) == 200
    assert sum(len(s) for _, s in picked) >= serve.SAMPLE_TOKENS
