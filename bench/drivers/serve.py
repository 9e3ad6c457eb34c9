"""Serving cells: the program's continuous-batching ``Engine`` under
open-loop Poisson traffic.

Set-up builds the engine as ``launch/serve.py --engine --full`` would,
with the cell's slots, page size, chunk and sequence budget and the
engine's defaults otherwise, on weights made from the seed, then runs its
``warmup()`` twice (which compiles every program the traffic reaches)
and two priming requests. The window replays the cell's requests at their due
times, timing each from when it was due; arrivals go on after the window
until every request due in it has finished, so its last tokens are
served under the same load. Once the window has closed, a sample of the
finished requests drawn from the seed, the longest among them, is checked
against ``bench/reference.py``.
"""
from __future__ import annotations

import gc
import math
import sys
import time

import numpy as np

from bench import reference, traffic, weights
from bench.util import check, span

GRACE_S = 60.0          # how long requests due in the window may take
SAMPLE_TOKENS = 300     # served tokens the reference checks, at least
PRIME = ((24, 8), (40, 8))


def build(ctx):
    from repro.core import resolve_hw
    from repro.models.api import get_model
    from repro.obs import Recorder
    from repro.serve import Engine, EngineOptions

    from bench import spec

    import jax

    cfg = spec.program_config(ctx.conf)
    eng = ctx.cell["engine"]
    params = weights.make(ctx.model, ctx.seed)
    weights.check_matches(params, get_model(cfg).abstract_params(cfg))
    opts = EngineOptions(page_size=eng["page_size"],
                         max_slots=eng["max_slots"],
                         max_seq_len=eng["max_seq_len"],
                         chunk=eng["chunk"], hw=resolve_hw("auto"),
                         prefix_cache=eng["prefix_cache"], obs=Recorder())
    engine = Engine(cfg, params, options=opts)
    del params
    engine.warmup()
    # Warm-up resolves the prefill buckets in turn, measuring each one's
    # candidates into the engine's one LRU of compiled prefill steps; with
    # more buckets than that LRU holds candidates for, later candidates
    # evict the first buckets' winners. A second pass brings every winner
    # back, compiling here any that went, so that none compiles in the
    # window.
    engine.warmup()
    for plen, gen in PRIME:
        engine.submit(np.arange(plen, dtype=np.int32) % cfg.vocab_size,
                      max_new_tokens=gen)
    engine.run_until_idle()
    ctx.devices = [jax.devices()[0]]
    return engine


def requests(ctx, rate: float, seconds: float):
    """(requests due in the window, arrivals that keep the load on
    after it)."""
    t = ctx.cell["params"]
    kw = dict(rate=rate, vocab_size=ctx.model["vocab_size"],
              prompt_len_range=tuple(t["prompt_len"]),
              gen_len_range=tuple(t["output_len"]), seed=ctx.seed)
    counted = traffic.poisson_requests(max(1, round(rate * seconds)),
                                       stream=0, **kw)
    tail = traffic.poisson_requests(math.ceil(rate * GRACE_S) + 1,
                                    stream=1, **kw)
    tail = [traffic.Request(seconds + r.due_s, r.prompt, r.max_new_tokens)
            for r in tail]
    return counted, tail


def _family(reg, name, **labels):
    fam = reg.get(name)
    if fam is None:
        return []
    return [c for c in fam.children()
            if all(dict(c.labels).get(k) == v for k, v in labels.items())]


def _counter_total(reg, name) -> float:
    return sum(c.value for c in _family(reg, name))


def replay(ctx, engine, counted, tail, seconds: float, window=True):
    """Drive ``engine`` through the requests in wall-clock time. Returns
    a dict of what happened, per request and per engine step."""
    from repro.serve.request import RequestState

    reg = engine.obs.registry
    kv, sched = engine.kv, engine.scheduler
    pending = sorted([(r.due_s, i, r) for i, r in enumerate(counted)]
                     + [(r.due_s, len(counted) + i, r)
                        for i, r in enumerate(tail)], key=lambda e: e[:2])
    pending.reverse()                      # pop() takes the earliest
    live = {}                              # rid -> (counted?, due_s, Request)
    by_index = {}
    lateness, steps = [], []
    hists = {k: _family(reg, "repro_step_seconds", kind=k)
             for k in ("decode", "prefill")}

    def snapshot():
        """(count, sum) of the step-time histograms, and the jit traces
        and prefill compiles so far."""
        return ({k: (sum(h.count for h in v), sum(h.sum for h in v))
                 for k, v in hists.items()},
                _counter_total(reg, "repro_jit_traces_total")
                + _counter_total(reg, "repro_prefill_compiles_total"))

    hist0, traces0 = snapshot()
    t0 = ctx.start_window() if window else time.perf_counter()
    closed = not window
    hist1, traces1 = hist0, traces0
    while True:
        now = time.perf_counter() - t0
        if not closed and now >= seconds:
            ctx.end_window(stop_trace=False)
            closed = True
            hist1, traces1 = snapshot()
        outstanding = [r for c, _, r in live.values()
                       if c and r.state != RequestState.DONE]
        all_in = len(by_index) >= len(counted)
        if all_in and not outstanding and closed:
            break
        if now >= seconds + GRACE_S:
            break
        with span("bench.arrivals"):
            while pending and pending[-1][0] <= now:
                due, idx, r = pending.pop()
                req = engine.submit(r.prompt,
                                    max_new_tokens=r.max_new_tokens,
                                    arrival_s=t0 + due)
                is_counted = idx < len(counted)
                live[req.rid] = (is_counted, due, req)
                if is_counted:
                    by_index[idx] = req
                    lateness.append(now - due)
        if engine.has_work:
            lens = kv.lens.copy()
            dslots = sched.decode_slots()
            ts = time.perf_counter()
            with span("bench.engine_step"):
                info = engine.step()
            te = time.perf_counter()
            rec = {"t0": ts - t0, "t1": te - t0, "kind": info["kind"]}
            if info["kind"] == "decode":
                rec["slots"] = len(dslots)
                rec["keys"] = int(sum(int(lens[s]) + 1 for s in dslots))
            elif info["kind"] == "prefill" and info.get("tokens"):
                req = live[info["rid"]][2]
                rec["tokens"] = info["tokens"]
                rec["pos0"] = req.prefill_pos - info["tokens"]
            steps.append(rec)
        elif pending:
            with span("bench.idle_wait"):
                time.sleep(max(0.0, min(0.05, pending[-1][0] - now)))
    t_end = time.perf_counter() - t0
    if window:
        ctx.stop_trace()
    return {"t0": t0, "t_end": t_end, "counted": by_index,
            "live": live, "lateness": lateness, "steps": steps,
            "hist0": hist0, "hist1": hist1,
            "window_traces": traces1 - traces0}


def e2e(ctx, run, n_counted: int, seconds: float) -> dict:
    from repro.serve.request import RequestState

    ttft, gaps = [], []
    for i in range(n_counted):
        req = run["counted"].get(i)
        if req is None or req.state != RequestState.DONE:
            due = 0.0 if req is None else req.arrival_s - run["t0"]
            ttft.append(run["t_end"] - due)   # never served: misses all
            continue
        ttft.append(req.ttft_s)
        gaps.extend(req.itl_s)
    return {"ttft_p90_s": float(np.percentile(ttft, 90)),
            "itl_p95_ms": float(np.percentile(gaps, 95)) * 1e3
            if gaps else float("nan"),
            "ttft": ttft, "gaps": gaps}


def sample(ctx, run, n_counted: int):
    """Finished requests due in the window for the reference: the
    longest, then others in an order drawn from the seed, until
    ``SAMPLE_TOKENS`` served tokens are covered."""
    from repro.serve.request import RequestState

    done = [run["counted"][i] for i in range(n_counted)
            if i in run["counted"]
            and run["counted"][i].state == RequestState.DONE]
    if not done:
        return []
    longest = max(done, key=lambda r: (len(r.output), len(r.prompt)))
    rest = [r for r in done if r is not longest]
    order = traffic.rng_for(ctx.seed, 3).permutation(len(rest))
    picked, tokens = [longest], len(longest.output)
    for j in order:
        if tokens >= SAMPLE_TOKENS:
            break
        picked.append(rest[j])
        tokens += len(rest[j].output)
    return [(np.asarray(r.prompt, np.int32), np.asarray(r.output, np.int32))
            for r in picked]


def run(ctx, control: bool = False) -> bool:
    from repro.serve.request import RequestState

    engine = build(ctx)
    rate = ctx.cell["params"]["rate"]
    counted, tail = requests(ctx, rate, ctx.seconds)
    run_ = replay(ctx, engine, counted, tail, ctx.seconds)
    n = len(counted)
    ctx.read_memory_peak()
    met = e2e(ctx, run_, n, ctx.seconds)
    ctx.e2e = {"itl_p95_ms": met["itl_p95_ms"],
               "peak_hbm_gib": ctx.memory_peak_bytes / 2 ** 30,
               "setup_s": run_["t0"] - ctx.t_start}
    unfinished = [i for i in range(n)
                  if i not in run_["counted"]
                  or run_["counted"][i].state != RequestState.DONE
                  or len(run_["counted"][i].output)
                  != counted[i].max_new_tokens]
    ctx.attempted, ctx.failed = n, len(unfinished)
    late = np.asarray(run_["lateness"]) * 1e3
    print(f"arrival generator lateness over {late.size} requests: "
          f"p50 {np.median(late):.3f} ms, max {late.max():.3f} ms",
          file=sys.stderr)
    ctx.window_programs += int(run_["window_traces"])
    ctx.layer = {"run": run_, "model": ctx.model, "seconds": ctx.seconds,
                 "kind": ctx.devices[0].device_kind}
    seqs = sample(ctx, run_, n)
    nonfinite = engine.nonfinite_logit_rows
    max_seq = ctx.cell["engine"]["max_seq_len"]
    # the program's state goes before the reference runs
    del engine, run_
    ctx.layer["run"] = _strip(ctx.layer["run"])
    gc.collect()
    params = weights.make(ctx.model, ctx.seed)
    gaps, cgaps = reference.served_gaps(params, seqs, ctx.model, max_seq,
                                        control=control)
    del params
    ctx.layer["sample_tokens"] = int(sum(len(s) for _, s in seqs))
    ctx.control_gaps = cgaps
    limits = ctx.cell["limits"]
    ok = check(ctx, "served_gap", max(gaps) if gaps else float("inf"),
               limits["served_gap"])
    ok &= check(ctx, "unfinished", len(unfinished), 0)
    ok &= check(ctx, "nonfinite_rows", nonfinite, 0)
    return ok


def _strip(run):
    """Keep the per-request timings the readers need, drop the Request
    objects (and with them the engine's references)."""
    out = dict(run)
    out["due"] = {rid: (c, due) for rid, (c, due, _) in run["live"].items()}
    del out["live"]
    out["counted"] = {i: {"rid": r.rid} for i, r in run["counted"].items()}
    return out
