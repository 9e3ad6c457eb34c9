"""Telemetry (repro.obs): the shared nearest-rank quantile (property-
tested against a definitional reference), the metrics registry and its
Prometheus exposition, the Chrome-trace schema over a real preemption
storm, the engine's phase spans and GC pauses on the JAX profiler's host
plane, the device-scope table, the live /metrics exporter agreeing with
``Engine.stats()``, and the zero-cost contract of disabled telemetry
(identical jit traces and identical tokens with the tracer and the
profiler on or off)."""
import dataclasses
import gc
import glob
import json
import math
import urllib.error
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import lm
from repro.obs import (MetricsServer, NullTracer, Recorder, Registry,
                       Tracer, quantile, scopes)
from repro.serve import Engine, EngineOptions

PHASES = ("engine.admit", "engine.kv_grow", "engine.inputs",
          "engine.dispatch", "engine.sync", "engine.emit")
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

PROMPT_LENS = (13, 29, 7, 21, 5)
MAX_NEW = (6, 4, 8, 5, 7)


@pytest.fixture(scope="module")
def setup():
    cfg = dataclasses.replace(get_config("llama3-8b").reduced(),
                              compute_dtype="float32")
    params = lm.init(cfg, jax.random.PRNGKey(0))
    rng = np.random.Generator(np.random.Philox(key=7))
    prompts = [rng.integers(0, cfg.vocab_size, size=n, dtype=np.int32)
               for n in PROMPT_LENS]
    return cfg, params, prompts


def _run(cfg, params, prompts, *, obs=None, **over):
    # same constrained pool as tests/test_preemption.py: ~28 pages of
    # demand over 11 usable pages, so recompute preemptions fire
    kw = dict(page_size=4, max_slots=3, max_seq_len=64, chunk=16,
              min_bucket=8, num_pages=12, preempt="recompute", obs=obs)
    kw.update(over)
    eng = Engine(cfg, params, options=EngineOptions(**kw))
    for p, m in zip(prompts, MAX_NEW):
        eng.submit(p, max_new_tokens=m, arrival_s=0.0)
    eng.run_until_idle()
    return eng


# ---------------------------------------------------------------------------
# quantile: the one shared nearest-rank implementation
# ---------------------------------------------------------------------------

def _reference_quantile(xs, p):
    """Definitional nearest-rank: the smallest sample whose empirical
    CDF reaches p/100 (p0 = min, p100 = max)."""
    s = sorted(xs)
    n = len(s)
    for i, v in enumerate(s):
        if (i + 1) / n >= p / 100.0 - 1e-12:
            return v
    return s[-1]


def test_quantile_pinned_examples():
    # the Engine.stats() bug this replaced: int(p/100*n) indexed one
    # rank too high, so p50 of a 2-element list returned the max
    assert quantile([1.0, 2.0], 50) == 1.0
    assert quantile([1.0, 2.0], 100) == 2.0
    assert quantile([2.0, 1.0], 0) == 1.0
    assert quantile([5.0], 99) == 5.0
    assert quantile([], 50) == 0.0
    assert quantile([3, 1, 4, 1, 5], 50) == 3.0      # unsorted input ok


try:
    from hypothesis import given, settings, strategies as st
    _HAVE_HYPOTHESIS = True
except ImportError:                                   # pragma: no cover
    _HAVE_HYPOTHESIS = False

if _HAVE_HYPOTHESIS:
    @given(st.lists(st.floats(allow_nan=False, allow_infinity=False,
                              width=32),
                    min_size=1, max_size=200),
           st.integers(min_value=0, max_value=100))
    @settings(max_examples=200, deadline=None)
    def test_quantile_matches_reference(xs, p):
        got = quantile(xs, p)
        assert got == _reference_quantile(xs, p)
        assert got in xs                  # nearest-rank never interpolates
        # ceil(p/100*n) is the textbook closed form of the same rank
        rank = max(1, math.ceil(p / 100.0 * len(xs)))
        assert got == sorted(xs)[rank - 1]


# ---------------------------------------------------------------------------
# registry + Prometheus exposition
# ---------------------------------------------------------------------------

def test_registry_render_and_snapshot():
    reg = Registry()
    c = reg.counter("repro_test_total", "things done")
    c.inc()
    c.inc(2)
    g = reg.gauge("repro_test_gauge", "a level")
    g.set(3.5)
    g.inc()
    g.dec(0.5)
    h = reg.histogram("repro_test_seconds", "a timing")
    for v in (1, 2, 3, 4):
        h.observe(v)
    text = reg.render()
    assert "# HELP repro_test_total things done" in text
    assert "# TYPE repro_test_total counter" in text
    assert "repro_test_total 3" in text.splitlines()
    assert "repro_test_gauge 4" in text.splitlines()
    assert "# TYPE repro_test_seconds summary" in text
    assert 'repro_test_seconds{quantile="0.5"} 2' in text
    assert 'repro_test_seconds{quantile="0.99"} 4' in text
    assert "repro_test_seconds_sum 10" in text
    assert "repro_test_seconds_count 4" in text

    snap = reg.snapshot()
    json.dumps(snap)                       # JSON-serializable end to end
    assert snap["repro_test_total"] == 3
    assert snap["repro_test_gauge"] == 4
    assert snap["repro_test_seconds"] == {
        "count": 4, "sum": 10, "p50": 2, "p90": 4, "p99": 4}


def test_registry_labels_and_idempotent_registration():
    reg = Registry()
    fam = reg.counter("repro_modes_total", "by mode", labels=("mode",))
    fam.labels(mode="a").inc()
    fam.labels(mode="b").inc(2)
    # idempotent: re-declaring returns the same family object
    assert reg.counter("repro_modes_total", "by mode",
                       labels=("mode",)) is fam
    text = reg.render()
    assert 'repro_modes_total{mode="a"} 1' in text
    assert 'repro_modes_total{mode="b"} 2' in text
    assert reg.snapshot()["repro_modes_total"] == {
        'mode="a"': 1, 'mode="b"': 2}
    # kind and label-set mismatches are registration bugs, not merges
    with pytest.raises(AssertionError):
        reg.gauge("repro_modes_total", "by mode", labels=("mode",))
    with pytest.raises(AssertionError):
        reg.counter("repro_modes_total", "by mode", labels=("kind",))
    with pytest.raises(AssertionError):
        fam.labels(kind="a")


def test_histogram_window_bounds_quantiles_not_totals():
    reg = Registry()
    h = reg.histogram("repro_win_seconds", "w", window=4)
    for v in (100, 100, 1, 2, 3, 4):
        h.observe(v)
    # quantiles see only the last 4 observations...
    assert h.quantile(99) == 4
    # ...while count/sum stay lifetime totals
    assert h.count == 6 and h.sum == 210


# ---------------------------------------------------------------------------
# tracer: schema/golden over a preemption storm
# ---------------------------------------------------------------------------

def test_null_tracer_is_inert():
    t = NullTracer()
    assert not t.enabled
    with t.span("x", args={"k": 1}) as sp:
        sp["late"] = 2
    t.instant("i")
    t.begin("b")
    t.end("b")
    t.thread_name(1, 1, "steps")
    assert t.export()["traceEvents"] == []


def test_trace_schema_over_preemption_storm(setup, tmp_path):
    cfg, params, prompts = setup
    obs = Recorder(tracer=Tracer())
    eng = _run(cfg, params, prompts, obs=obs)
    assert eng.preempts["recompute"] > 0            # the storm happened

    doc = obs.tracer.export()
    evs = doc["traceEvents"]
    meta = [e for e in evs if e["ph"] == "M"]
    real = [e for e in evs if e["ph"] != "M"]
    assert real and set(e["ph"] for e in real) <= {"B", "E", "X", "i"}

    # stable pid/tid naming
    proc = {e["pid"]: e["args"]["name"] for e in meta
            if e["name"] == "process_name"}
    assert proc[1] == "engine" and proc[2] == "requests" \
        and proc[3] == "resolver"
    threads = {(e["pid"], e["tid"]): e["args"]["name"] for e in meta
               if e["name"] == "thread_name"}
    assert threads[(1, 1)] == "steps"
    for r in eng.done:
        assert threads[(2, r.rid)] == f"req {r.rid}"

    # timestamps sorted; X complete events carry a duration
    ts = [e["ts"] for e in real]
    assert ts == sorted(ts)
    for e in real:
        if e["ph"] == "X":
            assert e["dur"] >= 0
        if e["ph"] == "i":
            assert e["s"] == "t"

    # B/E balanced and properly nested per (pid, tid)
    stacks = {}
    for e in real:
        key = (e["pid"], e["tid"])
        if e["ph"] == "B":
            stacks.setdefault(key, []).append(e["name"])
        elif e["ph"] == "E":
            assert stacks.get(key), f"E without matching B: {e}"
            assert stacks[key].pop() == e["name"]
    assert all(not s for s in stacks.values())

    # lifecycle instants: one ADMIT + one RETIRE per request; every
    # PREEMPT has its RESUME; counts match the engine's own counters
    by_name = {}
    for e in real:
        by_name.setdefault(e["name"], []).append(e)
    n = len(prompts)
    assert len(by_name["ADMIT"]) == n
    assert len(by_name["RETIRE"]) == n
    assert len(by_name["PREEMPT"]) == eng.preempts["recompute"]
    assert len(by_name["RESUME"]) == len(by_name["PREEMPT"])
    assert all(e["args"]["mode"] == "recompute"
               for e in by_name["PREEMPT"])
    assert by_name["PREFILL"] and by_name["engine.step"]

    # each step's phases nest inside it, each phase carries its kind,
    # and the old per-kind child spans are gone
    assert "prefill" not in by_name and "decode" not in by_name
    steps = [(e["ts"], e["ts"] + e["dur"], e["args"]["kind"])
             for e in by_name["engine.step"]]
    for name in PHASES:
        assert by_name[name], name
        for e in by_name[name]:
            assert e["ph"] == "X"
            a, b = e["ts"], e["ts"] + e["dur"]
            outer = [s for s in steps if s[0] <= a and b <= s[1]]
            assert len(outer) == 1, (name, e)
            assert e["args"]["kind"] in ("prefill", "decode")
            if name != "engine.admit":
                assert e["args"]["kind"] == outer[0][2]

    # the written file is valid JSON and identical to export()
    path = tmp_path / "trace.json"
    obs.tracer.write(str(path))
    assert json.loads(path.read_text()) == doc


# ---------------------------------------------------------------------------
# the profiler sink: phase spans and GC pauses on the profiler's clock
# ---------------------------------------------------------------------------

def _host_spans(trace_dir):
    """(start_ns, end_ns, name, stats) of the program's spans on the
    host plane of the profiler trace under ``trace_dir``."""
    from jax.profiler import ProfileData
    path, = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(("engine.", "host.gc")):
                        out.append((ev.start_ns,
                                    ev.start_ns + ev.duration_ns, ev.name,
                                    {k: v for k, v in ev.stats}))
    return out


def test_phase_and_gc_spans_on_the_profiler_host_plane(setup, tmp_path):
    cfg, params, prompts = setup
    eng = Engine(cfg, params, options=EngineOptions(
        page_size=4, max_slots=3, max_seq_len=64, chunk=16, min_bucket=8))
    for p, m in zip(prompts[:2], MAX_NEW):
        eng.submit(p, max_new_tokens=m, arrival_s=0.0)
    admit = eng.scheduler.admit

    def admit_after_a_collection():
        gc.collect()
        return admit()

    eng.scheduler.admit = admit_after_a_collection
    with jax.profiler.trace(str(tmp_path)):
        eng.run_until_idle()
    spans = _host_spans(tmp_path)
    steps = [s for s in spans if s[2] == "engine.step"]
    assert len(steps) == eng.step_count
    assert {s[3]["kind"] for s in steps} == {"prefill", "decode"}

    def inside_a_step(span):
        return any(a <= span[0] and span[1] <= b for a, b, *_ in steps)

    for name in PHASES:
        found = [s for s in spans if s[2] == name]
        assert found, name
        assert all(inside_a_step(s) for s in found), name
        assert {s[3]["kind"] for s in found} <= {"prefill", "decode"}
    full = [s for s in spans if s[2] == "host.gc"
            and s[3]["generation"] == 2 and inside_a_step(s)]
    assert len(full) >= eng.step_count        # one forced in each step


def test_gc_pauses_feed_the_histogram_and_the_tracer():
    obs = Recorder(tracer=Tracer())
    gc.collect(1)
    gc.collect()
    hist = {dict(c.labels)["generation"]: c for c in
            obs.registry.get("repro_gc_pause_seconds").children()}
    assert hist["1"].count >= 1 and hist["2"].count >= 1
    assert all(v >= 0 for v in hist["2"].values())
    ev = [e for e in obs.tracer.export()["traceEvents"]
          if e["name"] == "host.gc"]
    assert {e["args"]["generation"] for e in ev} >= {1, 2}
    assert all(e["ph"] == "X" and e["dur"] >= 0 for e in ev)


# ---------------------------------------------------------------------------
# the scope table
# ---------------------------------------------------------------------------

def _compiles():
    counts = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, duration, **_: counts.append(event)
        if event == COMPILE_EVENT else None)
    return counts


def test_scope_table_maps_instructions_in_scan_bodies():
    def step(x, w):
        with jax.named_scope("moe.route"):
            x = jnp.tanh(x) * 2.0

        def body(c, wi):
            with jax.named_scope("moe.expert_ffn"):
                c = jnp.sin(c @ wi)
            return c, None

        y, _ = jax.lax.scan(body, x, w)
        with jax.named_scope("optimizer"):
            return y - 0.1 * jnp.cos(y).sum()

    fn = jax.jit(step)
    x, w = jnp.ones((8, 8)), jnp.ones((3, 8, 8)) * 0.1
    fn(x, w).block_until_ready()
    compiles = _compiles()
    compiled = fn.lower(x, w).compile()
    name = scopes.register_compiled(compiled)
    assert compiles == []                      # nothing compiled again
    assert name == "jit_step"
    text = compiled.as_text()
    assert " while(" in text                   # the scan is a loop
    ops = scopes.table()[name]
    dots = [ln.split(" = ")[0].strip().removeprefix("ROOT ").lstrip("%")
            for ln in text.splitlines() if " dot(" in ln]
    assert dots and all(ops[d] == "moe.expert_ffn" for d in dots)
    assert set(ops.values()) >= {"moe.route", "moe.expert_ffn",
                                 "optimizer"}
    assert scopes.innermost(
        "jit(f)/transpose(jvp(moe.route))/moe.expert_ffn/dot") == \
        "moe.expert_ffn"
    assert scopes.innermost("jit(f)/moe.router/paged_attention/x") is None


HOISTED = """HloModule jit_step, entry_computation_layout={(f32[2,4])->f32[2,4]}

%fused_update (q0: f32[2,4], q1: f32[2,4]) -> (f32[2,4], f32[2,4]) {
  %q0 = f32[2,4] parameter(0)
  %q1 = f32[2,4] parameter(1)
  %dot.20 = f32[2,4] multiply(%q0, %q1), metadata={op_name="jit(step)/transpose(jvp(moe.expert_ffn))/dot_general"}
  %add.21 = f32[2,4] add(%dot.20, %q0), metadata={op_name="jit(step)/optimizer/add"}
  %multiply.22 = f32[2,4] multiply(%dot.20, %q1), metadata={op_name="jit(step)/optimizer/mul"}
  ROOT %tuple.23 = (f32[2,4], f32[2,4]) tuple(%add.21, %multiply.22)
}

%body (arg: (s32[], bf16[2,4])) -> (s32[], bf16[2,4]) {
  %arg = (s32[], bf16[2,4]) parameter(0)
  %get-tuple-element.1 = s32[] get-tuple-element(%arg), index=0
  %get-tuple-element.2 = bf16[2,4] get-tuple-element(%arg), index=1
  %dynamic-slice.3 = bf16[1,4] dynamic-slice(%get-tuple-element.2, %get-tuple-element.1), dynamic_slice_sizes={1,4}, metadata={op_name="jit(step)/while/body/dynamic_slice"}
  %fusion.4 = bf16[1,4] fusion(%dynamic-slice.3), kind=kLoop, calls=%f, metadata={op_name="jit(step)/while/body/closed_call/moe.expert_ffn/dot_general"}
  ROOT %tuple.5 = (s32[], bf16[2,4]) tuple(%get-tuple-element.1, %get-tuple-element.2)
}

ENTRY %main (p: f32[2,4]) -> f32[2,4] {
  %p = f32[2,4] parameter(0)
  %convert.6 = bf16[2,4] convert(%p)
  %constant.7 = s32[] constant(0)
  %tuple.8 = (s32[], bf16[2,4]) tuple(%constant.7, /*index=1*/%convert.6)
  %while.9 = (s32[], bf16[2,4]) while(%tuple.8), condition=%c, body=%body, metadata={op_name="jit(step)/while"}
  %reduce-window.10 = f32[2,4] reduce-window(%p), metadata={op_name="reduce_window_sum"}
  %fusion.11 = f32[2,4] fusion(%reduce-window.10), kind=kLoop, calls=%g, metadata={op_name="jit(step)/sample/cumsum"}
  %copy.12 = f32[2,4] copy(%p)
  %fusion.14 = (f32[2,4], f32[2,4]) fusion(%p, %p), kind=kOutput, calls=%fused_update, metadata={op_name="jit(step)/transpose(jvp(moe.expert_ffn))/dot_general"}
  ROOT %fusion.13 = f32[2,4] fusion(%copy.12, %fusion.11), kind=kLoop, calls=%h, metadata={op_name="jit(step)/add"}
}
"""


def test_compiler_made_ops_take_their_consumers_scope():
    """A cast the compiler hoisted out of the layer loop (no op_name)
    counts where the loop body uses it; a bare op_name is the
    compiler's too; a value that ends in an unscoped op stays out; a
    weight gradient fused with the update that writes the parameter
    and its moment counts as the optimizer."""
    name = scopes.register(HOISTED)
    ops = scopes.table()[name]
    assert ops["convert.6"] == "moe.expert_ffn"
    assert ops["fusion.4"] == "moe.expert_ffn"
    assert ops["reduce-window.10"] == "sample"
    assert "copy.12" not in ops and "fusion.13" not in ops
    assert "dynamic-slice.3" not in ops        # traced, in no scope
    assert ops["fusion.14"] == "optimizer"


def test_warmup_publishes_every_program_and_compiles_nothing(setup,
                                                             monkeypatch):
    cfg, params, _ = setup
    seen = []
    publish = Engine._publish_scopes

    def counted(self, ran):
        compiles = _compiles()
        traces = (self.decode_traces, self.prefill_traces)
        publish(self, ran)
        seen.append((list(compiles), traces,
                     (self.decode_traces, self.prefill_traces), len(ran)))

    monkeypatch.setattr(Engine, "_publish_scopes", counted)
    eng = Engine(cfg, params, options=EngineOptions(
        page_size=4, max_slots=3, max_seq_len=64, chunk=16, min_bucket=8))
    eng.warmup()
    (compiles, before, after, programs), = seen
    assert compiles == [] and before == after and programs == 3
    table = scopes.table()
    assert {"sample", "attention"} <= set(table["jit__decode_step"].values())
    for b in (8, 16):
        name, = [k for k in table if k.startswith(f"jit_prefill_b{b}_")]
        assert "sample" in table[name].values()


# ---------------------------------------------------------------------------
# live exporter: /metrics agrees with Engine.stats()
# ---------------------------------------------------------------------------

def test_metrics_server_agrees_with_stats(setup):
    cfg, params, prompts = setup
    obs = Recorder()
    eng = _run(cfg, params, prompts, obs=obs)
    server = MetricsServer(obs.registry, port=0,
                           refresh=eng._refresh_gauges).start()
    try:
        assert server.port > 0
        text = urllib.request.urlopen(server.url + "/metrics",
                                      timeout=10).read().decode()
        health = urllib.request.urlopen(server.url + "/healthz",
                                        timeout=10).read().decode()
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(server.url + "/nope", timeout=10)
    finally:
        server.stop()
    assert health == "ok\n"
    assert "# TYPE repro_step_seconds summary" in text

    def metric(name):
        for line in text.splitlines():
            if line.startswith(name + " "):
                return float(line.rsplit(" ", 1)[1])
        raise AssertionError(f"{name} not in exposition")

    s = eng.stats()
    assert metric("repro_requests_done_total") == len(eng.done) == \
        len(prompts)
    assert metric("repro_tokens_generated_total") == \
        sum(len(r.output) for r in eng.done)
    assert metric('repro_preempts_total{mode="recompute"}') == \
        eng.preempts["recompute"]
    # scrape-time refresh: the gauges /metrics serves are the ones
    # stats() reports
    assert metric("repro_waiting_requests") == s["queue_waiting"] == 0
    assert metric("repro_resuming_requests") == s["queue_resuming"] == 0
    assert metric("repro_running_slots") == s["running_slots"] == 0
    assert metric('repro_kv_free_pages{shard="0"}') == \
        s["free_units_by_shard"]["0"] == eng.kv.num_pages - 1


def test_metrics_server_survives_midwrite_hangup():
    """A scraper that hangs up mid-response (curl timeout, ^C) must not
    traceback the handler thread — the write path is guarded."""
    reg = Registry()
    reg.counter("repro_x_total", "x").inc()
    server = MetricsServer(reg, port=0)
    try:
        handler_cls = server._httpd.RequestHandlerClass

        class Gone:
            def write(self, *_):
                raise BrokenPipeError

            def flush(self):
                pass

        h = handler_cls.__new__(handler_cls)
        h.path = "/metrics"
        h.request_version = "HTTP/1.1"
        h.requestline = "GET /metrics HTTP/1.1"
        h.client_address = ("127.0.0.1", 0)
        h.wfile = Gone()
        h.do_GET()                         # must not raise
        h.path = "/healthz"
        h.do_GET()
    finally:
        server.stop()


def test_metrics_server_stop_is_idempotent():
    """CLI finally-blocks, tests and signal handlers may all call
    stop(); the second call must be a no-op, not a hang or error."""
    server = MetricsServer(Registry(), port=0).start()
    assert urllib.request.urlopen(server.url + "/healthz",
                                  timeout=10).read() == b"ok\n"
    server.stop()
    server.stop()                          # second shutdown: no-op
    with pytest.raises(OSError):
        urllib.request.urlopen(server.url + "/healthz", timeout=2)


def test_stats_quantiles_use_shared_util(setup):
    cfg, params, prompts = setup
    eng = _run(cfg, params, prompts)
    s = eng.stats()
    lats = sorted(r.latency_s for r in eng.done)
    assert s["p50_latency_s"] == quantile(lats, 50)
    # 5 samples: nearest-rank p50 is the 3rd, not the 4th (the old
    # int(p/100*n) bias)
    assert s["p50_latency_s"] == lats[2]


# ---------------------------------------------------------------------------
# zero-cost when disabled: tokens and jit traces identical on vs off
# ---------------------------------------------------------------------------

def test_telemetry_on_off_identical_traces_and_tokens(setup, tmp_path):
    cfg, params, prompts = setup
    off = _run(cfg, params, prompts)          # default no-op recorder
    on = _run(cfg, params, prompts, obs=Recorder(tracer=Tracer()))
    # the profiler sink: spans of both tracers become TraceMe events
    with jax.profiler.trace(str(tmp_path)):
        prof = _run(cfg, params, prompts, obs=Recorder(tracer=Tracer()))
        prof_null = _run(cfg, params, prompts)
    assert any(s[2] == "engine.dispatch" for s in _host_spans(tmp_path))
    for eng in (on, prof, prof_null):
        assert eng.decode_traces == off.decode_traces
        assert eng.prefill_traces == off.prefill_traces
        assert eng.prefill_rejits == off.prefill_rejits
        assert [r.output for r in sorted(eng.done, key=lambda r: r.rid)] \
            == [r.output for r in sorted(off.done, key=lambda r: r.rid)]
    # default recorder still counts jit traces in its registry
    snap = off.obs.registry.snapshot()
    assert snap["repro_jit_traces_total"]['body="decode"'] == \
        off.decode_traces
