"""Continuous-batching serving engine with preemptive scheduling.

One :class:`Engine` owns: the model params, a ``StateCache`` (the
per-request device-state cache behind the protocol in
``repro.serve.state_cache`` — paged KV pools for attention, slot-indexed
O(1) state for recurrent mixers, or a composite of both for mixed
models like jamba; the kind is decided by ``models/api.serving_support``
and everything below talks only to the protocol surface), a
:class:`Scheduler` (admission + prefill/decode interleave + preemption
bookkeeping) and a :class:`PrefillBucketAdaptive` (per-bucket MPipeMoE
(n, strategy) resolution). Each ``step()`` runs one jitted program —
either a chunked-prefill step for the head-of-line prefilling request or
one decode step over the whole slot batch — so batch composition can
change every step while compiled programs are reused from two small
caches:

* decode: compiled **once** (slot count is static; finished / mid-prefill
  slots are masked, their KV writes going to the reserved sink page);
* prefill: one compiled step per (bucket, n, strategy) in an LRU,
  mirroring the train-side AdaptiveController cache.

Overload behaviour (``EngineOptions.preempt``): with the default
``"auto"`` policy, admission reserves only the first prefill chunk and
slots grow page-by-page on demand; when the pool runs dry the engine
preempts the lowest-priority (then youngest) victim, choosing per victim
between *recompute* (drop pages, re-prefill at resume) and *offload*
(round-trip pages over the host link) via
:class:`repro.core.memory_model.PreemptionCost` — the serving analogue
of the paper's strategy selector, gated by
``core.strategies.host_offload_supported``. ``"never"`` restores the
conservative full-budget admission-blocking baseline.

Sampling: temperature / top-k / top-p with per-request seeds and stop
sequences (``repro.serve.sampling``), executed inside the jitted steps
with per-slot parameter arrays so the one-compile invariant holds;
``temperature <= 0`` (default) is exact greedy argmax.

Bucket (n, strategy) resolution can measure candidates by wall clock
(``EngineOptions.measure``): compiled prefill candidates are timed
against the live pools (writes masked into the sink page) through the
same LRU the serving steps use — the winner's program is already warm.

Mesh-sharded serving (``EngineOptions.devices > 1``): the engine builds
a ``(data=dp, model=ep)`` mesh through
``distributed.context.make_serving_context``, shards the expert
weights over the EP axis and replicates everything else, and threads
the resulting ``DistContext`` into both jitted step bodies. Chunked
prefill then runs ``pipelined_moe``'s **sharded** layout (tokens split
over EP, real dispatch/combine All-to-Alls — which the wall-clock
measure therefore times too) while decode runs the **replicated**
psum-combine layout.

The cache pools have two mesh layouts
(``EngineOptions.kv_sharding``, see ``repro.serve.state_cache``):
``"replicated"`` keeps one logical pool with a replica on every device
(the PR 4 baseline — devices add compute but zero KV capacity), while
``"dp"`` shards the pools' page axis, the page table, the lens and the
decode batch over the mesh ``data`` axis — each dp group owns
``num_pages / dp`` pages with its own host-side free list, requests are
placed on a shard at admission (least-loaded, sticky for life), decode
runs data-parallel over the shards, and pool-dry preemption fires (and
picks its victim) per shard. Per-device resident KV drops ``dp``×, so
the same per-device page budget admits ``~dp``× the concurrent
requests before the first preemption. Everything else host-side —
scheduler queues, offload round-trips — is unchanged: one logical
engine, N devices under it. See ``docs/distributed.md``.
"""
from __future__ import annotations

import contextlib
import dataclasses
import logging
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.core.memory_model import PreemptionCost
from repro.core.strategies import host_offload_supported
from repro.core.types import TPU_V5E, HardwareSpec, Strategy
from repro.distributed.context import make_serving_context
from repro.models.api import get_model, serving_support
from repro.obs import PID_ENGINE, PID_REQUESTS, Recorder, quantile, scopes
from repro.serve.adaptive import PrefillBucketAdaptive, force_adaptive
from repro.serve.state_cache import KV_SHARDINGS, make_state_cache
from repro.serve.request import Request, RequestState
from repro.serve.sampling import (SAMPLE_PATHS, SamplingParams,
                                  sample_path, sample_tokens)
from repro.serve.scheduler import Scheduler

log = logging.getLogger("repro.serve")

__all__ = ["Engine", "EngineOptions"]

PREEMPT_POLICIES = ("auto", "recompute", "offload", "never")
ATTN_KERNELS = ("auto", "pallas", "gather")
PREFIX_CACHE_MODES = ("on", "off")


@dataclasses.dataclass
class EngineOptions:
    page_size: int = 16
    max_slots: int = 8                 # continuous-batch width (static)
    max_seq_len: int = 512             # per-request prompt + gen budget
    num_pages: int = 0                 # 0 = auto (worst case + sink page)
    chunk: int = 64                    # prefill chunk (tokens per step)
    min_bucket: int = 8
    hw: HardwareSpec = TPU_V5E
    devices: int = 0                   # 0/1 = single device; N>1 = build
                                       # a dp x ep mesh over N devices
    kv_sharding: str = "replicated"    # "replicated" | "dp": KV pool
                                       # layout over the mesh data axis
    ep_size: int = 1                   # resolver hints; overridden by the
    dp: int = 1                        # mesh when devices > 1
    dtype: Optional[str] = None        # None = cfg.compute_dtype
    cache_size: int = 16               # LRU bound on compiled prefill steps
    adaptive: bool = True              # resolve (n, strategy) per bucket
    measure: str = "auto"              # auto | wallclock | simulate
    measure_steps: int = 2             # wallclock reps per candidate
    measure_fn: Optional[Callable] = None
    preempt: str = "auto"              # auto | recompute | offload | never
    attn_kernel: str = "auto"          # decode attention over the paged
                                       # pools: "pallas" = fused page-walk
                                       # kernel (repro.kernels.
                                       # paged_attention), "gather" =
                                       # gather_pages baseline, "auto" =
                                       # pallas on TPU / gather elsewhere
                                       # (CPU runs the kernel in interpret
                                       # mode — exact but slow). Both
                                       # paths are bit-identical.
    prefix_cache: str = "off"          # "on": cross-request prefix reuse
                                       # over the paged pools (per-shard
                                       # trie of full-page token keys +
                                       # refcounted pages + copy-on-write
                                       # — see serve/paged_kv.py). Warm
                                       # prompts skip prefill for their
                                       # cached prefix; "off" is
                                       # bit-identical to the pre-prefix
                                       # allocator. Caches without
                                       # shareable page state (constant /
                                       # composite) degrade to "off".
    allow_offload: Optional[bool] = None   # None = host_offload_supported
    preempt_mfu: float = 0.5           # assumed MFU of re-prefill (cost)
    storm_every: int = 0               # N>0: force-preempt a victim every
                                       # N steps (preemption-storm tests —
                                       # constant-state caches never run
                                       # dry on their own)
    obs: Optional[Recorder] = None     # telemetry: None = metrics-only
                                       # registry + no-op tracer (the
                                       # zero-cost disabled path); pass
                                       # Recorder(tracer=Tracer()) to
                                       # record Perfetto spans

    @property
    def max_pages_per_seq(self) -> int:
        return -(-self.max_seq_len // self.page_size)


class Engine:
    def __init__(self, cfg: ArchConfig, params=None, *,
                 options: Optional[EngineOptions] = None, key=None):
        kind, why = serving_support(cfg)
        if kind is None:
            raise NotImplementedError(f"{cfg.name}: {why}")
        self.cache_kind = kind
        self.opts = opts = options or EngineOptions()
        # the registry is always real (stats() reads it; /metrics and
        # stats() agree by construction); only the tracer is optional
        self.obs = opts.obs if opts.obs is not None else Recorder()
        assert opts.preempt in PREEMPT_POLICIES, opts.preempt
        assert opts.kv_sharding in KV_SHARDINGS, opts.kv_sharding
        assert opts.attn_kernel in ATTN_KERNELS, opts.attn_kernel
        assert opts.prefix_cache in PREFIX_CACHE_MODES, opts.prefix_cache
        self._attn_kernel = opts.attn_kernel
        if self._attn_kernel == "auto":
            self._attn_kernel = ("pallas"
                                 if jax.default_backend() == "tpu"
                                 else "gather")
        if opts.adaptive:
            cfg = force_adaptive(cfg)
        self.cfg = cfg
        self.model = get_model(cfg)
        # device mesh (devices > 1): expert weights sharded over EP;
        # the KV pool layout follows opts.kv_sharding, the rest
        # replicates
        self.dist = make_serving_context(
            opts.devices,
            num_experts=cfg.moe.num_experts if cfg.moe is not None else 0)
        if opts.kv_sharding == "dp" and self.dist is None:
            raise ValueError(
                "kv_sharding='dp' shards the KV pools over the mesh "
                "data axis — a single-device engine has no mesh to "
                "shard over (set devices > 1, or use 'replicated')")
        self._replicated = None
        if self.dist is not None:
            from jax.sharding import NamedSharding, PartitionSpec
            self._replicated = NamedSharding(self.dist.mesh,
                                             PartitionSpec())
        ep_size = self.dist.ep_size if self.dist else opts.ep_size
        dp = self.dist.dp_size if self.dist else opts.dp
        if params is None:
            params = self.model.init(cfg, key or jax.random.PRNGKey(0))
        self.params = self._place_params(params)

        dtype = jnp.dtype(opts.dtype or cfg.compute_dtype)
        # the cache kind (paged / constant / composite) was decided by
        # serving_support above; num_pages=0 = auto (the paged cache
        # sizes the worst case itself — it owns the shard rounding +
        # per-shard sink rules)
        self.kv = make_state_cache(
            cfg, kind, num_pages=opts.num_pages,
            page_size=opts.page_size, max_slots=opts.max_slots,
            max_pages_per_seq=opts.max_pages_per_seq,
            max_seq_len=opts.max_seq_len, dtype=dtype, dist=self.dist,
            kv_sharding=opts.kv_sharding,
            prefix_cache=(opts.prefix_cache == "on"))
        if opts.prefix_cache == "on" and not self.kv.prefix_enabled:
            log.warning(
                "prefix_cache='on' but the %s cache has no shareable "
                "page-boundary state (recurrent rows are position-"
                "dependent) — prefix reuse is disabled; serving is "
                "otherwise unaffected", kind)
        if opts.kv_sharding == "dp" and self.kv.n_shards == 1:
            log.warning(
                "kv_sharding='dp' but the mesh's data axis has extent 1 "
                "(ep_split used every device for experts): the pools "
                "degenerate to the replicated layout — none of the "
                "dp-fold KV capacity/residency wins apply")
        self.scheduler = Scheduler(self.kv, chunk=opts.chunk,
                                   full_reserve=(opts.preempt == "never"),
                                   obs=self.obs)
        measure_fn = opts.measure_fn
        mode = opts.measure
        if mode == "auto":
            mode = ("wallclock" if jax.default_backend() != "cpu"
                    else "simulate")
        if measure_fn is None and mode == "wallclock":
            measure_fn = self._wallclock_measure
        self.adaptive = PrefillBucketAdaptive(
            cfg, hw=opts.hw, ep_size=ep_size, dp=dp,
            min_bucket=min(opts.min_bucket, opts.chunk),
            max_bucket=opts.chunk, measure_fn=measure_fn,
            shards=ep_size, obs=self.obs)
        # forward FLOPs/token of the active parameter set, for the
        # offload-vs-recompute preemption cost model
        self._flops_per_token = 2.0 * self.model.count_params(
            cfg, active_only=True)

        self._decode_fn = jax.jit(self._decode_step)
        self._prefill_fns: Dict[Tuple, Callable] = {}
        # per-slot sink page ids: constant for the engine's lifetime, so
        # one committed device copy serves every decode step
        self._decode_sinks = self.kv.device_sinks()
        self._next_rid = 0
        self.step_count = 0
        self._storm_tick = 0
        self.prefill_rejits = 0
        # actual trace counts of the jitted step bodies (a retrace means
        # the jit cache churned — e.g. an input arrived with a different
        # committed sharding); pinned by the compile-count regression
        # test in tests/test_serving_conformance.py
        self.decode_traces = 0
        self.prefill_traces = 0
        self.preempts: Dict[str, int] = {"recompute": 0, "offload": 0}
        self.nonfinite_logit_rows = 0
        # high-water mark of concurrently running requests while the
        # engine had not yet preempted anyone — the "admitted before
        # first preemption" capacity the DP-sharded benchmark reports
        self.peak_running_preempt_free = 0
        self.done: List[Request] = []
        # cancelled requests are kept apart from ``done``: they carry a
        # truncated output and (often) no tokens at all, so folding them
        # into the latency/TTFT percentiles would corrupt the SLO story
        self.cancelled: List[Request] = []
        self._init_metrics()

    # -- telemetry -------------------------------------------------------
    def _init_metrics(self) -> None:
        """Register this engine's metric families (idempotent — a shared
        registry across engines merges families)."""
        reg = self.obs.registry
        self._m_steps = reg.counter(
            "repro_engine_steps_total", "engine host steps", ["kind"])
        self._m_done = reg.counter(
            "repro_requests_done_total", "requests retired")
        self._m_tokens = reg.counter(
            "repro_tokens_generated_total", "tokens emitted to requests")
        self._m_prefill_tokens = reg.counter(
            "repro_prefill_tokens_total", "prompt tokens prefilled")
        self._m_preempts = reg.counter(
            "repro_preempts_total", "preemptions by mode", ["mode"])
        self._m_cancels = reg.counter(
            "repro_cancels_total",
            "cancelled requests by lifecycle stage", ["stage"])
        self._m_jit = reg.counter(
            "repro_jit_traces_total",
            "XLA traces of the jitted step bodies", ["body"])
        self._m_compiles = reg.counter(
            "repro_prefill_compiles_total", "compiled prefill programs")
        self._m_nonfinite = reg.counter(
            "repro_nonfinite_logit_rows_total",
            "served logit rows holding a NaN or inf")
        self._m_sample_path = reg.counter(
            "repro_sample_path_total",
            "sampler branch of each jitted prefill or decode call",
            ["path"])
        self._m_step_s = reg.histogram(
            "repro_step_seconds", "host wall time per engine step",
            ["kind"])
        self._m_lat = reg.histogram(
            "repro_latency_seconds", "request latency (submit to done)")
        self._m_ttft = reg.histogram(
            "repro_ttft_seconds", "time to first token")
        self._m_itl = reg.histogram(
            "repro_itl_seconds", "inter-token latency")
        # point-in-time gauges, filled by _refresh_gauges on demand
        reg.gauge("repro_waiting_requests", "admission queue depth")
        reg.gauge("repro_resuming_requests",
                  "preempted requests awaiting resume")
        reg.gauge("repro_running_slots", "occupied decode slots")
        self.obs.tracer.thread_name(PID_ENGINE, 1, "steps")
        self.kv.record_metrics(reg)

    def _refresh_gauges(self) -> None:
        """Pull point-in-time gauges into the registry: called by
        ``stats()`` and by the /metrics exporter's refresh hook, never
        per step — the disabled path pays nothing for them."""
        reg = self.obs.registry
        reg.gauge("repro_waiting_requests").set(
            len(self.scheduler.waiting))
        reg.gauge("repro_resuming_requests").set(
            len(self.scheduler.resuming))
        reg.gauge("repro_running_slots").set(
            len(self.scheduler.running))
        self.kv.record_metrics(reg)

    # -- mesh plumbing ---------------------------------------------------
    def _place_params(self, params):
        """Place the parameter tree on the mesh: expert weights sharded
        over the EP axis (matching ``moe.layer``'s shard_map in_specs, so
        no resharding on entry), everything else replicated. Leaves keep
        their single-device placement when there is no mesh."""
        if self.dist is None:
            return params
        from jax.sharding import NamedSharding, PartitionSpec as P
        mesh, ep = self.dist.mesh, self.dist.ep_size
        repl = self._replicated

        def place(path, leaf):
            under_experts = any(
                getattr(k, "key", None) == "experts" for k in path)
            # stacked expert leaves: [num_periods, num_experts, ...]
            if (ep > 1 and under_experts and leaf.ndim >= 2
                    and leaf.shape[1] % ep == 0):
                return jax.device_put(
                    leaf, NamedSharding(mesh, P(None, "model")))
            return jax.device_put(leaf, repl)

        return jax.tree_util.tree_map_with_path(place, params)

    def _put(self, x):
        """Host value -> device array (replicated under a mesh), so every
        step input carries one consistent committed sharding. Delegates
        to the KV cache's placement policy — the single source of truth
        for where step state lives."""
        return self.kv.to_device(x)

    def _put_slots(self, x):
        """Host ``[max_slots, ...]`` decode-batch array -> device, sharded
        over the slot axis when the KV pools are DP-sharded (each dp
        group computes only its own slots), replicated otherwise."""
        return self.kv.to_device_slots(x)

    def _mesh_scope(self):
        """Context activating the mesh around traces/executions (the
        bare-PartitionSpec constraints in ``DistContext.constrain``
        resolve against it)."""
        if self.dist is None:
            return contextlib.nullcontext()
        return jax.set_mesh(self.dist.mesh)

    def _pin_pools(self, pools):
        """Keep step outputs on the committed pool layout (replicated,
        or page-sharded over "data" under ``kv_sharding="dp"``) — without
        the constraint GSPMD may scatter the updated pools over whatever
        layout the (EP-sharded) chunk activations suggest, and the next
        step would recompile against it. Under the DP layout this is
        also the prefill→decode handoff: the chunk's KV writes land
        pinned on the owning shard's pages, so decode reads them with no
        re-placement. Delegates to the cache, which owns the layout."""
        return self.kv.pin_pools(pools)

    # -- jitted step bodies ---------------------------------------------
    def _decode_step(self, params, pools, page_table, lens, tokens, active,
                     sinks, temp, top_k, top_p, seed, pos):
        self.decode_traces += 1        # body runs only while tracing
        self._m_jit.labels(body="decode").inc()
        self.obs.tracer.instant("jit.trace", args={"body": "decode"})
        logits, new_pools = self.model.decode_step_paged(
            params, pools, page_table, lens, tokens, self.cfg,
            active=active, dist=self.dist, write_sink=sinks,
            attn_kernel=self._attn_kernel,
            kv_sharded=(self.opts.kv_sharding == "dp"
                        and self.kv.n_shards > 1))
        return (sample_tokens(logits, temp, top_k, top_p, seed, pos),
                jnp.isfinite(logits).all(axis=-1),
                self._pin_pools(new_pools))

    def _prefill_fn(self, bucket: int, rcfg: ArchConfig) -> Callable:
        m = rcfg.moe
        key = (bucket, (m.num_partitions, m.memory_reuse_strategy)
               if m is not None else (1, "none"))
        fn = self._prefill_fns.pop(key, None)          # LRU: re-insert
        if fn is None:
            def body(params, pools, pt_row, pos0, toks, valid_len, slot,
                     sink, temp, top_k, top_p, seed, pos, _cfg=rcfg):
                self.prefill_traces += 1
                self._m_jit.labels(body="prefill").inc()
                self.obs.tracer.instant("jit.trace",
                                        args={"body": "prefill"})
                logits, new_pools = self.model.prefill_chunk_paged(
                    params, pools, pt_row, pos0, toks, valid_len, _cfg,
                    dist=self.dist, write_sink=sink, slot=slot)
                return (sample_tokens(logits, temp, top_k, top_p, seed,
                                      pos),
                        jnp.isfinite(logits).all(axis=-1),
                        self._pin_pools(new_pools))
            # one program name per (bucket, n, strategy): the scope
            # table is keyed by the name a profiler trace prints
            body.__name__ = body.__qualname__ = "prefill_b{}_n{}_{}".format(
                bucket, *key[1])
            fn = jax.jit(body)
            self.prefill_rejits += 1
            self._m_compiles.inc()
        self._prefill_fns[key] = fn
        while len(self._prefill_fns) > max(1, self.opts.cache_size):
            self._prefill_fns.pop(next(iter(self._prefill_fns)))
        return fn

    # -- sampling parameter arrays ---------------------------------------
    def _sample_args(self, reqs: Sequence[Optional[Request]], *,
                     slots: bool = False):
        """Per-slot sampling arrays for ``sample_tokens`` (None slots are
        masked-off: greedy with dummy state, output discarded).
        ``slots=True`` marks a decode batch (one entry per slot), which
        shards over the slot axis with the DP-KV layout; prefill's
        single-row arrays stay replicated."""
        n = len(reqs)
        put = self._put_slots if slots else self._put
        temp = np.zeros((n,), np.float32)
        top_k = np.zeros((n,), np.int32)
        top_p = np.ones((n,), np.float32)
        seed = np.zeros((n,), np.int32)
        pos = np.zeros((n,), np.int32)
        for i, r in enumerate(reqs):
            if r is None:
                continue
            sp = r.sampling
            temp[i], top_k[i], top_p[i], seed[i] = (
                sp.temperature, sp.top_k, sp.top_p, sp.seed)
            pos[i] = len(r.output)
        # the branch sample_tokens will take on device, by the same rule
        self._m_sample_path.labels(
            path=SAMPLE_PATHS[sample_path(temp)]).inc()
        return tuple(put(a) for a in (temp, top_k, top_p, seed, pos))

    # -- serve-side wall-clock measurement -------------------------------
    def _wallclock_measure(self, b: int, n: int,
                           strategy: Strategy) -> float:
        """Algorithm 1's measure function for prefill buckets: time the
        compiled candidate (n, strategy) chunk step against the live
        pools. All writes go through a zeroed page-table row, i.e. into
        the reserved sink page, and the output pools are discarded — the
        probe cannot perturb serving state. Candidates land in the same
        prefill LRU the engine serves from, so the winner is pre-warmed.
        """
        rcfg = dataclasses.replace(
            self.cfg, moe=dataclasses.replace(
                self.cfg.moe, num_partitions=n,
                memory_reuse_strategy=strategy.value))
        fn = self._prefill_fn(b, rcfg)
        kv = self.kv
        args = (self.params, kv.pools,
                self._put(np.zeros((1, kv.page_table_width), np.int32)),
                self._put(np.zeros((1,), np.int32)),
                self._put(np.zeros((1, b), np.int32)),
                self._put(np.asarray(b, np.int32)),
                self._put(np.zeros((1,), np.int32)),     # slot 0 (probe)
                self._put(np.zeros((1,), np.int32)),     # sink: page 0
                *self._sample_args([None]))
        with self._mesh_scope():
            out = fn(*args)
            jax.block_until_ready(out[0])        # compile + warm up
            reps = max(1, self.opts.measure_steps)
            t0 = time.perf_counter()
            for _ in range(reps):
                out = fn(*args)
            jax.block_until_ready(out[0])
        return (time.perf_counter() - t0) / reps

    # -- request API -----------------------------------------------------
    def submit(self, prompt, *, max_new_tokens: int = 32,
               eos_id: Optional[int] = None, stop=(),
               sampling: Optional[SamplingParams] = None,
               priority: int = 0, on_token=None, on_done=None,
               arrival_s: Optional[float] = None) -> Request:
        req = Request(rid=self._next_rid, prompt=np.asarray(prompt),
                      max_new_tokens=max_new_tokens, eos_id=eos_id,
                      stop=stop, sampling=sampling or SamplingParams(),
                      priority=priority, on_token=on_token,
                      on_done=on_done,
                      arrival_s=(time.perf_counter() if arrival_s is None
                                 else arrival_s))
        self._next_rid += 1
        if not self.kv.admissible(req.total_budget):
            raise ValueError(
                f"request {req.rid}: budget {req.total_budget} tokens "
                f"exceeds engine capacity "
                f"({self.kv.max_slot_tokens} tokens per slot)")
        self.scheduler.submit(req)
        return req

    def cancel(self, req: Request) -> bool:
        """Cancel ``req`` from whatever lifecycle stage it is in,
        releasing its slot, pages and/or host-offload snapshot. Returns
        True if the request was live (now CANCELLED), False if it had
        already finished — a race every disconnect path hits, so it is
        not an error. NOT thread-safe: call between steps on the thread
        driving the engine (the ingress tier routes client disconnects
        through its engine-thread command queue for exactly this
        reason)."""
        if req.state in (RequestState.DONE, RequestState.CANCELLED):
            return False
        stage = self.scheduler.cancel(req)
        req.state = RequestState.CANCELLED
        req.finish_reason = "cancelled"
        req.finish_s = time.perf_counter()
        tracer = self.obs.tracer
        if req.decode_span_open:
            tracer.end("DECODE", pid=PID_REQUESTS, tid=req.rid)
            req.decode_span_open = False
        tracer.instant("CANCEL", pid=PID_REQUESTS, tid=req.rid,
                       args={"stage": stage,
                             "tokens": len(req.output)})
        self._m_cancels.labels(stage=stage).inc()
        self.cancelled.append(req)
        if req.on_done is not None:
            req.on_done(req)
        return True

    @property
    def has_work(self) -> bool:
        return self.scheduler.has_work

    def warmup(self) -> int:
        """Compile the decode program and every reachable prefill bucket
        up front, so serving latency (and benchmark numbers) reflect
        steady state instead of first-request XLA compiles. All warmup
        KV writes are masked into the sink page (inactive slots / zeroed
        page-table rows) and the resulting pools are discarded. Returns
        the number of programs compiled."""
        kv = self.kv
        before = self.prefill_rejits
        ran = [(self._decode_fn, self._idle_decode_args())]
        with self._mesh_scope():
            out = self._decode_fn(*ran[0][1])
            jax.block_until_ready(out[0])
        buckets, c = set(), 1
        while c < self.scheduler.chunk:
            buckets.add(self.adaptive.bucket_of(c))
            c *= 2
        buckets.add(self.adaptive.bucket_of(self.scheduler.chunk))
        for b in sorted(buckets):
            fn = self._prefill_fn(b, self.adaptive.cfg_for(b))
            args = (self.params, kv.pools, kv.device_page_table(0),
                    kv.device_lens(0),
                    self._put(np.zeros((1, b), np.int32)),
                    self._put(np.asarray(0, np.int32)),
                    self._put(np.zeros((1,), np.int32)),
                    self._put(kv.sink_row(0)),
                    *self._sample_args([None]))
            with self._mesh_scope():
                out = fn(*args)
                jax.block_until_ready(out[0])
            ran.append((fn, args))
        self._publish_scopes(ran)
        return 1 + self.prefill_rejits - before

    def _publish_scopes(self, ran) -> None:
        """Put the device scopes of each (program, arguments it ran
        with) into the process's scope table. Lowering a program that
        has run hits jit's caches: nothing is traced or compiled."""
        with self._mesh_scope():
            for fn, args in ran:
                scopes.register_compiled(fn.lower(*args).compile())

    def _idle_decode_args(self) -> tuple:
        """Decode-step arguments with every slot inactive (all KV writes
        masked into the sink page) — the warmup/lowering probe."""
        kv = self.kv
        return (self.params, kv.pools, kv.device_page_table(),
                kv.device_lens(),
                self._put_slots(np.zeros((kv.max_slots, 1), np.int32)),
                self._put_slots(np.zeros((kv.max_slots,), bool)),
                self._decode_sinks,
                *self._sample_args([None] * kv.max_slots, slots=True))

    def compiled_decode_text(self) -> str:
        """HLO text of the compiled decode program (from jit's caches
        once the program has run; compiled here otherwise)."""
        with self._mesh_scope():
            return self._decode_fn.lower(
                *self._idle_decode_args()).compile().as_text()

    # -- preemption ------------------------------------------------------
    def _pick_victim(self, shard: Optional[int] = None
                     ) -> Optional[Request]:
        """Lowest priority, then youngest, among running requests that
        actually hold cache bytes — on ``shard`` when given (pool-dry is
        a per-shard event under the DP-KV layout: only a victim on the
        dry shard frees capacity the grower can use)."""
        on_shard = [r for r in self.scheduler.running.values()
                    if shard is None
                    or self.kv.shard_of_slot(r.slot) == shard]
        cands = [r for r in on_shard if self.kv.held_bytes(r.slot) > 0]
        if not cands:
            # prefix cache: every page on the shard may be shared (zero
            # exclusive bytes per slot), yet preempting still helps —
            # the victim's dropped references turn shared pages into
            # evictable trie-only entries
            cands = on_shard
        if not cands:
            return None
        return min(cands, key=lambda r: (r.priority, -r.rid))

    def _preempt_mode(self, req: Request) -> str:
        """Per-victim offload-vs-recompute choice (PreemptionCost), gated
        by hardware/host capability like the train-side strategy mask."""
        if self.opts.preempt in ("recompute", "offload"):
            return self.opts.preempt
        offload_ok = self.opts.allow_offload
        if offload_ok is None:
            offload_ok = (self.opts.hw.has_host_offload
                          and host_offload_supported())
        if not offload_ok:
            return "recompute"
        hw = self.opts.hw
        cost = PreemptionCost(
            tokens_cached=int(self.kv.lens[req.slot]),
            bytes_held=self.kv.held_bytes(req.slot),
            flops_per_token=self._flops_per_token, flops=hw.flops,
            host_bw=hw.host_bw, mfu=self.opts.preempt_mfu,
            eta=hw.interference.eta_comp,
            link_shards=self.kv.n_shards)
        return cost.choice

    def _do_preempt(self, victim: Request) -> None:
        mode = self.scheduler.preempt(victim, self._preempt_mode(victim))
        self.preempts[mode] += 1
        self._m_preempts.labels(mode=mode).inc()
        log.info("preempt rid=%d mode=%s cached=%d", victim.rid, mode,
                 victim.cached_tokens if mode == "offload" else 0)

    def _ensure(self, slot: int, tokens: int) -> bool:
        """Grow ``slot`` until it can hold ``tokens``, preempting victims
        on the slot's shard while that shard is dry. Returns False if the
        slot's own request was chosen as the victim (it must skip this
        step)."""
        shard = self.kv.shard_of_slot(slot)
        while self.kv.slot_capacity(slot) < tokens:
            if self.kv.grow_slot(slot):
                continue
            victim = self._pick_victim(shard)
            if victim is None:
                raise RuntimeError(
                    f"page pool wedged: KV shard {shard} has no free "
                    f"pages and no victim")
            vslot = victim.slot
            self._do_preempt(victim)
            if vslot == slot:
                return False
        # prefix cache: the positions this step writes may live on pages
        # shared with the trie or other requests — copy-on-write (or
        # steal) them first; a dry shard preempts like growth does.
        # No-op with the prefix cache off.
        while not self.kv.ensure_private(slot, tokens):
            victim = self._pick_victim(shard)
            if victim is None:
                raise RuntimeError(
                    f"page pool wedged: KV shard {shard} cannot supply "
                    f"a copy-on-write page and has no victim")
            vslot = victim.slot
            self._do_preempt(victim)
            if vslot == slot:
                return False
        return True

    # -- engine iteration ------------------------------------------------
    def step(self) -> Dict[str, Any]:
        """Admit, then run one jitted step (prefill chunk or decode).

        Each phase is a span inside ``engine.step`` (args: kind):
        ``engine.admit`` (admission and the next action),
        ``engine.kv_grow`` (page growth, copy-on-write and preemption),
        ``engine.inputs`` (host arrays and their uploads),
        ``engine.dispatch`` (the jitted call), ``engine.sync`` (the first
        host read of its outputs) and ``engine.emit`` (tokens, retires).
        """
        t0 = time.perf_counter()
        tracer = self.obs.tracer
        with tracer.span("engine.step",
                         args={"step": self.step_count}) as sp:
            with tracer.span("engine.admit") as phase:
                # storm injection (tests/benchmarks): constant-state
                # caches hold O(1) bytes per slot and never run dry, so
                # preemption storms must be forced rather than provoked
                # by a small pool
                if (self.opts.storm_every and self.opts.preempt != "never"
                        and self.scheduler.running):
                    self._storm_tick += 1
                    if self._storm_tick >= self.opts.storm_every:
                        self._storm_tick = 0
                        victim = self._pick_victim()
                        if victim is not None:
                            self._do_preempt(victim)
                self.scheduler.admit()
                if not (self.preempts["recompute"]
                        or self.preempts["offload"]):
                    self.peak_running_preempt_free = max(
                        self.peak_running_preempt_free,
                        len(self.scheduler.running))
                action, req = self.scheduler.next_action()
                phase["kind"] = action
            sp["kind"] = action
            info: Dict[str, Any] = {"kind": action}
            if action == "prefill":
                info.update(self._run_prefill(req))
            elif action == "decode":
                info.update(self._run_decode())
            elif self.scheduler.waiting or self.scheduler.resuming:
                raise RuntimeError("scheduler idle with waiting "
                                   "requests — admission wedged")
        self.step_count += 1
        self._m_steps.labels(kind=action).inc()
        self._m_step_s.labels(kind=action).observe(
            time.perf_counter() - t0)
        return info

    def _run_prefill(self, req: Request) -> Dict[str, Any]:
        kv, slot = self.kv, req.slot
        tracer, kind = self.obs.tracer, {"kind": "prefill"}
        c = min(self.scheduler.chunk, req.remaining_prefill)
        with tracer.span("engine.kv_grow", args=kind):
            if not self._ensure(slot, int(kv.lens[slot]) + c):
                return {"tokens": 0, "rid": req.rid,
                        "self_preempted": True}
        with tracer.span("engine.inputs", args=kind):
            bucket = self.adaptive.bucket_of(c)
            rcfg = self.adaptive.cfg_for(bucket)
            fn = self._prefill_fn(bucket, rcfg)
            toks = np.zeros((1, bucket), np.int32)
            toks[0, :c] = req.prefill_tokens[req.prefill_pos:
                                             req.prefill_pos + c]
            args = (self.params, kv.pools, kv.device_page_table(slot),
                    kv.device_lens(slot), self._put(toks),
                    self._put(np.asarray(c, np.int32)),
                    self._put(np.asarray([slot], np.int32)),
                    self._put(kv.sink_row(slot)), *self._sample_args([req]))
        with tracer.span("engine.dispatch", args=kind), \
             tracer.span("PREFILL", pid=PID_REQUESTS, tid=req.rid,
                         args={"chunk": c, "bucket": bucket,
                               "pos": req.prefill_pos}), \
             self._mesh_scope():
            tok, finite, kv.pools = fn(*args)
        del args
        with tracer.span("engine.sync", args=kind):
            finite = np.asarray(finite)
        with tracer.span("engine.emit", args=kind):
            self._count_nonfinite(finite)
            req.prefill_pos += c
            kv.lens[slot] += c
            self._m_prefill_tokens.inc(c)
            self.scheduler.prefill_advanced(req)
            if req.remaining_prefill == 0:
                # publish the finished prompt's full pages for later
                # requests sharing the prefix (no-op with prefix off)
                kv.cache_slot_prefix(slot, req.prefill_tokens)
                req.state = RequestState.DECODE
                tracer.begin("DECODE", pid=PID_REQUESTS, tid=req.rid)
                req.decode_span_open = True
                # a resumed re-prefill (recompute preemption) replays
                # tokens that were already emitted — its final-chunk
                # sample is the pending decode input, not a new token
                if not req.output and req.emit(int(tok[0]),
                                               time.perf_counter()):
                    self._retire(req)
        info = {"tokens": c, "bucket": bucket, "rid": req.rid}
        if rcfg.moe is not None:
            info.update(n=rcfg.moe.num_partitions,
                        strategy=rcfg.moe.memory_reuse_strategy)
        return info

    def _run_decode(self) -> Dict[str, Any]:
        kv = self.kv
        tracer, kind = self.obs.tracer, {"kind": "decode"}
        with tracer.span("engine.kv_grow", args=kind):
            # every decoding slot writes one KV position this step —
            # grow on-demand slots first, preempting victims if the pool
            # is dry (a victim may itself be one of the decoding slots)
            for s in list(self.scheduler.decode_slots()):
                req = self.scheduler.running.get(s)
                if req is None or req.state != RequestState.DECODE:
                    continue                   # preempted by an earlier
                self._ensure(s, int(kv.lens[s]) + 1)  # slot's growth
        slots = self.scheduler.decode_slots()
        if not slots:
            return {"tokens": 0}
        with tracer.span("engine.inputs", args=kind):
            tokens = np.zeros((kv.max_slots, 1), np.int32)
            active = np.zeros((kv.max_slots,), bool)
            by_slot: List[Optional[Request]] = [None] * kv.max_slots
            for s in slots:
                req = self.scheduler.running[s]
                tokens[s, 0] = req.output[-1]
                active[s] = True
                by_slot[s] = req
            args = (self.params, kv.pools, kv.device_page_table(),
                    kv.device_lens(), self._put_slots(tokens),
                    self._put_slots(active), self._decode_sinks,
                    *self._sample_args(by_slot, slots=True))
        with tracer.span("engine.dispatch", args=kind), \
             self._mesh_scope():
            toks, finite, kv.pools = self._decode_fn(*args)
        del args
        with tracer.span("engine.sync", args=kind):
            toks = np.asarray(toks)
            finite = np.asarray(finite)
        with tracer.span("engine.emit", args=kind):
            self._count_nonfinite(finite[slots])
            now = time.perf_counter()
            for s in slots:
                req = self.scheduler.running[s]
                kv.lens[s] += 1                # the input token's KV slot
                if req.emit(int(toks[s]), now):
                    self._retire(req)
        return {"tokens": len(slots)}

    def _count_nonfinite(self, finite: np.ndarray) -> None:
        """Served logit rows with a NaN/inf entry: a sampled token from
        such a row is garbage, so the count is a model-health signal."""
        bad = int(finite.size - np.count_nonzero(finite))
        if bad:
            self.nonfinite_logit_rows += bad
            self._m_nonfinite.inc(bad)

    def _retire(self, req: Request) -> None:
        # publish the retiring request's written full pages (prompt plus
        # generated turn) before the slot frees: the trie's reference
        # keeps them alive for the conversation's next turn
        self.kv.cache_slot_prefix(req.slot, req.prefill_tokens)
        tracer = self.obs.tracer
        if req.decode_span_open:
            tracer.end("DECODE", pid=PID_REQUESTS, tid=req.rid)
            req.decode_span_open = False
        tracer.instant("RETIRE", pid=PID_REQUESTS, tid=req.rid,
                       args={"reason": req.finish_reason})
        self.scheduler.finish(req)
        self.done.append(req)
        self._m_done.inc()
        self._m_tokens.inc(len(req.output))
        self._m_lat.observe(req.latency_s)
        self._m_ttft.observe(req.ttft_s)
        for g in req.itl_s:
            self._m_itl.observe(g)

    def run_until_idle(self, max_steps: int = 1_000_000) -> None:
        steps = 0
        while self.has_work:
            self.step()
            steps += 1
            if steps > max_steps:
                raise RuntimeError(f"no quiescence in {max_steps} steps")

    # -- reporting -------------------------------------------------------
    def stats(self) -> Dict[str, Any]:
        # percentiles via the shared nearest-rank quantile (repro.obs)
        # — the old hand-rolled int(p/100*n) index overshot by a rank
        lat = [r.latency_s for r in self.done]
        ttft = [r.ttft_s for r in self.done]
        itl = [g for r in self.done for g in r.itl_s]
        self._refresh_gauges()
        reg = self.obs.registry
        free_fam = reg.get("repro_kv_free_units")
        return {
            "requests_done": len(self.done),
            "requests_cancelled": len(self.cancelled),
            "cancelled_by_stage": {
                dict(c.labels)["stage"]: int(c.value)
                for c in self._m_cancels.children()},
            "tokens_generated": sum(len(r.output) for r in self.done),
            "devices": 1 if self.dist is None else self.dist.mesh.size,
            "ep_size": 1 if self.dist is None else self.dist.ep_size,
            "dp_size": 1 if self.dist is None else self.dist.dp_size,
            "kv_sharding": self.opts.kv_sharding,
            "kv_shards": self.kv.n_shards,
            "attn_kernel": self._attn_kernel,
            "engine_steps": self.step_count,
            "prefill_compiles": self.prefill_rejits,
            "decode_traces": self.decode_traces,
            "prefill_traces": self.prefill_traces,
            "nonfinite_logit_rows": self.nonfinite_logit_rows,
            "p50_latency_s": quantile(lat, 50),
            "p99_latency_s": quantile(lat, 99),
            "p50_ttft_s": quantile(ttft, 50),
            "p99_ttft_s": quantile(ttft, 99),
            "p50_itl_s": quantile(itl, 50),
            "p99_itl_s": quantile(itl, 99),
            # live gauges, read back from the registry so /metrics and
            # stats() report the same values by construction
            "queue_waiting": int(
                reg.gauge("repro_waiting_requests").value),
            "queue_resuming": int(
                reg.gauge("repro_resuming_requests").value),
            "running_slots": int(
                reg.gauge("repro_running_slots").value),
            "free_units_by_shard": {
                dict(c.labels)["shard"]: int(c.value)
                for c in (free_fam.children() if free_fam else ())},
            "prefix_cache": self.opts.prefix_cache,
            "prefix_hits": self.kv.prefix_hits,
            "prefix_misses": self.kv.prefix_misses,
            "prefix_hit_tokens": self.kv.prefix_hit_tokens,
            "prefix_hit_rate": (
                self.kv.prefix_hits
                / max(1, self.kv.prefix_hits + self.kv.prefix_misses)),
            "prefix_cow_copies": self.kv.prefix_cow_copies,
            "prefix_cow_bytes": self.kv.prefix_cow_bytes,
            "prefix_evicted_pages": self.kv.prefix_evicted_pages,
            "prefix_cached_pages": sum(
                self.kv.prefix_cached_pages_of(s)
                for s in range(self.kv.n_shards)),
            "prefix_shared_pages": sum(
                self.kv.prefix_shared_pages_of(s)
                for s in range(self.kv.n_shards)),
            "preempt_recompute": self.preempts["recompute"],
            "preempt_offload": self.preempts["offload"],
            "resumes": self.scheduler.resume_count,
            "swap_out_bytes": self.kv.swap_out_bytes,
            "swap_in_bytes": self.kv.swap_in_bytes,
            "cache_bytes": self.kv.cache_bytes,
            "peak_kv_used_bytes": self.kv.peak_used_bytes,
            "per_device_cache_bytes": self.kv.per_device_cache_bytes,
            "per_device_peak_kv_used_bytes":
                self.kv.per_device_peak_used_bytes,
            "peak_running_preempt_free": self.peak_running_preempt_free,
            "resolutions": {str(b): list(r) for b, r in
                            self.adaptive.resolutions.items()},
        }
