"""Batched serving engine with DAK tiered offloading.

Ragged continuous batching over a fixed pool of ``max_batch`` slots:
requests are admitted into any free slot (no alignment requirement — every
slot tracks its own KV length), decode steps take the per-slot ``lens``
vector, and finished requests free their slot for the next queued request.

Admission and prefill slicing are delegated to a pluggable scheduler
(`repro.frontend.scheduler`): the FCFS default reproduces the classic
whole-prompt submit/run loop exactly, while the priority and SLO-aware
(earliest-deadline-first) schedulers add **chunked prefill** — long
prompts split into fixed per-step token budgets (`models.prefill_chunk`
against a private per-request cache) interleaved with decode steps, so
the telemetry/AIMD plane sees a smooth prefill/decode mix — and
**tier-demotion preemption**: on KV page pressure a victim's local pages
are demoted to the remote pool (`PagedTieredCache.demote_slot_pages`,
budget shared with the live migrator) and the victim keeps decoding
through the direct-access paged kernel, exact tokens, no recompute.
Scheduling never changes any request's tokens — only when they are
produced; per-request lifecycle metrics (queue delay, TTFT, end-to-end
latency, per-class SLO attainment — `frontend.metrics`) fold into
`EngineStats`, and trace replay runs on a modeled clock so scheduler
comparisons are deterministic.

Offloading is planned once at startup (OffloadEngine) and realized through
the unified tiering API: ``TieringPlan.partition`` wraps every registered
operand (`models.registry`) in a `TieredArray` — dense/VLM linears, MoE
expert stacks, MLA latent projections, SSM projections — and dispatch is by
operand type, for every decoder family:

* prefill runs `models.prefill` directly over the tiered params (pure-jnp
  operand dispatch) — remote partitions are never concatenated back into
  HBM;
* decode runs the direct-access kernels (`serving.tiered_decode`): the
  tiered GEMM for weights plus the paged tiered KV cache
  (`serving.paged_cache.PagedTieredCache`) for the attention families
  (GQA pages, or MLA latent pages attended in absorbed form), the
  recurrent tiered step for SSM, and the grouped step for hybrids.

The reference pjit path (`models.decode_step`) accepts the same tiered
params and serves as the no-kernel fallback.

With ``adaptive=True`` the engine closes the loop through the adaptive
runtime (`repro.runtime`): every step it reports a telemetry sample
(bytes per tier, queue depth, prefill/decode token mix) to a
`RuntimeController`, reads back the AIMD-controlled in-flight DMA window
(threaded per-step into the kernels instead of the plan-time constant),
lets the bounded-budget migrator re-place KV pages between tiers, and —
when the observed workload mix drifts — swaps in incrementally
repartitioned params from the phase-aware re-planner.  With every runtime
budget at zero the adaptive engine is bitwise-identical to the static one.

With a ``mesh`` the engine serves one replica across P chips, each with
its own host link (paper §4.3.2 fetch-once-broadcast as a serving mode):
the plan is solved on the aggregate of the P links, every host-resident
weight partition is committed as disjoint 1/P slices
(`launch.sharding.shard_tiered_params`), the paged KV cache shards its
remote pools the same way, and each step rebuilds the full operands
through one `kernels.ops.broadcast_remote` pass inside ``shard_map`` —
so each offloaded byte crosses one host link per step and the per-link
traffic drops ~1/P vs naive replication, while tokens stay
bitwise-identical to the single-chip engine.  Telemetry and the adaptive
runtime account and pace each link separately (per-link congestion
windows).
"""
from __future__ import annotations

import dataclasses
import time
import warnings
from collections import deque
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core import engine as offload_engine
from repro.core import multicast
from repro.core.ebmodel import WorkloadSpec
from repro.core.hardware import HardwareSpec, MeshSpec, TPU_V5E
from repro.frontend.metrics import (
    Clock,
    ModeledClock,
    RequestRecord,
    WallClock,
    modeled_step_cost,
    percentile,
)
from repro.frontend.scheduler import Scheduler, get_scheduler
from repro.models import model as M
from repro.obs.attribution import NULL_PROFILER
from repro.obs.trace import (
    ENGINE,
    HEALTH_LEVEL,
    LINKS,
    NULL_RECORDER,
    REQUESTS,
    TraceRecorder,
)
from repro.runtime.controller import RuntimeController
from repro.runtime.health import HEALTHY, HealthMonitor
from repro.runtime.telemetry import (
    StepSample,
    weight_link_bytes,
    weight_tier_bytes,
)
from repro.serving import tiered_decode as TD
from repro.serving.paged_cache import REMOTE, CacheFull, PagedTieredCache

# Families served through the direct-access kernel path ("encoder" has no
# decode step; everything else goes tiered).
TIERED_FAMILIES = ("dense", "vlm", "moe", "ssm", "hybrid")


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray                     # [T] int32
    max_new_tokens: int = 16
    eos_id: int = -1                       # -1: never stop early
    out_tokens: list[int] = dataclasses.field(default_factory=list)
    t_submit: float = 0.0
    t_first: float = 0.0
    t_done: float = 0.0
    # -- scheduling metadata (frontend) --------------------------------
    cls: str = "default"                   # tenant / priority class name
    priority: int = 0                      # higher = more urgent
    arrival_s: float | None = None         # trace arrival (clock seconds);
    #                                        None = ready at submit
    slo_ttft_s: float | None = None        # TTFT SLO (None = best effort)
    t_admit: float = 0.0                   # first prefill chunk scheduled
    preemptions: int = 0                   # tier-demotion preemptions suffered
    admitted_degraded: bool = False        # admitted while health != healthy


@dataclasses.dataclass
class PrefillState:
    """An in-flight chunked prefill: the request holds a private batch-1
    cache that successive `models.prefill_chunk` calls fill; on the last
    chunk the cache is committed to the slot (paged pools / reference
    cache) and the request joins the decode batch."""
    req: Request
    cache: dict[str, jax.Array] | None = None   # lazy: only chunked prefills
    #                                             allocate it (whole-prompt
    #                                             admissions use M.prefill's)
    pos: int = 0                           # prompt tokens processed so far
    logits: jax.Array | None = None        # last chunk's final-position logits


@dataclasses.dataclass
class EngineStats:
    served: int = 0
    generated_tokens: int = 0              # tokens actually emitted (all reqs)
    decode_steps: int = 0
    decode_time: float = 0.0
    # Host seconds of every prefill chunk from its dispatch; a prompt's
    # last chunk ends after the first-token sync, so the device has run it.
    prefill_time: float = 0.0
    local_pages_hwm: int = 0               # peak pages resident per tier
    remote_pages_hwm: int = 0
    spills: int = 0                        # pressure-driven local->remote moves
    promoted_pages: int = 0                # migration: remote->local
    demoted_pages: int = 0                 # migration: local->remote
    replans: int = 0                       # phase-aware re-planner firings
    final_window: int = 0                  # in-flight DMA window after the run
    prefill_chunks: int = 0                # continuation chunks (beyond 1st)
    preemptions: int = 0                   # tier-demotion preemption events
    preempt_demoted_pages: int = 0         # pages demoted by preemptions
    # -- elastic degradation (never-OOM): the engine catches CacheFull and
    # degrades, so failed_requests stays 0 by construction — the counter
    # exists so chaos runs can *assert* the guarantee, not hope for it.
    failed_requests: int = 0
    health: str = "healthy"                # final health state
    cache_full_caught: int = 0             # CacheFull converted to demotion
    elastic_demoted_pages: int = 0         # deficit-drain demotions
    remote_grown_pages: int = 0            # emergency host-pool growth
    shed_steps: int = 0                    # steps admissions were shed
    elastic_replans: int = 0               # forced higher-ratio re-plans
    ttfts: list[float] = dataclasses.field(default_factory=list)
    # per-request time-to-first-token (t_first - t_submit), appended at admit
    queue_delays: list[float] = dataclasses.field(default_factory=list)
    # per-request queue delay (t_admit - t_submit), appended at admission
    e2e_latencies: list[float] = dataclasses.field(default_factory=list)
    # per-request end-to-end latency (t_done - t_submit), appended at finish
    requests: list = dataclasses.field(default_factory=list)
    # per-request lifecycle records (frontend.metrics.RequestRecord)

    @property
    def tpot(self) -> float:
        return self.decode_time / max(1, self.decode_steps)

    @staticmethod
    def _pct(values: list[float], q: float) -> float:
        return percentile(values, q)

    @property
    def ttft_p50(self) -> float:
        return self._pct(self.ttfts, 50)

    @property
    def ttft_p95(self) -> float:
        return self._pct(self.ttfts, 95)

    @property
    def queue_delay_p50(self) -> float:
        return self._pct(self.queue_delays, 50)

    @property
    def queue_delay_p95(self) -> float:
        return self._pct(self.queue_delays, 95)

    @property
    def e2e_p50(self) -> float:
        return self._pct(self.e2e_latencies, 50)

    @property
    def e2e_p95(self) -> float:
        return self._pct(self.e2e_latencies, 95)

    def slo_report(self) -> dict:
        """Per-tenant-class SLO attainment + latency percentiles
        (`frontend.metrics.slo_report` over the request records)."""
        from repro.frontend.metrics import slo_report

        return slo_report(self.requests)

    def register_metrics(self, reg, *, global_ratio: float,
                         wall_s: float) -> None:
        """Register the serving counters into a
        `repro.obs.metrics.MetricsRegistry`.  Registration order mirrors
        the legacy ``launch.serve.bench_report`` fields exactly, so the
        registry's JSON view is byte-identical to the hand-built stats
        block it replaces (pinned by tests/test_obs.py)."""
        reg.counter("served", "requests finished").set_total(self.served)
        reg.gauge("global_ratio",
                  "planned global offload ratio").set(global_ratio)
        reg.gauge("wall_s", "run wall time").set(wall_s)
        reg.counter("generated_tokens",
                    "tokens actually emitted").set_total(self.generated_tokens)
        reg.gauge("tokens_per_s").set(
            self.generated_tokens / wall_s if wall_s > 0 else 0.0)
        reg.gauge("tpot_ms", "mean time per output token").set(self.tpot * 1e3)
        reg.gauge("ttft_p50_ms").set(self.ttft_p50 * 1e3)
        reg.gauge("ttft_p95_ms").set(self.ttft_p95 * 1e3)
        reg.gauge("queue_delay_p50_ms").set(self.queue_delay_p50 * 1e3)
        reg.gauge("queue_delay_p95_ms").set(self.queue_delay_p95 * 1e3)
        reg.gauge("e2e_p50_ms").set(self.e2e_p50 * 1e3)
        reg.gauge("e2e_p95_ms").set(self.e2e_p95 * 1e3)
        reg.counter("decode_steps").set_total(self.decode_steps)
        reg.counter("scheduling.prefill_chunks").set_total(self.prefill_chunks)
        reg.counter("scheduling.preemptions").set_total(self.preemptions)
        reg.counter("scheduling.preempt_demoted_pages").set_total(
            self.preempt_demoted_pages)
        reg.const("scheduling.slo", self.slo_report())
        reg.counter("kv.spills").set_total(self.spills)
        reg.gauge("kv.local_pages_hwm").set(self.local_pages_hwm)
        reg.gauge("kv.remote_pages_hwm").set(self.remote_pages_hwm)
        reg.counter("failed_requests").set_total(self.failed_requests)


class ServingEngine:
    def __init__(
        self,
        cfg: ModelConfig,
        params: dict[str, Any],
        *,
        max_batch: int = 4,
        max_len: int = 128,
        hw: HardwareSpec = TPU_V5E,
        hbm_budget_bytes: float | None = None,
        global_offload_ratio: float | None = None,
        use_kernels: bool = True,
        page_size: int = 8,
        adaptive: bool = False,
        runtime: RuntimeController | None = None,
        mesh: jax.sharding.Mesh | None = None,
        mesh_axis: str | None = None,
        scheduler: str | Scheduler | None = None,
        prefill_chunk: int | None = None,
        clock: Clock | None = None,
        check_invariants: bool = False,
        recorder: TraceRecorder | None = None,
        flight=None,
        jit_step: bool = True,
        tuner: Any = None,
        profiler=None,
    ):
        """``scheduler`` selects the serving frontend policy — a name
        ('fcfs' | 'priority' | 'slo'), a `frontend.scheduler.Scheduler`
        instance, or None for the default FCFS whole-prompt behaviour
        (identical to the pre-frontend engine).  ``prefill_chunk`` caps
        the prompt tokens prefilled per step (chunked prefill; only
        applies when a scheduler name is given — an instance carries its
        own chunk budget).  ``clock`` is the lifecycle timestamp source:
        wall time by default, or a `frontend.metrics.ModeledClock` that
        the engine advances by the analytical step latency (trace replay
        and scheduler comparisons run on the modeled clock).
        ``check_invariants`` audits the paged cache's page-table
        invariants (``repro.analysis.page_table``, DAK301-305) after
        every step and raises ``InvariantViolation`` on the first
        inconsistency — the checks are read-only host-side bookkeeping,
        so enabling them never changes tokens or stats.  ``recorder`` is
        an `obs.trace.TraceRecorder` (default: the no-op null recorder —
        the serving path is bitwise-identical with tracing off) and
        ``flight`` an `obs.flight.FlightRecorder` that keeps a bounded
        ring of per-step state snapshots and dumps a post-mortem bundle
        when a run dies or breaches its SLO.  ``profiler`` is an
        `obs.attribution.AttributionProfiler` that receives the modeled
        per-step cost decomposition (default: the no-op null profiler —
        attribution off is bitwise-identical, same contract as the
        recorder)."""
        self.cfg = cfg
        self.hw = hw
        self.max_batch = max_batch
        self.max_len = max_len
        self.page_size = page_size
        self.clock = clock if clock is not None else WallClock()
        if isinstance(scheduler, Scheduler):
            self.scheduler = scheduler
        else:
            kw = {"chunk_tokens": prefill_chunk} if prefill_chunk else {}
            self.scheduler = get_scheduler(scheduler or "fcfs", **kw)
        self.use_kernels = use_kernels and cfg.family in TIERED_FAMILIES
        self.mesh = mesh
        self.mesh_axis = (mesh_axis or mesh.axis_names[-1]) if mesh is not None else None
        self.n_links = int(mesh.shape[self.mesh_axis]) if mesh is not None else 1
        wl = WorkloadSpec(batch=max_batch, seq_len=max_len, phase="decode")
        self.plan = offload_engine.plan(
            cfg, wl, hw, hbm_budget_bytes=hbm_budget_bytes,
            global_ratio=global_offload_ratio, kv_page_size=page_size,
            mesh=(MeshSpec(n_devices=self.n_links, axis_name=self.mesh_axis)
                  if mesh is not None else None))
        self.window = self.plan.window.n_inflight
        self._align = 32 if cfg.d_model < 1024 else 128
        # One partition pass for every family (the unified API); at ratio 0
        # no leaf is wrapped and the kernel path runs over plain weights.
        self.tiered = self.use_kernels
        dtype = next(iter(jax.tree.leaves(params))).dtype
        if self.tiered:
            self.params = self.plan.partition(params, align=self._align)
        else:
            self.params = params
        # The engine keeps only the partitioned tree: when the caller holds
        # no other reference, the unsplit weights are freed here.
        del params
        if mesh is not None:
            # Commit the tree to the serving mesh: remote partitions as
            # disjoint 1/P host-link slices, everything else replicated.
            from repro.launch.sharding import shard_tiered_params

            self.params = shard_tiered_params(self.params, mesh, self.mesh_axis)
        # Adaptive runtime: seeded from the static plan; pass `runtime` to
        # override budgets/measurement source (tests use the zero-budget
        # no-op configuration and the analytical model source).
        self.runtime: RuntimeController | None = runtime
        if adaptive and self.runtime is None:
            self.runtime = RuntimeController(cfg, self.plan, hw,
                                             align=self._align)
        self._weight_bytes = weight_tier_bytes(self.params)
        self._weight_link_bytes = weight_link_bytes(self.params, self.n_links)

        self.pcache: PagedTieredCache | None = None
        self.cache: dict[str, jax.Array] | None = None
        if self.tiered and cfg.family in ("dense", "vlm", "moe"):
            self.pcache = self._make_pcache(cfg.n_layers, dtype)
        elif self.tiered and cfg.family == "hybrid" and cfg.hybrid_attn_every:
            self.pcache = self._make_pcache(
                cfg.n_layers // cfg.hybrid_attn_every, dtype)
            full = M.init_cache(cfg, max_batch, max_len, dtype)
            self.cache = {"conv": full["conv"], "state": full["state"]}
        else:
            # SSM (no KV cache) or the reference fallback path.
            self.cache = M.init_cache(cfg, max_batch, max_len, dtype)
        self._dtype = dtype
        self._t0 = self.clock.now()        # clock origin trace arrivals anchor to
        self.lens = np.zeros(max_batch, dtype=np.int32)     # per-slot kv length
        self.active: list[Request | None] = [None] * max_batch
        self.prefilling: dict[int, PrefillState] = {}   # slot -> chunked prefill
        self.stats = EngineStats()
        self.stats.final_window = self.window
        self._next_tok = np.zeros((max_batch, 1), dtype=np.int32)
        self._prefill_calls_step = 0       # prefill passes in the last _admit
        self._preempt_moved_step = 0       # preemption demotions this step
        self._step_params: dict[str, Any] | None = None  # per-step fetch cache
        # Compiled decode step: one jax.jit per (kind, window-bucket,
        # pool-shape) bucket, with the K/V page pools (and recurrent state)
        # donated so per-layer scatters write in place instead of
        # materializing a functional copy of each pool per layer.  The
        # non-tiered reference path stays eager (it is the oracle the
        # tiered path is checked against).
        self.tuner = tuner
        self._jit = bool(jit_step) and self.use_kernels
        self._compiled: dict[tuple, Any] = {}
        self.compile_count = 0             # fresh jit compilations (buckets)
        self.compile_cache_hits = 0        # steps served by a cached bucket
        # Elastic degradation: the engine always owns a health monitor
        # (runtime attached or not) — with no pressure it never leaves
        # `healthy` and every counter stays zero.
        self.health = HealthMonitor()
        self._pending_shrink: tuple[int, float] | None = None
        self.check_invariants = check_invariants
        # Observability: both default off (NULL_RECORDER's emissions are
        # no-ops, flight=None records nothing), and every emission site is
        # guarded, so the disabled engine is bitwise-identical (pinned by
        # the parity test in tests/test_obs.py).
        self.recorder = recorder if recorder is not None else NULL_RECORDER
        self.flight = flight
        self.profiler = profiler if profiler is not None else NULL_PROFILER
        if self.profiler.enabled:
            # The optimality-fraction denominator: the plan's converged
            # AIMD aggregate (`core.congestion.optimal_window`).
            self.profiler.attach(clock_kind=self.clock.kind,
                                 optimal_bw=float(self.plan.window.aggregate_bw))
        self._slo_dumped = False
        if self.recorder.enabled:
            self._wire_observability()

    def _wire_observability(self) -> None:
        """Point the health monitor's and runtime controller's event hooks
        at the trace recorder.  The hooks default to None, so with tracing
        off neither component ever makes a call."""
        rec = self.recorder
        rec.clock = self.clock.now         # phases stamp the engine's clock
        rec.name_thread(ENGINE, 0, "step")

        def on_health(event: str, **info) -> None:
            t = self.clock.now()
            if event == "transition":
                rec.instant(ENGINE, 0, f"health:{info['src']}->{info['dst']}",
                            t, cat="health")
            else:
                rec.instant(ENGINE, 0, f"pressure:{info['kind']}", t,
                            cat="elastic", pages=info.get("pages", 0))

        self.health.listener = on_health
        if self.runtime is not None:
            def on_runtime(name: str, **args) -> None:
                rec.instant(ENGINE, 0, name, self.clock.now(),
                            cat="runtime", **args)

            self.runtime.on_event = on_runtime

    def _audit_page_table(self) -> None:
        """Debug hook: fail fast on page-table corruption (DAK301-305)."""
        if not self.check_invariants or self.pcache is None:
            return
        from repro.analysis.page_table import InvariantViolation, check_page_table

        findings = check_page_table(
            self.pcache, where=f"engine.step[{self.stats.decode_steps}]")
        if findings:
            raise InvariantViolation(findings)

    @property
    def queue(self) -> deque[Request]:
        """Admissible requests, in arrival order (the scheduler's ready
        queue; future trace arrivals wait in its pending heap)."""
        return self.scheduler.ready

    def _make_pcache(self, n_kv_layers: int, dtype) -> PagedTieredCache:
        cfg = self.cfg
        if cfg.use_mla:
            # MLA pages carry the latent [ckv | k_rope] as one kv head,
            # stored once (K-only; the V read aliases the K pool) — pool
            # bytes match the planner's per-token KV accounting.
            kv_heads, head_dim = 1, cfg.kv_lora_rank + cfg.rope_head_dim
        else:
            kv_heads, head_dim = cfg.n_kv_heads, cfg.resolved_head_dim
        pp = self.plan.kv_pages
        return PagedTieredCache(
            n_kv_layers, kv_heads, head_dim,
            page_size=self.page_size,
            local_pages=pp.local_pages,
            remote_pages=pp.remote_pages,
            max_slots=self.max_batch,
            max_pages_per_slot=-(-self.max_len // self.page_size),
            dtype=dtype,
            store_v=not cfg.use_mla,
            mesh=self.mesh,
            mesh_axis=self.mesh_axis)

    # ------------------------------------------------------------------
    def submit(self, req: Request) -> None:
        """Hand a request to the scheduler.  ``req.arrival_s`` is an
        offset from engine start: it is anchored to this engine's clock
        origin here, so a trace replays correctly on the modeled clock
        (origin 0.0 — offsets pass through) *and* on the wall clock
        (real-time replay: arrivals release as wall time reaches them),
        instead of virtual offsets being compared against epoch time."""
        now = self.clock.now()
        if req.arrival_s is not None:
            req.arrival_s = self._t0 + req.arrival_s
        req.t_submit = now
        if self.recorder.enabled:
            self.recorder.name_thread(REQUESTS, req.rid, f"req{req.rid}")
            self.recorder.instant(
                REQUESTS, req.rid, "submit",
                req.arrival_s if req.arrival_s is not None else now,
                cat="lifecycle", cls=req.cls, prompt=len(req.prompt))
        self.scheduler.submit(req, now)

    def _free_slots(self) -> list[int]:
        return [i for i, r in enumerate(self.active)
                if r is None and i not in self.prefilling]

    def _admit(self) -> int:
        """One scheduling round: continue in-flight chunked prefills, then
        admit ready requests into free slots, all within the scheduler's
        per-step prompt-token budget.  Returns the number of prompt tokens
        prefetched (the telemetry prefill mix).

        Prefill runs directly over the tiered params (operand dispatch in
        `models.layers`): remote weight partitions are streamed, never
        concatenated back into HBM.  The FCFS default (no chunk budget)
        prefills each prompt whole in admission order — exactly the
        pre-frontend behaviour.  A request whose prefill-produced first
        token is EOS (or whose budget is a single token) finishes at its
        last chunk without occupying a slot or burning decode steps."""
        prefill_tokens = 0
        self._prefill_calls_step = 0
        sched = self.scheduler
        now = self.clock.now()
        sched.release(now)
        qd_ema = (self.runtime.telemetry.queue_depth
                  if self.runtime is not None else float(len(sched.ready)))
        budget = sched.chunk_budget(qd_ema)
        left = budget                      # None = unbounded (whole prompts)
        # 1) continue in-flight chunked prefills, scheduler order
        order = sched.order_prefilling(
            [(s, ps.req) for s, ps in self.prefilling.items()])
        for slot in order:
            if left is not None and left <= 0:
                break
            ps = self.prefilling[slot]
            n = len(ps.req.prompt) - ps.pos
            if left is not None:
                n = min(n, left)
                left -= n
            prefill_tokens += n
            self._run_prefill_chunk(slot, ps, n)
        # 2) admit new requests into free slots, within the health quota
        # (elastic-degradation backoff: shed while spilling, trickle while
        # recovering).  An idle engine always admits — with nothing active
        # there is no pressure for a new prompt to worsen, and a full shed
        # would spin the run loop on a non-empty ready queue.
        quota = sched.admission_quota(self.health.state)
        if (quota == 0 and not self.prefilling
                and not any(r is not None for r in self.active)):
            quota = 1
        shed = False
        while sched.ready and (left is None or left > 0):
            if quota is not None and quota <= 0:
                shed = True
                break
            free = self._free_slots()
            if not free:
                break
            req = sched.select(now)
            slot = free[0]
            req.t_admit = now
            req.admitted_degraded = self.health.state != HEALTHY
            self.stats.queue_delays.append(req.t_admit - req.t_submit)
            if self.recorder.enabled:
                self.recorder.span(REQUESTS, req.rid, "queued",
                                   req.t_submit, now, cat="lifecycle")
                if req.admitted_degraded:
                    self.recorder.instant(
                        REQUESTS, req.rid, "admitted_degraded", now,
                        cat="lifecycle", health=self.health.state)
            if quota is not None:
                quota -= 1
            if self.pcache is not None and sched.preemptive:
                self._maybe_preempt(req)
            ps = PrefillState(req=req)
            self.prefilling[slot] = ps
            n = len(req.prompt)
            if left is not None:
                n = min(n, left)
                left -= n
            prefill_tokens += n
            self._run_prefill_chunk(slot, ps, n)
        if shed and sched.ready:
            self.health.shed()
        return prefill_tokens

    def _run_prefill_chunk(self, slot: int, ps: PrefillState, n: int) -> None:
        """Process `n` prompt tokens of the slot's in-flight prefill.  A
        whole prompt in one chunk takes the classic `models.prefill` path;
        continuations go through `models.prefill_chunk` against the
        request's private cache.  The last chunk commits: first token
        sampled from the chunk's final logits, cache written to the slot
        (paged pools / reference cache), request joins the decode batch."""
        req, rec = ps.req, self.recorder
        self._prefill_calls_step += 1
        t0 = time.time()
        with rec.phase("prefill", label=f"prefill[{req.rid}]", cat="prefill",
                       rid=req.rid, slot=slot, tokens=n, pos=ps.pos + n):
            chunk = jnp.asarray(req.prompt[ps.pos:ps.pos + n], jnp.int32)[None, :]
            if ps.pos == 0 and n == len(req.prompt):
                ps.logits, ps.cache = M.prefill(
                    self.cfg, self._fetched_params(), {"tokens": chunk},
                    max_len=self.max_len)
            else:
                if ps.cache is None:       # first chunk of a split prompt
                    ps.cache = M.init_cache(self.cfg, 1, self.max_len, self._dtype)
                ps.logits, ps.cache = M.prefill_chunk(
                    self.cfg, self._fetched_params(), ps.cache, chunk, ps.pos)
                self.stats.prefill_chunks += 1
            ps.pos += n
            self._clock_tick_prefill(n)
        if ps.pos < len(req.prompt):
            self.stats.prefill_time += time.time() - t0
            return
        del self.prefilling[slot]
        # The first-token sync: the prompt's last chunk has run on the
        # device when it returns, so prefill_time ends after it.
        with rec.phase("prefill.sync", rid=req.rid):
            nxt = int(jnp.argmax(ps.logits[0, -1]))
        self.stats.prefill_time += time.time() - t0
        req.out_tokens.append(nxt)
        self.stats.generated_tokens += 1
        req.t_first = self.clock.now()
        self.stats.ttfts.append(req.t_first - req.t_submit)
        if self.recorder.enabled:
            self.recorder.instant(REQUESTS, req.rid, "first_token",
                                  req.t_first, cat="lifecycle",
                                  ttft_s=req.t_first - req.t_submit)
        if (self.flight is not None and not self._slo_dumped
                and self.flight.breached(req.t_first - req.t_submit)):
            # One post-mortem per run: the first SLO breach captures the
            # window that caused it; later breaches are the same story.
            self._slo_dumped = True
            self.flight.dump("slo_breach",
                             final_snapshot=self._flight_snapshot(),
                             recorder=self.recorder)
        if nxt == req.eos_id or req.max_new_tokens <= 1:
            self._finish_request(req)      # slot stays free for the next
            return
        if self.pcache is not None and self.scheduler.preemptive:
            # Preemption timing race: the shortfall was demoted at
            # *admission*, but a chunked prefill only allocates its pages
            # here, steps later — other slots' decode-tail growth can have
            # stolen the freed pages in between.  Re-check at commit time
            # (a no-op in the same-step whole-prompt case: nothing could
            # allocate between the admission check and this one).
            self._maybe_preempt(req)
        with rec.phase("kv_write", rid=req.rid,
                       pages=-(-len(req.prompt) // self.page_size)):
            self._write_slot_cache(slot, ps.cache, len(req.prompt))
        self.lens[slot] = len(req.prompt)
        self._next_tok[slot, 0] = nxt
        self.active[slot] = req
        self._note_occupancy()

    def _finish_request(self, req: Request) -> None:
        req.t_done = self.clock.now()
        if self.recorder.enabled:
            self.recorder.span(REQUESTS, req.rid, "active", req.t_admit,
                               req.t_done, cat="lifecycle",
                               tokens=len(req.out_tokens),
                               preemptions=req.preemptions)
        self.stats.served += 1
        self.stats.e2e_latencies.append(req.t_done - req.t_submit)
        self.stats.requests.append(RequestRecord(
            rid=req.rid, cls=req.cls, priority=req.priority,
            prompt_tokens=len(req.prompt), output_tokens=len(req.out_tokens),
            queue_delay=req.t_admit - req.t_submit,
            ttft=req.t_first - req.t_submit,
            e2e=req.t_done - req.t_submit,
            preemptions=req.preemptions, slo_ttft_s=req.slo_ttft_s,
            admitted_degraded=req.admitted_degraded))

    def _preempt_shortfall(self, incoming: Request) -> int:
        """Local pages the incoming prompt still lacks: prompt pages (plus
        the next decode token's) beyond the elastic free count, plus the
        live migrator's allocation headroom — demoting exactly the raw
        shortfall leaves zero headroom, so the migrator's very next
        demote-for-headroom pass would fire again (demote ping-pong).
        Headroom only applies when the migrator actually runs (budget
        > 0): with a zero budget there is no ping-pong to prevent, and
        folding it in would break the zero-budget no-op parity."""
        need = -(-(len(incoming.prompt) + 1) // self.page_size)
        if self.runtime is not None and self.runtime.migrator.pages_per_step > 0:
            need += self.runtime.migrator.headroom
        return need - self.pcache.local_free

    def _maybe_preempt(self, incoming: Request) -> None:
        """Tier-demotion preemption: when the incoming request's prompt
        pages exceed the local pool's free pages, ask the scheduler for
        victims and demote the shortfall of their local KV pages to the
        remote pool.  Victims keep decoding through the direct-access
        paged kernel — exact tokens, no recompute — while the freed local
        pages receive the (hot) incoming prompt.

        Loops over `pick_victim` candidates until the shortfall is covered
        or candidates are exhausted: a single victim whose local pages run
        short would otherwise leave the remainder to synchronous
        coldest-spills in `alloc`, silently bypassing the scheduler's
        victim policy."""
        shortfall = self._preempt_shortfall(incoming)
        if shortfall <= 0:
            return
        tried: set[int] = set()
        while shortfall > 0:
            candidates = [(slot, r) for slot, r in enumerate(self.active)
                          if r is not None and slot not in tried]
            victim = self.scheduler.pick_victim(candidates, incoming)
            if victim is None:
                return
            tried.add(victim)
            moved = self.pcache.demote_slot_pages(victim, max_pages=shortfall)
            if not moved:
                continue               # victim held no demotable local pages
            shortfall -= moved
            self.active[victim].preemptions += 1
            self.stats.preemptions += 1
            self.stats.preempt_demoted_pages += moved
            self._preempt_moved_step += moved
            if self.recorder.enabled:
                self.recorder.instant(
                    REQUESTS, self.active[victim].rid, "preempted",
                    self.clock.now(), cat="lifecycle", pages=moved,
                    by=incoming.rid)

    # -- elastic degradation (never-OOM) ------------------------------------
    def schedule_hbm_shrink(self, step: int, fraction: float) -> None:
        """Chaos hook (`--hbm-shrink STEP:FRAC`): at decode step `step`,
        shrink the modeled HBM page budget to `fraction` of the local
        pool.  The engine degrades — demotes the deficit, re-plans to a
        higher offload ratio, sheds admissions while spilling — instead
        of crashing; the chaos tests pin zero failed requests and exact
        tokens against the unpressured run."""
        if not 0.0 <= fraction <= 1.0:
            raise ValueError(f"shrink fraction must be in [0, 1], got {fraction}")
        self._pending_shrink = (int(step), float(fraction))

    def shrink_local_budget(self, fraction: float) -> int:
        """Apply an elastic local-budget shrink now: cap the cache's local
        limit at ``fraction`` of the pool, mark the engine spilling, and
        ask the runtime (when attached) for a higher-offload re-plan.
        Returns the resulting page deficit (drained by `_elastic_step`)."""
        if self.pcache is None:
            return 0
        deficit = self.pcache.set_local_limit(
            int(self.pcache.n_local * fraction))
        self.health.pressure("shrink", pages=deficit)
        self._elastic_replan()
        return deficit

    def _elastic_replan(self) -> None:
        """Ask the re-planner for a higher offload ratio matching the
        shrunken local budget (PR 3's incremental repartition realizes
        it); no-op without the adaptive runtime."""
        if self.runtime is None or self.pcache is None:
            return
        frac = self.pcache.local_limit / max(1, self.pcache.n_local)
        new_params = self.runtime.elastic_replan(frac, self.params)
        if new_params is not None and new_params is not self.params:
            self.health.pressure("replan")
            self._install_params(new_params)

    def _install_params(self, new_params: dict[str, Any]) -> None:
        """Swap in a repartitioned params tree (re-plan paths): re-shard
        under a mesh, invalidate the per-step fetch cache, refresh the
        traffic accounting."""
        if self.mesh is not None:
            from repro.launch.sharding import shard_tiered_params

            new_params = shard_tiered_params(
                new_params, self.mesh, self.mesh_axis)
        self.params = new_params
        self._step_params = None           # repartitioned: refetch next use
        self._weight_bytes = weight_tier_bytes(self.params)
        self._weight_link_bytes = weight_link_bytes(self.params, self.n_links)

    def _elastic_recover(self, need_pages: int = 1) -> None:
        """Convert a ``CacheFull`` into degradation: grow the elastic
        remote (host) pool so the blocked allocation can land — capacity
        pressure becomes host-bandwidth pressure, the trade the
        direct-access path exists to make — then drain any local deficit
        and re-plan toward a higher offload ratio."""
        self.health.pressure("cache_full")
        # Grow by at least one full sequence's pages so a long-context
        # burst recovers in one growth, not one page at a time.
        grow = max(need_pages, self.pcache.max_pages)
        self.pcache.grow_remote(grow)
        self.health.pressure("grow", pages=grow)
        deficit = self.pcache.local_deficit
        if deficit > 0:
            moved = self.pcache.demote_coldest(deficit)
            if moved:
                self.health.pressure("demote", pages=moved)
                self._preempt_moved_step += moved
        self._elastic_replan()

    def _ensure_capacity_elastic(self, slot: int, length: int) -> None:
        """`ensure_capacity` with the never-OOM guarantee: a CacheFull is
        caught, converted into remote growth + demotion, and the
        allocation retried.  A second failure is a real bug (max_pages
        overflow) and surfaces."""
        try:
            self.pcache.ensure_capacity(slot, length)
        except CacheFull:
            need = (-(-length // self.page_size)
                    - int(self.pcache.n_pages[slot]))
            self._elastic_recover(max(1, need))
            self.pcache.ensure_capacity(slot, length)

    def _elastic_step(self) -> None:
        """Per-step elastic drain: demote the deficit a shrunken local
        budget left behind (globally coldest pages first), growing the
        remote pool when it cannot absorb them.  Movement draws down the
        shared per-step migration budget via `_preempt_moved_step`."""
        if self.pcache is None:
            return
        deficit = self.pcache.local_deficit
        if deficit <= 0:
            return
        short = deficit - len(self.pcache.free[REMOTE])
        if short > 0:
            self.pcache.grow_remote(short)
            self.health.pressure("grow", pages=short)
        moved = self.pcache.demote_coldest(deficit)
        if moved:
            self.health.pressure("demote", pages=moved)
            self._preempt_moved_step += moved

    def _finish_step_health(self) -> None:
        """End-of-step health update: walk the recovery ladder against the
        cache's current deficit and sync the counters into EngineStats."""
        deficit = self.pcache.local_deficit if self.pcache is not None else 0
        self.health.observe(deficit)
        self._note_health()

    def _note_health(self) -> None:
        """Fold the health monitor's state/counters into EngineStats."""
        c = self.health.counters
        self.stats.health = self.health.state
        self.stats.cache_full_caught = c.cache_full_caught
        self.stats.elastic_demoted_pages = c.elastic_demoted_pages
        self.stats.remote_grown_pages = c.remote_grown_pages
        self.stats.shed_steps = c.shed_steps
        self.stats.elastic_replans = c.elastic_replans

    # -- modeled clock ------------------------------------------------------
    def _clock_tick_prefill(self, n_tokens: int) -> None:
        """Advance a virtual clock by the analytical cost of one prefill
        chunk (no-op on the wall clock), before TTFT is stamped.

        The cost is computed once as a decomposed `StepCost`; the modeled
        clock advances by its ``total`` and the attribution profiler
        records the parts — one pricing path, so the clock and the ledger
        cannot drift.  On a wall clock with the profiler attached the
        same decomposition is recorded as a modeled *estimate* (the clock
        itself never advances)."""
        if not n_tokens:
            return
        modeled = isinstance(self.clock, ModeledClock)
        if not modeled and not self.profiler.enabled:
            return
        cost = modeled_step_cost(self.cfg, self.hw, self.plan.op_ratios,
                                 prefill_tokens=n_tokens)
        if modeled:
            self.clock.advance(cost.total)
        if self.profiler.enabled:
            self.profiler.on_tick(cost)

    def _clock_tick_decode(self, active: np.ndarray) -> None:
        """Advance a virtual clock by the analytical cost of one decode
        step over the active slots, pricing the KV read off the *live*
        page residency — so spills, migration and tier-demotion
        preemptions are visible to the modeled latencies.  Same
        single-pricing-path contract as `_clock_tick_prefill`."""
        n_active = int(active.sum())
        if not n_active:
            return
        modeled = isinstance(self.clock, ModeledClock)
        if not modeled and not self.profiler.enabled:
            return
        kv_local = kv_remote = 0.0
        if self.pcache is not None:
            kv_local, kv_remote = self.pcache.attended_bytes(self.lens, active)
        cost = modeled_step_cost(
            self.cfg, self.hw, self.plan.op_ratios,
            decode_slots=n_active,
            mean_kv_len=float(self.lens[active].mean()),
            kv_local_bytes=kv_local, kv_remote_bytes=kv_remote,
            hbm_copy_bytes=self._decode_copy_bytes())
        if modeled:
            self.clock.advance(cost.total)
        if self.profiler.enabled:
            self.profiler.on_tick(cost)

    def _decode_copy_bytes(self) -> float:
        """Functional-update copy traffic of one eager decode step: without
        donation, every per-layer K/V scatter materializes a fresh copy of
        each page pool (`tiered_decode._paged_writer`), so the eager step
        moves `n_layers * pool_bytes` of pure copy through HBM.  The jitted
        step donates the pools and writes in place — zero.  This is the
        term the eager-vs-jitted throughput gate measures."""
        if self._jit or self.pcache is None:
            return 0.0
        pools = self.pcache.pools
        n_layers = pools["k_local"].shape[0]
        return float(n_layers) * float(sum(p.nbytes for p in pools.values()))

    def _fetched_params(self) -> dict[str, Any]:
        """The step's fetch-once broadcast of the sharded host partitions
        (`tiered_decode.fetch_remote_shards`; identity off-mesh), cached so
        a step that both admits prefills and decodes gathers each operand
        once.  The traffic *model* still charges one weight read per pass —
        on hardware every forward re-streams the remote partitions; the
        cached tree is the CPU simulation's stand-in for that stream."""
        if self._step_params is None:
            self._step_params = TD.fetch_remote_shards(
                self.params, self.mesh, self.mesh_axis)
        return self._step_params

    def params_for_prefill(self) -> dict[str, Any]:
        """Deprecated shim: prefill no longer materializes the tiers —
        `models.prefill` consumes the tiered params directly."""
        warnings.warn(
            "params_for_prefill is deprecated: prefill runs over the tiered "
            "params via operand dispatch; no materialization happens",
            DeprecationWarning, stacklevel=2)
        return self.params

    def _write_slot_cache(self, slot: int, cache1: dict[str, jax.Array],
                          prompt_len: int) -> None:
        if self.pcache is None:
            # Reference dense cache, or SSM conv/state (both [L, B, ...]).
            for k in self.cache:
                self.cache[k] = self.cache[k].at[:, slot].set(cache1[k][:, 0])
            return
        # write_prompt's internal ensure_capacity is the allocation edge:
        # pre-allocate through the elastic guard so a full pool degrades
        # (grow remote, demote, retry) instead of raising CacheFull.
        self._ensure_capacity_elastic(slot, prompt_len)
        if self.cfg.family == "hybrid":
            for k in self.cache:               # conv/state recurrent state
                self.cache[k] = self.cache[k].at[:, slot].set(cache1[k][:, 0])
            self.pcache.write_prompt(
                slot, cache1["k"][:, 0, :prompt_len], cache1["v"][:, 0, :prompt_len])
            return
        if self.cfg.use_mla:
            ckv = cache1["ckv"][:, 0, :prompt_len]       # [L, T, rank]
            krope = cache1["krope"][:, 0, :prompt_len]   # [L, T, rd]
            k = jnp.concatenate([ckv, krope], axis=-1)[:, :, None, :]
            self.pcache.write_prompt(slot, k)            # K-only latent pages
            return
        self.pcache.write_prompt(
            slot, cache1["k"][:, 0, :prompt_len], cache1["v"][:, 0, :prompt_len])

    def _note_occupancy(self) -> None:
        if self.pcache is None:
            return
        self.stats.local_pages_hwm = max(
            self.stats.local_pages_hwm, self.pcache.local_in_use)
        self.stats.remote_pages_hwm = max(
            self.stats.remote_pages_hwm, self.pcache.remote_in_use)
        self.stats.spills = self.pcache.spills

    # ------------------------------------------------------------------
    @staticmethod
    def _bucket_window(w: int) -> int:
        """Round the AIMD window up to the next power of two.  The compiled
        step closes over the window (a static kernel parameter), so
        bucketing keeps the number of distinct compilations at O(log W)
        while the controller sweeps — safe because outputs are
        bitwise-independent of the window (it only paces DMA issue)."""
        return 1 << max(0, int(w) - 1).bit_length()

    def _compiled_step(self, kind: str):
        """The jitted decode step for the current (kind, window-bucket,
        pool-shape) bucket — compiled on first call, cached after.

        The K/V page pools (and the hybrid/SSM recurrent state) are
        *donated*: XLA reuses their buffers for the outputs, so the
        per-layer scatters in `tiered_decode._paged_writer` lower to
        in-place dynamic-update-slices instead of materializing a
        functional copy of each pool per layer.  The engine's
        `compute_pools → step → commit_pools` contract makes this safe:
        nothing reads the donated arrays between the call and the commit
        that replaces them.  Params are passed raw so the fetch-once
        broadcast (`fetch_remote_shards`) traces inside the compiled step
        (identity off-mesh; one in-jit all-gather per operand under a
        mesh).  The argmax head also lives inside the jit, so only [B]
        int32 tokens ever cross back to the host.

        Pool growth (`grow_remote`) and sink moves change pool shapes, so
        they key the cache alongside the window bucket — a changed key is
        a fresh compile, counted in ``compile_count`` (and, with a
        recorder, booked to the ``decode`` phase by its compile meter)."""
        wb = self._bucket_window(self.window)
        if self.pcache is not None:
            sl, sr = self.pcache.sink_local, self.pcache.sink_remote
            key = (kind, wb, sl, sr,
                   self.pcache.pools["k_local"].shape,
                   self.pcache.pools["k_remote"].shape)
        else:
            sl = sr = 0
            key = (kind, wb)
        fn = self._compiled.get(key)
        if fn is not None:
            self.compile_cache_hits += 1
            return fn
        self.compile_count += 1
        cfg, mesh, axis = self.cfg, self.mesh, self.mesh_axis
        tuner = self.tuner
        if kind == "paged":
            def run(params, pools, tokens, positions, attn_lens, table, tier,
                    wr_tier, wr_idx, wr_off):
                logits, pools = TD.paged_tiered_decode_step(
                    cfg, params, pools, tokens, positions, attn_lens, table, tier,
                    wr_tier, wr_idx, wr_off,
                    sink_local=sl, sink_remote=sr, window=wb,
                    use_kernel=True, mesh=mesh, mesh_axis=axis, tuner=tuner)
                tok = jnp.argmax(logits[:, 0], axis=-1).astype(jnp.int32)
                return tok, pools
            fn = jax.jit(run, donate_argnums=(1,))
        elif kind == "hybrid":
            def run(params, cache, pools, tokens, positions, attn_lens, table,
                    tier, wr_tier, wr_idx, wr_off):
                logits, cache, pools = TD.tiered_hybrid_decode_step(
                    cfg, params, cache, pools, tokens, positions, attn_lens, table, tier,
                    wr_tier, wr_idx, wr_off,
                    sink_local=sl, sink_remote=sr, window=wb,
                    use_kernel=True, mesh=mesh, mesh_axis=axis, tuner=tuner)
                tok = jnp.argmax(logits[:, 0], axis=-1).astype(jnp.int32)
                return tok, cache, pools
            fn = jax.jit(run, donate_argnums=(1, 2))
        else:                              # pure-SSM recurrent state
            def run(params, cache, tokens):
                logits, cache = TD.tiered_ssm_decode_step(
                    cfg, params, cache, tokens, window=wb, use_kernel=True, mesh=mesh,
                    mesh_axis=axis, tuner=tuner)
                tok = jnp.argmax(logits[:, 0], axis=-1).astype(jnp.int32)
                return tok, cache
            fn = jax.jit(run, donate_argnums=(1,))
        self._compiled[key] = fn
        return fn

    def step(self) -> None:
        """One decode step for all active slots (ragged: each slot at its
        own position).  With the adaptive runtime attached, the in-flight
        DMA window is re-read from the controller every step and a
        telemetry sample is reported after the compute.

        With a recorder the step opens these phases (`TraceRecorder.phase`),
        each a span on the engine track and an ``engine:<phase>`` profiler
        annotation::

            step
              admission            prompts prefilled this step
                prefill            one chunk's dispatch (rid, slot, tokens)
                prefill.sync       the first-token readback (rid)
                kv_write           the prompt's KV into its pages (rid, pages)
              decode               the decode step (step, slots)
                decode.prep        pages, write targets, tables, pools
                decode.wait        waiting for the step's tokens
              finish               runtime hook, token readback, bookkeeping
        """
        with self.recorder.phase("step"):
            self._step()

    def _step(self) -> None:
        rec = self.recorder
        t_step_clock = self.clock.now()    # engine-clock step origin: wall
        #                                    seconds on WallClock, modeled
        #                                    seconds on ModeledClock replays
        self._step_params = None           # new step, new fetch
        self._preempt_moved_step = 0
        if self.runtime is not None:
            self.window = self.runtime.window
        if (self._pending_shrink is not None
                and self.stats.decode_steps >= self._pending_shrink[0]):
            _, frac = self._pending_shrink
            self._pending_shrink = None
            self.shrink_local_budget(frac)
        self._elastic_step()               # drain any local-budget deficit
        with rec.phase("admission", cat="sched") as adm:
            prefill_tokens = self._admit()
            if adm is not None:            # the span's args, written at exit
                adm["prefill_tokens"] = prefill_tokens
        if self._jit:
            # The compiled step gathers the sharded partitions itself: free
            # the prefills' gathered copy (a whole remote tier per device
            # under a mesh) before it runs.
            self._step_params = None
        if not any(r is not None for r in self.active):
            with rec.phase("finish"):
                if prefill_tokens:
                    self._runtime_step(t_step_clock, prefill_tokens,
                                       np.zeros(self.max_batch, dtype=bool))
                elif not self.prefilling and self.scheduler.waiting:
                    # Idle but a trace arrival is pending: fast-forward the
                    # modeled clock to it (no-op on the wall clock, which
                    # just polls until the arrival time comes to pass).
                    nxt = self.scheduler.next_arrival()
                    if nxt is not None:
                        self.clock.advance(max(0.0, nxt - self.clock.now()))
                self._finish_step_health()
                if self.flight is not None:
                    self.flight.record(self._flight_snapshot())
                self._audit_page_table()
            return
        active = np.array([r is not None for r in self.active])
        if self.pcache is not None:
            # Heat bookkeeping is unconditional: the histogram is the single
            # source of page temperature (spill victims included), so static
            # and adaptive runs see identical placement decisions.
            self.pcache.touch_step(self.lens, active)
        tokens = jnp.asarray(self._next_tok)
        positions = np.where(active, self.lens, 0).astype(np.int32)
        with rec.phase("decode", cat="decode", slots=int(active.sum()),
                       step=self.stats.decode_steps + 1):
            tok_dev = self._decode(active, tokens, positions)
        with rec.phase("finish"):
            self._runtime_step(t_step_clock, prefill_tokens, active)
            self._finish_step_health()
            nxt = np.asarray(tok_dev, dtype=np.int32)
            for slot, req in enumerate(self.active):
                if req is None:
                    continue
                tok = int(nxt[slot])
                req.out_tokens.append(tok)
                self.stats.generated_tokens += 1
                self.lens[slot] += 1
                done = (len(req.out_tokens) >= req.max_new_tokens
                        or tok == req.eos_id
                        or self.lens[slot] >= self.max_len - 1)
                if done:
                    self._finish_request(req)
                    self.active[slot] = None
                    self.lens[slot] = 0
                    if self.pcache is not None:
                        self.pcache.free_slot(slot)
                else:
                    self._next_tok[slot, 0] = tok
            if self.flight is not None:
                self.flight.record(self._flight_snapshot())
            self._audit_page_table()

    def _decode(self, active: np.ndarray, tokens: jax.Array,
                positions: np.ndarray) -> jax.Array:
        """Run the decode step over the active slots and return the [B]
        sampled tokens on the device (synced on a wall clock)."""
        rec = self.recorder
        t0 = time.time()
        if not self.tiered:
            logits, self.cache = M.decode_step(
                self.cfg, self.params, self.cache, tokens,
                jnp.asarray(positions))
            tok_dev = jnp.argmax(logits[:, 0], axis=-1).astype(jnp.int32)
        elif self.pcache is None:
            # Pure-SSM decoder: recurrent tiered step, no KV pages.  The
            # jitted path passes the raw params so the fetch-once broadcast
            # traces *inside* the compiled step (identity off-mesh).
            if self._jit:
                fn = self._compiled_step("ssm")
                tok_dev, self.cache = fn(self.params, self.cache, tokens)
            else:
                logits, self.cache = TD.tiered_ssm_decode_step(
                    self.cfg, self._fetched_params(), self.cache, tokens,
                    window=self.window, use_kernel=True,
                    mesh=self.mesh, mesh_axis=self.mesh_axis,
                    tuner=self.tuner)
                tok_dev = jnp.argmax(logits[:, 0], axis=-1).astype(jnp.int32)
        else:
            with rec.phase("decode.prep"):
                for slot in np.nonzero(active)[0]:
                    self._ensure_capacity_elastic(int(slot), int(self.lens[slot]) + 1)
                self._note_occupancy()
                wr_tier, wr_idx, wr_off = self.pcache.write_targets(self.lens, active)
                table, tier = self.pcache.device_tables()
                attn_lens = np.where(active, self.lens + 1, 0).astype(np.int32)
                paged_args = (tokens, jnp.asarray(positions), jnp.asarray(attn_lens),
                              table, tier, wr_tier, wr_idx, wr_off)
                pools_in = self.pcache.compute_pools()
            if self.cfg.family == "hybrid":
                if self._jit:
                    fn = self._compiled_step("hybrid")
                    tok_dev, self.cache, pools_out = fn(
                        self.params, self.cache, pools_in, *paged_args)
                else:
                    logits, self.cache, pools_out = TD.tiered_hybrid_decode_step(
                        self.cfg, self._fetched_params(), self.cache, pools_in,
                        *paged_args,
                        sink_local=self.pcache.sink_local,
                        sink_remote=self.pcache.sink_remote,
                        window=self.window, use_kernel=True,
                        mesh=self.mesh, mesh_axis=self.mesh_axis,
                        tuner=self.tuner)
                    tok_dev = jnp.argmax(logits[:, 0], axis=-1).astype(jnp.int32)
            elif self._jit:
                fn = self._compiled_step("paged")
                tok_dev, pools_out = fn(self.params, pools_in, *paged_args)
            else:
                logits, pools_out = TD.paged_tiered_decode_step(
                    self.cfg, self._fetched_params(), pools_in, *paged_args,
                    sink_local=self.pcache.sink_local,
                    sink_remote=self.pcache.sink_remote,
                    window=self.window, use_kernel=True,
                    mesh=self.mesh, mesh_axis=self.mesh_axis,
                    tuner=self.tuner)
                tok_dev = jnp.argmax(logits[:, 0], axis=-1).astype(jnp.int32)
            self.pcache.commit_pools(pools_out)
        if self.clock.kind == "wall":
            # Host sync only where wall-clock timing needs it; modeled-clock
            # replays dispatch fully async (the [B] int32 token fetch in
            # `finish` is the step's only device dependency).
            with rec.phase("decode.wait"):
                jax.block_until_ready(tok_dev)
        self.stats.decode_time += time.time() - t0
        self.stats.decode_steps += 1
        self._clock_tick_decode(active)
        return tok_dev

    def _runtime_step(self, t_step_clock: float, prefill_tokens: int,
                      active: np.ndarray) -> None:
        """Report one step to the adaptive runtime and apply its actions:
        window update (read back at the top of the next step), bounded page
        migration, and — on a re-plan — the repartitioned params tree.
        With tracing on, the same per-step accounting also feeds the
        counter tracks (per-link bytes, window, queue depth, deficit,
        health), runtime attached or not.

        ``t_step_clock`` is the step origin on the *engine clock*, so the
        telemetry ``duration_s`` is wall seconds on a WallClock run and
        modeled seconds on a ModeledClock replay — one time base per run,
        never mixed (trace replays used to stamp wall durations here,
        which made achieved-bandwidth figures nondeterministic noise)."""
        if (self.runtime is None and not self.recorder.enabled
                and not self.profiler.enabled):
            return
        n_active = int(active.sum())
        # Traffic accounting: decode reads every weight once per step, each
        # prefill pass reads them once more; KV traffic follows the page
        # table's tier map.  Under a mesh each host link carries its 1/P
        # slice of every sharded partition (whole copies for the
        # divisibility fallback); remote_bytes is the sum over links.
        w_local, _ = self._weight_bytes
        passes = (1 if n_active else 0) + self._prefill_calls_step
        local_b = w_local * passes
        link_b = [b * passes for b in self._weight_link_bytes]
        if self.pcache is not None and n_active:
            kv_local, _ = self.pcache.attended_bytes(self.lens, active)
            local_b += kv_local
            kv_links = self.pcache.attended_link_bytes(
                self.lens, active, self.n_links)
            link_b = [a + b for a, b in zip(link_b, kv_links)]
        sample = StepSample(
            step=self.stats.decode_steps,
            duration_s=max(self.clock.now() - t_step_clock, 1e-9),
            prefill_tokens=prefill_tokens,
            decode_tokens=n_active,
            queue_depth=len(self.queue),
            active_slots=n_active,
            mean_kv_len=float(self.lens[active].mean()) if n_active else 0.0,
            local_bytes=local_b,
            remote_bytes=sum(link_b),
            window=self.window,
            remote_bytes_per_link=tuple(link_b) if self.n_links > 1 else None,
            health=self.health.state,
            local_deficit=(self.pcache.local_deficit
                           if self.pcache is not None else 0))
        if self.recorder.enabled:
            rec, t = self.recorder, self.clock.now()
            rec.counter(LINKS, "link_bytes", t,
                        {f"link{i}": b for i, b in enumerate(link_b)})
            rec.counter(LINKS, "window", t, {"slots": self.window})
            rec.counter(LINKS, "queue_depth", t,
                        {"requests": sample.queue_depth})
            rec.counter(LINKS, "local_deficit", t,
                        {"pages": sample.local_deficit})
            rec.counter(LINKS, "health", t,
                        {"level": HEALTH_LEVEL.get(self.health.state, -1)})
        if self.profiler.enabled:
            # Close this step's ledger (the ticks recorded by the clock
            # hooks) and surface it on the trace: per-component seconds +
            # bw optimality as counter tracks, label changes as instants.
            ledger = self.profiler.close_step(sample, t_start=t_step_clock)
            if self.recorder.enabled:
                rec, t = self.recorder, self.clock.now()
                rec.counter(LINKS, "attribution", t, ledger.components())
                rec.counter(LINKS, "bw.optimal_fraction", t,
                            {"fraction": ledger.optimal_fraction})
                tr = self.profiler.last_transition
                if tr is not None:
                    rec.instant(ENGINE, 0, f"bottleneck:{tr[1]}->{tr[2]}", t,
                                cat="bottleneck", step=tr[0])
        if self.runtime is None:
            return
        new_params = self.runtime.on_step(
            sample, cache=self.pcache, params=self.params,
            migration_used=self._preempt_moved_step)
        if new_params is not None and new_params is not self.params:
            self._install_params(new_params)
        rs = self.runtime.stats
        self.stats.replans = rs.replans
        self.stats.promoted_pages = rs.promoted_pages
        self.stats.demoted_pages = rs.demoted_pages
        self.stats.final_window = self.runtime.window
        self._note_occupancy()

    def _flight_snapshot(self) -> dict:
        """One step's engine state for the flight-recorder ring (plain
        JSON-serializable host values — no arrays, no jax)."""
        snap: dict[str, Any] = {
            "step": self.stats.decode_steps,
            "clock_s": self.clock.now(),
            "health": self.health.state,
            "window": self.window,
            "waiting": self.scheduler.waiting,
            "prefilling": sorted(self.prefilling),
            "active": [r.rid if r is not None else None for r in self.active],
            "lens": self.lens.tolist(),
            "served": self.stats.served,
            "generated_tokens": self.stats.generated_tokens,
        }
        if self.pcache is not None:
            snap["pages"] = {
                "local_in_use": self.pcache.local_in_use,
                "remote_in_use": self.pcache.remote_in_use,
                "local_free": self.pcache.local_free,
                "remote_free": len(self.pcache.free[REMOTE]),
                "local_deficit": self.pcache.local_deficit,
                "spills": self.pcache.spills,
            }
        led = self.profiler.last_ledger if self.profiler.enabled else None
        if led is not None:
            # At-failure decomposition: the last closed step's ledger, so a
            # post-mortem bundle says where the dying run's time was going.
            snap["attribution"] = {
                "step": led.step,
                "label": led.label,
                "components": led.components(),
                "unattributed_s": led.unattributed(),
                "optimal_fraction": led.optimal_fraction,
            }
        return snap

    @property
    def mesh_shape(self) -> list[int]:
        """Device-axis shape of the serving mesh (``[1]`` off-mesh)."""
        return [self.n_links]

    def mesh_traffic_report(self) -> dict:
        """Modeled host-link traffic for one full read of the offloaded
        weights, against the §4.3.2 read-amplification oracle.

        ``per_link_bytes`` is what the engine's own accounting says each
        chip's host link carries (realized shard extents, burst-granularity
        overhead applied); the oracle figures come from
        `core.multicast.sharded_fetch_report` on the same host footprint.
        On the fetch-once path the two agree and sit at ~1/P of the naive
        figure; operands that fell back to replicated remotes push
        ``per_link_bytes`` toward the naive bound.
        """
        _, w_remote = self._weight_bytes
        rep = multicast.sharded_fetch_report(w_remote, self.n_links)
        ov = multicast.GRANULARITY_OVERHEAD
        return {
            "n_devices": self.n_links,
            "host_bytes": w_remote,
            "per_link_bytes": [b * ov for b in self._weight_link_bytes],
            "oracle_per_link_multicast": rep.traffic_multicast / self.n_links,
            "oracle_per_link_naive": rep.traffic_no_multicast / self.n_links,
        }

    def run(self, max_steps: int = 10_000, *,
            step_hook=None) -> EngineStats:
        """Drive the engine to completion.  ``step_hook`` (optional) is
        called as ``step_hook(steps)`` after every engine step — the
        driver uses it for periodic metrics flushes (`--metrics-interval`);
        it runs inside the try so a hook failure still dumps the flight
        ring."""
        steps = 0
        try:
            while (self.scheduler.waiting or self.prefilling
                   or any(r is not None
                          for r in self.active)) and steps < max_steps:
                self.step()
                steps += 1
                if step_hook is not None:
                    step_hook(steps)
        except Exception as e:
            # Post-mortem: dump the flight ring (plus a snapshot of the
            # state the failing step left behind) before surfacing.
            if self.flight is not None:
                self.flight.dump(type(e).__name__, error=str(e),
                                 final_snapshot=self._flight_snapshot(),
                                 recorder=self.recorder)
            raise
        return self.stats
