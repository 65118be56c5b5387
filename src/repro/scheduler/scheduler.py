"""The continuous-batching graph-serving scheduler (DESIGN.md §8).

Pipeline: ``submit()`` → :class:`~repro.scheduler.queue.AdmissionQueue` →
geometry buckets (:class:`~repro.scheduler.bucketing.TierPolicy`) →
:class:`~repro.scheduler.dispatcher.ContinuousDispatcher` picks the next
wave → the tier's cached :class:`~repro.serving.engine.GraphServeEngine`
program executes it → :class:`~repro.scheduler.metrics.ServeMetrics`
accounts for it. ``drain()`` is an event loop over a pluggable clock:

- :class:`RealClock` — wall time; waiting sleeps.
- :class:`VirtualClock` — simulated time; waiting jumps to the next event
  and each wave advances the clock by its (measured or modeled) service
  time. This is what makes arrival-process benchmarks and latency tests
  deterministic and fast.

Numerics: the scheduler serves with ``bn_mode="sample"`` by default —
per-graph batch-norm statistics — because under continuous batching the set
of co-batched requests is a scheduling accident, and a request's logits must
not depend on it. With sample-mode BN every request's output is bitwise
identical to scoring it alone through a ``GraphServeEngine`` of the same
tier geometry (tests/test_scheduler.py asserts exactly that).
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import time
from typing import Callable, Sequence

from repro.core.gcn import GCNConfig
from repro.observability import TRACER, enabled
from repro.scheduler.bucketing import GeometryTier, TierPolicy
from repro.scheduler.dispatcher import ContinuousDispatcher, Wait, WavePlan
from repro.scheduler.metrics import ServeMetrics
from repro.scheduler.programs import ProgramCache
from repro.scheduler.queue import AdmissionQueue, PendingRequest
from repro.serving.engine import GraphRequest, GraphServeEngine


class RealClock:
    """Wall time (monotonic); waiting really sleeps."""

    def now(self) -> float:
        return time.monotonic()

    def sleep_until(self, t: float) -> None:
        dt = t - time.monotonic()
        if dt > 0:
            time.sleep(dt)

    def on_service(self, dt: float) -> None:
        pass                        # wall time already advanced while serving


class VirtualClock:
    """Simulated time for deterministic scheduling runs: waiting jumps the
    clock forward, and each executed wave advances it by the wave's service
    time (measured wall time, or the caller's service model)."""

    def __init__(self, start: float = 0.0):
        self._t = float(start)

    def now(self) -> float:
        return self._t

    def sleep_until(self, t: float) -> None:
        self._t = max(self._t, t)

    def on_service(self, dt: float) -> None:
        self._t += dt


@dataclasses.dataclass(frozen=True)
class SchedulerConfig:
    """Knobs of the continuous-batching policy."""

    batch: int | None = None        # wave slots per tier; None inherits the
                                    # TierPolicy's batch (default 32). Setting
                                    # both this and an explicit `tiers=` to
                                    # different values is a config error.
    flush_after: float = 0.05       # straggler guard / deadline margin (s)
    bn_mode: str = "sample"         # wave-composition-invariant numerics;
                                    # "batch" restores legacy wave statistics
    default_slo: float | None = None  # deadline = arrival + slo when the
                                      # caller gives none (None: best effort)


class Scheduler:
    """Continuous-batching front end over per-tier ``GraphServeEngine``s.

    Either one-shot::

        sched = Scheduler(params, cfg, tiers=TierPolicy.for_sizes(...))
        sched.serve(requests)                  # everything, now

    or streaming::

        sched.submit(r, arrival=t, deadline=t + 0.2)
        ...
        sched.drain()                          # event loop until empty

    ``mesh=`` flows to every tier engine, so each wave spans the device mesh
    exactly as ``GraphServeEngine(mesh=...)`` waves do (DESIGN.md §6).

    Telemetry (DESIGN.md §13): each wave runs in a ``sched/wave`` span and
    each wait for an arrival or a flush in a ``sched/wait`` span (profiler
    annotations). With ``REPRO_TELEMETRY`` on, the request lifecycle —
    arrival → admit → dispatch → finish — also lands in the tracer's ring
    as instants plus one complete span per request and per wave, stamped
    from the SCHEDULER's clock (virtual or wall) on the shared
    ``tid="clock"`` track, with queue-depth counter samples at every
    admit/dispatch. ``telemetry=False`` silences both; ``registry=`` hands
    :class:`ServeMetrics` a shared metrics registry (plus ``instance``
    label) instead of its own.
    """

    def __init__(
        self,
        params,
        cfg: GCNConfig,
        *,
        tiers: TierPolicy | None = None,
        config: SchedulerConfig | None = None,
        mesh=None,
        clock=None,
        service_model: Callable[[GeometryTier, int], float] | None = None,
        engine_factory: Callable[[GeometryTier], GraphServeEngine]
        | None = None,
        telemetry: bool = True,
        registry=None,
        instance: str = "default",
    ):
        self.config = config or SchedulerConfig()
        if self.config.bn_mode != cfg.bn_mode:
            cfg = dataclasses.replace(cfg, bn_mode=self.config.bn_mode)
        self.cfg = cfg
        self.policy = tiers or TierPolicy(batch=self.config.batch or 32)
        if self.config.batch is not None and any(
                t.batch != self.config.batch for t in self.policy.tiers):
            raise ValueError(
                f"SchedulerConfig.batch={self.config.batch} disagrees with "
                f"the tier policy's wave size(s) "
                f"{sorted({t.batch for t in self.policy.tiers})}; wave "
                "geometry comes from the TierPolicy — set batch there, or "
                "leave SchedulerConfig.batch=None to inherit it")
        self.clock = clock or RealClock()
        self.service_model = service_model
        self.dispatcher = ContinuousDispatcher(
            flush_after=self.config.flush_after)
        self.queue = AdmissionQueue()
        self.buckets: dict[GeometryTier, collections.deque[PendingRequest]]
        self.buckets = {}
        self.telemetry = telemetry
        self.tracer = TRACER
        self.metrics = ServeMetrics(
            registry=registry,
            labels=None if registry is None else {"instance": instance})
        # engine_factory lets several schedulers share warm engines (one
        # compile per geometry across e.g. a benchmark's policy variants);
        # a custom factory owns the engines' cfg/numerics
        self.programs = ProgramCache(
            engine_factory or (lambda tier: GraphServeEngine(
                params, self.cfg, batch=tier.batch, m_pad=tier.m_pad,
                nnz_pad=tier.nnz_pad, mesh=mesh)))
        self.completed: list[PendingRequest] = []

    # -- intake -------------------------------------------------------------
    def submit(self, request: GraphRequest, *, arrival: float | None = None,
               deadline: float | None = None) -> PendingRequest:
        """Queue one request. ``arrival`` defaults to the clock's now (a
        future arrival is admitted when the clock reaches it); ``deadline``
        defaults to ``arrival + default_slo`` when an SLO is configured."""
        if arrival is None:
            arrival = self.clock.now()
        if deadline is None and self.config.default_slo is not None:
            deadline = arrival + self.config.default_slo
        if self._ring():
            self.tracer.instant(
                "request/arrival", ts=arrival, cat="sched",
                args={"n_nodes": request.n_nodes,
                      "max_nnz": request.max_nnz, "deadline": deadline})
        return self.queue.submit(request, arrival=arrival, deadline=deadline)

    def _ring(self) -> bool:
        """Whether lifecycle events go to the tracer's ring."""
        return self.telemetry and enabled()

    def _queue_depth(self) -> int:
        return sum(len(b) for b in self.buckets.values())

    def _admit(self, now: float) -> None:
        ring = self._ring()
        admitted = False
        for p in self.queue.due(now):
            tier = self.policy.assign(p.request)
            if tier is None:
                r = p.request
                r.failed, r.done = True, False
                r.error = (
                    f"no geometry tier fits n_nodes={r.n_nodes}, "
                    f"max_nnz={r.max_nnz} (top tier: {self.policy.tiers[-1]})")
                self.metrics.record_rejection(arrival=p.arrival)
                self.completed.append(p)
                if ring:
                    self.tracer.instant("request/reject", ts=now, cat="sched",
                                        args={"reason": r.error})
                continue
            p.tier = tier
            self.buckets.setdefault(tier, collections.deque()).append(p)
            admitted = True
            if ring:
                self.tracer.instant("request/admit", ts=now, cat="sched",
                                    args={"tier": tier.key})
        if admitted and ring:
            self.tracer.counter("queue_depth", self._queue_depth(), ts=now,
                                cat="sched")

    # -- execution ----------------------------------------------------------
    def warmup(self, requests: Sequence[GraphRequest]) -> int:
        """Pre-compile the tier program of every geometry these requests
        would use; returns the number of programs now cached. Benchmarks
        call this so compile time stays out of the timed run."""
        tiers = {self.policy.assign(r) for r in requests} - {None}
        for tier in sorted(tiers):
            self.programs.get(tier).warm()
        self.metrics.compile_count = self.programs.compile_count
        return self.programs.compile_count

    def _execute(self, plan: WavePlan) -> None:
        wave: list[PendingRequest] = []
        for src, count in plan.takes:
            bucket = self.buckets[src]
            wave.extend(bucket.popleft() for _ in range(count))
        # the chosen tier's own requests first, then top-ups (already the
        # takes order) — slot order inside one wave is irrelevant to outputs
        # (bn_mode="sample": per-slot numerics), but keep it deterministic
        program = self.programs.get(plan.tier)
        dispatch = self.clock.now()
        ring = self._ring()
        # the wall-clock sched/wave span wraps the engine's serve/wave span
        # (which wraps any trace-time kernel spans): the nested scheduler →
        # wave → kernel structure the trace viewer shows
        span = self._span("sched/wave", {
            "tier": plan.tier.key,
            "n_requests": sum(c for _, c in plan.takes)} if ring else None)
        t0 = time.perf_counter()
        with span:
            report = program.engine.run_wave([p.request for p in wave])
        measured = time.perf_counter() - t0
        served = report.n_requests - report.n_failed
        service = (measured if self.service_model is None
                   else self.service_model(plan.tier, served))
        self.clock.on_service(service)
        finish = self.clock.now()
        self.metrics.record_wave(plan.tier.key, dispatch, service, report)
        if ring:
            # clock-domain twin of the wall span: where the wave sits on the
            # scheduler's (possibly virtual) timeline
            self.tracer.complete(
                f"wave[{plan.tier.key}]", ts=dispatch, dur=service,
                cat="sched", args={"served": served,
                                   "n_failed": report.n_failed})
        for p in wave:
            p.served_tier = plan.tier
            p.dispatch, p.finish = dispatch, finish
            self.metrics.record_request(
                arrival=p.arrival, dispatch=dispatch, finish=finish,
                deadline=p.deadline, failed=p.request.failed)
            self.completed.append(p)
            if ring:
                self.tracer.complete(
                    "request", ts=p.arrival, dur=max(finish - p.arrival, 0.0),
                    tid="requests", cat="sched",
                    args={"tier": plan.tier.key,
                          "wait_s": dispatch - p.arrival,
                          "failed": bool(p.request.failed),
                          "deadline_missed": bool(
                              p.deadline is not None and finish > p.deadline)})
        if ring:
            self.tracer.counter("queue_depth", self._queue_depth(),
                                ts=finish, cat="sched")
        self.metrics.compile_count = self.programs.compile_count

    def _span(self, name: str, args: dict | None = None):
        return self.tracer.span(name, cat="sched", args=args) \
            if self.telemetry else contextlib.nullcontext()

    def drain(self) -> list[PendingRequest]:
        """Event loop: admit arrivals, dispatch ready waves, wait (sleep or
        simulated jump) when batching longer is the better trade. Returns
        every request completed during this drain, completion order."""
        start = len(self.completed)
        while True:
            now = self.clock.now()
            self._admit(now)
            plan = self.dispatcher.next_wave(
                self.buckets, now, draining=len(self.queue) == 0)
            if isinstance(plan, WavePlan):
                self._execute(plan)
                continue
            nxt = self.queue.next_arrival()
            if isinstance(plan, Wait):
                target = plan.until if nxt is None else min(plan.until, nxt)
            elif nxt is not None:       # buckets empty, arrivals pending
                target = nxt
            else:                       # fully drained
                break
            with self._span("sched/wait"):
                self.clock.sleep_until(max(target, now))
        return self.completed[start:]

    def serve(self, requests: Sequence[GraphRequest], *,
              arrivals: Sequence[float] | None = None,
              deadlines: Sequence[float] | None = None,
              ) -> list[GraphRequest]:
        """Submit a whole stream (optionally with per-request arrival times
        and deadlines) and drain it. Returns the same request objects with
        ``logits``/``done`` (or ``failed``/``error``) filled in."""
        for i, r in enumerate(requests):
            self.submit(
                r,
                arrival=None if arrivals is None else arrivals[i],
                deadline=None if deadlines is None else deadlines[i])
        self.drain()
        return list(requests)

    # -- convenience constructors ------------------------------------------
    @classmethod
    def fixed_wave(cls, params, cfg: GCNConfig, *, batch: int = 32,
                   m_pad: int = 56, nnz_pad: int = 256,
                   **kw) -> "Scheduler":
        """The pre-scheduler baseline expressed in scheduler terms: ONE
        geometry tier at the worst-case padding, waves launch only when full
        (or at final drain) — exactly the old ``_serve_in_waves`` slicing,
        but measured by the same clock and metrics as the bucketed policy,
        so benchmark comparisons are apples-to-apples."""
        import math

        config = kw.pop("config", None) or SchedulerConfig(
            batch=batch, flush_after=math.inf)
        if not math.isinf(config.flush_after):
            config = dataclasses.replace(config, flush_after=math.inf)
        tiers = TierPolicy.single(m_pad=m_pad, nnz_pad=nnz_pad, batch=batch)
        return cls(params, cfg, tiers=tiers, config=config, **kw)
