"""Unified telemetry layer (DESIGN.md §13): span tracer, metrics registry,
regret auditor, ServeMetrics-on-registry, strict-JSON exporters.

The load-bearing assertions (ISSUE acceptance criteria):

- a telemetry-enabled serve run produces a Chrome trace with NESTED
  scheduler → wave → kernel spans that passes the trace sanity gate;
- with telemetry off nothing reaches the ring, while the profiler still
  sees every host span, nested, and a ``host/gc`` span per collection;
- the regret auditor FLAGS a deliberately mis-cached decision (a poisoned
  tuning-cache ``best``) and names the would-have-won alternative;
- disabled-mode kernel hooks cost < 5% of one XLA-impl dispatch;
- ``write_bench_json`` never emits a bare ``NaN`` literal;
- ``ServeMetrics.summary()`` keys and the histogram bucket boundaries are
  pinned (downstream dashboards key on both).
"""
import json
import math
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import random_batch
from repro.core.spmm import batched_spmm
from repro.observability import (
    DEFAULT_TIME_BUCKETS,
    MetricsRegistry,
    RegretAuditor,
    TRACER,
    Tracer,
    sanitize_json,
    span,
    telemetry,
)
from repro.observability import trace as obs_trace


def _small_batch(batch=2, dim=16, nnz_per_row=2, n_b=8, seed=0):
    rng = np.random.default_rng(seed)
    a, m_pad = random_batch(rng, batch=batch, dim=dim,
                            nnz_per_row=nnz_per_row)
    b = jnp.asarray(rng.standard_normal((batch, m_pad, n_b)), jnp.float32)
    return a, b


# ---------------------------------------------------------------------------
# tracer
# ---------------------------------------------------------------------------

def test_span_records_complete_event_with_args():
    tr = Tracer()
    with telemetry(), tr.span("outer", cat="t", args={"k": 1}):
        time.sleep(0.001)
    (ev,) = tr.events()
    assert ev.name == "outer" and ev.ph == "X" and ev.cat == "t"
    assert ev.dur >= 1000          # ≥ 1ms in µs
    assert ev.args == {"k": 1}


def test_nested_spans_contain_by_timestamp():
    tr = Tracer()
    with telemetry(), tr.span("outer"):
        with tr.span("inner"):
            pass
    inner, outer = tr.events()     # inner closes (appends) first
    assert inner.name == "inner" and outer.name == "outer"
    assert outer.ts <= inner.ts
    assert inner.ts + inner.dur <= outer.ts + outer.dur


def test_ring_buffer_bounds_and_counts_drops():
    tr = Tracer(capacity=4)
    with telemetry():
        for i in range(10):
            tr.instant(f"e{i}")
    evs = tr.events()
    assert len(evs) == 4 and tr.dropped == 6
    assert [e.name for e in evs] == ["e6", "e7", "e8", "e9"]


def test_module_span_disabled_is_shared_null_context():
    obs_trace.set_enabled(False)
    assert span("x") is obs_trace._NULL
    assert span("y") is span("z")       # no allocation per call
    n0 = len(TRACER.events())
    with span("nothing"):
        pass
    assert len(TRACER.events()) == n0


def test_telemetry_context_scopes_enabled():
    obs_trace.set_enabled(False)
    with telemetry():
        assert obs_trace.enabled()
        with telemetry(False):
            assert not obs_trace.enabled()
        assert obs_trace.enabled()
    assert not obs_trace.enabled()


def test_export_chrome_is_strict_json_and_sanitizes_args(tmp_path):
    tr = Tracer()
    with telemetry():
        with tr.span("s", args={"bad": float("nan"), "ok": 2.0}):
            pass
        tr.instant("mark")
        tr.counter("depth", 3)
    path = tr.export_chrome(tmp_path / "t.json")

    def boom(tok):
        raise AssertionError(f"non-strict literal {tok}")

    doc = json.loads(path.read_text(), parse_constant=boom)
    evs = doc["traceEvents"]
    assert {e["ph"] for e in evs} == {"X", "i", "C"}
    s = next(e for e in evs if e["ph"] == "X")
    assert s["args"] == {"bad": None, "ok": 2.0}
    assert all({"name", "ph", "ts", "pid", "tid"} <= set(e) for e in evs)


def test_sanitize_json_maps_all_non_finite():
    out = sanitize_json({"a": float("inf"), "b": [float("-inf"),
                                                 float("nan"), 1.5]})
    assert out == {"a": None, "b": [None, None, 1.5]}


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------

def test_counter_labels_and_negative_rejected():
    reg = MetricsRegistry()
    c = reg.counter("hits")
    c.inc(impl="csr")
    c.inc(2, impl="ell")
    assert c.value(impl="csr") == 1 and c.value(impl="ell") == 2
    assert c.value(impl="none") == 0 and c.total() == 3
    with pytest.raises(ValueError, match="negative"):
        c.inc(-1)


def test_gauge_nan_until_set():
    reg = MetricsRegistry()
    g = reg.gauge("depth")
    assert math.isnan(g.value())
    g.set(4)
    assert g.value() == 4.0


def test_registry_kind_mismatch_raises_and_same_name_shares():
    reg = MetricsRegistry()
    c = reg.counter("n")
    assert reg.counter("n") is c
    with pytest.raises(ValueError, match="already registered"):
        reg.gauge("n")


def test_default_bucket_boundaries_pinned():
    # downstream dashboards key on these exact le bounds — changing them is
    # a schema change, not a tweak
    assert DEFAULT_TIME_BUCKETS == (
        1e-6, 1e-5, 1e-4, 1e-3, 1e-2, 1e-1, 1.0, 10.0, 100.0)


def test_histogram_bucket_boundaries_are_le_inclusive():
    reg = MetricsRegistry()
    h = reg.histogram("lat", buckets=(1.0, 10.0))
    for v in (0.5, 1.0, 1.0001, 10.0, 11.0):
        h.observe(v)
    (row,) = list(h.rows())
    assert [b["le"] for b in row["buckets"]] == [1.0, 10.0, float("inf")]
    assert [b["count"] for b in row["buckets"]] == [2, 2, 1]   # le-inclusive
    assert row["count"] == 5 and row["min"] == 0.5 and row["max"] == 11.0


def test_histogram_rejects_unsorted_buckets():
    with pytest.raises(ValueError, match="ascending"):
        MetricsRegistry().histogram("bad", buckets=(2.0, 1.0))


def test_histogram_exact_percentile_with_keep_samples():
    reg = MetricsRegistry()
    h = reg.histogram("lat", keep_samples=True)
    for v in range(1, 101):
        h.observe(float(v))
    assert h.percentile(50) == pytest.approx(50.5)
    assert h.percentile(99) == pytest.approx(
        float(np.percentile(np.arange(1.0, 101.0), 99)))


def test_histogram_single_sample_percentiles():
    reg = MetricsRegistry()
    h = reg.histogram("lat", keep_samples=True)
    h.observe(0.25)
    assert h.percentile(50) == 0.25 and h.percentile(99) == 0.25
    assert math.isnan(h.percentile(50, tier="other"))   # empty series


def test_export_jsonl_strict_with_nan_gauge(tmp_path):
    reg = MetricsRegistry()
    reg.gauge("g").set(float("nan"))
    reg.counter("c").inc()
    path = reg.export_jsonl(tmp_path / "m.jsonl", extra={"run": "t"})

    def boom(tok):
        raise AssertionError(f"non-strict literal {tok}")

    lines = [json.loads(ln, parse_constant=boom)
             for ln in path.read_text().splitlines()]
    assert lines[0] == {"type": "meta", "run": "t"}
    by_name = {ln.get("metric"): ln for ln in lines[1:]}
    assert by_name["g"]["value"] is None        # NaN → null
    assert by_name["c"]["value"] == 1


# ---------------------------------------------------------------------------
# kernel-dispatch spans + overhead guard
# ---------------------------------------------------------------------------

def test_kernel_dispatch_span_carries_geometry_and_prediction():
    a, b = _small_batch(seed=0)
    TRACER.clear()
    with telemetry():
        batched_spmm(a, b, impl="csr")
    evs = [e for e in TRACER.events() if e.name.startswith("spmm/")]
    assert evs, "no kernel span recorded under telemetry"
    args = evs[0].args
    assert args["impl"] == "csr" and args["source"] == "forced"
    assert args["batch"] == 2 and args["n_b"] == 8
    assert args["predicted_s"] is None or args["predicted_s"] > 0
    assert args["key"]            # the Workload key ties span → cache/audit
    TRACER.clear()


def test_kernel_span_feeds_regret_auditor():
    from repro.observability import default_auditor

    a, b = _small_batch(seed=1)
    aud = default_auditor()
    n0 = len(aud.entries)
    with telemetry():
        batched_spmm(a, b, impl="auto")
    new = aud.entries[n0:]
    assert new and all(e.source == "span" for e in new)
    assert all(e.regret_ratio == 1.0 for e in new)
    TRACER.clear()


def test_disabled_telemetry_overhead_under_5pct_of_xla_dispatch():
    """The ISSUE overhead gate: with telemetry OFF, the per-dispatch hook
    cost (one predicate + null context) must be < 5% of one jitted XLA-impl
    batched_spmm dispatch. Comparing hook-cost against the dispatch median
    (not two nearly-equal end-to-end timings) keeps this robust to CI
    timing noise."""
    obs_trace.set_enabled(False)
    a, b = _small_batch(batch=4, dim=32, nnz_per_row=2, n_b=16, seed=2)
    f = jax.jit(lambda bb: batched_spmm(a, bb, impl="csr"))
    jax.block_until_ready(f(b))
    ts = []
    for _ in range(30):
        t0 = time.perf_counter()
        jax.block_until_ready(f(b))
        ts.append(time.perf_counter() - t0)
    dispatch_s = float(np.median(ts))

    n = 2000
    t0 = time.perf_counter()
    for _ in range(n):
        with span("x"):
            pass
        obs_trace.enabled()
    hook_s = (time.perf_counter() - t0) / n
    assert hook_s < 0.05 * dispatch_s, (
        f"disabled-mode hook {hook_s:.2e}s >= 5% of dispatch "
        f"{dispatch_s:.2e}s")


# ---------------------------------------------------------------------------
# regret auditor
# ---------------------------------------------------------------------------

def test_auditor_flags_deliberately_poisoned_cache(tmp_path):
    """Poison a tuning-cache record so its pinned ``best`` is a measured
    LOSER; the auditor must replay the cache-driven decision, flag it, and
    name the measured winner as would_have_won — the ISSUE acceptance."""
    from repro.autotune import TuningCache, Workload, select_impl

    cache = TuningCache(str(tmp_path / "tune.json"))
    w = Workload(batch=4, m_pad=16, nnz_pad=64, k_pad=4, n_b=8)
    times = {"ref": 5e-4, "csr": 1e-4, "dense": 2e-4}
    cache.put(w.key(), times, interpret=True)
    cache.records[w.key()]["best"] = "ref"      # the poison: pin a loser
    d = select_impl(w, allow_pallas=False, cache=cache)
    assert d.impl == "ref" and d.source == "cache"   # poison took effect

    aud = RegretAuditor()
    (entry,) = aud.audit_cache(cache, [w], allow_pallas=False)
    assert entry.flagged and entry.chosen == "ref" and entry.best == "csr"
    assert entry.regret_ratio == pytest.approx(5.0)
    rep = aud.report()
    assert rep["n_flagged"] == 1
    assert rep["flagged"][0]["would_have_won"] == "csr"
    assert rep["flagged"][0]["source"] == "cache"
    json.dumps(sanitize_json(rep), allow_nan=False)   # strict-JSON-able
    assert "FLAG" in aud.format_report()


def test_auditor_clean_cache_not_flagged(tmp_path):
    from repro.autotune import TuningCache, Workload

    cache = TuningCache(str(tmp_path / "tune.json"))
    w = Workload(batch=4, m_pad=16, nnz_pad=64, k_pad=4, n_b=8)
    cache.put(w.key(), {"ref": 5e-4, "csr": 1e-4}, interpret=True)
    aud = RegretAuditor()
    (entry,) = aud.audit_cache(cache, [w], allow_pallas=False)
    assert not entry.flagged and entry.regret_ratio == pytest.approx(1.0)


def test_auditor_per_impl_ratios_geomean():
    from repro.autotune import Workload

    aud = RegretAuditor()
    w = Workload(batch=4, m_pad=16, nnz_pad=64, k_pad=4, n_b=8)
    # measured = 2x predicted twice → geomean exactly 2.0
    for _ in range(2):
        p = aud.entries  # noqa: F841
        from repro.autotune.cost_model import estimate

        pred = estimate(w, "ref", aud.hw)
        aud.record(w.key(), "ref", predicted_s=pred, measured_s=2 * pred)
    r = aud.per_impl_ratios()
    assert r["ref"]["n"] == 2
    assert r["ref"]["geomean_measured_over_predicted"] == pytest.approx(2.0)


# ---------------------------------------------------------------------------
# ServeMetrics on the registry
# ---------------------------------------------------------------------------

SUMMARY_KEYS = {
    "served", "rejected", "deadline_misses", "waves", "compile_count",
    "throughput_rps", "latency_p50_s", "latency_p99_s", "mean_wait_s",
    "padding_waste_nodes", "padding_waste_nnz", "fill_rate",
}


def _report(**kw):
    from repro.serving.engine import GraphWaveReport

    base = dict(slots=4, n_requests=2, n_failed=0, real_nodes=20,
                real_nnz=40, node_capacity=64, nnz_capacity=512)
    base.update(kw)
    return GraphWaveReport(**base)


def test_servemetrics_empty_run_summary_keys_pinned():
    from repro.scheduler.metrics import ServeMetrics

    s = ServeMetrics().summary()
    assert set(s) == SUMMARY_KEYS       # the BENCH_serve.json schema
    assert s["served"] == 0 and s["waves"] == 0
    for k in ("throughput_rps", "latency_p50_s", "latency_p99_s",
              "mean_wait_s", "padding_waste_nodes", "fill_rate"):
        assert math.isnan(s[k]), k


def test_servemetrics_all_rejected():
    from repro.scheduler.metrics import ServeMetrics

    m = ServeMetrics()
    m.record_rejection(arrival=0.0)
    m.record_request(arrival=1.0, dispatch=2.0, finish=3.0, failed=True)
    assert m.served == 0 and m.rejected == 2
    assert math.isnan(m.throughput) and math.isnan(m.p50)


def test_servemetrics_single_sample_percentiles():
    from repro.scheduler.metrics import ServeMetrics

    m = ServeMetrics()
    m.record_request(arrival=0.0, dispatch=0.5, finish=2.0)
    assert m.p50 == pytest.approx(2.0) and m.p99 == pytest.approx(2.0)


def test_servemetrics_single_request_throughput_not_nan():
    """Regression: ONE request finishing at its own arrival timestamp
    (zero-width clock span) used to make throughput NaN; it must fall back
    to the wave's service time."""
    from repro.scheduler.metrics import ServeMetrics

    m = ServeMetrics()
    m.record_wave("t0", dispatch=0.0, service_time=0.25, report=_report())
    m.record_request(arrival=0.0, dispatch=0.0, finish=0.0)
    assert m.throughput == pytest.approx(1 / 0.25)
    assert not math.isnan(m.summary()["throughput_rps"])


def test_servemetrics_deadline_and_waste_accounting():
    from repro.scheduler.metrics import ServeMetrics

    m = ServeMetrics()
    m.record_wave("t0", dispatch=1.0, service_time=0.5, report=_report())
    m.record_request(arrival=0.0, dispatch=1.0, finish=1.5, deadline=1.2)
    m.record_request(arrival=0.5, dispatch=1.0, finish=1.5, deadline=2.0)
    assert m.served == 2 and m.deadline_misses == 1
    assert m.padding_waste_nodes == pytest.approx(1 - 20 / 64)
    assert m.padding_waste_nnz == pytest.approx(1 - 40 / 512)
    assert m.fill_rate == pytest.approx(2 / 4)
    assert m.throughput == pytest.approx(2 / 1.5)


def test_servemetrics_snapshot_carries_serve_series():
    from repro.scheduler.metrics import ServeMetrics

    m = ServeMetrics()
    m.record_wave("t0", dispatch=0.0, service_time=0.1, report=_report())
    m.record_request(arrival=0.0, dispatch=0.0, finish=0.1)
    names = {r["metric"] for r in m.registry.snapshot()}
    assert {"serve_requests_total", "serve_latency_seconds",
            "serve_wave_service_seconds", "serve_waves_total"} <= names


def test_shared_registry_with_instance_labels():
    from repro.scheduler.metrics import ServeMetrics

    reg = MetricsRegistry()
    a = ServeMetrics(registry=reg, labels={"instance": "a"})
    b = ServeMetrics(registry=reg, labels={"instance": "b"})
    a.record_request(arrival=0.0, dispatch=0.0, finish=1.0)
    assert a.served == 1 and b.served == 0      # series stay separate


# ---------------------------------------------------------------------------
# end-to-end: telemetry-enabled serve run → nested trace + regret report
# ---------------------------------------------------------------------------

def test_serve_run_produces_nested_trace_and_regret_report(tmp_path):
    from benchmarks.check_trace_json import check_file
    from repro.core.gcn import GCNConfig, init_gcn
    from repro.data.graphs import GraphDatasetSpec, generate
    from repro.scheduler import Scheduler, TierPolicy, VirtualClock
    from repro.serving import GraphRequest

    spec = GraphDatasetSpec.tox21_like(
        n_samples=6, n_features=8, channels=2, seed=3)
    data = generate(spec)
    cfg = GCNConfig(n_features=8, channels=2, conv_widths=(8,), n_tasks=3)
    params = init_gcn(jax.random.key(0), cfg)
    policy = TierPolicy.from_requests(
        [(s.n_nodes, max(len(r) for r in s.rows)) for s in data],
        levels=1, batch=4)
    reqs = [GraphRequest(rows=s.rows, cols=s.cols, features=s.features,
                         n_nodes=s.n_nodes) for s in data]

    TRACER.clear()
    with telemetry():       # kernel spans on; no warmup → trace-time spans
        sched = Scheduler(params, cfg, tiers=policy, clock=VirtualClock())
        out = sched.serve(reqs)
    assert all(r.done and not r.failed for r in out)

    evs = TRACER.events()
    sched_spans = [e for e in evs if e.name == "sched/wave"]
    wave_spans = [e for e in evs if e.name == "serve/wave"]
    kern_spans = [e for e in evs if e.name.startswith(("spmm/", "gspmm/"))]
    assert sched_spans and wave_spans and kern_spans

    def contains(outer, inner):
        return (outer.ts <= inner.ts
                and inner.ts + inner.dur <= outer.ts + outer.dur)

    # nesting: every engine wave sits inside a scheduler wave; at least one
    # kernel span (fired at trace time, first wave per geometry) sits
    # inside an engine wave
    assert all(any(contains(s, w) for s in sched_spans) for w in wave_spans)
    assert any(any(contains(w, k) for w in wave_spans) for k in kern_spans)
    # lifecycle events on the scheduler's clock track
    names = {e.name for e in evs}
    assert {"request/arrival", "request/admit", "request", "queue_depth"} \
        <= names

    # the exported trace passes the CI gate
    path = TRACER.export_chrome(tmp_path / "serve_trace.json")
    assert check_file(path) == []
    TRACER.clear()


def test_trainer_metrics_hooks(tmp_path):
    from repro.core.gcn import GCNConfig
    from repro.data.graphs import GraphDatasetSpec, batches, generate
    from repro.training import GCNTrainer, TrainerConfig

    spec = GraphDatasetSpec.tox21_like(
        n_samples=8, n_features=8, channels=2, seed=4)
    data = generate(spec)
    cfg = GCNConfig(n_features=8, channels=2, conv_widths=(8,), n_tasks=12)
    reg = MetricsRegistry()
    trainer = GCNTrainer(
        cfg, tcfg=TrainerConfig(checkpoint_dir=str(tmp_path),
                                checkpoint_every=1000, log_every=1),
        registry=reg)
    TRACER.clear()
    with telemetry():
        _, _, metrics = trainer.fit(
            lambda e: batches(data, spec, 4, seed=e), epochs=1)
    labels = {"layer": cfg.layer, "impl": cfg.impl}
    assert reg.get("train_steps_total").value(**labels) == 2    # 8/4 graphs
    assert reg.get("train_step_seconds") is None
    assert np.isfinite(reg.get("train_loss").value(**labels))
    assert reg.get("train_grad_norm").value(**labels) > 0
    assert metrics["grad_norm"] > 0
    evs = TRACER.events()
    steps = [e for e in evs if e.name == "train/step"]
    assert [e.args["step"] for e in steps] == [1, 2]
    # one train/iter per batch, and one for the fetch that ends the epoch
    iters = [e for e in evs if e.name == "train/iter"]
    assert len(iters) == 3
    assert all(any(i.ts <= s.ts and s.ts + s.dur <= i.ts + i.dur
                   for i in iters) for s in steps)
    TRACER.clear()


def test_trainer_telemetry_opt_out(tmp_path):
    from repro.core.gcn import GCNConfig
    from repro.data.graphs import GraphDatasetSpec, batches, generate
    from repro.training import GCNTrainer, TrainerConfig

    spec = GraphDatasetSpec.tox21_like(
        n_samples=4, n_features=8, channels=2, seed=5)
    data = generate(spec)
    cfg = GCNConfig(n_features=8, channels=2, conv_widths=(8,), n_tasks=12)
    reg = MetricsRegistry()
    trainer = GCNTrainer(
        cfg, tcfg=TrainerConfig(checkpoint_dir=str(tmp_path),
                                checkpoint_every=1000),
        registry=reg, telemetry=False)
    TRACER.clear()
    trainer.fit(lambda e: batches(data, spec, 4, seed=e), epochs=1)
    assert reg.get("train_steps_total").total() == 0
    assert not any(e.name == "train/step" for e in TRACER.events())


# ---------------------------------------------------------------------------
# bench-JSON strictness satellites
# ---------------------------------------------------------------------------

def test_write_bench_json_serializes_nan_as_null(tmp_path):
    from benchmarks import common
    from benchmarks.check_bench_json import check_file

    start = common.results_snapshot()
    common.RESULTS.append({"name": "t/nan", "us_per_call": float("nan"),
                           "derived": ""})
    path = common.write_bench_json(
        "obs_test", start=start, path=tmp_path / "BENCH_obs_test.json",
        extra={"inf": float("inf")})
    common.RESULTS.pop()

    def boom(tok):
        raise AssertionError(f"bare {tok} literal in bench JSON")

    doc = json.loads(path.read_text(), parse_constant=boom)
    assert doc["rows"][0]["us_per_call"] is None
    assert doc["inf"] is None
    assert check_file(path) == []       # schema-clean too


def test_check_bench_json_rejects_nan_literal(tmp_path):
    from benchmarks.check_bench_json import check_file

    p = tmp_path / "BENCH_bad.json"
    p.write_text('{"suite": "bad", "backend": "cpu", "rows": '
                 '[{"name": "x", "us_per_call": NaN, "derived": ""}]}')
    errs = check_file(p)
    assert errs and "NaN" in errs[0]


def test_check_trace_json_gates(tmp_path):
    from benchmarks.check_trace_json import check_file

    empty = tmp_path / "empty.json"
    empty.write_text('{"traceEvents": []}')
    assert any("EMPTY" in e for e in check_file(empty))

    nan = tmp_path / "nan.json"
    nan.write_text('{"traceEvents": [{"name": "x", "ph": "X", "ts": NaN, '
                   '"pid": 1, "tid": 1, "dur": 1}]}')
    assert any("non-finite" in e for e in check_file(nan))

    bad_ph = tmp_path / "ph.json"
    bad_ph.write_text('{"traceEvents": [{"name": "x", "ph": "Q", "ts": 1, '
                      '"pid": 1, "tid": 1}]}')
    assert any("unknown" in e for e in check_file(bad_ph))


# ---------------------------------------------------------------------------
# one switch: profiler annotations always, the ring under REPRO_TELEMETRY
# ---------------------------------------------------------------------------

def _profile(trace_dir, body):
    """Run ``body()`` under ``jax.profiler`` (no Python tracer); returns
    ``(name, start_ns, end_ns, stats)`` of every host-plane event."""
    import glob

    from jax.profiler import ProfileData

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)[-1]
    return [(e.name, e.start_ns, e.end_ns, dict(e.stats))
            for p in ProfileData.from_file(path).planes
            if p.name == "/host:CPU" for line in p.lines
            for e in line.events]


def _tiny_train_and_serve(tmp_path):
    """A two-layer GCN trainer over 8 graphs and a scheduler serving 6
    requests that arrive 10 ms apart on a virtual clock."""
    from repro.core.gcn import GCNConfig
    from repro.data.graphs import GraphDatasetSpec, batches, generate
    from repro.scheduler import Scheduler, TierPolicy, VirtualClock
    from repro.serving import GraphRequest
    from repro.training import GCNTrainer, TrainerConfig

    spec = GraphDatasetSpec.tox21_like(
        n_samples=8, n_features=8, channels=2, seed=6)
    data = generate(spec)
    cfg = GCNConfig(n_features=8, channels=2, conv_widths=(8, 8),
                    n_tasks=12)
    trainer = GCNTrainer(
        cfg, tcfg=TrainerConfig(checkpoint_dir=str(tmp_path / "ckpt"),
                                checkpoint_every=2, log_every=1),
        registry=MetricsRegistry())
    policy = TierPolicy.from_requests(
        [(s.n_nodes, max(len(r) for r in s.rows)) for s in data[:6]],
        levels=1, batch=4)
    sched = Scheduler(trainer.init_state()[0], cfg, tiers=policy,
                      clock=VirtualClock())
    reqs = [GraphRequest(rows=s.rows, cols=s.cols, features=s.features,
                         n_nodes=s.n_nodes) for s in data[:6]]

    def run():
        trainer.fit(lambda e: batches(data, spec, 4, seed=e), epochs=1)
        out = sched.serve(reqs, arrivals=[0.01 * i for i in range(6)])
        assert all(r.done and not r.failed for r in out)

    return run


def test_telemetry_off_leaves_ring_empty_and_profiler_sees_spans(tmp_path):
    obs_trace.set_enabled(False)
    run = _tiny_train_and_serve(tmp_path)
    TRACER.clear()
    events = _profile(tmp_path / "prof", run)
    assert TRACER.events() == []

    spans: dict[str, list] = {}
    for name, s, e, _ in events:
        spans.setdefault(name, []).append((s, e))
    names = ("train/iter", "train/batch", "train/step", "train/sync",
             "train/checkpoint", "sched/wave", "sched/wait", "serve/wave",
             "serve/assemble", "serve/dispatch", "serve/fetch")
    assert all(spans.get(n) for n in names), \
        [n for n in names if not spans.get(n)]

    def inside(inner, outer):
        return all(any(os <= s and e <= oe for os, oe in spans[outer])
                   for s, e in spans[inner])

    for inner in ("train/batch", "train/step", "train/sync",
                  "train/checkpoint"):
        if inner != "train/sync":   # the epoch-end sync follows the loop
            assert inside(inner, "train/iter"), inner
    for inner in ("serve/assemble", "serve/dispatch", "serve/fetch"):
        assert inside(inner, "serve/wave"), inner
    assert inside("serve/wave", "sched/wave")
    assert not any(os <= s and e <= oe for s, e in spans["sched/wait"]
                   for os, oe in spans["sched/wave"])
    # 8 graphs in batches of 4: two steps, and the fetch that ends the epoch
    assert len(spans["train/step"]) == 2 and len(spans["train/iter"]) == 3


def test_telemetry_on_ring_holds_the_new_spans(tmp_path):
    run = _tiny_train_and_serve(tmp_path)
    TRACER.clear()
    with telemetry():
        run()
    names = {e.name for e in TRACER.events()}
    assert {"train/iter", "train/batch", "train/step", "sched/wait",
            "serve/assemble", "serve/dispatch", "serve/fetch", "request",
            "queue_depth"} <= names
    TRACER.clear()


def test_collection_runs_in_a_host_gc_span_and_counts(tmp_path):
    import gc

    from repro.observability import REGISTRY, collector

    assert collector.installed() is not None
    assert sum(type(cb).__name__ == "_Hook" for cb in gc.callbacks) == 1
    pauses = REGISTRY.get("process_gc_pause_seconds_total")
    counts = REGISTRY.get("process_gc_collections_total")
    p0, c0 = pauses.value(gen="2"), counts.value(gen="2")
    events = _profile(tmp_path / "prof", gc.collect)
    assert counts.value(gen="2") >= c0 + 1
    assert pauses.value(gen="2") > p0
    gcs = [stats for name, _, _, stats in events if name == "host/gc"]
    assert any(st.get("gen") == 2 for st in gcs), gcs


def _step_hlo(trainer, batch):
    """The compiled HLO text of the trainer's jitted step on one batch."""
    params, state = trainer.init_state()
    adj = [(a.row_ids, a.col_ids, a.values, a.nnz, a.n_rows)
           for a in batch["adj"]]
    return trainer._step.lower(params, state, adj, batch["x"],
                               batch["n_nodes"], batch["labels"]
                               ).compile().as_text()


def test_compiled_step_names_conv_layers_and_kernels(tmp_path):
    import re

    from repro.core.gcn import GCNConfig
    from repro.data.graphs import GraphDatasetSpec, batches, generate
    from repro.training import GCNTrainer, TrainerConfig

    spec = GraphDatasetSpec.tox21_like(
        n_samples=4, n_features=8, channels=2, seed=7)
    batch = next(iter(batches(generate(spec), spec, 4, seed=0)))
    cfg = GCNConfig(n_features=8, channels=2, conv_widths=(8, 8),
                    n_tasks=12)
    trainer = GCNTrainer(cfg, tcfg=TrainerConfig(
        checkpoint_dir=str(tmp_path)), registry=MetricsRegistry())
    obs_trace.set_enabled(False)
    off = _step_hlo(trainer, batch)
    ops = re.findall(r'op_name="([^"]*)"', off)
    for layer in ("conv0", "conv1"):
        assert any(f"({layer})/" in o or f"/{layer}/" in o for o in ops)
        assert any(re.search(rf"\b{layer}\)?/spmm/\w+/", o) for o in ops)
    assert any(o.startswith("jit(step)/transpose(jvp(conv1))/")
               for o in ops)
    with telemetry():
        on = _step_hlo(trainer, batch)
    assert on == off        # traced and untraced runs compile the same HLO
    TRACER.clear()


def test_step_lowers_the_flat_program_fit_runs(tmp_path):
    """``_step.lower`` takes ``init_state``'s pytrees, packs them and lowers
    the program ``fit`` runs: named ``step``, with its ``conv<i>`` and
    ``spmm/<impl>`` scopes, the carry donated, and the argument and result
    buffers that ``train_step_buffers`` reports — at most 26 and 4 for
    ``GCNConfig.tox21()`` at batch 4 (54 and 34 as a pytree step)."""
    import re

    from repro.core.gcn import GCNConfig
    from repro.data.graphs import GraphDatasetSpec, batches, generate
    from repro.training import GCNTrainer, TrainerConfig

    spec = GraphDatasetSpec.tox21_like(n_samples=4, seed=7)
    batch = next(iter(batches(generate(spec), spec, 4, seed=0)))
    registry = MetricsRegistry()
    trainer = GCNTrainer(GCNConfig.tox21(), tcfg=TrainerConfig(
        checkpoint_dir=str(tmp_path)), registry=registry)
    params, state = trainer.init_state()
    adj = [(a.row_ids, a.col_ids, a.values, a.nnz, a.n_rows)
           for a in batch["adj"]]
    lowered = trainer._step.lower(params, state, adj, batch["x"],
                                  batch["n_nodes"], batch["labels"])
    hlo = lowered.compile().as_text()
    assert hlo.startswith("HloModule jit_step")
    ops = re.findall(r'op_name="([^"]*)"', hlo)
    for layer in ("conv0", "conv1"):
        assert any(f"({layer})/" in o or f"/{layer}/" in o for o in ops)
        assert any(re.search(rf"\b{layer}\)?/spmm/\w+/", o) for o in ops)
    (carry, *batch_args), _ = lowered.args_info
    assert all(a.donated for a in jax.tree.leaves(carry))
    assert not any(a.donated for a in jax.tree.leaves(batch_args))
    n_in = len(jax.tree.leaves(lowered.args_info))
    n_out = len(jax.tree.leaves(lowered.out_info))
    assert n_in <= 26 and n_out <= 4, (n_in, n_out)

    trainer.fit([batch])
    buffers = registry.get("train_step_buffers")
    assert (buffers.value(dir="in"), buffers.value(dir="out")) == (
        n_in, n_out)
    assert trainer._step.jitted._cache_size() == 1
