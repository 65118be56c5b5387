"""Online scoring traffic: the program's ``Scheduler`` on ``RealClock``, fed
an open loop of single-molecule requests.

Cell parameters: ``molecules``, ``size_dist``, ``generator_seed`` (the
fixed pool the requests are drawn from), ``tiers`` (the ``TierPolicy``
ladder, checked at set-up against ``TierPolicy.from_requests`` of the pool)
and ``arrivals`` (``rate_per_s``, ``burst``, ``draw_seed``). The
``SchedulerConfig`` is the default.

A window of ``s`` seconds offers ``rate * s`` requests. Set-up draws them
(one fixed multiset per cell, in the seed's order), makes the weights with
``init_gcn`` from the seed and compiles every tier through
``Scheduler.warmup``. The window submits every request before the first
due time, then ``drain`` serves them. A request's latency runs from when it
was due to when its logits were on the host; the window reports its 50th,
90th, 95th and 99th percentiles (``p50_ms`` ...) over every request due in
it, a failed one counting as infinite.

The check compares every served answer's logits with the reference's for
its molecule, with per-molecule batch norm as the scheduler serves them.
"""
from __future__ import annotations

import gc
import time

import numpy as np

SUBMIT_LEAD_S = 0.1         # fixed part of the lead before the first due
SUBMIT_PER_REQUEST_S = 2e-5  # time, plus this much per request submitted


def setup(run):
    import jax

    from chipbench import traffic
    from repro.core.gcn import init_gcn
    from repro.scheduler import (RealClock, Scheduler, SchedulerConfig,
                                 TierPolicy)
    from repro.serving import GraphRequest

    cell = run.cell.spec
    cfg = run.gcn_config()
    t0 = time.monotonic()
    pool = traffic.molecule_pool(run.cell.config, cell)
    policy = TierPolicy(**cell["tiers"])
    derived = TierPolicy.from_requests(
        [(m.n_nodes, max(len(r) for r in m.rows)) for m in pool],
        levels=len(policy.tiers), batch=cell["tiers"]["batch"])
    if derived.tiers != policy.tiers:
        raise RuntimeError(f"the cell's tiers {policy.tiers} are not the "
                           f"pool's {derived.tiers}")
    arr = cell["arrivals"]
    n = max(1, round(arr["rate_per_s"] * run.seconds))
    ids = traffic.draws(len(pool), n, arr["draw_seed"], run.seed)
    offsets = traffic.arrival_offsets(n, arr["rate_per_s"], arr["burst"],
                                      arr["draw_seed"], run.seed)
    requests = [GraphRequest(rows=pool[i].rows, cols=pool[i].cols,
                             features=pool[i].features,
                             n_nodes=pool[i].n_nodes) for i in ids]
    run.log(f"data: pool of {len(pool)} molecules, {n} requests at "
            f"{arr['rate_per_s']}/s (burst {arr['burst']}) in "
            f"{time.monotonic() - t0:.2f} s; tiers "
            f"{[t.key for t in policy.tiers]}")
    params = init_gcn(jax.random.key(run.seed), cfg)
    sched = Scheduler(params, cfg, tiers=policy, clock=RealClock(),
                      config=SchedulerConfig())
    sched.warmup(requests)
    for key, d in sched.programs.decisions().items():
        run.log(f"tier {key}: auto resolves to {d.impl} [{d.source}]")
    return {"sched": sched, "requests": requests, "offsets": offsets,
            "ids": ids, "pool": pool}


def window(state, run):
    sched, requests = state["sched"], state["requests"]
    clock = sched.clock
    lead = SUBMIT_LEAD_S + SUBMIT_PER_REQUEST_S * len(requests)
    start = clock.now() + lead
    pending = [sched.submit(r, arrival=start + off)
               for r, off in zip(requests, state["offsets"])]
    # the queued requests live until the window ends, as set-up's objects
    # do: keep the collector from scanning them too
    gc.freeze()
    late = clock.now() - start
    clock.sleep_until(start)
    run.open_window()
    sched.drain()
    end = clock.now()
    run.close_window()
    ok = [p.finish is not None and p.request.done and not p.request.failed
          for p in pending]
    done = [p for p, k in zip(pending, ok) if k]
    failed = len(pending) - len(done)
    lat = np.array([p.finish - p.arrival for p in done] + [np.inf] * failed)
    wait = np.array([p.dispatch - p.arrival for p in done])
    waves = sched.metrics.waves
    tail = {f"p{q}_ms": float(np.percentile(lat, q)) * 1e3
            for q in (50, 90, 95, 99)}
    p50, p99 = tail["p50_ms"], tail["p99_ms"]
    run.log(f"window: {len(pending)} requests due over "
            f"{state['offsets'][-1]:.4f} s, all submitted "
            f"{-late * 1e3:.3f} ms before the first due time; {len(waves)} "
            f"waves; latency ms {tail}; served in "
            f"{end - start:.4f} s")
    state["served"] = [(i, p.request.logits) for i, p, k in
                       zip(state["ids"], pending, ok) if k]
    return {"t_start": start, "t_end": end, "attempted": len(pending),
            "failed": failed,
            "metrics": tail,
            "counters": {
                "p50_ms": p50, "p99_ms": p99,
                "queue_wait_ms": float(wait.mean()) * 1e3 if len(wait)
                else None,
                "pad_waste": sched.metrics.padding_waste_nnz,
                "waves": len(waves)}}


def check(state, run, extra=()):
    import jax

    from chipbench import compare, reference

    config = run.cell.config
    pool = state["pool"]
    served = state.pop("served", [])
    state.clear()                   # free the program before the reference
    jax.clear_caches()
    got = np.stack([l for _, l in served]) if served else np.zeros((0, 1))
    used = sorted({int(i) for i, _ in served})
    row = {i: k for k, i in enumerate(used)}
    take = [row[int(i)] for i, _ in served]

    def logits(precision):
        ref = reference.serve_logits(
            run.seed, config["gcn"], [pool[i] for i in used],
            config["molecules"]["max_nodes"], precision=precision)
        return ref[take]

    want = logits("highest")
    out = {"program": compare.logit_readings(got, want)}
    if "control" in extra:
        out["control"] = compare.logit_readings(logits("high"), want)
    return out
