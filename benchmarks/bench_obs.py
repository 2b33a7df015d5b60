"""Observability-layer bench: overhead, trace completeness, drift parity.

The paper's core serving claim is *negligible runtime overhead* for
learned eviction — the observability layer that verifies the claim must
itself be near-free, or its numbers are fiction.  Three checks, one
gated ``obs/overhead_verdict``:

1. **Overhead** — the CI long-tail trace (``bench_serving``'s shape)
   replayed through two identical chunked engines, obs off (no tracer,
   registry only — the always-on cost) vs obs on (span tracer attached,
   which also flips the engine's timers to device-synced mode).  Best-of
   interleaved trials; obs-on throughput must land within
   ``OVERHEAD_BUDGET`` (3%) of obs-off.
2. **Trace completeness** — the obs-on replay's trace must satisfy the
   structural span invariants (``validate_trace``: well-nested, closed,
   monotone per track) and every admitted request must close a full
   span tree: >= 1 ``prefill_chunk``, a ``finalize``, a ``first_token``
   instant, a ``decode`` span, final outcome ``done``.
3. **Drift parity** — a small trace served with a ``DriftMonitor``
   attached; the streaming ``lookahead_drift_overlap`` gauge must match
   an *offline* recomputation on the ring's records — raw
   ``objective.gt_scores`` / ``objective.lookahead_scores`` calls plus
   the shared ``kept_overlaps`` (the ``bench_lookahead_quality``
   machinery) — to within ``DRIFT_TOL``.

Artifacts: ``BENCH_obs_metrics.json`` (the obs-on engine's registry
snapshot) and ``BENCH_obs_trace.json`` (Chrome trace-event JSON — load
it in https://ui.perfetto.dev), uploaded by CI next to ``BENCH_ci.json``.

    PYTHONPATH=src python -m benchmarks.bench_obs
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.bench_serving import (BUDGET, CHUNK, MAX_NEW, PROMPT_LENS,
                                      make_trace)
from benchmarks.common import clone_requests
from repro.common.compile_cache import enable_compile_cache
from repro.common.config import EvictionConfig
from repro.configs import get_smoke_config
from repro.core import objective
from repro.core.lookahead import init_lookahead_params
from repro.models import transformer as tf
from repro.obs import DriftMonitor, TraceRecorder, kept_overlaps
from repro.obs.trace import request_span_trees, validate_trace
from repro.serving import (ChunkingConfig, ContinuousEngine, Request,
                           ServingConfig)

OVERHEAD_BUDGET = 0.03  # obs-on tok/s within 3% of obs-off
DRIFT_TOL = 1e-6  # streaming gauge vs offline recomputation
SLOTS = 4

METRICS_OUT = "BENCH_obs_metrics.json"
TRACE_OUT = "BENCH_obs_trace.json"


def _engine(params, cfg, lkv, **obs):
    sc = ServingConfig(
        policy="lookaheadkv", evict=EvictionConfig(budget=BUDGET),
        chunking=ChunkingConfig(chunk=CHUNK,
                                max_context=max(PROMPT_LENS) + CHUNK),
        num_slots=SLOTS, max_new_tokens=MAX_NEW, eos_id=-1, **obs)
    return ContinuousEngine(params, cfg, sc, lkv_params=lkv)


def _tree_complete(trees) -> bool:
    """A served request's span forest ends in a closed ``done`` tree
    carrying the full phase skeleton."""
    if not trees or trees[-1]["end_args"].get("outcome") != "done":
        return False
    names = [n["name"] for t in trees for n in _nodes(t)]
    instants = [i["name"] for t in trees for n in _nodes(t)
                for i in n["instants"]]
    return ("prefill_chunk" in names and "finalize" in names
            and "decode" in names and "first_token" in instants)


def _nodes(tree):
    yield tree
    for c in tree["children"]:
        yield from _nodes(c)


def bench_overhead(params, cfg, lkv, *, n_requests=12, rate_hz=20.0,
                   seed=0, trials=2):
    """Obs-off vs obs-on replays of the CI long-tail trace.  Returns the
    per-config metrics plus the final obs-on engine + trace (for the
    completeness check and the artifacts)."""
    trace = make_trace(n_requests, rate_hz, seed, cfg.vocab_size,
                       long_tail=True, long_len=2048, n_long=1)
    eng_off = _engine(params, cfg, lkv)
    eng_on = _engine(params, cfg, lkv, trace=TraceRecorder())
    for eng in (eng_off, eng_on):
        eng.run(clone_requests(trace))  # compile off the clock
    res = {"obs_off": {"tok_per_s": 0.0}, "obs_on": {"tok_per_s": 0.0}}
    last_trace, last_done = None, None
    # trials interleave off/on so a host load spike hits both; best-of
    # damps the one-sided noise a shared CI runner adds
    for _ in range(trials):
        for name, eng in (("obs_off", eng_off), ("obs_on", eng_on)):
            if name == "obs_on":
                eng.set_trace(TraceRecorder())  # fresh trace per replay
            t0 = time.perf_counter()
            done = eng.run(clone_requests(trace))
            wall = time.perf_counter() - t0
            tps = sum(len(r.out_tokens) for r in done) / wall
            res[name]["tok_per_s"] = max(res[name]["tok_per_s"], tps)
            res[name]["wall_s"] = wall
            if name == "obs_on":
                last_trace, last_done = eng.trace, done
    summary = validate_trace(last_trace)  # raises on a broken trace
    complete = all(
        _tree_complete(request_span_trees(last_trace, r.uid))
        for r in last_done)
    res["trace"] = {"complete": complete, "requests": len(last_done),
                    **summary}
    return res, eng_on, last_trace


def bench_drift(params, cfg, lkv, *, seed=1):
    """Serve a small trace with a ``DriftMonitor`` riding the retirement
    hook, then recompute the overlap offline on the ring's records."""
    rng = np.random.default_rng(seed)
    lens = (41, 48, 60, 41)  # > BUDGET so the kept set is non-vacuous
    reqs = [Request(uid=i,
                    prompt=rng.integers(0, cfg.vocab_size,
                                        n).astype(np.int32),
                    max_new_tokens=8, arrival_s=0.01 * i)
            for i, n in enumerate(lens)]
    mon = DriftMonitor(params, cfg, lkv, budget=BUDGET, ring_size=8,
                       sample_every=1, eval_every=10_000)  # eval after run
    eng = _engine(params, cfg, lkv, drift=mon)
    eng.run([r.clone() for r in reqs])
    online = mon.evaluate()
    gauge = eng.metrics.value("lookahead_drift_overlap")
    # offline recomputation: raw objective calls + the shared kept-set
    # machinery — no DriftMonitor code on this side of the comparison
    ovs: list[float] = []
    for x, y in mon._ring:
        xy = jnp.asarray(np.concatenate([x, y]))[None]
        gt = np.asarray(
            objective.gt_scores(params, cfg, xy, len(x))[:, 0], np.float32)
        pred = np.asarray(
            objective.lookahead_scores(params, cfg, lkv,
                                       jnp.asarray(x)[None])[:, 0],
            np.float32)
        ovs.extend(kept_overlaps(pred, gt, BUDGET))
    offline = float(np.mean(ovs))
    return {"online": online, "gauge": gauge, "offline": offline,
            "records": len(mon._ring), "abs_err": abs(online - offline)}


def _verdict(res, drift) -> tuple[bool, str]:
    off, on = res["obs_off"]["tok_per_s"], res["obs_on"]["tok_per_s"]
    within = on >= off * (1.0 - OVERHEAD_BUDGET)
    complete = res["trace"]["complete"]
    parity = (drift["abs_err"] <= DRIFT_TOL
              and abs(drift["gauge"] - drift["online"]) <= DRIFT_TOL)
    ok = within and complete and parity
    return ok, (
        f"{'PASS' if ok else 'FAIL'}: obs-on {on:.1f} tok/s vs obs-off "
        f"{off:.1f} ({100 * (1 - on / max(off, 1e-9)):+.1f}% overhead, "
        f"budget {100 * OVERHEAD_BUDGET:.0f}%, "
        f"{'within' if within else 'OVER'}); span trees "
        f"{'complete' if complete else 'INCOMPLETE'} over "
        f"{res['trace']['requests']} requests "
        f"({res['trace']['spans']} spans); drift gauge "
        f"{drift['gauge']:.6f} vs offline {drift['offline']:.6f} "
        f"(|err| {drift['abs_err']:.2e}, "
        f"{'parity' if parity else 'DIVERGED'})")


def bench(*, n_requests=12, trials=2, seed=0):
    cfg = get_smoke_config("smollm-135m")
    params = tf.init_params(jax.random.PRNGKey(0), cfg)
    lkv = init_lookahead_params(jax.random.PRNGKey(1), cfg, params["layers"])
    res, eng_on, trace = bench_overhead(params, cfg, lkv,
                                        n_requests=n_requests, seed=seed,
                                        trials=trials)
    drift = bench_drift(params, cfg, lkv, seed=seed + 1)
    return res, drift, eng_on, trace


def run(report):
    """benchmarks.run / ci_smoke entry point."""
    res, drift, eng_on, trace = bench()
    eng_on.metrics.to_json(METRICS_OUT)
    trace.to_chrome(TRACE_OUT)
    off, on = res["obs_off"]["tok_per_s"], res["obs_on"]["tok_per_s"]
    report("obs/off_tok_per_s", None, f"{off:.1f}")
    report("obs/on_tok_per_s", None, f"{on:.1f}")
    report("obs/overhead_pct", None,
           f"{100 * (1 - on / max(off, 1e-9)):+.1f}")
    report("obs/trace_spans", None, str(res["trace"]["spans"]))
    report("obs/trace_events", None, str(res["trace"]["events"]))
    report("obs/drift_overlap", None, f"{drift['gauge']:.4f}")
    report("obs/drift_abs_err", None, f"{drift['abs_err']:.2e}")
    ok, verdict = _verdict(res, drift)
    print(verdict)
    report("obs/overhead_verdict", None, "pass" if ok else "fail")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--trials", type=int, default=2)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()
    res, drift, eng_on, trace = bench(n_requests=args.requests,
                                      trials=args.trials, seed=args.seed)
    eng_on.metrics.to_json(METRICS_OUT)
    trace.to_chrome(TRACE_OUT)
    for name in ("obs_off", "obs_on"):
        m = res[name]
        print(f"{name:8s} {m['tok_per_s']:8.1f} tok/s  "
              f"wall {m['wall_s']:.2f}s")
    t = res["trace"]
    print(f"trace: {t['events']} events, {t['spans']} spans over "
          f"{t['tracks']} tracks; complete={t['complete']}")
    print(f"drift: gauge {drift['gauge']:.6f} offline "
          f"{drift['offline']:.6f} over {drift['records']} records")
    print(_verdict(res, drift)[1])
    print(f"artifacts: {METRICS_OUT}, {TRACE_OUT}")


if __name__ == "__main__":
    main()
