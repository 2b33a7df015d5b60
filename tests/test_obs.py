"""Observability layer: registry semantics, export formats, span
invariants over served traces, preemption/replay linkage, the deprecated
legacy stats view, and drift-monitor parity.

Layers, least to most end-to-end:

1. **Registry units** (no model): typed counter/gauge/histogram/info
   semantics, label series, get-or-create with kind mismatch failing
   loudly, per-run ``reset`` that zeroes written series but preserves
   callback gauges, Prometheus text exposition and JSON snapshot.
2. **Validator units** (no model): ``validate_trace`` rejects unclosed,
   crossed, and time-travelling span streams.
3. **Span invariants** (served): a traced run through the
   ``tests/trace_utils.py`` harness yields a well-nested, closed,
   monotone trace in which every request closes a complete span tree —
   one ``prefill_chunk`` per prompt chunk, ``finalize``, ``first_token``,
   ``decode``, outcome ``done`` — and jit compiles land on the engine
   track.
4. **Replay linkage** (served, tiny pool): a preempted request's spans
   close with outcome ``preempted`` and its re-serve opens a fresh
   ``request`` span whose ``replay_of`` names the original admission.
5. **Legacy view**: ``engine.stats`` still reads like the old dict but
   warns ``DeprecationWarning`` and mirrors the registry exactly.
6. **Drift monitor**: sampling rules (stride, short-prompt skip), and
   the streaming overlap equalling an offline recomputation from raw
   ``objective`` calls on the same records.
"""

import math
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core import objective
from repro.core.lookahead import init_lookahead_params
from repro.models import transformer as tf
from repro.obs import (DriftMonitor, MetricsRegistry, TraceRecorder,
                       kept_overlaps, phase_table, request_span_trees,
                       validate_trace)
from repro.obs.metrics import bind_stat_gauges
from repro.serving import KVBlockPool
from trace_utils import make_trace_requests, run_trace


@pytest.fixture(scope="module")
def model():
    cfg = get_smoke_config("smollm-135m")
    params = tf.init_params(jax.random.PRNGKey(0), cfg)
    lkv = init_lookahead_params(jax.random.PRNGKey(1), cfg, params["layers"])
    return cfg, params, lkv


# ---------------------------------------------------------------------------
# 1. registry units
# ---------------------------------------------------------------------------


def test_counter_only_goes_up():
    reg = MetricsRegistry()
    c = reg.counter("requests_total", "Requests.", labelnames=("path",))
    c.inc(path="dense")
    c.inc(2, path="dense")
    c.inc(path="paged")
    assert c.value(path="dense") == 3
    assert c.value(path="paged") == 1
    with pytest.raises(ValueError):
        c.inc(-1, path="dense")
    with pytest.raises(ValueError):  # wrong label set fails loudly
        c.inc(mesh="x")


def test_gauge_set_inc_max_and_callback():
    reg = MetricsRegistry()
    g = reg.gauge("depth", "Queue depth.")
    g.set(3)
    g.inc(2)
    assert g.value() == 5
    g.max(4)
    assert g.value() == 5, "max keeps the running high water"
    g.max(9)
    assert g.value() == 9
    state = {"n": 7}
    live = reg.gauge("live", "Live mirror.")
    live.set_fn(lambda: state["n"])
    assert live.value() == 7
    state["n"] = 11
    assert live.value() == 11, "callback gauges read at collection time"


def test_reset_preserves_callbacks_and_info():
    reg = MetricsRegistry()
    reg.counter("c").inc(5)
    reg.gauge("g").set(5)
    state = {"n": 3}
    reg.gauge("live").set_fn(lambda: state["n"])
    reg.histogram("h").observe(0.2)
    reg.info("build").set(path="kernel")
    reg.reset()
    assert reg.value("c") == 0
    assert reg.value("g") == 0
    assert reg.value("live") == 3, "live mirrors survive the run boundary"
    assert reg.get("h").count() == 0
    assert reg.value("build") == {"path": "kernel"}


def test_get_or_create_and_kind_mismatch():
    reg = MetricsRegistry()
    a = reg.counter("x", "first")
    assert reg.counter("x") is a, "re-registration returns the metric"
    with pytest.raises(TypeError):
        reg.gauge("x")
    assert "x" in reg and "y" not in reg
    assert reg.value("never_registered", default=-1) == -1


def test_histogram_buckets_cumulative():
    reg = MetricsRegistry()
    h = reg.histogram("lat", "Latency.", buckets=(0.01, 0.1, 1.0))
    for v in (0.005, 0.05, 0.05, 0.5, 5.0):
        h.observe(v)
    val = h.collect()["values"]["lat"]
    assert val["buckets"] == {"0.01": 1, "0.1": 3, "1.0": 4, "+Inf": 5}
    assert val["count"] == 5
    assert math.isclose(val["sum"], 5.605)


def test_prometheus_text_and_json_snapshot(tmp_path):
    reg = MetricsRegistry()
    reg.counter("req_total", "Total requests.").inc(3)
    reg.histogram("lat_s", "Latency.", buckets=(0.1, 1.0)).observe(0.05)
    reg.info("build", "Build info.").set(mesh="none", path="kernel")
    text = reg.prometheus_text()
    assert "# HELP req_total Total requests." in text
    assert "# TYPE req_total counter" in text
    assert "req_total 3" in text
    assert 'lat_s_bucket{le="0.1"} 1' in text
    assert 'lat_s_bucket{le="+Inf"} 1' in text
    assert "lat_s_sum 0.05" in text and "lat_s_count 1" in text
    assert 'build_info{mesh="none",path="kernel"} 1' in text
    out = tmp_path / "metrics.json"
    reg.to_json(str(out))
    import json
    snap = json.loads(out.read_text())
    assert snap["req_total"]["kind"] == "counter"
    assert snap["req_total"]["values"]["req_total"] == 3


def test_bind_stat_gauges_numeric_only():
    reg = MetricsRegistry()
    state = {"hits": 2, "rate": 0.5, "enabled": True, "keys": [1, 2],
             "path": "kernel"}
    bound = bind_stat_gauges(reg, "comp", lambda: state)
    assert sorted(bound) == ["hits", "rate"], \
        "bools, lists and strings stay out of the numeric mirror"
    assert reg.value("comp_hits") == 2
    state["hits"] = 9
    assert reg.value("comp_hits") == 9


# ---------------------------------------------------------------------------
# 2. validator units
# ---------------------------------------------------------------------------


def _ev(ph, name, ts, tid="t"):
    return {"name": name, "ph": ph, "ts": ts, "tid": tid, "args": {}}


def test_validate_trace_accepts_well_nested():
    events = [_ev("B", "a", 0), _ev("B", "b", 1), _ev("i", "x", 2),
              _ev("E", "b", 3), _ev("E", "a", 4)]
    assert validate_trace(events) == {"tracks": 1, "spans": 2, "events": 5}


def test_validate_trace_rejects_violations():
    with pytest.raises(AssertionError):  # unclosed
        validate_trace([_ev("B", "a", 0)])
    with pytest.raises(AssertionError):  # crossed
        validate_trace([_ev("B", "a", 0), _ev("B", "b", 1),
                        _ev("E", "a", 2), _ev("E", "b", 3)])
    with pytest.raises(AssertionError):  # time travel
        validate_trace([_ev("B", "a", 5), _ev("E", "a", 1)])
    with pytest.raises(AssertionError):  # end with nothing open
        validate_trace([_ev("E", "a", 0)])


# ---------------------------------------------------------------------------
# 3. span invariants over a served trace
# ---------------------------------------------------------------------------


def _walk(node):
    yield node
    for c in node["children"]:
        yield from _walk(c)


class _Annotations:
    """Stands in for ``jax.profiler.TraceAnnotation``: logs each enter and
    exit as a B / E event on one track."""

    def __init__(self):
        self.events: list[dict] = []

    def __call__(self, name, **args):
        log = self.events

        class _Ann:
            def __enter__(self):
                log.append({"name": name, "ph": "B", "ts": len(log),
                            "tid": "thread", "args": args})

            def __exit__(self, *exc):
                log.append({"name": name, "ph": "E", "ts": len(log),
                            "tid": "thread", "args": {}})

        return _Ann()


# the serving loop's host phases (engine track) every paged run passes
LOOP_PHASES = {"schedule", "admit", "admit_sync", "place", "first_token",
               "append_blocks", "decode_chunk", "token_sync", "collect",
               "clock"}
# long-lived spans: opened and closed in different calls, recorder-only
RECORDER_ONLY = ("request", "decode")


def test_span_invariants_over_served_trace(model, monkeypatch):
    cfg, params, lkv = model
    chunk = 64
    reqs = make_trace_requests(cfg, chunk=chunk, seed=0, n_requests=5,
                               max_new=3)
    rec = TraceRecorder()
    ann = _Annotations()
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", ann)
    got, eng = run_trace(cfg, params, lkv, policy="lookaheadkv",
                         requests=reqs, chunk=chunk, trace=rec)
    summary = validate_trace(rec)  # raises on nesting/closure/monotone
    # every span but the long-lived ones is also a profiler annotation,
    # entered and left in the order the recorder opens and closes it, so
    # the annotations nest on the engine thread as the spans do
    validate_trace(ann.events)
    spans = [e for e in rec.events
             if e["ph"] in "BE" and e["name"] not in RECORDER_ONLY]
    assert [(e["ph"], e["name"]) for e in ann.events] == \
        [(e["ph"], e["name"]) for e in spans]
    assert all(a["args"] == e["args"] for a, e in zip(ann.events, spans)
               if a["ph"] == "B")
    assert summary["tracks"] == len(reqs) + 1  # engine + one per request
    seqs = []
    for uid, r in got.items():
        trees = request_span_trees(rec, uid)
        assert len(trees) == 1, "no pool -> no replays"
        tree = trees[0]
        assert tree["name"] == "request"
        assert tree["args"]["n_prompt"] == len(r.prompt)
        assert tree["end_args"]["outcome"] == "done"
        seqs.append(tree["args"]["admission_seq"])
        names = [n["name"] for n in _walk(tree)]
        assert names.count("prefill_chunk") == math.ceil(
            len(r.prompt) / chunk), "one span per prompt chunk"
        assert "finalize" in names and "decode" in names
        instants = [i["name"] for n in _walk(tree) for i in n["instants"]]
        assert "first_token" in instants and "retire" in instants
    assert sorted(seqs) == list(range(len(reqs))), \
        "admission sequence numbers the serve attempts densely"
    # engine-track work: decode chunks spanned, fresh-engine compiles
    # surfaced as instants (the ChunkCompileCache proxy)
    eng_names = {e["name"] for e in rec.events if e["tid"] == rec.ENGINE}
    assert "decode_chunk" in eng_names
    assert "jit_compile" in eng_names
    assert LOOP_PHASES - {"append_blocks"} <= eng_names, \
        "the dense engine runs every loop phase but the pool's"
    # the trace was captured with device-synced timers (the default when
    # tracing), and the chrome export records that
    assert rec.sync and rec.chrome_trace()["otherData"]["sync_timers"]
    rows = {row["uid"]: row for row in phase_table(rec, got)}
    for uid, r in got.items():
        row = rows[uid]
        assert row["outcome"] == "done" and row["replays"] == 0
        assert row["prefill_ms"] > 0
        assert row["first_token_ms"] is not None
        assert row["decode_ms"] > 0


def _engine_thread(log_dir, names) -> list:
    """(start ns, end ns, name) of the events named in ``names`` on the
    host line that holds most of them, in the newest profile."""
    from pathlib import Path

    from jax.profiler import ProfileData

    f = sorted(Path(log_dir).rglob("*.xplane.pb"),
               key=lambda p: p.stat().st_mtime)[-1]
    best: list = []
    for plane in ProfileData.from_file(str(f)).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            evs = [(e.start_ns, e.end_ns, e.name) for e in line.events
                   if e.name in names]
            best = max(best, evs, key=len)
    return sorted(best, key=lambda e: (e[0], -e[1]))


def test_engine_phases_on_the_profiler_clock(model, tmp_path):
    """Under ``jax.profiler.trace`` a recorder-attached engine's phases are
    annotations on the engine thread of the host plane: one per recorder
    span, nested as the spans are, and the loop's phases cover the serving
    loop; with no recorder attached, none appear."""
    cfg, params, lkv = model
    reqs = make_trace_requests(cfg, chunk=64, seed=2, n_requests=4,
                               max_new=4)
    for i, r in enumerate(reqs):  # the last arrives after the others end
        r.arrival_s = 0.0 if i < 3 else 1.5
    pool = KVBlockPool(cfg, block_size=16, num_blocks=64)
    _, eng = run_trace(cfg, params, lkv, policy="lookaheadkv",
                       requests=reqs, chunk=64, kv_pool=pool)  # compiles
    rec = TraceRecorder(sync=False)
    eng.set_trace(rec)
    with jax.profiler.trace(str(tmp_path / "on")):
        eng.run([r.clone() for r in reqs])
    spans = [e for e in rec.events
             if e["ph"] == "B" and e["name"] not in RECORDER_ONLY]
    names = {e["name"] for e in spans}
    assert LOOP_PHASES | {"prefill_chunk", "finalize", "wait_arrival"} \
        <= names
    got = _engine_thread(tmp_path / "on", names)
    assert sorted(e[2] for e in got) == sorted(e["name"] for e in spans)
    stack: list = []
    for s, e, name in got:  # properly nested
        while stack and stack[-1][1] <= s:
            stack.pop()
        assert not stack or e <= stack[-1][1], (name, stack[-1])
        stack.append((s, e, name))
    # the loop's phases (engine track) leave little of the loop unnamed
    loop = [(s, e) for s, e, n in got
            if n in LOOP_PHASES | {"wait_arrival"} and n != "clock"]
    top, end = [], -1
    for s, e in loop:
        if s >= end:
            top.append((s, e))
            end = e
    covered = sum(e - s for s, e in top)
    assert covered >= 0.9 * (top[-1][1] - top[0][0])
    eng.set_trace(None)
    with jax.profiler.trace(str(tmp_path / "off")):
        eng.run([r.clone() for r in reqs[:2]])
    assert _engine_thread(tmp_path / "off", names | {"request"}) == []


def test_preempted_request_carries_replay_linkage(model):
    cfg, params, lkv = model
    chunk = 128
    reqs = make_trace_requests(cfg, chunk=chunk, seed=5, n_requests=6,
                               max_new=8, suffix_lens=(0, 1, 77))
    for r in reqs:
        r.arrival_s = 0.0
    # the tiny-pool burst from test_kv_pool: admits optimistically, must
    # preempt mid-decode when the pool cannot cover every growth
    pool = KVBlockPool(cfg, block_size=4, num_blocks=7)
    rec = TraceRecorder()
    got, eng = run_trace(cfg, params, lkv, policy="streaming_llm",
                         requests=reqs, chunk=chunk, num_slots=3,
                         decode_chunk=1, kv_pool=pool,
                         reserve_appends=False, trace=rec)
    validate_trace(rec)
    assert eng.metrics.value("serving_preemptions_total") > 0
    preempted = 0
    for uid in got:
        trees = request_span_trees(rec, uid)
        assert trees and trees[-1]["end_args"]["outcome"] == "done"
        first_seq = trees[0]["args"]["admission_seq"]
        assert "replay_of" not in trees[0]["args"]
        for later in trees[1:]:
            assert later["args"]["replay_of"] == first_seq, \
                "every re-serve names its original admission"
        for tree in trees[:-1]:
            assert tree["end_args"]["outcome"] in ("preempted",
                                                   "admission_blocked")
            if tree["end_args"]["outcome"] == "preempted":
                preempted += 1
                instants = [i["name"] for n in _walk(tree)
                            for i in n["instants"]]
                assert "preempt" in instants
    assert preempted > 0, "the tiny pool must actually preempt a decode"
    rows = phase_table(rec, got)
    assert any(row["replays"] > 0 for row in rows)


# ---------------------------------------------------------------------------
# 5. legacy stats view
# ---------------------------------------------------------------------------


def test_legacy_stats_view_warns_and_mirrors_registry(model):
    cfg, params, lkv = model
    reqs = make_trace_requests(cfg, chunk=64, seed=1, n_requests=3,
                               max_new=2)
    got, eng = run_trace(cfg, params, lkv, policy="lookaheadkv",
                         requests=reqs, chunk=64)
    with pytest.warns(DeprecationWarning, match="engine.metrics"):
        s = eng.stats
    assert s["decode_steps"] == eng.metrics.value(
        "serving_decode_steps_total")
    assert s["decode_chunks"] == eng.metrics.value(
        "serving_decode_chunks_total")
    assert s["max_concurrency"] == eng.metrics.value(
        "serving_max_concurrency")
    assert s["decode_path"] == eng.metrics.value("serving_build")[
        "decode_path"]
    assert s["decode_time_s"] == pytest.approx(
        eng.metrics.value("serving_decode_seconds_total"))
    with pytest.raises(TypeError):  # a *view*: reads only
        s["decode_steps"] = 0
    assert "prefill_chunks" in dict(s)


def test_legacy_stats_view_empty_before_first_run(model):
    cfg, params, lkv = model
    from repro.serving import ContinuousEngine, ServingConfig
    eng = ContinuousEngine(params, cfg, ServingConfig(num_slots=1),
                           lkv_params=lkv)
    with pytest.warns(DeprecationWarning):
        assert dict(eng.stats) == {}, "the historical pre-run shape"


# ---------------------------------------------------------------------------
# 6. drift monitor
# ---------------------------------------------------------------------------


def _fake_req(prompt_len, out_len, seed=0):
    rng = np.random.default_rng(seed)
    return SimpleNamespace(
        prompt=rng.integers(0, 100, prompt_len).astype(np.int32),
        out_tokens=[int(t) for t in rng.integers(0, 100, out_len)])


def test_drift_monitor_sampling_rules():
    mon = DriftMonitor({}, None, {}, budget=8, ring_size=3,
                       sample_every=2, eval_every=10_000)
    reg = MetricsRegistry()
    mon.bind(metrics=reg)
    assert reg.value("lookahead_drift_overlap") == -1.0, \
        "sentinel before the first evaluation"
    for i in range(6):
        mon.on_retire(_fake_req(20, 4, seed=i))
    assert mon.samples == 3, "stride-2 sampling over 6 retirements"
    mon.on_retire(_fake_req(8, 4))  # len(x) <= budget: vacuous, skipped
    mon.on_retire(_fake_req(20, 0))  # no generated future: skipped
    assert mon.samples == 3
    assert len(mon._ring) == 3, "ring capped at ring_size"
    assert reg.value("lookahead_drift_ring") == 3
    assert reg.value("lookahead_drift_samples") == 3
    assert mon.evals == 0, "eval_every not reached"
    empty = DriftMonitor({}, None, {}, budget=8)
    assert empty.evaluate() is None, "empty ring evaluates to None"


def test_drift_gauge_matches_offline_recomputation(model):
    cfg, params, lkv = model
    budget = 8
    mon = DriftMonitor(params, cfg, lkv, budget=budget, ring_size=4)
    reg = MetricsRegistry()
    mon.bind(metrics=reg)
    rng = np.random.default_rng(7)
    records = [(rng.integers(0, cfg.vocab_size, n).astype(np.int32),
                rng.integers(0, cfg.vocab_size, 6).astype(np.int32))
               for n in (24, 33)]
    for x, y in records:
        mon.observe(x, y)
    online = mon.evaluate()
    assert reg.value("lookahead_drift_overlap") == online
    assert reg.value("lookahead_drift_evals") == 1
    # offline: raw objective calls + the shared kept-set machinery —
    # the bench_lookahead_quality computation on the same records
    ovs = []
    for x, y in records:
        xy = jnp.asarray(np.concatenate([x, y]))[None]
        gt = np.asarray(objective.gt_scores(params, cfg, xy, len(x))[:, 0],
                        np.float32)
        pred = np.asarray(
            objective.lookahead_scores(params, cfg, lkv,
                                       jnp.asarray(x)[None])[:, 0],
            np.float32)
        ovs.extend(kept_overlaps(pred, gt, budget))
    assert online == pytest.approx(float(np.mean(ovs)), abs=1e-6)
