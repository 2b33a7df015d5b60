"""Engine phases on the device trace's clock, and the named kernels: the
clock offset from the blocking syncs, device idle charged to the innermost
phase, labelled idle gaps, the ``lookahead_score`` roofline and the
KV-copy share; on synthetic traces whose answers are summed by hand, and
on a slice of a real traced run recorded on a TPU v5e."""

import json
from pathlib import Path

import numpy as np
import pytest

from chipbench import cells, named, phases, tracing
from chipbench.flops import Widths

DATA = Path(__file__).resolve().parent / "data"
ROOT = Path(__file__).resolve().parents[2]
MS = 1_000_000  # ns


def B(name, ts, tid="engine", **args):
    return {"name": name, "ph": "B", "ts": ts, "tid": tid, "args": args}


def E(name, ts, tid="engine"):
    return {"name": name, "ph": "E", "ts": ts, "tid": tid, "args": {}}


def test_phases_nest_and_innermost_names_each_moment():
    spans = [B("request", 0.0, "req:1", uid=1, n_prompt=10),
             B("admit", 1.0), B("finalize", 1.0, "req:1"),
             E("finalize", 2.0, "req:1"), B("admit_sync", 2.0),
             E("admit_sync", 5.0), E("admit", 6.0),
             B("clock", 6.5, recorder_us=6.5e6), E("clock", 6.5),
             B("wait_arrival", 7.0), E("wait_arrival", 9.0),
             E("request", 9.5, "req:1")]
    iv = phases.phase_intervals(spans)
    assert iv == [(1.0, 6.0, "admit"), (1.0, 2.0, "finalize"),
                  (2.0, 5.0, "admit_sync"), (7.0, 9.0, "wait_arrival")]
    assert phases.innermost(iv) == [
        (1.0, 2.0, "finalize"), (2.0, 5.0, "admit_sync"),
        (5.0, 6.0, "admit"), (7.0, 9.0, "wait_arrival")]


def synthetic(offset=3.25, latency=40e-6, n=40, seed=0):
    """One chip running a program of 4-6 ms every 10 ms, each waited on by
    a ``token_sync`` that ends ``latency`` after it; the engine clock is the
    trace clock plus ``offset`` seconds."""
    rng = np.random.default_rng(seed)
    ops, mods, spans = [], [], []
    for i in range(n):
        s = 0.5 + i * 0.010
        e = s + rng.uniform(0.004, 0.006)
        mods.append((s * 1e9, e * 1e9, f"jit_body({i % 3})", ""))
        ops.append((s * 1e9, e * 1e9, "fusion", "fusion"))
        h = s + offset
        spans += [B("decode_chunk", h - 0.0002, steps=8, slots=1),
                  E("decode_chunk", h - 0.0001),
                  B("token_sync", h - 0.0001),
                  E("token_sync", e + offset + latency),
                  B("collect", e + offset + latency),
                  E("collect", e + offset + latency + 0.001)]
    dev = tracing.Device("/device:TPU:0", ops=ops, modules=mods)
    span = (0.5 + offset, 0.5 + offset + n * 0.010)
    rec = {"trace": tracing.Trace([dev], window_s=span[1] - span[0]),
           "trace_span": span, "spans": spans}
    return rec, dev


def test_clock_offset_from_blocking_syncs():
    rec, dev = synthetic()
    assert phases.clock_offset_s(rec, dev) == pytest.approx(3.25 + 40e-6,
                                                            abs=1e-6)
    few, fdev = synthetic(n=5)
    assert phases.clock_offset_s(few, fdev) is None
    parent = dict(rec, spans=[e for e in rec["spans"]
                              if e["name"] != "token_sync"])
    assert phases.clock_offset_s(parent, dev) is None


def brute_idle(rec, dev, off, step_ns=1000):
    """Idle by innermost phase, by sampling the stretch every microsecond:
    the hand sum the sweep in ``phases`` must agree with."""
    a, b = rec["trace_span"]
    t = np.arange((a - off) * 1e9, (b - off) * 1e9, step_ns) + step_ns / 2
    busy = np.zeros(t.size, bool)
    for o in dev.ops:
        busy |= (t >= o[0]) & (t < o[1])
    label = np.full(t.size, "host", dtype=object)
    for s, e, name in phases.phase_intervals(rec["spans"]):  # outer first
        label[(t >= (s - off) * 1e9) & (t < (e - off) * 1e9)] = name
    out: dict = {}
    for name in set(label[~busy]):
        out[name] = float(np.sum(label[~busy] == name)) * step_ns / 1e9
    return out


def test_idle_is_charged_to_the_innermost_phase():
    rec, dev = synthetic()
    off = phases.clock_offset_s(rec, dev)
    by = phases.idle_by_phase_s(rec, dev, off)
    busy = sum(o[1] - o[0] for o in dev.ops) / 1e9
    span = rec["trace_span"][1] - rec["trace_span"][0]
    assert sum(by.values()) == pytest.approx(span - busy, abs=1e-9)
    hand = brute_idle(rec, dev, off)
    assert set(by) == set(hand) == {"host", "collect", "token_sync",
                                    "decode_chunk"}
    for name in hand:
        assert by[name] == pytest.approx(hand[name], abs=2e-6), name
    # each 10 ms period: 1 ms of collect after the program, on the chip's
    # idle; host idle is all but the unnamed stretch, over the stretch
    assert by["collect"] == pytest.approx(40 * 0.001, abs=1e-6)
    assert phases.host_idle_pct(rec) == pytest.approx(
        100 * (span - busy - by["host"]) / span, rel=1e-9)
    gaps = phases.labelled_gaps(rec, top=3)
    assert [g[0] for g in gaps] == ["collect"] * 3
    assert all(0.004 < g[1] < 0.0061 for g in gaps)
    # a wait under wait_arrival is idle with no work due: not host idle
    end10, start11 = dev.modules[10][1] / 1e9, dev.modules[11][0] / 1e9
    waits = dict(rec, spans=rec["spans"] + [
        B("wait_arrival", end10 + 3.25 + 0.0015),
        E("wait_arrival", start11 + 3.25 - 0.0003)])
    by_w = phases.idle_by_phase_s(waits, dev, off)
    assert by_w["wait_arrival"] > 0.002
    assert by_w["host"] == pytest.approx(by["host"] - by_w["wait_arrival"])
    assert phases.host_idle_pct(waits) == pytest.approx(
        phases.host_idle_pct(rec))
    idle_pct = 100 * (span - busy) / span
    assert phases.host_idle_pct(rec) <= idle_pct


def test_a_chip_that_cannot_place_its_clock_takes_the_others():
    rec, dev = synthetic()
    idle = tracing.Device("/device:TPU:1")  # no programs: no offset of its own
    two = dict(rec, trace=tracing.Trace([dev, idle], rec["trace"].window_s))
    assert phases.clock_offset_s(two, idle) is None
    by = phases.idle_by_phase(two)
    one = phases.idle_by_phase(rec)
    span = rec["trace_span"][1] - rec["trace_span"][0]
    # the idle chip is idle all the stretch, under the same phases
    assert sum(by.values()) == pytest.approx(
        (sum(one.values()) + span) / 2, abs=1e-9)


def test_no_phases_no_reading():
    """A program whose engine records no phases (the parent of the change
    that added them) reads nothing, and does not raise."""
    rec, _ = synthetic()
    old = dict(rec, spans=[e for e in rec["spans"]
                           if e["name"] == "decode_chunk"])
    assert phases.host_idle_pct(old) is None
    assert phases.labelled_gaps(old) is None
    empty = dict(rec, trace=tracing.Trace([], window_s=1.0))
    assert phases.host_idle_pct(empty) is None


def test_lookahead_score_call_counts():
    w = Widths(layers=2, d=8, ff=16, heads=4, kv_heads=2, head_dim=4,
               vocab=32)
    ops, nbytes = named.lookahead_score_call(w, n_prompt=100, n_obs=2)
    assert ops == 2 * 2 * 4 * 4 * 100
    assert nbytes == 2 * (2 * 4 + 100 * 2) * 4 + 4 * 4 * 100
    ops2, _ = named.lookahead_score_call(w, 100, 2, chips=2)
    assert ops2 == ops / 2


def test_lookahead_score_roofline_on_a_synthetic_finalize(monkeypatch):
    w = Widths(layers=2, d=8, ff=16, heads=4, kv_heads=2, head_dim=4,
               vocab=32)
    peak = {"bf16_flops": 1e9, "hbm_bytes_per_s": 1e9}
    dev = tracing.Device(
        "/device:TPU:0",
        ops=[(1 * MS, 2 * MS, "lookahead_score",
              "custom-call:tpu_custom_call"),
             (2 * MS, 3 * MS, "sort", "sort"),
             (5 * MS, 6 * MS, "chunk_attention",
              "custom-call:tpu_custom_call")],
        modules=[(0, 4 * MS, "jit_fn(3)", ""), (5 * MS, 7 * MS, "jit_fn(2)",
                                                "")])
    spans = [B("request", 0.1, "req:7", uid=7, n_prompt=300),
             B("finalize", 0.2, "req:7"), E("finalize", 0.3, "req:7")]
    rec = {"trace": tracing.Trace([dev], window_s=0.01),
           "trace_span": (0.0, 1.0), "spans": spans, "peak": peak,
           "widths": w, "chips": 1, "config": {"lookahead": {
               "n_lookahead": 2}}}
    # no blocking syncs: the clocks cannot be placed, so nothing is read
    assert named.lookahead_score_roofline_pct(rec) is None
    # the trace clock's zero at engine time 0: the finalize program (at
    # 0-4 ms) follows the dispatch at 0.2 s only if its zero is later
    monkeypatch.setattr(phases, "clock_offset_s", lambda rec, dev: 0.0)
    assert named.lookahead_score_roofline_pct(rec) is None
    monkeypatch.setattr(phases, "clock_offset_s", lambda rec, dev: 0.25)
    ops, nbytes = named.lookahead_score_call(w, 300, 2)
    least = 2 * max(ops / 1e9, nbytes / 1e9)
    assert named.lookahead_score_roofline_pct(rec) == pytest.approx(
        100 * least / 0.001)
    rec["trace"].devices[0].ops[0] = (1 * MS, 2 * MS, "closed_call",
                                      "custom-call:tpu_custom_call")
    assert named.lookahead_score_roofline_pct(rec) is None  # unnamed kernel


def test_kv_copy_share_by_scope():
    dev = tracing.Device(
        "/device:TPU:0",
        ops=[(0, 1 * MS, "copy", "copy"),
             (1 * MS, 3 * MS, "chunk_attention",
              "custom-call:tpu_custom_call"),
             (3 * MS, 4 * MS, "fusion", "fusion"),
             (5 * MS, 6 * MS, "copy", "copy")],
        modules=[(0, 4 * MS, "jit_fn(2)", ""),
                 (5 * MS, 7 * MS, "jit_body(4)", "")])
    tr = tracing.Trace([dev], window_s=0.01)
    scopes = {dev.name: ["kv_head_major", "", "kv_buffer_write",
                         "kv_head_major"]}
    # the decode program's copy is not prefill; 2 of the chunk's 4 ms
    assert named.kv_copy_share_pct(tr, scopes) == pytest.approx(50.0)
    assert named.kv_copy_share_pct(tr, {}) is None
    # scopes that were not found read as nothing, not as no copy time
    assert named.kv_copy_share_pct(tr, {dev.name: [""] * 4}) is None


def test_scope_of_an_op_name():
    assert named._scope_of("jit(fn)/while/body/lookahead_rows/"
                           "kv_buffer_write/dynamic_update_slice") \
        == "kv_buffer_write"
    assert named._scope_of("jit(fn)/while/body/lookahead_scores/"
                           "lookahead_score/pallas_call") == "lookahead_scores"
    assert named._scope_of("jit(fn)/add") == ""


def test_op_names_from_a_profiles_hlo(tmp_path):
    """The profile keeps each program's HLO, with the instructions'
    op_name metadata: the scopes are read from there."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def fn(x):
        with jax.named_scope("kv_head_major"):
            y = jnp.swapaxes(x, 0, 1) * 2
        return y + 1

    x = jnp.ones((8, 128))
    fn(x).block_until_ready()
    with jax.profiler.trace(str(tmp_path)):
        fn(x).block_until_ready()
    names = named.hlo_op_names(named._newest(str(tmp_path)))
    progs = [k for k in names if k.startswith("jit_fn(")]
    assert progs, sorted(names)
    assert any(named._scope_of(v) == "kv_head_major"
               for v in names[progs[0]].values())


def test_new_readers_are_wired():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    by = {m["name"]: m for m in bench["per_layer"]}
    for name in ("host_idle_share.ttft_p50", "lookahead_score_roofline"):
        assert by[name]["moves"] == "ttft_p50_ms"
        assert set(by[name]["workloads"]) == {
            "qwen2-1.5b.longctx", "qwen2-7b-tp4.longctx"}
        assert callable(cells.metric_reader(ROOT, name))


# a slice of a traced qwen2-1.5b.longctx run on a TPU v5e (chipbench/
# record.py --slice): chip 0 around its shortest whole finalize program
SLICE = DATA / "trace-qwen2-1.5b-finalize.json"


def chip_slice():
    d = json.loads(SLICE.read_text())
    dev = tracing.Device("/device:TPU:0",
                         ops=[tuple(o[:4]) for o in d["ops"]],
                         modules=[tuple(m) for m in d["modules"]])
    rec = {"trace": tracing.Trace([dev], window_s=d["window_s"]),
           "trace_span": (0.0, d["window_s"]), "spans": d["spans"],
           "peak": d["peak"], "chips": d["chips"], "config": d["config"],
           "widths": Widths.of(d["config"]), "serving": d["serving"]}
    return d, rec, dev, {dev.name: [o[4] for o in d["ops"]]}


def test_chip_clock_offset_from_syncs():
    """On the chip, the offset the blocking syncs give lies after the one
    the profiler's ``clock`` annotations give by the syncs' usual wake-up
    latency: about 2.1 ms on a TPU v5e for the decode chunk's token sync,
    which is most of them."""
    full = chip_slice()[0]["full_stretch"]
    spans = []
    for s, e in full["syncs"]:
        spans += [B("token_sync", s), E("token_sync", e)]
    dev = tracing.Device("/device:TPU:0", modules=[
        (0.0, t * 1e9, "jit_fn(1)", "") for t in full["module_ends_s"]])
    rec = {"trace_span": tuple(full["trace_span"]), "spans": spans}
    late = phases.clock_offset_s(rec, dev) - full["offset_s"]
    assert 0 < late < 3e-3


def test_chip_slice_idle_and_gaps_by_hand(monkeypatch):
    d, rec, dev, _ = chip_slice()
    # the slice's clocks are aligned by construction (the annotations')
    monkeypatch.setattr(phases, "clock_offset_s", lambda rec, dev: 0.0)
    by = phases.idle_by_phase_s(rec, dev, 0.0)
    hand = brute_idle(rec, dev, 0.0)
    assert set(by) == set(hand)
    for name in hand:
        assert by[name] == pytest.approx(hand[name], abs=5e-5), name
    assert phases.host_idle_pct(rec) == pytest.approx(
        100 * sum(v for k, v in hand.items() if k not in ("host", "wait_arrival"))
        / d["window_s"], abs=0.1)
    gaps = phases.labelled_gaps(rec, top=5)
    assert gaps and all(name != "host" for name, _ in gaps)


def test_chip_slice_lookahead_roofline_by_hand(monkeypatch):
    """The reader's arithmetic on the chip's own ``lookahead_score`` calls.
    The slice starts after the program's dispatch, so the test gives that
    ``finalize`` span and its request a prompt length of 4,000 (the
    program ran on the 4k rung): the value is a check of the reader, not a
    reading."""
    d, rec, dev, _ = chip_slice()
    monkeypatch.setattr(phases, "clock_offset_s", lambda rec, dev: 0.0)
    n = 4000
    rec["spans"] = [B("request", -1.0, "req:7", uid=7, n_prompt=n),
                    B("finalize", -0.04, "req:7"),
                    E("finalize", -0.039, "req:7")] + rec["spans"]
    fin = [m for m in dev.modules if any(
        o[2] == "lookahead_score" and m[0] <= o[0] < m[1] for o in dev.ops)]
    assert len(fin) == 1
    m = fin[0]
    calls = [o for o in dev.ops
             if o[2] == "lookahead_score" and m[0] <= o[0] < m[1]]
    c = d["config"]
    L, H, KV = (c["num_hidden_layers"], c["num_attention_heads"],
                c["num_key_value_heads"])
    assert len(calls) == L  # one call per layer
    spent = sum(o[1] - o[0] for o in calls) / 1e9
    hd, n_obs = c["hidden_size"] // H, c["lookahead"]["n_lookahead"]
    ops = 2 * n_obs * H * hd * n
    nbytes = 2 * (n_obs * H + n * KV) * hd + 4 * H * n
    least = L * max(ops / d["peak"]["bf16_flops"],
                    nbytes / d["peak"]["hbm_bytes_per_s"])
    got = named.lookahead_score_roofline_pct(rec)
    assert got == pytest.approx(100 * least / spent, rel=1e-9)
    assert 0 < got <= 100
