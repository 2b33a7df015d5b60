"""The reduction from device events to busy and idle time, program and
kernel groups, and collective share."""

import json
from pathlib import Path

import numpy as np
import pytest

from chipbench import cells, reduce, tracing

DATA = Path(__file__).resolve().parent / "data"
Widths = cells.arch_module(Path(__file__).resolve().parents[2],
                           "qwen2").Widths


def synthetic():
    """Two chips, 10 ms traced; programs and ops in ns."""
    ms = 1_000_000
    kernel = "custom-call:tpu_custom_call"
    d0 = tracing.Device(
        "/device:TPU:0",
        ops=[(0, 2 * ms, "fusion", "fusion"),
             (1 * ms, 3 * ms, "closed_call", kernel),
             (5 * ms, 6 * ms, "all-gather", "all-gather"),
             (6 * ms, 7 * ms, "sort", "sort")],
        modules=[(0, 3 * ms, "jit_fn(11)", ""),
                 (5 * ms, 7 * ms, "jit_fn(12)", "")])
    d1 = tracing.Device(
        "/device:TPU:1",
        ops=[(0, 4 * ms, "shard_map", kernel)],
        modules=[(0, 4 * ms, "jit_fn(11)", "")])
    return tracing.Trace(devices=[d0, d1], window_s=0.010)


def test_union_and_gaps():
    assert tracing.union_ns([(0, 2), (1, 3), (5, 6), (6, 7)]) == 5
    assert tracing.union_ns([]) == 0
    assert tracing.gaps_ns([(1, 2), (4, 5)], 0, 8) == [(0, 1), (2, 4), (5, 8)]


def test_busy_idle_and_groups():
    tr = synthetic()
    # chip 0 busy 3 + 2 ms, chip 1 busy 4 ms: 4.5 ms on average
    assert tracing.busy_s(tr) == pytest.approx(0.0045)
    rec = {"trace": tr}
    assert reduce.idle_pct(rec) == pytest.approx(55.0)
    kernel = ["custom-call:tpu_custom_call"]
    assert tracing.op_time_s(tr, kernel) == pytest.approx(0.003)
    classes = tracing.groups()["programs"]
    t = tracing.program_time_s(tr, classes)
    # chip 0: a 3 ms prefill program and a 2 ms finalize; chip 1: a 4 ms
    # prefill program; averaged over 2 chips
    assert t == pytest.approx({"prefill_chunk": 0.0035, "finalize": 0.001})
    assert tracing.kernel_time_s(
        tr, {"program": "prefill_chunk", "ops": kernel}, classes) \
        == pytest.approx(0.003)
    assert tracing.kernel_time_s(
        tr, {"program": "finalize", "ops": kernel}, classes) == 0
    # collectives: 1 ms on chip 0 over 4.5 ms mean busy, per chip average
    assert reduce.collective_pct(rec) == pytest.approx(100 * 0.0005 / 0.0045)


def test_hlo_tags():
    """Op names on the TPU are HLO instructions: their base name and
    opcode, with a custom call's target; an operand named after a
    collective does not make an op a collective."""
    assert tracing.tags(
        '%shard_map.616 = bf16[16,1,7,128]{3,2,1,0:T(8,128)(2,1)S(1)} '
        'custom-call(s32[16,19]{1,0:T(8,128)S(1)} %get-tuple-element.1810),'
        ' custom_call_target="tpu_custom_call", operand_layout_constraints='
        '{s32[16,19]{1,0}}') == ("shard_map", "custom-call:tpu_custom_call")
    assert tracing.tags(
        '%all-gather.41 = bf16[16,1,3584]{2,0,1:T(8,128)(2,1)S(1)} '
        'all-gather(bf16[16,1,896]{2,0,1:T(8,128)(2,1)S(1)} %x), '
        'channel_id=2') == ("all-gather", "all-gather")
    assert tracing.tags(
        '%fusion.166 = f32[16]{0:T(128)S(1)} fusion(bf16[16,1,3584]'
        '{2,0,1:T(8,128)(2,1)S(1)} %all-gather.41), kind=kLoop') \
        == ("fusion", "fusion")
    assert tracing.tags(
        '%copy-start.4 = (f32[64]{0:T(128)S(1)}, f32[64]{0:T(128)}, '
        'u32[]{:S(2)}) copy-start(f32[64]{0:T(128)} %g)') \
        == ("copy-start", "copy-start")


def test_breakdown_lists_top_ops_and_gaps():
    b = tracing.breakdown(synthetic())
    assert b["device_ops"][0] == ["shard_map (custom-call:tpu_custom_call)",
                                  pytest.approx(0.002)]
    assert b["idle_gaps"] == [["host", pytest.approx(0.002)]]


def test_trace_round_trips_through_json():
    tr = synthetic()
    back = tracing.Trace.from_json(json.loads(json.dumps(tr.to_json())))
    assert back.window_s == tr.window_s
    assert back.devices[0].ops == tr.devices[0].ops


def test_span_arithmetic():
    """Queue waits, prefill chunks and model operations from request
    spans, on the engine clock."""
    w = Widths(layers=2, d=8, ff=16, heads=2, kv_heads=1, head_dim=4,
               vocab=32)
    spans = [
        {"name": "request", "ph": "B", "ts": 1.5, "tid": "req:0",
         "args": {"uid": 0, "n_prompt": 300}},
        {"name": "prefill_chunk", "ph": "B", "ts": 1.6, "tid": "req:0",
         "args": {"s": 0}},
        {"name": "prefill_chunk", "ph": "B", "ts": 2.6, "tid": "req:0",
         "args": {"s": 256}},
        {"name": "decode_chunk", "ph": "B", "ts": 2.7, "tid": "engine",
         "args": {"steps": 8, "slots": 1}},
    ]
    rec = {"spans": spans, "trace_span": (2.0, 3.0),
           "serving": {"chunk": 256, "budget": 128}, "widths": w,
           "requests": [{"uid": 0, "arrival_s": 1.0, "first_token_s": 2.65,
                         "finish_s": 2.85, "n_prompt": 300, "n_out": 20}]}
    assert reduce.queue_waits_ms(rec) == [pytest.approx(500.0)]
    assert reduce.prefill_chunks(rec) == [(256, 44)]
    (t, steps, slots, keys), = reduce.decode_chunks(rec)
    assert (steps, slots) == (8, 1) and keys == pytest.approx(128 + 5)


def recorded():
    d = json.loads((DATA / "trace-qwen2-7b-tp4-decode.json").read_text())
    return tracing.Trace.from_json(d)


def test_recorded_trace_busy_matches_a_timeline():
    """Busy time of a recorded four-chip trace equals an independent count
    on a 1 us timeline, and collectives are found by opcode only."""
    tr = recorded()
    assert len(tr.devices) == 4
    busy = []
    for d in tr.devices:
        lo = min(o[0] for o in d.ops)
        line = np.zeros(int((max(o[1] for o in d.ops) - lo) // 1000) + 2,
                        bool)
        for o in d.ops:
            line[int((o[0] - lo) // 1000):int(-(-(o[1] - lo) // 1000))] = True
        busy.append(line.sum() * 1e-6)
    # the timeline rounds each interval out to whole microseconds
    assert tracing.busy_s(tr) == pytest.approx(np.mean(busy), rel=0.02)
    coll = {"all-gather", "all-reduce", "all-to-all"}
    want = np.mean([sum(o[1] - o[0] for o in d.ops if o[3] in coll)
                    for d in tr.devices]) / 1e9
    assert tracing.op_time_s(tr, tracing.groups()["collectives"]) \
        == pytest.approx(want)
    assert 0 < reduce.collective_pct({"trace": tr}) < 100
    # Pallas kernels are custom calls; fusions that read a collective's
    # result are not collectives
    kinds = {o[3] for d in tr.devices for o in d.ops}
    assert "custom-call:tpu_custom_call" in kinds
    assert all(not o[3].startswith("fusion") or o[2] == "fusion"
               or "fusion" in o[2] for d in tr.devices for o in d.ops)
