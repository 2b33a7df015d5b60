"""Record one traced run of a cell for study, on the chips of this machine.

    python3 chipbench/record.py --workload qwen2-1.5b.longctx --seed 7 \
        --seconds 51 --out record.json [--slice slice.json]

It runs ``run.run_cell`` with tracing on, as ``run.py --trace 1`` does, and
keeps besides what ``tracing.load`` leaves out of the profile: each op's
scope and the engine thread's profiler annotations (``named.load_scopes``).
It writes one JSON object to ``--out``: the run's result line, the wall
time, the offset between the engine clock and each chip's trace clock as
the ``clock`` annotations give it and as ``phases.clock_offset_s`` infers
it from the blocking syncs, the device idle by engine phase and the
labelled idle gaps at both offsets, the KV-copy share of prefill, how much
copy time carries a scope, and each program class's top ops by scope.
With ``--slice``, it also writes a short stretch of chip 0 around the
shortest whole finalize program, with the engine phases and scopes of that
stretch, as test data for ``tests/test_bench_phases.py``.  Exits 2 without
a TPU, like ``run.py``.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

from chipbench import cells, named, phases, run, tracing  # noqa: E402

SLICE_BEFORE_NS = 5_000_000  # the slice starts this long before the program
SLICE_AFTER_NS = 15_000_000  # ... and ends this long after it
SLICE_MAX_BYTES = 280_000


def traced_run(cell, seed: int, seconds: float) -> tuple[dict, dict, dict]:
    """(result line, reader record, what ``tracing.load`` leaves out) of
    one traced run of ``cell``."""
    got: dict = {"seconds": {}}
    load, per_layer = tracing.load, run.per_layer

    def keep_load(log_dir, window_s, chips=1):
        t = time.perf_counter()
        got["named"] = named.load_scopes(log_dir)
        got["seconds"]["load_scopes"] = time.perf_counter() - t
        got["seconds"]["window_end_to_load"] = t - T_START
        t = time.perf_counter()
        tr = load(log_dir, window_s, chips)
        got["seconds"]["tracing.load"] = time.perf_counter() - t
        return tr

    def keep_rec(c, rec):
        got["rec"] = rec
        t = time.perf_counter()
        out = per_layer(c, rec)
        got["seconds"]["readers"] = time.perf_counter() - t
        return out

    tracing.load, run.per_layer = keep_load, keep_rec
    try:
        result = run.run_cell(cell, seed=seed, seconds=seconds, trace=True,
                              t_start=T_START)
    finally:
        tracing.load, run.per_layer = load, per_layer
    result["record_seconds"] = got["seconds"]
    return result, got["rec"], got["named"]


def annotation_offsets(rec, host) -> list:
    """Engine-clock time of the trace clock's zero at each ``clock``
    annotation: the recorder's span carrying the same ``recorder_us``."""
    at = sorted((e["args"]["recorder_us"], e["ts"]) for e in rec["spans"]
                if e["name"] == "clock" and e["ph"] == "B")
    out = []
    for s, _, name, stats in host:
        if name == "clock" and at:
            us = float(stats.get("recorder_us", -1))
            ts = min(at, key=lambda x: abs(x[0] - us))
            if abs(ts[0] - us) < 1.0:
                out.append(ts[1] - s / 1e9)
    return out


def ops_by_program(trace, scopes, top: int = 8) -> dict:
    """Per program class: its device seconds, its top ops (base name,
    opcode, scope) by seconds, and seconds by scope; averaged over
    chips."""
    classes = tracing.groups()["programs"]
    out: dict = {}
    n = max(len(trace.devices), 1)
    for d in trace.devices:
        scope = dict(zip(d.ops, scopes.get(d.name, [])))
        for m, inside in tracing.module_ops(d):
            c = out.setdefault(tracing.program_class(m, inside, classes),
                               {"s": 0.0, "ops": {}, "scopes": {}})
            c["s"] += (m[1] - m[0]) / 1e9 / n
            for o in inside:
                if o[3] in tracing.CONTAINERS:
                    continue
                sc = scope.get(o, "")
                k = f"{o[2]} ({o[3]}) [{sc}]"
                c["ops"][k] = c["ops"].get(k, 0.0) + (o[1] - o[0]) / 1e9 / n
                c["scopes"][sc] = c["scopes"].get(sc, 0.0) \
                    + (o[1] - o[0]) / 1e9 / n
    for c in out.values():
        c["ops"] = sorted(c["ops"].items(), key=lambda x: -x[1])[:top]
    return out


def copy_scopes(trace, scopes) -> dict:
    """Inside chunk programs: seconds of ``copy`` ops by scope ("" none),
    averaged over chips."""
    classes = tracing.groups()["programs"]
    out: dict = {}
    for d in trace.devices:
        row = scopes.get(d.name, [])
        scope = dict(zip(d.ops, row))
        for m, inside in tracing.module_ops(d):
            if tracing.program_class(m, inside, classes) != "prefill_chunk":
                continue
            for o in inside:
                if o[3] == "copy":
                    k = scope.get(o, "")
                    out[k] = out.get(k, 0.0) + (o[1] - o[0]) / 1e9
    n = max(len(trace.devices), 1)
    return {k: v / n for k, v in out.items()}


def overlapping_spans(spans, a: float, b: float) -> list:
    """The engine's span events, minus the recorder-only ones, of every
    span that overlaps [a, b), plus the ``request`` begins of the requests
    finalized in it; in their order."""
    keep, stack = set(), []
    for i, e in enumerate(spans):
        if e["ph"] not in ("B", "E") or e["name"] in phases.NOT_PHASES:
            continue
        if e["ph"] == "B":
            stack.append(i)
        elif stack:
            j = stack.pop()
            if spans[j]["ts"] < b and e["ts"] > a:
                keep.update((i, j))
    uids = {spans[i]["tid"] for i in keep if spans[i]["name"] == "finalize"}
    return [e for i, e in enumerate(spans) if i in keep
            or (e["name"] == "request" and e["ph"] == "B"
                and e["tid"] in uids)]


def make_slice(rec, nm, offset: float) -> dict | None:
    """Chip 0 from ``SLICE_BEFORE_NS`` before the shortest whole finalize
    program of the stretch to ``SLICE_AFTER_NS`` after it: its ops (with
    scopes), programs and the engine spans over it, all times from the
    slice's start (ops in ns, spans in s); plus the whole stretch's
    program ends and blocking syncs, for the clock offset."""
    d = rec["trace"].devices[0]
    classes = tracing.groups()["programs"]
    fin = [m for m, inside in tracing.module_ops(d)
           if tracing.program_class(m, inside, classes) == "finalize"
           and any(o[2] == "lookahead_score" for o in inside)]
    if not fin:
        return None
    m = min(fin, key=lambda x: x[1] - x[0])
    lo, hi = m[0] - SLICE_BEFORE_NS, m[1] + SLICE_AFTER_NS
    scope = dict(zip(d.ops, nm["scopes"].get(d.name, [])))
    syncs = [[s, e] for s, e, n in phases.phase_intervals(rec["spans"])
             if n in phases.SYNCS]
    # the spans reach back to the program's dispatch, for its request
    fin_ts = max((e["ts"] for e in rec["spans"] if e["name"] == "finalize"
                  and e["ph"] == "B" and e["ts"] <= m[0] / 1e9 + offset),
                 default=lo / 1e9 + offset)
    while True:
        a, b = lo / 1e9 + offset, hi / 1e9 + offset
        out = {"about": "chip 0 of a traced run on a TPU v5e around its "
                        "shortest whole finalize program: ops (start ns, "
                        "end ns, base name, opcode, scope) and programs, "
                        "and the engine's spans over them (s), from the "
                        "slice's start; full_stretch: the whole traced "
                        "stretch's program ends (trace clock, s), blocking "
                        "syncs (engine clock, s) and the offset the clock "
                        "annotations gave",
               "workload": rec.get("workload"), "window_s": (hi - lo) / 1e9,
               "ops": [[o[0] - lo, o[1] - lo, o[2], o[3], scope.get(o, "")]
                       for o in d.ops if lo <= o[0] and o[1] <= hi],
               "modules": [[x[0] - lo, x[1] - lo, x[2], x[3]]
                           for x in d.modules if lo <= x[0] and x[1] <= hi],
               "spans": [dict(e, ts=e["ts"] - a) for e in overlapping_spans(
                   rec["spans"], min(a, fin_ts), b)],
               "serving": rec["serving"], "chips": rec["chips"],
               "peak": rec["peak"], "config": {
                   k: rec["config"][k] for k in (
                       "num_hidden_layers", "hidden_size",
                       "intermediate_size", "num_attention_heads",
                       "num_key_value_heads", "vocab_size", "lookahead")},
               "full_stretch": {
                   "trace_span": list(rec["trace_span"]),
                   "module_ends_s": [x[1] / 1e9 for x in d.modules],
                   "syncs": syncs, "offset_s": offset}}
        if len(json.dumps(out)) <= SLICE_MAX_BYTES or hi - m[1] < 1e6:
            return out
        hi -= max((hi - m[1]) // 2, 500_000)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", required=True)
    ap.add_argument("--slice", default=None)
    args = ap.parse_args(argv)
    cell = cells.load_cell(ROOT, args.workload)
    try:
        result, rec, nm = traced_run(cell, args.seed, args.seconds)
    except run.NoChip as e:
        run.log(f"record: {e}")
        return 2
    wall = time.perf_counter() - T_START
    tr = rec["trace"]
    exact = annotation_offsets(rec, nm["host"])
    off = sum(exact) / len(exact) if exact else None
    ttft = [(r["first_token_s"] - r["arrival_s"]) * 1e3
            for r in rec["requests"]]
    out = {"workload": args.workload, "seed": args.seed,
           "wall_s_to_metrics": wall, "result": result,
           "ttft_p50_ms_traced": run.pct(ttft, 50) if ttft else None,
           "hlo_programs": nm["hlo_programs"],
           "scoped_ops": nm["scoped_ops"], "matched": nm["matched"],
           "module_names": sorted({m[2] for d in tr.devices
                                   for m in d.modules})[:5],
           "annotations": len(nm["host"]),
           "offset_from_clock_annotations_s": exact,
           "offset_from_syncs_s": [phases.clock_offset_s(rec, d)
                                   for d in tr.devices],
           "idle_s": tr.window_s - tracing.busy_s(tr),
           "idle_by_phase_syncs": phases.idle_by_phase(rec),
           "labelled_gaps_syncs": phases.labelled_gaps(rec, top=20),
           "kv_copy_share_prefill_pct": named.kv_copy_share_pct(
               tr, nm["scopes"]),
           "chunk_copy_s_by_scope": copy_scopes(tr, nm["scopes"]),
           "ops_by_program": ops_by_program(tr, nm["scopes"])}
    if off is not None:
        by_rec = {}
        for d in tr.devices:
            for k, v in phases.idle_by_phase_s(rec, d, off).items():
                by_rec[k] = by_rec.get(k, 0.0) + v / len(tr.devices)
        out["idle_by_phase_clock"] = by_rec
        out["labelled_gaps_clock"] = phases.labelled_gaps(rec, top=20,
                                                          offset=off)
        if args.slice:
            sl = make_slice(dict(rec, workload=args.workload), nm, off)
            if sl is not None:
                Path(args.slice).write_text(json.dumps(sl))
                out["slice_bytes"] = len(json.dumps(sl))
    Path(args.out).parent.mkdir(parents=True, exist_ok=True)
    Path(args.out).write_text(json.dumps(out, indent=1, default=str))
    run.log(json.dumps({k: out[k] for k in (
        "wall_s_to_metrics", "ttft_p50_ms_traced", "offset_from_syncs_s",
        "offset_from_clock_annotations_s", "idle_s",
        "idle_by_phase_syncs", "kv_copy_share_prefill_pct")}, default=str))
    print(json.dumps(result["metrics"]), flush=True)
    return 0


if __name__ == "__main__":
    os.environ.setdefault("TPU_STDERR_LOG_LEVEL", "3")
    sys.exit(main())
