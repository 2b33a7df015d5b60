"""Shared arithmetic of the per-layer metric readers.

A reader gets the run record ``rec`` that ``run.py`` builds in a traced
run: the served requests, the engine's request spans on the engine clock
(``obs.trace`` events, seconds), the reduced device trace
(``tracing.Trace``) with the engine-clock span it covers, and the cell's
widths (its architecture module's ``widths``, which gives every count),
serving settings, chip count and device peaks.
"""

from __future__ import annotations

import numpy as np

from chipbench import flops, tracing


def in_span(rec, t: float) -> bool:
    a, b = rec["trace_span"]
    return a <= t < b


def request_starts(rec) -> dict:
    """uid -> engine-clock start of its first ``request`` span (when its
    prefill began), and uid -> prompt length."""
    start, n_prompt = {}, {}
    for e in rec["spans"]:
        if e["ph"] == "B" and e["name"] == "request":
            uid = e["args"]["uid"]
            start.setdefault(uid, e["ts"])
            n_prompt[uid] = e["args"]["n_prompt"]
    return start, n_prompt


def queue_waits_ms(rec) -> list:
    start, _ = request_starts(rec)
    return [(start[r["uid"]] - r["arrival_s"]) * 1e3
            for r in rec["requests"] if r["uid"] in start]


def prefill_chunks(rec) -> list:
    """(start row, real rows) of each prefill chunk dispatched inside the
    traced span."""
    _, n_prompt = request_starts(rec)
    chunk = int(rec["serving"]["chunk"])
    out = []
    for e in rec["spans"]:
        if e["ph"] == "B" and e["name"] == "prefill_chunk" \
                and in_span(rec, e["ts"]):
            uid = int(e["tid"].split(":", 1)[1])
            s = int(e["args"]["s"])
            out.append((s, max(0, min(chunk, n_prompt[uid] - s))))
    return out


def decode_chunks(rec) -> list:
    """(time, steps, slots, mean cached rows of the live requests) of each
    decode chunk dispatched inside the traced span; the rows of a request
    are its kept prompt rows plus the tokens it has generated by then."""
    budget = int(rec["serving"]["budget"])
    reqs = rec["requests"]
    out = []
    for e in rec["spans"]:
        if e["ph"] == "B" and e["name"] == "decode_chunk" \
                and in_span(rec, e["ts"]):
            t = e["ts"]
            rows = []
            for r in reqs:
                a, b = r["first_token_s"], r["finish_s"]
                if a <= t < b:
                    done = (t - a) / max(b - a, 1e-9) * r["n_out"]
                    rows.append(min(r["n_prompt"], budget) + done)
            slots = int(e["args"]["slots"])
            out.append((t, int(e["args"]["steps"]), slots,
                        float(np.mean(rows)) if rows else float(budget)))
    return out


def model_flops(rec) -> float:
    """Model operations dispatched in the traced span: prompt rows through
    the whole model (attention over their causal prefix, logits of each
    chunk's last row) and generated tokens (attention over their cache)."""
    w = rec["widths"]
    total = sum(w.prefill_chunk_flops(s, rows)
                for s, rows in prefill_chunks(rec) if rows > 0)
    for _, steps, slots, keys in decode_chunks(rec):
        total += steps * slots * w.decode_token_flops(keys)
    return total


def mfu_pct(rec):
    peak = rec["peak"]
    tr = rec["trace"]
    if peak is None or not tr.devices:
        return None
    work = model_flops(rec)
    if work <= 0:
        return None
    return 100.0 * work / (tr.window_s * rec["chips"] * peak["bf16_flops"])


def idle_pct(rec):
    tr = rec["trace"]
    if not tr.devices or tr.window_s <= 0:
        return None
    return 100.0 * max(0.0, 1.0 - tracing.busy_s(tr) / tr.window_s)


def chunk_attn_roofline_pct(rec):
    """Least time at the chip's peaks for the chunk-attention calls the
    traced span's prefill chunks needed (one per layer), over the summed
    device time of the kernel's ops."""
    tr, peak = rec["trace"], rec["peak"]
    g = tracing.groups()
    spent = tracing.kernel_time_s(tr, g["kernels"]["chunk_attn"],
                                  g["programs"])
    if peak is None or spent <= 0:
        return None
    w = rec["widths"]
    least = 0.0
    for s, rows in prefill_chunks(rec):
        if rows > 0:
            least += flops.calls_least_s(
                w.chunk_attn_call(s, rows, rec["chips"]), peak)
    return 100.0 * least / spent if least > 0 else None


def program_share_pct(rec, part: str, whole) -> float | None:
    t = tracing.program_time_s(rec["trace"], tracing.groups()["programs"])
    den = sum(t.get(c, 0.0) for c in whole)
    if den <= 0 or part not in t:
        return None
    return 100.0 * t[part] / den


def collective_pct(rec):
    tr = rec["trace"]
    busy = tracing.busy_s(tr)
    if busy <= 0:
        return None
    spent = tracing.op_time_s(tr, tracing.groups()["collectives"])
    return 100.0 * spent / busy if spent > 0 else None
