"""Chunk attention of the windowed layers: least time at the chip's peaks
for the calls the traced prefill chunks needed on the sliding layers (each
query over the keys its window shows; the architecture module's
``window_attn_call``), over the device time of the chunk programs'
``chunk_attention_window`` calls (%).  A chunk program is one that runs no
``lookahead_score`` (the finalize program runs the lookahead rows' windowed
attention too).  None where the cell has no windowed layer or the trace no
such call."""

from chipbench import flops, reduce, tracing


def read(rec):
    tr, peak, w = rec["trace"], rec["peak"], rec["widths"]
    if peak is None or not tr.devices or not hasattr(w, "window_attn_call"):
        return None
    spent = 0.0
    for d in tr.devices:
        for _, inside in tracing.module_ops(d):
            if any(o[2] == "lookahead_score" for o in inside):
                continue
            spent += sum(o[1] - o[0] for o in inside
                         if o[2] == "chunk_attention_window")
    spent = spent / len(tr.devices) / 1e9
    least = sum(flops.calls_least_s(w.window_attn_call(s, rows, rec["chips"]),
                                    peak)
                for s, rows in reduce.prefill_chunks(rec) if rows > 0)
    return 100.0 * least / spent if least > 0 and spent > 0 else None
