"""The engine's host phases on the device trace's clock.

With a recorder attached, the serving loop runs every host stretch under a
named phase (``repro.obs.trace``): ``schedule``, ``prefill_chunk`` and
``finalize`` (dispatches), ``admit_sync``, ``place``, ``first_token``,
``append_blocks``, ``decode_chunk`` (dispatch), ``token_sync``,
``collect``, ``wait_arrival`` and the rest.  The record of a traced run
holds them on the engine clock (``rec["spans"]``, seconds); the device
ops (``rec["trace"]``) lie on each chip's trace clock (ns from the
profiler session's start).  This module finds the offset between the two
clocks and charges each stretch in which a chip ran no op to the innermost
phase the engine was in then ("host" where it was in none).

The offset comes from the blocking syncs (``token_sync``, ``admit_sync``):
the host leaves one a steady wake-up latency after the program it waited
on has ended on the device, so for the right offset most sync ends fall
that latency after some program's end, while a wrong offset lines them up
only by chance: the offset is the millisecond of (sync end - program end)
that the most syncs reach, and the median of the pairs in it.  It includes the usual
latency: on a TPU v5e the decode chunk's token sync wakes about 2.1 ms
after its program ends (against the profiler's own ``clock``
annotations), so the phases land about that much late; the total of idle
under phases hardly moves, the names of short gaps shift to the next
phase.  The annotations, which ``tracing.load`` does not keep, give the
exact offset (``record.py``).
"""

from __future__ import annotations

import numpy as np

from chipbench import tracing

# the engine thread's spans that are also profiler annotations
PHASES = ("schedule", "prefix_probe", "prefill_chunk", "admit", "finalize",
          "admit_sync", "place", "first_token", "evict_sweep", "paged_sweep",
          "append_blocks", "decode_chunk", "token_sync", "collect", "harvest",
          "drift_eval", "wait_arrival", "clock")
SYNCS = ("token_sync", "admit_sync")
WAIT = "wait_arrival"  # the engine sleeps: no work is due
# recorder-only spans (opened and closed in different calls) and the clock
# marker are not phases
NOT_PHASES = ("request", "decode", "clock")
MIN_BLOCK_S = 5e-4  # a sync this long waited on the device
MIN_SYNCS = 8  # fewer blocking syncs in the stretch cannot fix the offset
MAX_LEAD_S = 10.0  # the trace clock's zero precedes the stretch by at most
BIN_S = 1e-4  # offsets are counted in bins this wide ...
WINDOW_BINS = 10  # ... and the densest run of this many bins is taken


def phase_intervals(spans) -> list:
    """(start, end, name) of every phase the recorder closed, on the engine
    clock, ordered by start and outer before inner.  The phases nest: they
    are opened and closed in turn on the engine thread."""
    out, stack = [], []
    for e in spans:
        if e["ph"] not in ("B", "E") or e["name"] in NOT_PHASES:
            continue
        if e["ph"] == "B":
            stack.append((e["ts"], e["name"]))
        elif stack:
            ts, name = stack.pop()
            out.append((ts, e["ts"], name))
    return sorted(out, key=lambda p: (p[0], -p[1]))


def innermost(phases) -> list:
    """Non-overlapping (start, end, name) pieces naming the innermost phase
    at each moment that some phase covers, in order."""
    out, stack, t = [], [], None  # stack of (end, name)

    def emit(t0, t1, name):
        if t1 > t0:
            out.append((t0, t1, name))

    for s, e, name in phases:
        while stack and stack[-1][0] <= s:
            end, inner = stack.pop()
            emit(t, end, inner)
            t = max(t, end)
        if stack:
            emit(t, s, stack[-1][1])
        stack.append((e, name))
        t = s
    while stack:
        end, inner = stack.pop()
        emit(t, end, inner)
        t = max(t, end)
    return out


def clock_offset_s(rec, dev: tracing.Device):
    """Engine-clock time of ``dev``'s trace-clock zero, or None when the
    traced stretch holds too few blocking syncs or no offset lines most of
    them up with a program end."""
    a, b = rec["trace_span"]
    ends = [e for s, e, name in phase_intervals(rec["spans"])
            if name in SYNCS and e - s >= MIN_BLOCK_S and a <= e <= b]
    m = np.sort(np.asarray([x[1] for x in dev.modules], np.float64)) / 1e9
    if len(ends) < MIN_SYNCS or m.size == 0:
        return None
    lo = a - MAX_LEAD_S  # offsets searched: [a - MAX_LEAD_S, b]
    cands = []
    for e in ends:
        i, j = np.searchsorted(m, [e - b, e - lo])
        cands.append(e - m[i:j])
    # per window of WINDOW_BINS bins: how many syncs have a candidate in
    # it (a sync is counted once, however many programs ended just before)
    nbins = int((b - lo) / BIN_S) + 2
    diff = np.zeros(nbins + 1, np.int64)
    for c in cands:
        if c.size == 0:
            continue
        last = np.unique(((c - lo) / BIN_S).astype(np.int64))
        first = np.maximum(last - WINDOW_BINS + 1, 0)
        new = np.concatenate([[True], first[1:] > last[:-1] + 1])
        ends_at = np.concatenate([last[np.nonzero(new)[0][1:] - 1], last[-1:]])
        np.add.at(diff, first[new], 1)
        np.add.at(diff, ends_at + 1, -1)
    run = np.cumsum(diff)
    k = int(np.argmax(run))
    if run[k] < max(MIN_SYNCS, len(ends) // 2):
        return None
    left = lo + k * BIN_S
    c = np.concatenate(cands)
    return float(np.median(c[(c >= left) & (c < left + WINDOW_BINS * BIN_S)]))


def _charge(gaps, pieces) -> dict:
    """Seconds of the (start, end) gaps under each piece's name, and under
    "host" where no piece covers them; both lists ordered, times in ns."""
    out: dict = {}
    i = 0
    for gs, ge in gaps:
        covered = 0.0
        while i < len(pieces) and pieces[i][1] <= gs:
            i += 1
        j = i
        while j < len(pieces) and pieces[j][0] < ge:
            s, e, name = pieces[j]
            d = min(e, ge) - max(s, gs)
            if d > 0:
                out[name] = out.get(name, 0.0) + d / 1e9
                covered += d
            j += 1
        if ge - gs > covered:
            out["host"] = out.get("host", 0.0) + (ge - gs - covered) / 1e9
    return out


def _pieces_ns(rec, offset: float) -> list:
    return [((s - offset) * 1e9, (e - offset) * 1e9, name)
            for s, e, name in innermost(phase_intervals(rec["spans"]))]


def idle_by_phase_s(rec, dev: tracing.Device, offset: float) -> dict:
    """Seconds of the traced stretch in which ``dev`` ran no op, by the
    innermost engine phase of the moment ("host": none)."""
    a, b = rec["trace_span"]
    gaps = tracing.gaps_ns([(o[0], o[1]) for o in dev.ops],
                           (a - offset) * 1e9, (b - offset) * 1e9)
    return _charge(gaps, _pieces_ns(rec, offset))


def clock_offsets(rec):
    """``clock_offset_s`` of each chip, or None when no chip's clock can be
    placed.  A chip whose own syncs do not place its clock takes the
    median of the others' offsets: the chips' trace clocks are aligned to
    one host clock."""
    offs = [clock_offset_s(rec, d) for d in rec["trace"].devices]
    found = [o for o in offs if o is not None]
    if not found:
        return None
    mid = float(np.median(found))
    return [mid if o is None else o for o in offs]


def idle_by_phase(rec):
    """{phase: idle seconds}, averaged over the chips, or None when no
    chip's clock can be placed (``clock_offsets``)."""
    tr = rec["trace"]
    offs = clock_offsets(rec)
    if offs is None:
        return None
    tot: dict = {}
    for d, off in zip(tr.devices, offs):
        for k, v in idle_by_phase_s(rec, d, off).items():
            tot[k] = tot.get(k, 0.0) + v / len(tr.devices)
    return tot


def host_idle_pct(rec):
    """Share (%) of the traced stretch in which a chip ran no op while the
    engine was in a phase other than ``wait_arrival``: device idle that
    the host's own work caused, averaged over the chips."""
    by = idle_by_phase(rec)
    window = rec["trace"].window_s
    if by is None or window <= 0:
        return None
    return 100.0 * sum(v for k, v in by.items()
                       if k not in ("host", WAIT)) / window


def labelled_gaps(rec, top: int = 10, offset=None):
    """``tracing.breakdown``'s idle gaps (chip 0's longest, between its
    first and last op), each named after the innermost phase that covers
    most of it, or "host" where none covers it; None when the chip's clock
    cannot be placed.  ``offset`` (engine-clock time of the trace clock's
    zero) defaults to ``clock_offset_s``'s."""
    tr = rec["trace"]
    if not tr.devices or not tr.devices[0].ops:
        return None
    d = tr.devices[0]
    off = clock_offset_s(rec, d) if offset is None else offset
    if off is None:
        return None
    iv = [(o[0], o[1]) for o in d.ops]
    gaps = tracing.gaps_ns(iv, min(s for s, _ in iv), max(e for _, e in iv))
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:top]
    pieces = _pieces_ns(rec, off)
    out = []
    for g in gaps:
        by = _charge([g], pieces)
        name = max((k for k in by if k != "host"), key=by.get,
                   default="host")
        out.append([name, (g[1] - g[0]) / 1e9])
    return out
