"""The profiler trace of a run, reduced to device intervals.

``Capture`` traces a stretch of the measured window from a side thread
(the engine's loop keeps the main thread).  ``load`` reads the ``.xplane.pb``
that ``jax.profiler`` wrote with ``jax.profiler.ProfileData`` and keeps, per
TPU, the XLA op events and the XLA module (program) events as plain
tuples, so that the per-layer metrics and the tests need no profiler.

An op event's name on the TPU is its HLO instruction
(``%sort.12 = s32[...] sort(...)``); it is kept as two tags, the
instruction's base name (``sort``) and its opcode (``sort``; a custom call
as ``custom-call:<target>``).  Grouping into programs, kernels and
collectives follows ``chipbench/layers.json`` by those tags and by the
program (XLA module) names.
"""

from __future__ import annotations

import json
import re
import threading
import time
from dataclasses import dataclass, field
from pathlib import Path

LAYERS = Path(__file__).resolve().parent / "layers.json"
# ops that hold other ops (a layer scan's loop): their time is their
# children's, so the breakdown of op time leaves them out
CONTAINERS = ("while", "call", "conditional")


@dataclass
class Device:
    """One chip's events in the traced window, times in ns on the device's
    own clock: ops as (start, end, base name, opcode) and programs as
    (start, end, module name, "") tuples."""

    name: str
    ops: list = field(default_factory=list)
    modules: list = field(default_factory=list)


@dataclass
class Trace:
    devices: list  # [Device]
    window_s: float  # host-clock length of the traced stretch

    def to_json(self) -> dict:
        return {"window_s": self.window_s,
                "devices": [{"name": d.name, "ops": d.ops,
                             "modules": d.modules} for d in self.devices]}

    @classmethod
    def from_json(cls, d: dict) -> "Trace":
        return cls(devices=[Device(x["name"],
                                   [tuple(o) for o in x["ops"]],
                                   [tuple(m) for m in x["modules"]])
                            for x in d["devices"]],
                   window_s=float(d["window_s"]))


class Capture:
    """Trace ``[start_s, start_s + dur_s)`` after ``t0`` (perf_counter)
    into ``log_dir`` from a timer thread; ``join`` waits for it."""

    def __init__(self, log_dir: str, t0: float, start_s: float,
                 dur_s: float):
        self.log_dir = log_dir
        self.t0, self.start_s, self.dur_s = t0, start_s, dur_s
        self.span = None  # (start, stop) on the engine clock
        self.error = None
        self._thread = threading.Thread(target=self._run, daemon=True)

    def start(self) -> None:
        self._thread.start()

    def _run(self) -> None:
        import jax

        try:
            wait = self.t0 + self.start_s - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            jax.profiler.start_trace(self.log_dir)
            a = time.perf_counter()
            time.sleep(max(0.0, self.t0 + self.start_s + self.dur_s - a))
            b = time.perf_counter()
            jax.profiler.stop_trace()
            self.span = (a - self.t0, b - self.t0)
        except Exception as e:  # reported by the run, which then fails
            self.error = repr(e)

    def join(self) -> None:
        self._thread.join()


_OPCODE = re.compile(r"[\]\}\)] ([a-z][a-z0-9_\-]*)\(")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')


def tags(hlo: str) -> tuple[str, str]:
    """(base name, opcode) of an HLO instruction's text."""
    head, _, rest = hlo.partition(" = ")
    base = re.sub(r"\.\d+$", "", head.strip().lstrip("%"))
    m = _OPCODE.search(rest)
    kind = m.group(1) if m else ""
    if kind == "custom-call":
        t = _TARGET.search(rest)
        kind += ":" + (t.group(1) if t else "")
    return base, kind


def load(log_dir: str, window_s: float, chips: int = 1) -> Trace:
    """Device events of the newest ``.xplane.pb`` under ``log_dir``.  A
    chip on which no op ran in the traced stretch has no plane: it is kept
    as a device with no events."""
    from jax.profiler import ProfileData

    files = sorted(Path(log_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    data = ProfileData.from_file(str(files[-1]))
    devices = []
    for plane in data.planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        dev = Device(plane.name)
        seen: dict = {}  # an instruction repeats: parse its text once
        for line in plane.lines:
            if line.name == "XLA Ops":
                for e in line.events:
                    t = seen.get(e.name)
                    if t is None:
                        t = seen[e.name] = tags(e.name)
                    dev.ops.append((e.start_ns, e.end_ns) + t)
            elif line.name == "XLA Modules":
                dev.modules = [(e.start_ns, e.end_ns, e.name, "")
                               for e in line.events]
        devices.append(dev)
    while len(devices) < chips:
        devices.append(Device(f"/device:TPU:{len(devices)} (no events)"))
    return Trace(devices=devices, window_s=window_s)


def union_ns(intervals) -> int:
    """Length of the union of (start, end) intervals."""
    total, cur_s, cur_e = 0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps_ns(intervals, lo: int, hi: int) -> list:
    """Idle gaps (start, end) between the union of intervals in [lo, hi)."""
    out, at = [], lo
    for s, e in sorted(intervals):
        if s > at:
            out.append((at, min(s, hi)))
        at = max(at, e)
    if at < hi:
        out.append((at, hi))
    return [(s, e) for s, e in out if e > s]


def groups() -> dict:
    return json.loads(LAYERS.read_text())


def matches(op, patterns) -> bool:
    """An op matches a pattern equal to its base name or its opcode."""
    return op[2] in patterns or op[3] in patterns


def busy_s(trace: Trace) -> float:
    """Seconds with an op running, averaged over the chips."""
    if not trace.devices:
        return 0.0
    return sum(union_ns([(o[0], o[1]) for o in d.ops])
               for d in trace.devices) / len(trace.devices) / 1e9


def op_time_s(trace: Trace, patterns) -> float:
    """Device seconds of ops matching any pattern, averaged over chips."""
    if not trace.devices:
        return 0.0
    return sum(o[1] - o[0] for d in trace.devices for o in d.ops
               if matches(o, patterns)) / len(trace.devices) / 1e9


def module_ops(dev: Device) -> list:
    """[(module, [ops inside its interval])] for one device."""
    ops = sorted(dev.ops)
    out, i = [], 0
    for m in sorted(dev.modules):
        while i < len(ops) and ops[i][0] < m[0]:
            i += 1
        j = i
        inside = []
        while j < len(ops) and ops[j][0] < m[1]:
            inside.append(ops[j])
            j += 1
        out.append((m, inside))
    return out


def program_class(module, ops_inside, classes: dict) -> str:
    """The first class (in file order) whose module-name prefix the program
    has and one of whose op patterns (if it lists any) an op inside it
    matches; "other" when none does."""
    for cls, rule in classes.items():
        if not module[2].startswith(rule["module"]):
            continue
        if not rule["ops"] or any(matches(o, rule["ops"]) for o in ops_inside):
            return cls
    return "other"


def program_time_s(trace: Trace, classes: dict) -> dict:
    """Device seconds per program class (the durations of the program's
    executions), averaged over chips."""
    out: dict = {}
    n = max(len(trace.devices), 1)
    for d in trace.devices:
        for m, inside in module_ops(d):
            c = program_class(m, inside, classes)
            out[c] = out.get(c, 0.0) + (m[1] - m[0]) / 1e9
    return {k: v / n for k, v in out.items()}


def kernel_time_s(trace: Trace, rule: dict, classes: dict) -> float:
    """Device seconds of the ops matching ``rule["ops"]`` inside programs
    of class ``rule["program"]``, averaged over chips: a kernel the trace
    does not name, found by the program it runs in."""
    total = 0.0
    for d in trace.devices:
        for m, inside in module_ops(d):
            if program_class(m, inside, classes) == rule["program"]:
                total += sum(o[1] - o[0] for o in inside
                             if matches(o, rule["ops"]))
    return total / max(len(trace.devices), 1) / 1e9


def breakdown(trace: Trace, top: int = 10) -> dict:
    """Top device ops by summed time, and the longest idle gaps, labelled
    "host" (the engine's phases are not on the profiler's clock)."""
    tot: dict = {}
    for d in trace.devices:
        for o in d.ops:
            if o[3] in CONTAINERS:
                continue
            k = f"{o[2]} ({o[3]})"
            tot[k] = tot.get(k, 0) + (o[1] - o[0])
    n = max(len(trace.devices), 1)
    ops = sorted(tot.items(), key=lambda x: -x[1])[:top]
    gaps = []
    for d in trace.devices[:1]:
        iv = [(o[0], o[1]) for o in d.ops]
        if iv:
            lo = min(s for s, _ in iv)
            hi = max(e for _, e in iv)
            gaps = sorted(gaps_ns(iv, lo, hi), key=lambda g: g[0] - g[1])
    return {"device_ops": [[k, v / n / 1e9] for k, v in ops],
            "idle_gaps": [["host", (e - s) / 1e9] for s, e in gaps[:top]]}
