"""Named kernels and scopes in the device trace.

Each Pallas kernel runs as a custom call named after the kernel
(``chunk_attention``, ``chunk_attention_masses``, ``lookahead_score``,
``flash_attention``, ``decode_attention``, ``paged_attention``,
``paged_attention_masses``): that name is the op's base name in
``tracing``'s tags, so the reduced trace finds a kernel by it.

Scopes (``kv_head_major``, ``kv_buffer_write``, ``lookahead_rows``,
``lookahead_scores``, ``topk_evict``) do not reach an op's name: XLA names
a fused op after its opcodes.  They are in the ``op_name`` metadata of the
op's instruction, which the profile keeps only in each program's HLO, and
``tracing.load`` reads neither; ``load_scopes`` reads them, and the engine
thread's annotations, from the profile itself (``record.py``).
"""

from __future__ import annotations

import bisect
from pathlib import Path

from chipbench import flops, phases, reduce, tracing

SCOPES = ("kv_head_major", "kv_buffer_write", "lookahead_rows",
          "lookahead_scores", "topk_evict")
KV_COPIES = ("kv_head_major", "kv_buffer_write")
F32 = 4  # bytes


def lookahead_score_call(w: flops.Widths, n_prompt: int, n_obs: int,
                         chips: int = 1) -> tuple[float, float]:
    """(operations, bytes) of one ``lookahead_score`` call (one layer, one
    chip): QK^T of the ``n_obs`` lookahead rows of every head over the
    ``n_prompt`` prompt keys; it must read q and K once and write the f32
    scores.  The kernel's second pass over K (the masses, against the
    final softmax statistics) is not counted: the algorithm needs one."""
    h = w.heads // chips
    kv = w.kv_heads // chips
    ops = 2.0 * n_obs * h * w.head_dim * n_prompt
    nbytes = (flops.BF16 * (n_obs * h + n_prompt * kv) * w.head_dim
              + F32 * h * n_prompt)
    return ops, nbytes


def finalize_dispatches(rec) -> list:
    """(engine-clock start, prompt length) of each ``finalize`` span."""
    _, n_prompt = reduce.request_starts(rec)
    out = []
    for e in rec["spans"]:
        if e["ph"] == "B" and e["name"] == "finalize":
            uid = int(e["tid"].split(":", 1)[1])
            if uid in n_prompt:
                out.append((e["ts"], n_prompt[uid]))
    return sorted(out)


def lookahead_score_roofline_pct(rec):
    """Least time at the chip's peaks for the ``lookahead_score`` work of
    the finalize programs that ran whole in the traced stretch, over the
    named kernel's device time in them (%).  Each program's prompt length
    is its request's, through the latest ``finalize`` dispatch before the
    program started (``phases.clock_offsets``); None when no chip's clock
    can be placed."""
    tr, peak = rec["trace"], rec["peak"]
    if peak is None or not tr.devices:
        return None
    offs = phases.clock_offsets(rec)
    if offs is None:
        return None
    w = rec["widths"]
    n_obs = int(rec["config"]["lookahead"]["n_lookahead"])
    classes = tracing.groups()["programs"]
    disp = finalize_dispatches(rec)
    starts = [ts for ts, _ in disp]
    least = spent = 0.0
    for d, off in zip(tr.devices, offs):
        for m, inside in tracing.module_ops(d):
            if tracing.program_class(m, inside, classes) != "finalize":
                continue
            t = sum(o[1] - o[0] for o in inside if o[2] == "lookahead_score")
            i = _latest_before(starts, m[0] / 1e9 + off)
            if t <= 0 or i is None:
                continue
            n = disp[i][1]
            ops, nbytes = lookahead_score_call(w, n, n_obs, rec["chips"])
            least += w.layers * flops.least_time_s(ops, nbytes, peak)[0]
            spent += t / 1e9
    return 100.0 * least / spent if least > 0 and spent > 0 else None


def _latest_before(starts, t):
    """Index of the last of the ordered ``starts`` at or before ``t``."""
    i = bisect.bisect_right(starts, t) - 1
    return i if i >= 0 else None


def load_scopes(log_dir: str) -> dict:
    """What ``tracing.load`` leaves out of the newest profile under
    ``log_dir``: per TPU plane, each op's innermost scope of ``SCOPES``
    ("" for none), in the order ``tracing.load`` keeps the ops; and the
    engine thread's annotations on the host plane as (start ns, end ns,
    name, {arg: value}), the engine thread being the host line with the
    most events named after engine phases.  An op's scope is in its
    instruction's ``op_name`` metadata, which the profile keeps in the
    HLO of each program (``hlo_op_names``), not on the op's event.  No
    plane has scopes where the profile holds no program's HLO."""
    from jax.profiler import ProfileData

    path = _newest(log_dir)
    names = {prog: {i: _scope_of(op) for i, op in d.items()}
             for prog, d in hlo_op_names(path).items()}
    # where a trace names a program otherwise than its HLO does: by the
    # program's base name, an instruction whose programs disagree unscoped
    by_base: dict = {}
    for prog, d in names.items():
        m = by_base.setdefault(prog.split("(")[0], {})
        for i, sc in d.items():
            m[i] = sc if m.get(i, sc) == sc else ""
    matched = {"by_program": 0, "by_base_name": 0}

    def scope(prog, op):
        if prog in names:
            matched["by_program"] += 1
            return names[prog].get(op, "")
        matched["by_base_name"] += 1
        return by_base.get(prog.split("(")[0], {}).get(op, "")

    data = ProfileData.from_file(str(path))
    scopes, host = {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:") and names:
            mods, ops = [], []
            for line in plane.lines:
                if line.name == "XLA Modules":
                    mods += [(e.start_ns, e.end_ns, e.name)
                             for e in line.events]
                elif line.name == "XLA Ops":
                    ops += [(e.start_ns, e.name.partition(" = ")[0]
                             .strip().lstrip("%")) for e in line.events]
            scopes[plane.name] = [scope(m, op)
                                  for m, op in _in_modules(ops, mods)]
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                evs = [(e.start_ns, e.end_ns, e.name, dict(e.stats))
                       for e in line.events if e.name in phases.PHASES]
                host = max(host, evs, key=len)
    return {"scopes": scopes, "host": host,
            "hlo_programs": sorted(names)[:3] + [len(names)],
            "matched": matched,
            "scoped_ops": {k: sum(map(bool, v)) for k, v in scopes.items()}}


def _newest(log_dir: str) -> Path:
    return sorted(Path(log_dir).rglob("*.xplane.pb"),
                  key=lambda p: p.stat().st_mtime)[-1]


def _in_modules(ops, mods) -> list:
    """(enclosing program name or "", instruction) of each (start,
    instruction) op, in the ops' order."""
    mods = sorted(mods)
    starts = [m[0] for m in mods]
    out = []
    for t, op in ops:
        i = bisect.bisect_right(starts, t) - 1
        out.append((mods[i][2] if i >= 0 and t < mods[i][1] else "", op))
    return out


def _scope_of(op_name: str) -> str:
    """The innermost of ``SCOPES`` in an op name path such as
    ``jit(fn)/while/body/lookahead_rows/kv_buffer_write/dynamic_update_slice``."""
    best, at = "", -1
    for sc in SCOPES:
        i = op_name.rfind(sc + "/")
        if i > at:
            best, at = sc, i
    return best


def hlo_op_names(path) -> dict:
    """{program name: {instruction: op_name}} from the HLO that a profile
    keeps for each program (the ``/host:metadata`` plane).  The XSpace
    proto is read with the ``xplane_pb2`` that the installed TensorFlow
    ships, loaded alone; {} where there is none or the profile holds no
    program's HLO."""
    import importlib.util
    import re

    from jax._src.lib import xla_client

    tf = importlib.util.find_spec("tensorflow")
    pb = tf and Path(tf.origin).parent / "tsl/profiler/protobuf/xplane_pb2.py"
    if not pb or not pb.exists():
        return {}
    spec = importlib.util.spec_from_file_location("chipbench_xplane_pb2", pb)
    xplane = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(xplane)
    space = xplane.XSpace.FromString(Path(path).read_bytes())
    opts = xla_client._xla.HloPrintOptions()
    opts.print_metadata = True
    line = re.compile(r'^\s*(?:ROOT )?%?([\w.-]+) = [^\n]*?'
                      r'metadata=\{op_name="([^"]*)"', re.M)
    out = {}
    for plane in space.planes:
        if plane.name != "/host:metadata":
            continue
        for md in plane.event_metadata.values():
            for st in md.stats:
                module = _field(st.bytes_value, 1)  # HloProto.hlo_module
                if not module:
                    continue
                hlo = xla_client._xla.HloModule \
                    .from_serialized_hlo_module_proto(module).to_string(opts)
                out[md.name] = dict(line.findall(hlo))
    return out


def _field(buf: bytes, num: int):
    """The first length-delimited field ``num`` of a serialized proto."""
    i = 0
    while i < len(buf):
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            _, i = _varint(buf, i)
        elif wire == 1:
            i += 8
        elif wire == 5:
            i += 4
        elif wire == 2:
            n, i = _varint(buf, i)
            if key >> 3 == num:
                return buf[i:i + n]
            i += n
        else:
            return None
    return None


def _varint(buf: bytes, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        c = buf[i]
        i += 1
        out |= (c & 0x7F) << shift
        shift += 7
        if c < 0x80:
            return out, i


def kv_copy_share_pct(trace: tracing.Trace, scopes: dict):
    """Device time of ops under ``kv_head_major`` or ``kv_buffer_write``
    inside chunk and finalize programs, over those programs' device time
    (%), averaged over chips; ``scopes`` as ``load_scopes`` gives it.
    None where a chip's ops carry no scope at all: the scopes were not
    found, which must not read as no copy time."""
    classes = tracing.groups()["programs"]
    part = whole = 0.0
    for d in trace.devices:
        row = scopes.get(d.name)
        if row is None or len(row) != len(d.ops) or not any(row):
            return None
        scope = {o: s for o, s in zip(d.ops, row)}
        for m, inside in tracing.module_ops(d):
            if tracing.program_class(m, inside, classes) in (
                    "prefill_chunk", "finalize"):
                whole += m[1] - m[0]
                part += sum(o[1] - o[0] for o in inside
                            if scope.get(o) in KV_COPIES
                            and o[3] not in tracing.CONTAINERS)
    return 100.0 * part / whole if whole > 0 else None
