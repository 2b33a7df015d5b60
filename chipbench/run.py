"""Run one benchmark cell once on the chips of this machine.

    python3 chipbench/run.py --workload qwen2-1.5b.longctx --seed 7 \
        --seconds 51 --trace 0

Set-up (timed as ``setup_s``, from process start): find a TPU with the
cell's chip count (exit 2 without one), turn on the persistent compile
cache, make the weights on the device from the seed, build the serving
engine the cell's files describe, and serve one warm-up request for every
compiled shape the cell's traffic reaches.  Then the cell's requests, due
over ``[0, seconds)`` on the engine's clock, go through
``ContinuousEngine.run``, which serves every one of them to completion.

End-to-end metrics (``--trace 0``) come from the requests' own stamps:
TTFT from each request's due time to its first token, TPOT as
(finish - first token) / (tokens - 1).  Per-layer metrics (``--trace 1``)
come from a profiler trace of 8 s of the window and the engine's request
spans, through ``chipbench/metrics/<name>.py``.

After the window the engine is freed and a sample of the served requests,
the longest among them, is recomputed by the float32 reference
(``reference.py``); the run is correct when every request finished at its
length, nothing compiled inside the window, and the mean gap by which the
served tokens' logits lie below the reference's best is within its limit
(``chipbench/limits/<cell>.json``).  The last line of standard output is
one JSON object.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

import numpy as np  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

from chipbench import cells, flops, loadgen, tracing  # noqa: E402

TRACE_START = 0.4  # traced stretch: from 40% of the window ...
TRACE_SECONDS = 8.0  # ... for 8 s (or to the window's end)
WARM_NEW = 16  # warm-up output length: decode chunks of 8, 4, 2 and 1


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class NoChip(RuntimeError):
    pass


def devices_for(chips: int, require_tpu: bool):
    import jax

    devs = jax.devices()
    if require_tpu and (devs[0].platform != "tpu" or len(devs) < chips):
        raise NoChip(f"cell needs {chips} TPU chip(s); JAX found "
                     f"{len(devs)} {devs[0].platform} device(s)")
    if len(devs) < chips:
        raise NoChip(f"cell needs {chips} devices, found {len(devs)}")
    return devs[:chips]


def enable_cache() -> None:
    """The program's persistent compile cache (inside the checkout, or
    ``$JAX_COMPILATION_CACHE_DIR``), keeping every program however quick
    its compile, so that only a checkout's first run compiles."""
    import jax

    from repro.common.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class CompileCounter:
    """Counts backend compiles (persistent-cache misses) while armed."""

    def __init__(self):
        import jax

        self.armed = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, secs: float, **_) -> None:
        if self.armed and event == "/jax/core/compile/backend_compile_duration":
            self.count += 1


def build_engine(cell, cfg, params, lkv, mesh, max_new: int):
    from repro.common.config import EvictionConfig
    from repro.serving import (ChunkingConfig, ContinuousEngine,
                               DecodeEvictionConfig, KVBlockPool,
                               ServingConfig)

    s, sv = cell.config["serving"], cell.traffic["serving"]
    interval = sv.get("decode_evict_interval")
    pool = KVBlockPool(cfg, block_size=int(s["block_size"]),
                       pool_mb=float(sv["pool_mb"]), mesh=mesh)
    sc = ServingConfig(
        policy=s["policy"], evict=EvictionConfig(budget=int(s["budget"])),
        decode_evict=DecodeEvictionConfig(enabled=interval is not None,
                                          interval=int(interval or 64)),
        chunking=ChunkingConfig(chunk=int(s["chunk"]),
                                max_context=int(sv["max_context"]),
                                decode_chunk=int(s["decode_chunk"])),
        num_slots=int(sv["num_slots"]), max_new_tokens=max_new, eos_id=-1,
        kv_pool=pool, mesh=mesh, sync_timers=False)
    return ContinuousEngine(params, cfg, sc, lkv_params=lkv)


def shape_keys(cell, cfg):
    """(rung(n), blocks(n)) of a prompt of n tokens: the KV-buffer depth
    the engine streams it into (chunk * 2^k covering prompt + lookahead
    rows, at least the base rung) and the blocks of its admitted cache —
    the two things that select compiled programs."""
    s, sv = cell.config["serving"], cell.traffic["serving"]
    chunk, budget, bs = int(s["chunk"]), int(s["budget"]), int(s["block_size"])
    n_obs = cfg.lookahead.n_lookahead

    def ladder(need):
        r = chunk
        while r < need:
            r *= 2
        return r

    base = ladder(max(int(sv["max_context"]), budget))

    def rung(n):
        need = -(-(n + n_obs) // chunk) * chunk
        return max(ladder(need), base)

    def blocks(n):
        return -(-min(n, budget) // bs)

    return rung, blocks


def requests_of(specs):
    from repro.serving import Request

    return [Request(uid=s.uid, prompt=s.prompt, max_new_tokens=s.max_new_tokens,
                    arrival_s=s.arrival_s) for s in specs]


def pct(x, q) -> float:
    return float(np.percentile(np.asarray(x, np.float64), q))


def end_to_end(cell, done, seconds, setup_s) -> dict:
    ttft = [r.ttft_s * 1e3 for r in done]
    tpot = [r.tpot_s * 1e3 for r in done if len(r.out_tokens) > 1]
    val = {"setup_s": setup_s}
    if ttft:
        val["ttft_p50_ms"] = pct(ttft, 50)
        val["ttft_p95_ms"] = pct(ttft, 95)
    if tpot:
        val["tpot_p95_ms"] = pct(tpot, 95)
    # tokens emitted inside the window, each request's tokens spread
    # evenly between its first token and its finish
    out = 0.0
    for r in done:
        n = len(r.out_tokens)
        a, b = r.first_token_s, r.finish_s
        out += 1.0 if a < seconds else 0.0
        if n > 1:
            inside = max(0.0, min(b, seconds) - a) / max(b - a, 1e-9)
            out += (n - 1) * min(inside, 1.0)
    val["out_tok_s"] = out / seconds
    return {m["name"]: {"value": val[m["name"]], "unit": m["unit"]}
            for m in cell.end_to_end if m["name"] in val}


def peak_memory(devs) -> int:
    best = 0
    for d in devs:
        st = d.memory_stats() or {}
        best = max(best, int(st.get("peak_bytes_in_use", 0)))
    return best


def sample(done, seed: int, k: int) -> list:
    """k served requests drawn from the seed, the longest among them."""
    by_uid = {r.uid: r for r in done}
    longest = max(by_uid, key=lambda u: len(by_uid[u].prompt)
                  + len(by_uid[u].out_tokens))
    rest = sorted(u for u in by_uid if u != longest)
    rng = np.random.default_rng([abs(int(seed)), 2])
    pick = rng.choice(rest, min(k - 1, len(rest)), replace=False).tolist()
    return [by_uid[u] for u in [longest] + sorted(pick)]


def compare(cell, cfg, params, lkv, reqs, *, control: bool = False) -> dict:
    """Reference gaps of the served tokens of ``reqs``: by how much each
    served token's logit lies below the reference's best at its position,
    as the mean over all compared tokens and the widest.  With
    ``control``, the same for the tokens the float8 control puts first."""
    import jax.numpy as jnp

    from chipbench import reference

    spec = reference.spec_of(cfg, int(cell.config["serving"]["budget"]))
    m_pad = -(-int(cell.traffic["output_len"]["max"]) // 128) * 128
    gaps, cgaps = [], []
    for r in reqs:
        n, m = len(r.prompt), len(r.out_tokens)
        n_pad = max(reference.QBLOCK, 1 << (n - 1).bit_length())
        prompt = np.zeros(n_pad, np.int32)
        prompt[:n] = r.prompt
        gen = np.zeros(m_pad, np.int32)
        gen[:m] = r.out_tokens
        args = (params, lkv, jnp.asarray(prompt), jnp.asarray(n, jnp.int32),
                jnp.asarray(gen))
        logits, _ = reference.forward(*args, spec=spec)
        gaps.append(np.asarray(reference.gaps(logits, jnp.asarray(gen)))[:m])
        if control:
            low, _ = reference.forward(*args, spec=spec, quant=True)
            pick = jnp.argmax(low, -1).astype(jnp.int32)
            cgaps.append(np.asarray(reference.gaps(logits, pick))[:m])
            del low
        del logits
    g = np.concatenate(gaps)
    out = {"tokens": int(g.size), "mean": float(g.mean()),
           "widest": float(g.max())}
    if control:
        c = np.concatenate(cgaps)
        out.update(control_mean=float(c.mean()), control_widest=float(c.max()))
    return out


def check(cell, cfg, params, lkv, done, specs, seed) -> dict:
    """The compared numbers, over a seeded sample of the served requests
    that includes the longest."""
    if not done:
        return {"served": {"value": 0, "limit": len(specs)}}
    reqs = sample(done, seed, int(cell.limits["sample_requests"]))
    g = compare(cell, cfg, params, lkv, reqs)
    log(f"reference: {len(reqs)} requests, {g['tokens']} served tokens "
        f"compared, longest prompt {len(reqs[0].prompt)}; widest gap "
        f"{g['widest']} (not compared: it does not separate the control)")
    return {"mean_logit_gap": {
        "value": g["mean"],
        "limit": float(cell.limits["mean_logit_gap"]["limit"])}}


def per_layer(cell, rec) -> dict:
    out = {}
    for m in cell.per_layer:
        v = cells.metric_reader(cell.root, m["name"])(rec)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def run_cell(cell, *, seed: int, seconds: float, trace: bool,
             require_tpu: bool = True, t_start: float = T_START) -> dict:
    devs = devices_for(cell.chips, require_tpu)
    import jax

    from chipbench import model

    if require_tpu:
        enable_cache()
    counter = CompileCounter()
    cfg = model.model_config(cell.config)
    mesh = None
    if cell.chips > 1:
        from jax.sharding import Mesh
        mesh = Mesh(np.asarray(devs).reshape(1, cell.chips),
                    ("data", "model"))
    params, lkv = model.make_weights(seed, cfg, mesh)
    jax.block_until_ready((params, lkv))
    mix = cell.traffic
    max_new = int(mix["output_len"]["max"])
    eng = build_engine(cell, cfg, params, lkv, mesh, max_new)
    rung, blocks = shape_keys(cell, cfg)
    warm = loadgen.warmup_lengths(mix, rung=rung, blocks=blocks)
    wrng = np.random.default_rng([abs(int(seed)), 3])
    eng.run(requests_of([loadgen.Spec(
        uid=10_000_000 + i, arrival_s=0.0,
        prompt=wrng.integers(0, cfg.vocab_size, n, dtype=np.int32),
        max_new_tokens=min(WARM_NEW, max_new)) for i, n in enumerate(warm)]))
    jax.block_until_ready(eng.pool.tree())
    setup_s = time.perf_counter() - t_start
    log(f"set-up {setup_s:.2f}s: {len(warm)} warm-up prompts "
        f"{warm[:4]}{'...' if len(warm) > 4 else ''}")

    specs = loadgen.generate(mix, seed=seed, seconds=seconds,
                             vocab_size=cfg.vocab_size)
    reqs = requests_of(specs)
    cap = recorder = None
    if trace:
        from repro.obs.trace import TraceRecorder
        recorder = TraceRecorder(sync=False)
        eng.set_trace(recorder)
    counter.armed = True
    t0 = time.perf_counter()
    if trace:
        shift_s = -recorder.now_us() / 1e6  # recorder clock -> engine clock
        log_dir = tempfile.mkdtemp(prefix="chipbench-trace-")
        cap = tracing.Capture(log_dir, t0, TRACE_START * seconds,
                              min(TRACE_SECONDS, (1 - TRACE_START) * seconds))
        cap.start()
    done = eng.run(reqs)
    t_end = time.perf_counter() - t0
    counter.armed = False
    if cap is not None:
        cap.join()
    done = [r for r in done if r.done and len(r.out_tokens) == r.max_new_tokens]
    failed = len(specs) - len(done)
    log(f"window {seconds}s: {len(specs)} requests due, {len(done)} served "
        f"in full, {failed} failed; last finish at {t_end:.2f}s; "
        f"{counter.count} compiles inside the window")
    metrics = end_to_end(cell, done, seconds, setup_s)
    mem = peak_memory(devs)
    result = {"correct": False, "attempted": len(specs), "failed": failed}
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": mem}
    rec = None
    if trace:
        if cap.error or cap.span is None:
            raise RuntimeError(f"profiler capture failed: {cap.error}")
        tr = tracing.load(log_dir, cap.span[1] - cap.span[0],
                          cell.chips if require_tpu else 0)
        shutil.rmtree(log_dir, ignore_errors=True)
        peaks = json.loads((cells.BENCH / "peaks.json").read_text())["devices"]
        rec = {"requests": [{"uid": r.uid, "arrival_s": r.arrival_s,
                             "first_token_s": r.first_token_s,
                             "finish_s": r.finish_s,
                             "n_prompt": len(r.prompt),
                             "n_out": len(r.out_tokens)} for r in done],
               "window_s": seconds, "trace": tr, "trace_span": cap.span,
               "spans": [dict(e, ts=e["ts"] / 1e6 + shift_s)
                         for e in recorder.events],
               "config": cell.config, "serving": {**cell.config["serving"],
                                                  **mix["serving"]},
               "chips": cell.chips, "peak": peaks.get(devs[0].device_kind),
               "widths": flops.Widths.of(cell.config)}
        device["busy_s"] = tracing.busy_s(tr)
        device["window_s"] = tr.window_s
        result["breakdown"] = tracing.breakdown(tr)
    del eng
    checks = check(cell, cfg, params, lkv, done, specs, seed)
    checks["failed"] = {"value": failed, "limit": 0}
    checks["compiles_in_window"] = {"value": counter.count, "limit": 0}
    result["correct"] = all(c["value"] <= c["limit"] for c in checks.values())
    result["metrics"] = per_layer(cell, rec) if trace else metrics
    result["device"] = device
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = cells.load_cell(ROOT, args.workload)
    try:
        result = run_cell(cell, seed=args.seed, seconds=args.seconds,
                          trace=bool(args.trace))
    except NoChip as e:
        log(f"chipbench: {e}")
        return 2
    for name, c in result["checks"].items():
        log(f"check {name} {c['value']} limit {c['limit']}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    os.environ.setdefault("TPU_STDERR_LOG_LEVEL", "3")
    sys.exit(main())
