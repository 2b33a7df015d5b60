"""Compiled-artifact analysis: collective-byte accounting + roofline terms.

This is the §Roofline source: it reasons from the lowered / compiled HLO,
not wall-clock.  Hardware peaks are keyed by ``device_kind``; the dry-run
describes TPU v5e chips, and a device missing from ``PEAKS`` is an error,
never a default.
"""

from __future__ import annotations

import re
from dataclasses import asdict, dataclass

import numpy as np

# Per-chip peaks by ``jax.devices()[0].device_kind``.  TPU v5e: Google
# Cloud documentation, "TPU v5e" (197 TFLOP/s bf16, 819 GB/s HBM); the ICI
# figure is a per-link effective injection rate (1,600 Gbit/s per chip is
# the published aggregate).
PEAKS = {
    "TPU v5 lite": {"flops_bf16": 197e12, "hbm_bw": 819e9, "ici_bw": 50e9},
}
DRYRUN_DEVICE_KIND = "TPU v5 lite"  # the chip the dry-run describes

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "tuple": 0, "token": 0, "opaque": 0,
}

_COLLECTIVES = (
    "all-reduce", "all-gather", "reduce-scatter", "all-to-all",
    "collective-permute",
)

# `%x = bf16[8,128,4096]{2,1,0} all-gather(...)`
_LINE_RE = re.compile(
    r"=\s+(?:\()?([a-z0-9]+)\[([0-9,]*)\][^=]*?\b"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start|-done)?\("
)
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


def collective_bytes(hlo_text: str) -> dict:
    """Sum result bytes of every collective op in (post-SPMD) HLO text.

    Shapes in the partitioned module are per-device, so the totals are
    per-device bytes moved — the right numerator for the per-chip roofline
    term.  ``-start``/``-done`` async pairs are counted once (on -start).
    """
    out = {c: 0 for c in _COLLECTIVES}
    counts = {c: 0 for c in _COLLECTIVES}
    for line in hlo_text.splitlines():
        stripped = line.strip()
        hit = None
        for c in _COLLECTIVES:
            if f" {c}(" in stripped or f"{c}-start(" in stripped:
                hit = c
                break
        if hit is None or "-done(" in stripped:
            continue
        # result shape = first dtype[dims] on the line (possibly a tuple)
        m = _SHAPE_RE.search(stripped.split("=", 1)[-1])
        if not m:
            continue
        out[hit] += _shape_bytes(m.group(1), m.group(2))
        counts[hit] += 1
    out["total"] = sum(out[c] for c in _COLLECTIVES)
    out["counts"] = counts
    return out


@dataclass
class Roofline:
    arch: str
    shape: str
    mesh: str
    chips: int
    hlo_flops: float  # per device
    hlo_bytes: float  # per device (HBM traffic)
    coll_bytes: float  # per device
    model_flops: float  # 6·N_active·D tokens, global
    compute_s: float
    memory_s: float
    collective_s: float
    bottleneck: str
    useful_flop_ratio: float
    peak_bytes_per_device: float | None = None

    def to_dict(self):
        return asdict(self)


def roofline_terms(
    *, arch: str, shape: str, mesh: str, chips: int,
    hlo_flops_per_dev: float, hlo_bytes_per_dev: float,
    coll_bytes_per_dev: float, model_flops_global: float,
    peak_bytes: float | None = None,
    device_kind: str = DRYRUN_DEVICE_KIND,
) -> Roofline:
    peak = PEAKS[device_kind]
    compute_s = hlo_flops_per_dev / peak["flops_bf16"]
    memory_s = hlo_bytes_per_dev / peak["hbm_bw"]
    collective_s = coll_bytes_per_dev / peak["ici_bw"]
    terms = {"compute": compute_s, "memory": memory_s,
             "collective": collective_s}
    bottleneck = max(terms, key=terms.get)
    total_hlo = hlo_flops_per_dev * chips
    ratio = model_flops_global / total_hlo if total_hlo else 0.0
    return Roofline(
        arch=arch, shape=shape, mesh=mesh, chips=chips,
        hlo_flops=hlo_flops_per_dev, hlo_bytes=hlo_bytes_per_dev,
        coll_bytes=coll_bytes_per_dev, model_flops=model_flops_global,
        compute_s=compute_s, memory_s=memory_s, collective_s=collective_s,
        bottleneck=bottleneck, useful_flop_ratio=ratio,
        peak_bytes_per_device=peak_bytes,
    )


def model_flops(cfg, shape_kind: str, tokens: int) -> float:
    """MODEL_FLOPS = 6·N_active·tokens (train ≈ fwd+bwd => 6; inference 2)."""
    n_active = cfg.active_params()
    mult = 6.0 if shape_kind == "train" else 2.0
    return mult * n_active * tokens


def memory_analysis_dict(compiled) -> dict:
    try:
        ma = compiled.memory_analysis()
    except Exception:
        return {}
    out = {k: int(getattr(ma, k))
           for k in ("argument_size_in_bytes", "output_size_in_bytes",
                     "temp_size_in_bytes", "generated_code_size_in_bytes")}
    # CompiledMemoryStats reports no peak: the conservative bound is
    # arguments + outputs + temps + code
    out["peak_memory_in_bytes"] = sum(out.values())
    return out


def cost_analysis_dict(compiled) -> dict:
    try:
        ca = compiled.cost_analysis()
    except Exception:
        return {}
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    return {k: float(v) for k, v in dict(ca).items()
            if isinstance(v, (int, float)) and not k.startswith("utilization")}


# ---------------------------------------------------------------------------
# jaxpr-based cost model (scan-trip-count aware)
# ---------------------------------------------------------------------------
# Discovery (EXPERIMENTS.md §Dry-run): XLA's compiled.cost_analysis() counts
# a while-loop body ONCE, ignoring the trip count — with the whole depth under
# lax.scan this understates FLOPs by ~num_layers×.  We therefore walk the
# jaxpr, where scan lengths are explicit, and count:
#   flops: dot_general (2·M·N·K·batch) — the MXU work;
#   heavy_bytes: operand+result bytes of dot/gather/scatter/dyn-slice ops —
#     a fusion-aware-ish lower bound on HBM traffic (elementwise chains fuse).
# Shapes in the jaxpr are GLOBAL; divide by chip count for per-device terms.


def _aval_bytes(aval) -> int:
    try:
        return int(np.prod(aval.shape)) * aval.dtype.itemsize
    except Exception:
        return 0


_HEAVY_BYTES_PRIMS = {
    "gather", "scatter", "scatter-add", "dynamic_slice",
    "dynamic_update_slice", "take", "conv_general_dilated",
}


def jaxpr_cost(jaxpr) -> dict:
    """{'flops': float, 'heavy_bytes': float} with scan multipliers."""
    flops = 0.0
    bytes_ = 0.0
    for eqn in jaxpr.eqns:
        prim = eqn.primitive.name
        if prim == "dot_general":
            dnums = eqn.params["dimension_numbers"]
            (lc, rc), (lb, rb) = dnums
            a, b = eqn.invars[0].aval, eqn.invars[1].aval
            batch = int(np.prod([a.shape[i] for i in lb])) if lb else 1
            k = int(np.prod([a.shape[i] for i in lc])) if lc else 1
            m = int(np.prod([a.shape[i] for i in range(a.ndim)
                             if i not in lc and i not in lb]))
            n = int(np.prod([b.shape[i] for i in range(b.ndim)
                             if i not in rc and i not in rb]))
            flops += 2.0 * batch * m * n * k
            bytes_ += sum(_aval_bytes(v.aval) for v in eqn.invars)
            bytes_ += sum(_aval_bytes(v.aval) for v in eqn.outvars)
        elif prim in _HEAVY_BYTES_PRIMS:
            bytes_ += sum(_aval_bytes(v.aval) for v in eqn.invars)
            bytes_ += sum(_aval_bytes(v.aval) for v in eqn.outvars)
        elif prim == "scan":
            inner = jaxpr_cost(eqn.params["jaxpr"].jaxpr)
            length = eqn.params["length"]
            flops += length * inner["flops"]
            bytes_ += length * inner["heavy_bytes"]
        elif prim == "while":
            inner = jaxpr_cost(eqn.params["body_jaxpr"].jaxpr)
            flops += inner["flops"]  # trip count unknown; flagged by caller
            bytes_ += inner["heavy_bytes"]
        elif prim == "cond":
            branches = eqn.params["branches"]
            costs = [jaxpr_cost(br.jaxpr) for br in branches]
            flops += max(c["flops"] for c in costs)
            bytes_ += max(c["heavy_bytes"] for c in costs)
        elif prim in ("jit", "pjit", "closed_call", "core_call", "remat_call",
                      "custom_jvp_call", "custom_vjp_call",
                      "custom_vjp_call_jaxpr", "checkpoint", "remat"):
            sub = eqn.params.get("jaxpr") or eqn.params.get("call_jaxpr")
            if sub is not None:
                inner = jaxpr_cost(getattr(sub, "jaxpr", sub))
                flops += inner["flops"]
                bytes_ += inner["heavy_bytes"]
    return {"flops": flops, "heavy_bytes": bytes_}


def fn_cost(fn, *abstract_args) -> dict:
    """Global-shape cost of fn lowered at the given abstract args."""
    import jax

    jaxpr = jax.make_jaxpr(fn)(*abstract_args)
    return jaxpr_cost(jaxpr.jaxpr)


def collective_bytes_with_loops(hlo_text: str, loop_multiplier: int) -> dict:
    """Collective bytes with in-loop ops multiplied by ``loop_multiplier``
    (the layer-scan trip count — our only collective-bearing loop level).

    HLO text layout: each computation is printed as a block starting with
    ``%name (params) -> type {`` or ``name {``; while-loop bodies contain
    "while" in their computation name (XLA naming convention).
    """
    out = {c: 0 for c in _COLLECTIVES}
    counts = {c: 0 for c in _COLLECTIVES}
    in_loop_body = False
    depth = 0
    for line in hlo_text.splitlines():
        s = line.strip()
        if s.endswith("{") and not s.startswith("ROOT"):
            header = s.split("(")[0]
            in_loop_body = ("while" in header or "body" in header
                            or "cond" in header)
            depth = 1
            continue
        if in_loop_body:
            depth += s.count("{") - s.count("}")
            if depth <= 0:
                in_loop_body = False
        hit = None
        for c in _COLLECTIVES:
            if f" {c}(" in s or f"{c}-start(" in s:
                hit = c
                break
        if hit is None or "-done(" in s:
            continue
        m = _SHAPE_RE.search(s.split("=", 1)[-1])
        if not m:
            continue
        b = _shape_bytes(m.group(1), m.group(2))
        mult = loop_multiplier if in_loop_body else 1
        out[hit] += b * mult
        counts[hit] += 1
    out["total"] = sum(out[c] for c in _COLLECTIVES)
    out["counts"] = counts
    out["loop_multiplier"] = loop_multiplier
    return out


# ---------------------------------------------------------------------------
# Analytic HBM traffic model (TPU-target semantics)
# ---------------------------------------------------------------------------
# The jaxpr heavy-bytes counter over-counts the CPU fallback's attention
# tiles (on the TPU target those live in VMEM inside the Pallas kernels and
# never touch HBM).  The roofline memory term therefore uses this analytic
# model of *unavoidable* HBM traffic for our implementation:
#   · weights read once per pass (MoE dense-dispatch: once per seq chunk —
#     honestly charging the baseline's re-read, which §Perf attacks);
#   · activations written+read once per layer boundary (~8 stream tensors);
#   · flash attention reads K/V once per query block-row;
#   · decode reads the whole KV cache once per step;
#   · train charges 2 passes (GT + lookahead) + lookahead-row backward.
# Reported next to the jaxpr upper bound; both land in the JSON.


def analytic_hbm_bytes(cfg, shape_kind: str, batch: int, seq: int) -> float:
    bpe = 2.0  # bf16
    d = cfg.d_model
    L = cfg.num_layers
    p_bytes = cfg.num_params() * bpe

    if shape_kind == "decode":
        tokens = batch  # one token per sequence
        cache = 0.0
        if cfg.attn is not None:
            cache = L * batch * seq * cfg.attn.kv_dim * 2 * bpe
        if cfg.uses_ssm:
            s = cfg.ssm
            nh = s.num_heads(d)
            cache += L * batch * nh * s.head_dim * s.d_state * 4 * 2  # r+w f32
        act = L * tokens * d * bpe * 8
        return p_bytes + cache + act + tokens * cfg.vocab_size * 4

    tokens = batch * seq
    passes = 2.0 if shape_kind == "train" else 1.0
    act = passes * L * tokens * d * bpe * 8
    attn_io = 0.0
    if cfg.attn is not None:
        block_q = 512.0
        qblocks = max(seq / block_q, 1.0)
        kv_read = seq * cfg.attn.kv_dim * 2 * bpe
        attn_io = passes * L * batch * qblocks * kv_read
        if cfg.attn.sliding_window and not cfg.attn.global_every:
            attn_io *= min(cfg.attn.sliding_window / seq * qblocks, 1.0)
    moe_reread = 0.0
    if cfg.moe is not None:
        m = cfg.moe
        nchunks = max(seq / 256.0, 1.0)  # moe._CHUNK
        expert_bytes = L * m.num_experts * 3 * d * m.d_expert * bpe
        moe_reread = passes * (nchunks - 1) * expert_bytes
    weight_reads = passes * p_bytes
    if shape_kind == "train":
        weight_reads += p_bytes  # backward re-reads (remat-ish)
    return weight_reads + act + attn_io + moe_reread


# ---------------------------------------------------------------------------
# Sharding-aware per-component cost model (the roofline numerators)
# ---------------------------------------------------------------------------
# jaxpr totals are GLOBAL; dividing by chip count assumes every op shards
# over the whole mesh.  That hides replication waste: e.g. qwen2-1.5b has 12
# heads — not divisible by model=16 — so its attention runs replicated on
# every model rank.  Each component below carries its own effective shard
# count derived from the same divisibility rules as sharding.py; per-device
# cost = Σ_c flops_c / (dp_shards · model_shards_c).  The component
# breakdown is what §Perf iterates on.  Cross-checked against the jaxpr
# totals (reported as `jaxpr_check`).


def component_costs(cfg, shape_kind: str, batch: int, seq: int,
                    mesh_shape: dict, *, seq_sharded: bool = False) -> dict:
    """{component: {flops, bytes, model_shards}} — global flops/bytes and the
    model-axis parallelism each component actually achieves."""
    msize = mesh_shape.get("model", 1)
    a = cfg.attn
    d, L, V = cfg.d_model, cfg.num_layers, cfg.vocab_size
    bpe = 2.0
    tokens = batch * (1 if shape_kind == "decode" else seq)

    # forward-pass multiplier: GT + lookahead passes for train (+ backward
    # through the lookahead rows ~ small); plain LM train = fwd + 2×bwd.
    if shape_kind == "train":
        passes = 2.2 if cfg.technique_applies else 3.0
    else:
        passes = 1.0

    def div(n, s):
        return s > 0 and n % s == 0

    comps = {}

    if a is not None:
        shard_q = div(a.num_heads, msize)
        shard_kv = div(a.num_kv_heads, msize)
        proj_flops = 2.0 * tokens * d * (a.q_dim * 2 + a.kv_dim * 2) * L
        comps["attn_proj"] = {
            "flops": passes * proj_flops,
            "bytes": passes * L * (d * (a.q_dim * 2 + a.kv_dim * 2)) * bpe,
            "model_shards": msize if shard_q else 1,
        }
        if shape_kind == "decode":
            ctx = seq
            quad = 4.0 * batch * ctx * a.q_dim * L
            kv_bytes = L * batch * ctx * a.kv_dim * 2 * bpe
        else:  # data-like traffic: scales with the local batch shard
            causal_frac = 0.5
            window = a.sliding_window if (a.sliding_window and
                                          not a.global_every) else 0
            eff_ctx = min(window, seq) if window else seq * causal_frac
            if a.global_every:
                n_glob = L // a.global_every
                eff_ctx = (min(a.sliding_window, seq) * (L - n_glob)
                           + seq * causal_frac * n_glob) / L
            quad = 4.0 * batch * seq * eff_ctx * a.q_dim * L
            kv_bytes = passes * L * batch * (seq / 512.0) \
                * eff_ctx * a.kv_dim * 2 * bpe
        comps["attn_quadratic"] = {
            "flops": passes * quad,
            "bytes": 0.0,
            "data_bytes": kv_bytes,
            "model_shards": msize if shard_q else 1,
        }
    if cfg.moe is not None:
        m = cfg.moe
        shard_e = div(m.num_experts, msize)
        if m.dispatch == "sparse":
            # top-k + capacity slack; weights stream once (no chunk re-read)
            dense_e = m.top_k * m.capacity_factor
            nchunks = 1.0
        else:
            dense_e = m.num_experts  # dense dispatch computes every expert
            nchunks = max((1 if shape_kind == "decode" else seq) / 256.0, 1.0)
        expert_flops = 2.0 * tokens * 3 * d * m.d_expert * dense_e * L
        expert_bytes = L * m.num_experts * 3 * d * m.d_expert * bpe * nchunks
        comps["moe_experts"] = {
            "flops": passes * expert_flops,
            "bytes": passes * expert_bytes,
            "model_shards": msize if shard_e else 1,
        }
        if m.num_shared_experts:
            fs = m.num_shared_experts * m.d_expert
            comps["moe_shared"] = {
                "flops": passes * 2.0 * tokens * 3 * d * fs * L,
                "bytes": passes * L * 3 * d * fs * bpe,
                "model_shards": msize if div(fs, msize) else 1,
            }
        comps["moe_router"] = {
            "flops": passes * 2.0 * tokens * d * m.num_experts * L,
            "bytes": passes * L * d * m.num_experts * 4,
            "model_shards": 1,
        }
    elif cfg.d_ff > 0:
        comps["mlp"] = {
            "flops": passes * 2.0 * tokens * 3 * d * cfg.d_ff * L,
            "bytes": passes * L * 3 * d * cfg.d_ff * bpe,
            "model_shards": msize if div(cfg.d_ff, msize) else 1,
        }
    if cfg.ssm is not None:
        s = cfg.ssm
        di = s.d_inner(d)
        nh = s.num_heads(d)
        proj = 2.0 * tokens * d * (2 * di + 2 * s.d_state + nh) \
            + 2.0 * tokens * di * d
        # SSD: intra-chunk quadratic + state updates
        Q = s.chunk_size
        ssd = (2.0 * tokens * Q * nh * s.head_dim  # cb/w application
               + 2.0 * tokens * Q * s.d_state  # C·B
               + 4.0 * tokens * nh * s.head_dim * s.d_state)
        comps["ssm"] = {
            "flops": passes * (proj + ssd) * L,
            "bytes": passes * L * (d * (2 * di + 2 * s.d_state + nh)
                                   + di * d) * bpe,
            "model_shards": 1,  # baseline: replicated (DESIGN.md §4)
        }
    if cfg.is_encoder_decoder and shape_kind != "decode":
        F = cfg.encoder.num_frames
        enc_tokens = batch * F
        eL = cfg.encoder.num_layers
        enc = (2.0 * enc_tokens * d * (a.q_dim * 2 + a.kv_dim * 2)
               + 2.0 * enc_tokens * 3 * d * cfg.d_ff
               + 4.0 * enc_tokens * F * a.q_dim) * eL
        cross = (2.0 * tokens * d * a.q_dim * 2
                 + 4.0 * tokens * F * a.q_dim) * L
        comps["encoder_cross"] = {
            "flops": passes * (enc + cross),
            "bytes": passes * eL * (2 * d * (a.q_dim + a.kv_dim)
                                    + 3 * d * cfg.d_ff) * bpe,
            "model_shards": msize if div(cfg.d_ff, msize) else 1,
        }
    # logits / embeddings (padded vocab always shards — §Perf pair 2)
    Vp = getattr(cfg, "padded_vocab", V)
    if div(Vp, msize) or div(d, msize):
        lshard = msize
    else:
        lshard = 1
    logit_tokens = tokens if shape_kind != "train" else tokens  # 'all' logits
    if shape_kind == "train" and cfg.technique_applies:
        logit_tokens = 0  # KL objective needs no logits
    comps["logits"] = {
        "flops": 2.0 * logit_tokens * d * V,
        "bytes": V * d * bpe,
        "model_shards": lshard,
    }
    if seq_sharded:
        # sequence parallelism: every per-token component's *compute* shards
        # over the model axis too (weights are still read replicated — the
        # bytes keep their base shard counts).
        for c in comps.values():
            c["flops_shards"] = msize * max(c["model_shards"] // msize, 1) \
                if c["model_shards"] == msize else msize
    # decode cache traffic
    if shape_kind == "decode" and a is not None:
        kv_shards = msize if (div(a.num_kv_heads, msize) or
                              div(seq, msize)) else 1
        comps["kv_cache_io"] = {
            "flops": 0.0,
            "bytes": 0.0,
            "data_bytes": L * batch * seq * a.kv_dim * 2 * bpe,
            "model_shards": kv_shards,
        }
    return comps


def per_device_cost(comps: dict, mesh_shape: dict, global_batch: int) -> dict:
    """Fold components into per-device (flops, bytes) given batch sharding."""
    dp = 1
    for k, v in mesh_shape.items():
        if k != "model":
            dp *= v
    if global_batch % dp != 0:
        dp = mesh_shape.get("data", 1) if (
            global_batch % mesh_shape.get("data", 1) == 0) else 1
    flops = sum(c["flops"] / (dp * c.get("flops_shards", c["model_shards"]))
                for c in comps.values())
    # weight-like traffic: every device reads its weight shard each step;
    # data-like traffic (KV/cache streams) also divides by the batch shards.
    bytes_ = sum(c["bytes"] / c["model_shards"]
                 + c.get("data_bytes", 0.0) / (dp * c["model_shards"])
                 for c in comps.values())
    return {"flops_per_dev": flops, "bytes_per_dev": bytes_, "dp_shards": dp}
