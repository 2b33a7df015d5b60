"""Kernel micro-benchmarks: wall-time of the jnp fallbacks on CPU (ordering/
regression tracking) + analytic VMEM working-set check of the Pallas tilings
(the quantity that must stay under ~16 MB on v5e) + the paged flash-decode
roofline budget (``paged_decode_verdict``, gated by ci_smoke)."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import time_call
from repro.kernels import ops

# -- paged flash-decode roofline budget ------------------------------------
#
# (B, depth, block_size) points spanning the serving geometries; the block
# sizes are the kernel-parity sweep's {16, 64, 128}.  The achieved-bandwidth
# budget is *analytic* (HBM bytes the kernel's tiling must touch vs the
# bytes any exact decode must stream — valid on any host), while the
# kernel-vs-gather race is *measured*: on TPU the Pallas kernel itself, on
# CPU the streaming jnp fallback that implements the same block scan (the
# dispatch ops.paged_decode_attention actually takes there at these depths).
PAGED_POINTS = ((4, 2048, 16), (4, 2048, 64), (2, 4096, 128), (8, 1024, 64))
PAGED_KV, PAGED_GROUP, PAGED_HD = 2, 4, 64
#: the kernel's touched-bytes budget: at most 1/0.85 ≈ 1.18× the ideal
#: traffic, i.e. ≥ 85% of roofline bandwidth when HBM-bound at peak
ROOFLINE_FRAC = 0.85


def _paged_traffic_bytes(B, depth, bs, *, KV=PAGED_KV, H=PAGED_KV * PAGED_GROUP,
                         hd=PAGED_HD, itemsize=4):
    """HBM bytes one paged-decode call's tiling actually streams: whole K/V
    blocks (padding the depth up to the block grid), the int32 pos + bool
    mask metadata tiles, and the q/out rows."""
    nb = -(-depth // bs)
    kv = 2 * B * nb * bs * KV * hd * itemsize
    meta = B * nb * bs * KV * (4 + 1)  # pos int32 + mask bool
    io = 2 * B * H * hd * itemsize  # q in, out back
    return kv + meta + io


def _paged_ideal_bytes(B, depth, *, KV=PAGED_KV, H=PAGED_KV * PAGED_GROUP,
                       hd=PAGED_HD, itemsize=4):
    """The model-derived lower bound: any exact decode must stream every
    logical K and V row once, plus the q/out rows."""
    return 2 * B * depth * KV * hd * itemsize + 2 * B * H * hd * itemsize


def _paged_case(B, depth, bs, seed=0):
    rng = np.random.default_rng(seed)
    KV, hd = PAGED_KV, PAGED_HD
    H = PAGED_KV * PAGED_GROUP
    nb = -(-depth // bs)
    N = 1 + B * nb
    q = jnp.asarray(rng.standard_normal((B, H, hd)), jnp.float32)
    kp = jnp.asarray(rng.standard_normal((N, KV, bs, hd)), jnp.float32)
    vp = jnp.asarray(rng.standard_normal((N, KV, bs, hd)), jnp.float32)
    mp = jnp.asarray(rng.random((N, KV, bs)) < 0.9).at[0].set(False)
    pos = jnp.asarray(rng.integers(0, depth, (N, KV, bs)), jnp.int32)
    tbl = jnp.asarray(1 + np.arange(B * nb, dtype=np.int32).reshape(B, nb))
    return q, kp, vp, mp, pos, tbl


def paged_decode_rows():
    """One row per PAGED_POINTS entry: measured kernel-path and gather wall
    time plus the analytic roofline fraction.  Shared with
    ``bench_roofline`` (nightly sweep artifact)."""
    from repro.kernels import ref

    on_tpu = jax.default_backend() == "tpu"
    if on_tpu:
        from repro.kernels import paged_attention as pk

        kernel_fn = jax.jit(
            lambda q, k, v, m, t: pk.paged_decode_attention_pallas(
                q, k, v, m, t))
        path = "kernel"
    else:
        kernel_fn = jax.jit(
            lambda q, k, v, m, t: ops._paged_decode_streaming(q, k, v, m, t))
        path = "fallback"
    gather_fn = jax.jit(
        lambda q, k, v, m, t: ref.paged_decode_attention(q, k, v, m, t))

    rows = []
    for (B, depth, bs) in PAGED_POINTS:
        q, kp, vp, mp, _, tbl = _paged_case(B, depth, bs)
        us = time_call(kernel_fn, q, kp, vp, mp, tbl)
        gather_us = time_call(gather_fn, q, kp, vp, mp, tbl)
        touched = _paged_traffic_bytes(B, depth, bs)
        ideal = _paged_ideal_bytes(B, depth)
        rows.append({
            "B": B, "depth": depth, "block_size": bs, "path": path,
            "us": us, "gather_us": gather_us,
            "touched_bytes": touched, "ideal_bytes": ideal,
            "roofline_frac": ideal / touched,
            "achieved_gbps": touched / us * 1e-3,
        })
    return rows


def _vmem_bytes_flash(block_q, block_k, hd):
    # q tile + k tile + v tile + f32 accumulators
    return (block_q * hd * 2 + 2 * block_k * hd * 2
            + block_q * (hd + 2) * 4)


def _vmem_bytes_lookahead(n_obs, block_k, hd):
    return n_obs * hd * 2 + block_k * hd * 2 + 2 * n_obs * 4 + block_k * 4


def _vmem_bytes_ssd(chunk, bh, hd, ds):
    return (chunk * bh * (hd + 2) * 4 + 2 * chunk * ds * 4
            + bh * hd * ds * 4 + chunk * chunk * (bh + 1) * 4)


def _vmem_bytes_chunk_masses(C, block_k, hd):
    # fused score kernel: q tile + k/v tiles + f32 (m, l, acc) scratch +
    # the (block_k,) f32 mass output tile
    return (C * hd * 2 + 2 * block_k * hd * 2
            + C * (hd + 2) * 4 + block_k * 4)


def run(report):
    fits_all = True

    def vmem_row(name, vm):
        nonlocal fits_all
        fits_all &= vm < 16e6
        report(name, None, f"vmem_kb={vm/1024:.0f} fits_16MB={vm < 16e6}")

    for (bq, bk, hd) in ((128, 128, 128), (256, 512, 128), (128, 1024, 256)):
        vmem_row(f"kernels/flash_vmem/bq{bq}_bk{bk}_hd{hd}",
                 _vmem_bytes_flash(bq, bk, hd))
    for (no, bk, hd) in ((32, 512, 128), (32, 2048, 128), (128, 1024, 256)):
        vmem_row(f"kernels/lookahead_vmem/obs{no}_bk{bk}",
                 _vmem_bytes_lookahead(no, bk, hd))
    for (C, bk, hd) in ((128, 512, 128), (256, 512, 128), (256, 1024, 256)):
        vmem_row(f"kernels/chunk_masses_vmem/C{C}_bk{bk}_hd{hd}",
                 _vmem_bytes_chunk_masses(C, bk, hd))
    for (ck, bh, hd, ds) in ((128, 8, 64, 128), (128, 8, 64, 16)):
        vmem_row(f"kernels/ssd_vmem/chunk{ck}_bh{bh}_ds{ds}",
                 _vmem_bytes_ssd(ck, bh, hd, ds))
    # the CI smoke gate keys off this row: every tiling must fit v5e VMEM
    report("kernels/vmem_verdict", None, "pass" if fits_all else "fail")

    # CPU wall-time of the fallbacks (regression tracking)
    key = jax.random.PRNGKey(0)
    B, S, H, KV, hd = 1, 4096, 4, 2, 64
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, S, H, hd), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, KV, hd), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, KV, hd), jnp.float32)
    fa = jax.jit(lambda q, k, v: ops.flash_attention(q, k, v, causal=True))
    report("kernels/flash_fallback_4k", time_call(fa, q, k, v),
           "B1 S4096 H4 hd64 f32")
    qo = q[:, :32]
    ls = jax.jit(lambda qo, k: ops.lookahead_score(qo, k, S - 32))
    report("kernels/lookahead_fallback_4k", time_call(ls, qo, k),
           "n_obs=32 S4096")
    qd = q[:, 0, :, :]
    da = jax.jit(lambda qd, k, v: ops.decode_attention(qd, k, v))
    report("kernels/decode_fallback_4k", time_call(da, qd, k, v), "S4096")
    qc = q[:, :256]
    cm = jax.jit(lambda qc, k, v: ops.chunk_attention(
        qc, k, v, q_offset=jnp.asarray(S - 256, jnp.int32),
        score_masses=True, n_total=jnp.asarray(S, jnp.int32))[1])
    report("kernels/chunk_masses_fallback_4k", time_call(cm, qc, k, v),
           "fused-score streaming fallback C256 S4096")
    nh, ds = 8, 64
    x = jax.random.normal(ks[0], (B, 1024, nh, 32))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, 1024, nh)))
    A = -jnp.exp(jax.random.normal(ks[2], (nh,)))
    Bm = jax.random.normal(ks[0], (B, 1024, 1, ds))
    Cm = jax.random.normal(ks[1], (B, 1024, 1, ds))
    sc = jax.jit(lambda *a: ops.ssd_scan(*a, chunk=128))
    report("kernels/ssd_fallback_1k", time_call(sc, x, dt, A, Bm, Cm),
           "S1024 nh8 ds64")

    # paged flash-decode roofline budget: the kernel path must stay within
    # the analytic bandwidth budget at every point AND win the measured
    # race against the gather fallback (the O(depth) HBM copy it replaced)
    # wherever the depth is >= 2k
    ok_frac = ok_race = True
    for r in paged_decode_rows():
        name = (f"kernels/paged_decode_{r['path']}"
                f"_B{r['B']}_d{r['depth']}_bs{r['block_size']}")
        report(name, r["us"],
               f"gather_us={r['gather_us']:.0f} "
               f"roofline_frac={r['roofline_frac']:.3f} "
               f"touched_mb={r['touched_bytes']/1e6:.1f}")
        ok_frac &= r["roofline_frac"] >= ROOFLINE_FRAC
        if r["depth"] >= 2048:
            ok_race &= r["us"] < r["gather_us"]
    report("kernels/paged_decode_verdict", None,
           "pass" if (ok_frac and ok_race) else
           f"fail frac_ok={ok_frac} beats_gather={ok_race}")
