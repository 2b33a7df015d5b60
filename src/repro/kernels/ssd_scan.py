"""Pallas TPU kernel for the Mamba-2 SSD chunked scan (arXiv:2405.21060).

The state-space-duality decomposition: within a chunk of Q tokens the output
is a masked quadratic ("attention-like") form that maps onto the MXU; chunks
are linked by a rank-preserving state recurrence.  grid = (B, nh_blocks, nc)
with the chunk axis innermost (sequential); the (bh, hd, ds) f32 running
state lives in VMEM scratch and never round-trips HBM between chunks —
that is the TPU adaptation of the paper's kernel (the CUDA version re-reads
chunk states from HBM between its three sub-kernels).

Assumes ngroups == 1 (our configs): B/C tiles are shared across heads.

Oracle: ``ref.ssd_scan`` (sequential recurrence).
jnp fallback: ``ops.ssd_scan_chunked_jnp`` (same chunked math).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, dt_ref, A_ref, b_ref, c_ref, h0_ref, y_ref, hout_ref,
            state_scr, *, nc, chunk):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        state_scr[...] = h0_ref[0].astype(jnp.float32)  # (bh, hd, ds)

    x = x_ref[0].astype(jnp.float32)  # (Q, bh, hd)
    dt = dt_ref[0].astype(jnp.float32)  # (Q, bh)
    A = A_ref[...].astype(jnp.float32)  # (bh,)
    bm = b_ref[0, :, 0, :].astype(jnp.float32)  # (Q, ds) — group-shared
    cm = c_ref[0, :, 0, :].astype(jnp.float32)  # (Q, ds)

    a = A[None, :] * dt  # (Q, bh) log-decays, <= 0
    L = jnp.cumsum(a, axis=0)  # (Q, bh)

    # intra-chunk quadratic: y_t += Σ_{s<=t} (C_t·B_s) exp(L_t − L_s) dt_s x_s
    cb = jax.lax.dot_general(
        cm, bm, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # (Q, Q) = C_t · B_s
    decay = jnp.exp(jnp.clip(L[:, None, :] - L[None, :, :], -60.0, 0.0))
    causal = (
        jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
        >= jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    )
    w = cb[:, :, None] * decay * jnp.where(causal, 1.0, 0.0)[:, :, None]
    # (t, s, bh) weights; y_intra[t, n, h] = Σ_s w[t,s,n]·dt[s,n]·x[s,n,h]
    y_intra = jnp.einsum("tsn,sn,snh->tnh", w, dt, x)

    # inter-chunk: carried state h contributes C_t·h·exp(L_t)
    h = state_scr[...]  # (bh, hd, ds)
    eL = jnp.exp(jnp.clip(L, -60.0, 0.0))  # (Q, bh)
    y_inter = jnp.einsum("td,nhd,tn->tnh", cm, h, eL)

    y_ref[0] = (y_intra + y_inter).astype(y_ref.dtype)

    # state update: h' = exp(L_Q) h + Σ_s exp(L_Q − L_s) dt_s x_s ⊗ B_s
    Lq = L[-1]  # (bh,)
    rem = jnp.exp(jnp.clip(Lq[None, :] - L, -60.0, 0.0))  # (Q, bh)
    dstate = jnp.einsum("sn,sn,snh,sd->nhd", rem, dt, x, bm)
    state_scr[...] = h * jnp.exp(jnp.clip(Lq, -60.0, 0.0))[:, None, None] + dstate

    @pl.when(ic == nc - 1)
    def _emit_state():
        hout_ref[0] = state_scr[...].astype(hout_ref.dtype)


def ssd_scan_pallas(
    x: jnp.ndarray,  # (B, S, nh, hd)
    dt: jnp.ndarray,  # (B, S, nh)
    A: jnp.ndarray,  # (nh,)
    Bm: jnp.ndarray,  # (B, S, 1, ds)
    Cm: jnp.ndarray,  # (B, S, 1, ds)
    *,
    chunk: int = 128,
    block_nh: int = 8,
    initial_state: jnp.ndarray | None = None,  # (B, nh, hd, ds)
    interpret: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    B, S, nh, hd = x.shape
    G, ds = Bm.shape[2], Bm.shape[3]
    assert G == 1, "kernel assumes ngroups == 1 (shared B/C across heads)"
    assert S % chunk == 0, "pad sequence to chunk multiple before the kernel"
    nc = S // chunk
    block_nh = min(block_nh, nh)
    assert nh % block_nh == 0
    nhb = nh // block_nh
    if initial_state is None:
        initial_state = jnp.zeros((B, nh, hd, ds), jnp.float32)

    kernel = functools.partial(_kernel, nc=nc, chunk=chunk)
    y, hout = pl.pallas_call(
        kernel,
        grid=(B, nhb, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, block_nh, hd),
                         lambda b, n, ic: (b, ic, n, 0)),
            pl.BlockSpec((1, chunk, block_nh), lambda b, n, ic: (b, ic, n)),
            pl.BlockSpec((block_nh,), lambda b, n, ic: (n,)),
            pl.BlockSpec((1, chunk, 1, ds), lambda b, n, ic: (b, ic, 0, 0)),
            pl.BlockSpec((1, chunk, 1, ds), lambda b, n, ic: (b, ic, 0, 0)),
            pl.BlockSpec((1, block_nh, hd, ds), lambda b, n, ic: (b, n, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, block_nh, hd),
                         lambda b, n, ic: (b, ic, n, 0)),
            pl.BlockSpec((1, block_nh, hd, ds), lambda b, n, ic: (b, n, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, S, nh, hd), jnp.float32),
            jax.ShapeDtypeStruct((B, nh, hd, ds), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((block_nh, hd, ds), jnp.float32)],
        interpret=interpret,
        name="ssd_scan",
    )(x, dt, A, Bm, Cm, initial_state)
    return y, hout
