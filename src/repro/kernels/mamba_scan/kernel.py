"""Selective-scan (Mamba-1) Pallas TPU kernel.

TPU adaptation: a CUDA selective-scan holds per-thread recurrence state in
registers; here the channel axis is blocked to the VPU lane width (128
multiples) and the [block_c x d_state] state tile lives in VMEM scratch,
persisting across the sequence-chunk sweep (grid innermost axis).  Within
a chunk the recurrence runs as a fori_loop over timesteps on VMEM tiles;
chunk x block_c tiles of x/dt stream from HBM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _scan_kernel(x_ref, dt_ref, b_ref, c_ref, a_ref, d_ref, y_ref,
                 hout_ref, h_ref, *, chunk: int, n_chunks: int):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    a = -jnp.exp(a_ref[...].astype(jnp.float32))        # [bc, ds]
    d = d_ref[...].astype(jnp.float32)                  # [bc]

    def step(t, _):
        x_t = x_ref[0, t].astype(jnp.float32)           # [bc]
        dt_t = dt_ref[0, t].astype(jnp.float32)         # [bc]
        b_t = b_ref[0, t].astype(jnp.float32)           # [ds]
        c_t = c_ref[0, t].astype(jnp.float32)           # [ds]
        h = h_ref[...]
        h = jnp.exp(dt_t[:, None] * a) * h + \
            (dt_t * x_t)[:, None] * b_t[None, :]
        h_ref[...] = h
        y = jnp.sum(h * c_t[None, :], axis=1) + x_t * d
        y_ref[0, t] = y.astype(y_ref.dtype)
        return 0

    jax.lax.fori_loop(0, chunk, step, 0)

    @pl.when(ic == n_chunks - 1)
    def _fin():
        hout_ref[0] = h_ref[...].astype(hout_ref.dtype)


def mamba_scan_kernel(xc, dt, b, c, a_log, d, h0=None, *, chunk: int = 256,
                      block_c: int = 128, interpret: bool = False):
    """xc,dt [B,S,di]; b,c [B,S,ds]; a_log [di,ds]; d [di]
    -> (y [B,S,di], h_final [B,di,ds]).  h0 must be zeros (cache handoff
    restarts use the decode path)."""
    bsz, s, di = xc.shape
    ds = b.shape[-1]
    chunk = min(chunk, s)
    block_c = min(block_c, di)
    assert s % chunk == 0 and di % block_c == 0
    n_chunks, n_cb = s // chunk, di // block_c
    grid = (bsz, n_cb, n_chunks)
    kernel = functools.partial(_scan_kernel, chunk=chunk,
                               n_chunks=n_chunks)
    y, h_final = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, block_c),
                         lambda bi, ci, ii: (bi, ii, ci)),
            pl.BlockSpec((1, chunk, block_c),
                         lambda bi, ci, ii: (bi, ii, ci)),
            pl.BlockSpec((1, chunk, ds), lambda bi, ci, ii: (bi, ii, 0)),
            pl.BlockSpec((1, chunk, ds), lambda bi, ci, ii: (bi, ii, 0)),
            pl.BlockSpec((block_c, ds), lambda bi, ci, ii: (ci, 0)),
            pl.BlockSpec((block_c,), lambda bi, ci, ii: (ci,)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, block_c),
                         lambda bi, ci, ii: (bi, ii, ci)),
            pl.BlockSpec((1, block_c, ds), lambda bi, ci, ii: (bi, ci, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bsz, s, di), xc.dtype),
            jax.ShapeDtypeStruct((bsz, di, ds), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((block_c, ds), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(xc, dt, b, c, a_log, d)
    return y, h_final
