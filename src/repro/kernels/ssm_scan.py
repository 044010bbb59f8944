"""Pallas TPU kernel for the Mamba within-chunk selective scan.

Contract (matches repro.models.ssm._chunk_scan): given discretised
transition da and input dbx, both (B, L, D, ST), compute the inclusive scan
h_t = da_t * h_{t-1} + dbx_t from h_0 = 0 and return all h_t.

The recurrence is elementwise over (D, ST), so the wrapper flattens those
two axes into one lane-dense axis of D·ST: a (D, ST) tile would pad its
ST=16 lanes out to the 128-lane vreg width and spend 8× the VMEM it holds.
Grid: (B, n_lane_blocks); each program owns an (L, block_d·ST) tile and
runs the L-step recurrence in VMEM with a fori_loop, carrying one
(1, block_d·ST) row of state.  The default ``block_d=128`` keeps the three
double-buffered tiles at 12 MB for L=256, ST=16 — under the 16 MB default
scoped VMEM of a v5e.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(da_ref, dbx_ref, h_ref, *, length: int):
    def body(t, carry):
        h = da_ref[0, pl.ds(t, 1), :] * carry + dbx_ref[0, pl.ds(t, 1), :]
        h_ref[0, pl.ds(t, 1), :] = h.astype(h_ref.dtype)
        return h

    jax.lax.fori_loop(0, length, body,
                      jnp.zeros((1, h_ref.shape[-1]), jnp.float32))


@functools.partial(jax.jit, static_argnames=("block_d", "interpret"))
def ssm_chunk_scan(da, dbx, *, interpret: bool, block_d: int = 128):
    """da, dbx: (B, L, D, ST) fp32 -> h: (B, L, D, ST) fp32."""
    b, l, d, st = da.shape
    block_d = min(block_d, d)
    pad = (-d) % block_d
    if pad:
        da = jnp.pad(da, ((0, 0), (0, 0), (0, pad), (0, 0)))
        dbx = jnp.pad(dbx, ((0, 0), (0, 0), (0, pad), (0, 0)))
    nd = (d + pad) // block_d
    lanes = block_d * st
    flat = (b, l, (d + pad) * st)

    out = pl.pallas_call(
        functools.partial(_kernel, length=l),
        grid=(b, nd),
        in_specs=[
            pl.BlockSpec((1, l, lanes), lambda bi, di: (bi, 0, di)),
            pl.BlockSpec((1, l, lanes), lambda bi, di: (bi, 0, di)),
        ],
        out_specs=pl.BlockSpec((1, l, lanes), lambda bi, di: (bi, 0, di)),
        out_shape=jax.ShapeDtypeStruct(flat, jnp.float32),
        interpret=interpret,
    )(da.reshape(flat), dbx.reshape(flat))
    return out.reshape(b, l, d + pad, st)[:, :, :d]
