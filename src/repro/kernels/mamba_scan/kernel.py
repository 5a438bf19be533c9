"""Pallas TPU selective-scan kernel (Mamba-1).

TPU adaptation of the CUDA selective-scan: instead of warp-level parallel
prefix products, the channel axis E is blocked over a *parallel* grid
dimension (each (batch, channel-block) pair is an independent recurrence)
and time is blocked over an *arbitrary* (sequential) grid dimension with
the SSM state ``h (be, N)`` carried across chunks in VMEM scratch.  Inside
one time chunk the recurrence runs as a ``fori_loop`` over VREG-resident
slices — HBM traffic is exactly one read of (x, delta, B, C) and one write
of y per token, the roofline optimum for this memory-bound op.

VMEM: chunk=256, be=256, N=16 -> x/delta/y slabs 3*256*256*4 = 768 KiB,
B/C 2*256*16*4 = 32 KiB, h 256*16*4 = 16 KiB.
"""
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, d_ref, A_ref, B_ref, C_ref, D_ref, y_ref, h_ref, *,
            chunk: int):
    c = pl.program_id(2)

    @pl.when(c == 0)
    def _init():
        h_ref[:] = jnp.zeros_like(h_ref)

    A = A_ref[:]                                   # (be, N)
    Dd = D_ref[:]                                  # (1, be)

    def step(t, h):
        xt = x_ref[0, t, :].astype(jnp.float32)    # (be,)
        dt = d_ref[0, t, :].astype(jnp.float32)    # (be,)
        bt = B_ref[0, t, :].astype(jnp.float32)    # (N,)
        ct = C_ref[0, t, :].astype(jnp.float32)    # (N,)
        dA = jnp.exp(dt[:, None] * A)              # (be, N)
        h = dA * h + (dt * xt)[:, None] * bt[None, :]
        y = jnp.sum(h * ct[None, :], axis=1) + Dd[0] * xt
        y_ref[0, t, :] = y.astype(y_ref.dtype)
        return h

    h_ref[:] = jax.lax.fori_loop(0, chunk, step, h_ref[:])


def selective_scan_pallas(x, delta, A, Bm, Cm, D, *, be: int = 256,
                          chunk: int = 256, interpret: bool = False):
    Bsz, S, E = x.shape
    N = A.shape[1]
    be = min(be, E)
    chunk = min(chunk, S)
    assert E % be == 0 and S % chunk == 0
    grid = (Bsz, E // be, S // chunk)
    D2 = D.reshape(1, E)

    kern = functools.partial(_kernel, chunk=chunk)
    y = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, be), lambda b, e, c: (b, c, e)),   # x
            pl.BlockSpec((1, chunk, be), lambda b, e, c: (b, c, e)),   # delta
            pl.BlockSpec((be, N), lambda b, e, c: (e, 0)),             # A
            pl.BlockSpec((1, chunk, N), lambda b, e, c: (b, c, 0)),    # B
            pl.BlockSpec((1, chunk, N), lambda b, e, c: (b, c, 0)),    # C
            pl.BlockSpec((1, be), lambda b, e, c: (0, e)),             # D
        ],
        out_specs=pl.BlockSpec((1, chunk, be), lambda b, e, c: (b, c, e)),
        out_shape=jax.ShapeDtypeStruct((Bsz, S, E), x.dtype),
        scratch_shapes=[pltpu.VMEM((be, N), jnp.float32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(x, delta, A, Bm, Cm, D2)
    return y
