"""Pallas TPU flash attention (fwd) — blockwise online softmax.

Tiling (FlashAttention re-thought for VMEM/MXU, not a CUDA port):
* grid ``(B, Hq, Sq/bq, Skv/bk)``; the KV dimension is the innermost,
  sequential ("arbitrary") grid axis — running max ``m``, normalizer ``l``
  and the output accumulator live in VMEM scratch across KV steps.
* block shapes ``(bq, D)`` / ``(bk, D)`` with ``D`` padded to 128 by the
  caller — MXU-aligned matmul dims; default bq=bk=512 keeps the working
  set (q, k, v, s, acc ≈ bq*D + 2*bk*D + bq*bk + bq*D floats ≈ 2.5 MiB
  at D=128) comfortably inside the ~16 MiB v5e VMEM.
* GQA is expressed in the ``index_map`` — query head ``h`` reads KV head
  ``h // group`` — no repeated KV materialization in HBM.
* causal masking uses global row/col ids; fully-masked KV blocks are
  skipped with ``pl.when`` (upper-triangle blocks cost ~0).
"""
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            scale: float, causal: bool, bq: int, bk: int, skv: int,
            sq: int):
    qi = pl.program_id(2)
    kj = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(kj == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    # global row/col coordinates (right-aligned causal for Sq < Skv)
    row = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0) \
        + (skv - sq)
    col = kj * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)

    if causal:  # skip fully-masked upper-triangle KV blocks
        live = kj * bk <= qi * bq + (bq - 1) + (skv - sq)
    else:
        live = jnp.bool_(True)

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale          # (bq, D)
        k = k_ref[0, 0].astype(jnp.float32)                  # (bk, D)
        v = v_ref[0, 0].astype(jnp.float32)                  # (bk, D)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if causal:
            s = jnp.where(col > row, NEG_INF, s)
        m_prev = m_ref[:]                                    # (bq, 1)
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                               # (bq, bk)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[:] = l_ref[:] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[:] = m_new

    @pl.when(kj == nk - 1)
    def _finalize():
        l = jnp.maximum(l_ref[:], 1e-30)
        o_ref[0, 0] = (acc_ref[:] / l).astype(o_ref.dtype)


def flash_attention_fwd(q, k, v, *, causal: bool = True,
                        scale: float | None = None, bq: int = 512,
                        bk: int = 512, interpret: bool = False):
    """q: (B, Hq, Sq, D); k,v: (B, Hkv, Skv, D) -> (B, Hq, Sq, D)."""
    B, Hq, Sq, D = q.shape
    Hkv, Skv = k.shape[1], k.shape[2]
    group = Hq // Hkv
    bq = min(bq, Sq)
    bk = min(bk, Skv)
    assert Sq % bq == 0 and Skv % bk == 0, (Sq, bq, Skv, bk)
    scale = scale if scale is not None else D ** -0.5
    grid = (B, Hq, Sq // bq, Skv // bk)

    kern = functools.partial(_kernel, scale=scale, causal=causal,
                             bq=bq, bk=bk, skv=Skv, sq=Sq)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bk, D),
                         lambda b, h, i, j, g=group: (b, h // g, j, 0)),
            pl.BlockSpec((1, 1, bk, D),
                         lambda b, h, i, j, g=group: (b, h // g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hq, Sq, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, D), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
    )(q, k, v)
