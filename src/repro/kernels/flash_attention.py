"""Flash attention (blocked online-softmax) Pallas kernel — TPU target.

Grid (B·H, Sq/bq, Sk/bk); the k-grid is innermost and sequential on TPU, so
the running max / denominator / accumulator live in VMEM scratch across k
steps.  Supports causal and sliding-window masking (mask-based: TPU grids are
static, so fully-masked blocks are computed-and-masked rather than skipped —
the roofline ratio in README.md §EXPERIMENTS quantifies that 2× causal
overhead).

q: (BH, Sq, hd)   k, v: (BH, Sk, hd)   → o: (BH, Sq, hd)
GQA is handled by the ops.py wrapper (q heads grouped, k/v broadcast by
index mapping — no KV materialization).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .platform import by_platform

NEG = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
                  *, scale: float, causal: bool, window: Optional[int],
                  bq: int, bk: int, nk: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32)          # (bq, hd)
    k = k_ref[0].astype(jnp.float32)          # (bk, hd)
    s = jnp.dot(q * scale, k.T, preferred_element_type=jnp.float32)  # (bq, bk)

    qpos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    kpos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = jnp.ones((bq, bk), jnp.bool_)
    if causal:
        mask = mask & (kpos <= qpos)
    if window is not None:
        mask = mask & (kpos > qpos - window)
    s = jnp.where(mask, s, NEG)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new[:, None])
    l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1)
    m_scr[...] = m_new
    acc_scr[...] = acc_scr[...] * alpha[:, None] + jnp.dot(
        p, v_ref[0].astype(jnp.float32), preferred_element_type=jnp.float32)

    @pl.when(ki == nk - 1)
    def _done():
        denom = jnp.maximum(l_scr[...], 1e-30)[:, None]
        o_ref[0] = (acc_scr[...] / denom).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "window", "bq", "bk"))
def flash_attention(
    q: jax.Array, k: jax.Array, v: jax.Array, *,
    causal: bool = True,
    window: Optional[int] = None,
    bq: int = 128,
    bk: int = 128,
) -> jax.Array:
    BH, Sq, hd = q.shape
    Sk = k.shape[1]
    bq_, bk_ = min(bq, Sq), min(bk, Sk)
    while Sq % bq_:
        bq_ -= 1
    while Sk % bk_:
        bk_ -= 1
    grid = (BH, Sq // bq_, Sk // bk_)
    scale = hd ** -0.5
    call = lambda interp, q, k, v: pl.pallas_call(
        functools.partial(_flash_kernel, scale=scale, causal=causal,
                          window=window, bq=bq_, bk=bk_, nk=grid[2]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq_, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk_, hd), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk_, hd), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq_, hd), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, Sq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq_,), jnp.float32),
            pltpu.VMEM((bq_,), jnp.float32),
            pltpu.VMEM((bq_, hd), jnp.float32),
        ],
        interpret=interp,
    )(q, k, v)
    return by_platform(call, q, k, v)
