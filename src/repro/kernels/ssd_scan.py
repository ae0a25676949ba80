"""Mamba2 SSD chunked-scan Pallas kernel — TPU target.

The CUDA Mamba kernels are warp-level selective scans; the TPU-native SSD
formulation (Dao & Gu 2024) replaces them with chunk-local dense matmuls
(MXU) plus a sequential inter-chunk state recurrence, which maps exactly onto
a Pallas grid whose chunk axis is innermost-sequential with the running state
(hd × N) held in VMEM scratch.

Inputs (per head h folded into the grid):
  x:  (BH, S, hd)      dt: (BH, S)        A: (BH,)  (negative decay rate)
  Bm: (BH, S, N)       Cm: (BH, S, N)
Output: y (BH, S, hd) — Σ_{k≤q} exp(cs_q − cs_k)·(C_q·B_k)·dt_k·x_k.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .platform import by_platform


def _ssd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, s_scr, *, chunk: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        s_scr[...] = jnp.zeros_like(s_scr)

    x = x_ref[0].astype(jnp.float32)          # (c, hd)
    dt = dt_ref[0].astype(jnp.float32)        # (c,)
    A = a_ref[0]                               # scalar
    Bm = b_ref[0].astype(jnp.float32)          # (c, N)
    Cm = c_ref[0].astype(jnp.float32)          # (c, N)

    dA = dt * A                                # (c,) ≤ 0
    cs = jnp.cumsum(dA)                        # (c,)
    seg = cs[:, None] - cs[None, :]            # (c_q, c_k)
    iota = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    iotk = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    seg = jnp.where(iota >= iotk, seg, -1e30)  # mask BEFORE exp
    L = jnp.exp(seg)

    CB = jnp.dot(Cm, Bm.T, preferred_element_type=jnp.float32)   # (c, c)
    M = CB * L * dt[None, :]
    y_intra = jnp.dot(M, x, preferred_element_type=jnp.float32)  # (c, hd)

    # inter-chunk: contribution of the incoming state
    decay_in = jnp.exp(cs)                      # (c,)
    y_inter = decay_in[:, None] * jnp.dot(Cm, s_scr[...].T,
                                          preferred_element_type=jnp.float32)
    y_ref[0] = (y_intra + y_inter).astype(y_ref.dtype)

    # state update: S ← exp(cs_end)·S + Σ_k exp(cs_end − cs_k)·dt_k·x_k⊗B_k
    decay_out = jnp.exp(cs[-1] - cs) * dt       # (c,)
    s_new = jnp.dot((x * decay_out[:, None]).T, Bm,
                    preferred_element_type=jnp.float32)          # (hd, N)
    s_scr[...] = s_scr[...] * jnp.exp(cs[-1]) + s_new


@functools.partial(jax.jit, static_argnames=("chunk",))
def ssd_scan(
    x: jax.Array, dt: jax.Array, A: jax.Array, Bm: jax.Array, Cm: jax.Array,
    *, chunk: int = 128,
) -> jax.Array:
    BH, S, hd = x.shape
    N = Bm.shape[-1]
    c = min(chunk, S)
    while S % c:
        c -= 1
    grid = (BH, S // c)
    call = lambda interp, *args: pl.pallas_call(
        functools.partial(_ssd_kernel, chunk=c),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, c, hd), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, c), lambda b, i: (b, i)),
            pl.BlockSpec((1,), lambda b, i: (b,)),
            pl.BlockSpec((1, c, N), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, c, N), lambda b, i: (b, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, c, hd), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, S, hd), x.dtype),
        scratch_shapes=[pltpu.VMEM((hd, N), jnp.float32)],
        interpret=interp,
    )(*args)
    return by_platform(call, x, dt, A, Bm, Cm)
