"""Generic tiled matmul Pallas kernel — the MXU building block for the BL
compute hot spots (basis projection Γ = VᵀAV, GLM Hessian AᵀDA).

BlockSpec tiling: (bm × bk) · (bk × bn) tiles staged through VMEM, f32
accumulation in a VMEM scratch across the k-grid (TPU grids iterate the last
dimension fastest and sequentially, so the scratch carries between k steps).
Tile sizes default to 128/256 — MXU-aligned (multiples of 128) per the
hardware-adaptation notes in docs/ARCHITECTURE.md (§Pallas switches).
Interpreted on the CPU, compiled to Mosaic on a TPU (`platform.by_platform`).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .platform import by_platform


def _matmul_kernel(a_ref, b_ref, o_ref, acc_ref, *, nk: int):
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        a_ref[...].astype(jnp.float32),
        b_ref[...].astype(jnp.float32),
        preferred_element_type=jnp.float32,
    )

    @pl.when(ki == nk - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _pad_axis(x, ax, mult):
    r = (-x.shape[ax]) % mult
    if not r:
        return x
    pads = [(0, 0)] * x.ndim
    pads[ax] = (0, r)
    return jnp.pad(x, pads)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "out_dtype"))
def matmul(
    a: jax.Array,
    b: jax.Array,
    *,
    bm: int = 128,
    bn: int = 128,
    bk: int = 256,
    out_dtype=jnp.float32,
) -> jax.Array:
    """C = A @ B with (bm, bn, bk) VMEM tiles; pads to tile multiples."""
    M, K = a.shape
    K2, N = b.shape
    assert K == K2, (a.shape, b.shape)
    bm_, bn_, bk_ = min(bm, M), min(bn, N), min(bk, K)
    a_p = _pad_axis(_pad_axis(a, 0, bm_), 1, bk_)
    b_p = _pad_axis(_pad_axis(b, 0, bk_), 1, bn_)
    Mp, Kp = a_p.shape
    _, Np = b_p.shape
    grid = (Mp // bm_, Np // bn_, Kp // bk_)

    call = lambda interp, a_p, b_p: pl.pallas_call(
        functools.partial(_matmul_kernel, nk=grid[2]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm_, bk_), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk_, bn_), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((bm_, bn_), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Mp, Np), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm_, bn_), jnp.float32)],
        interpret=interp,
    )(a_p, b_p)
    out = by_platform(call, a_p, b_p)
    return out[:M, :N]
