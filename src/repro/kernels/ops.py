"""Jit'd public wrappers around the Pallas kernels.

Each kernel is interpreted when its program is lowered for the CPU and
compiled to Mosaic when it is lowered for a TPU (`platform.by_platform`).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from .basis_transform import basis_transform as _basis_transform
from .flash_attention import flash_attention as _flash
from .ssd_scan import ssd_scan as _ssd
from .tiled_matmul import matmul as _matmul
from .topk_threshold import topk_threshold as _topk

def matmul(a, b, out_dtype=jnp.float32, **tiles):
    return _matmul(a, b, out_dtype=out_dtype, **tiles)


def basis_project(V, A, **tiles):
    """Γ = Vᵀ A V — the per-iteration BL coefficient computation (Eq. 5).

    Accepts a leading batch dimension (the batched BL engine's stacked-client
    layout): V (n, d, r) with A (n, d, d) → (n, r, r), mapped over the same
    tiled Pallas matmul kernel.  2-D inputs keep the original single-client
    path.  The kernel computes in f32 (MXU), so float64 operands are cast
    to f32 before it (a TPU kernel takes no 64-bit operands) — use the
    engine's default einsum path when float64 trajectories matter (CPU
    parity tests).
    """
    V, A = V.astype(jnp.float32), A.astype(jnp.float32)
    if A.ndim == 3:
        if V.ndim == 2:
            V = jnp.broadcast_to(V, (A.shape[0],) + V.shape)

        def _one(Vi, Ai):
            T = matmul(Ai, Vi, **tiles)                  # (d, r)
            return matmul(Vi.T, T, **tiles)              # (r, r)

        return jax.vmap(_one)(V, A)
    T = matmul(A, V, **tiles)          # (d, r)
    return matmul(V.T, T, **tiles)     # (r, r)


def basis_transform(A, g, B):
    """A · gᵢ · B over a client-stacked (n, d1, d2) leaf — the pytree-basis
    rotation (Uᵀ g V / U c Vᵀ), tiled over the output per client (see
    kernels/basis_transform.py)."""
    return _basis_transform(A, g, B)


def glm_hessian(A, w, lam, **tiles):
    """(1/m) Aᵀ diag(w) A + λI — fused GLM Hessian (Eq. 3)."""
    m, d = A.shape
    Aw = A * w[:, None].astype(A.dtype)
    H = matmul(A.T, Aw, **tiles) / m
    return H + lam * jnp.eye(d, dtype=H.dtype)


def topk_compress(x, k: int):
    """Exact Top-K via the bitwise-binary-search threshold kernel (see
    topk_threshold.py) — keeps exactly min(k, numel) entries, ties broken
    by earliest index.  Returns (compressed_dense, kept_count)."""
    out, _, kept = _topk(x, k)
    return out, kept


def attention(q, k, v, *, causal=True, window: Optional[int] = None,
              bq: int = 128, bk: int = 128):
    """Flash attention over (B, S, H, hd) with GQA: kv heads broadcast via
    index mapping (fold heads into batch; repeat kv cheaply by gather)."""
    B, Sq, H, hd = q.shape
    KVH = k.shape[2]
    rep = H // KVH
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, Sq, hd)
    kf = jnp.repeat(k.transpose(0, 2, 1, 3), rep, axis=1).reshape(B * H, -1, hd)
    vf = jnp.repeat(v.transpose(0, 2, 1, 3), rep, axis=1).reshape(B * H, -1, hd)
    o = _flash(qf, kf, vf, causal=causal, window=window, bq=bq, bk=bk)
    return o.reshape(B, H, Sq, hd).transpose(0, 2, 1, 3)


def ssd(x, dt, A, Bm, Cm, chunk: int = 128):
    """Mamba2 SSD over (BH, S, hd) heads-folded layout."""
    return _ssd(x, dt, A, Bm, Cm, chunk=chunk)
