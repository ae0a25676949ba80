"""Exact |·|-Top-K threshold selection as a Pallas kernel (the compression
engine's measured hot spot).

GPU Top-K implementations sort (or radix-select) the |values|; TPUs have no
efficient global sort, and XLA's CPU fallback decomposes a partially-dead
``top_k`` into a full stable sort (~75× slower on the engine's d²
coefficient arrays — the reason the XLA selection path needs
``optimization_barrier``s, see `repro.core.compressors.topk_keep_mask`).
This kernel instead finds, per row, the EXACT k-th largest |value| by a
bitwise binary search over f32 bit patterns:

  * |x| ≥ 0, and the IEEE-754 bit pattern of a non-negative float is
    monotone in its value, so selection runs on int32 keys (sign bit 0);
  * 31 count-passes (one per non-sign bit, high → low) greedily build the
    largest threshold t with count(|x| ≥ t) ≥ k — which is exactly the
    k-th largest magnitude, ties included;
  * each pass is a vectorized compare+reduce over the VMEM-resident row —
    no sort, no scatter, O(31·T) work per row, trivially batched over the
    engine's client axis by the grid.

The returned threshold equals ``lax.top_k(|x|, k)[0][..., -1]`` bitwise, so
the shared tie-break algebra (`keep_mask`) selects the SAME entries as the
barrier'd XLA path — that is what lets ``REPRO_BL_PALLAS=1`` swap selection
backends without perturbing trajectories (tests/test_pallas_parity.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .platform import by_platform


def keep_mask(a32: jax.Array, t: jax.Array, k: int) -> jax.Array:
    """Exactly-k selection mask from a per-row threshold, along the last axis.

    `a32` are non-negative f32 magnitudes, `t` the k-th largest per row
    (shape ``a32.shape[:-1] + (1,)``).  Entries strictly above t are kept;
    the tie group at t is broken by earliest index.  This is the ONE
    tie-break rule both selection backends (Pallas kernel / barrier'd XLA
    ``top_k``) feed — identical thresholds ⇒ identical masks
    (`keep_mask_search` is the same rule in a form a TPU kernel lowers).
    """
    above = a32 > t
    eq = a32 == t
    n_above = jnp.sum(above, axis=-1, keepdims=True)
    cum = jnp.cumsum(eq, axis=-1)
    return above | (eq & (cum <= k - n_above))


def keep_mask_search(a32: jax.Array, t: jax.Array, k: int) -> jax.Array:
    """`keep_mask` without a prefix sum, for kernels: the same mask (the
    tie group at ``t`` broken by earliest index), found by a binary search
    for the tie-group cut-off index instead of a cumsum, which the TPU's
    kernel compiler cannot lower.

    With ``need = k - n_above`` tied entries to keep, the cut-off is the
    largest ``L`` in [0, T] with count(eq & index < L) ≤ need; the kept
    ties are those with index < L — exactly the entries whose inclusive
    tie count is ≤ need.  ⌈log₂(T+1)⌉ compare+reduce passes."""
    T = a32.shape[-1]
    above = a32 > t
    eq = a32 == t
    need = k - jnp.sum(above, axis=-1, keepdims=True, dtype=jnp.int32)
    idx = jax.lax.broadcasted_iota(jnp.int32, a32.shape, a32.ndim - 1)

    def body(i, cut):
        cand = cut | (jnp.int32(1) << (jnp.int32(T.bit_length() - 1) - i))
        cnt = jnp.sum(eq & (idx < cand), axis=-1, keepdims=True,
                      dtype=jnp.int32)
        return jnp.where((cand <= T) & (cnt <= need), cand, cut)

    # int32 loop bounds and counts: under x64 a python-int bound or a
    # default-dtype sum is int64, which Mosaic refuses
    cut = jax.lax.fori_loop(jnp.int32(0), jnp.int32(T.bit_length()), body,
                            jnp.zeros(need.shape, jnp.int32))
    return above | (eq & (idx < cut))


def _row_threshold(keys: jax.Array, k: int) -> jax.Array:
    """Per-row k-th largest int32 key of non-negative f32 bit patterns:
    31 greedy count passes, one per non-sign bit, high → low."""
    def body(i, t):
        cand = t | (jnp.int32(1) << (jnp.int32(30) - i))
        cnt = jnp.sum(keys >= cand, axis=1, keepdims=True, dtype=jnp.int32)
        return jnp.where(cnt >= k, cand, t)

    return jax.lax.fori_loop(jnp.int32(0), jnp.int32(31), body,
                             jnp.zeros((keys.shape[0], 1), jnp.int32))


#: rows per grid step of `topk_row_threshold` (the f32 sublane tile)
_ROWS = 8
#: lanes of the threshold output block (the lane tile; column 0 is read)
_LANES = 128


def _threshold_kernel(a_ref, t_ref, *, k: int):
    a = a_ref[...]                                     # (8, T) f32, |values|
    keys = jax.lax.bitcast_convert_type(a, jnp.int32)  # monotone for a ≥ 0
    t = jax.lax.bitcast_convert_type(_row_threshold(keys, k), jnp.float32)
    t_ref[...] = jnp.broadcast_to(t, t_ref.shape)


@functools.partial(jax.jit, static_argnames=("k",))
def topk_row_threshold(a32: jax.Array, k: int) -> jax.Array:
    """Per-row exact k-th largest of non-negative f32 `a32` (rows, T) →
    (rows, 1).  k is clamped to [1, T] — a threshold is undefined for an
    empty kept set; callers wanting k = 0 handle it before selection (see
    `topk_threshold`).  Rows go through the grid 8 at a time (zero rows
    pad the last block) and each block writes a lane-wide (8, 128)
    output tile, so every block shape is a whole TPU tile.  Block index
    maps spell 0 as ``i * 0``: a python 0 is int64 under x64, which
    Mosaic refuses."""
    rows, T = a32.shape
    kk = max(1, min(k, T))
    rows_p = -(-rows // _ROWS) * _ROWS
    a_p = jnp.pad(a32, ((0, rows_p - rows), (0, 0)))
    call = lambda interp, a: pl.pallas_call(
        functools.partial(_threshold_kernel, k=kk),
        grid=(rows_p // _ROWS,),
        in_specs=[pl.BlockSpec((_ROWS, T), lambda i: (i, i * 0))],
        out_specs=pl.BlockSpec((_ROWS, _LANES), lambda i: (i, i * 0)),
        out_shape=jax.ShapeDtypeStruct((rows_p, _LANES), jnp.float32),
        interpret=interp,
    )(a)
    return by_platform(call, a_p)[:rows, :1]


def _compress_sum_kernel(v_ref, out_ref, s_ref, *, k: int):
    """Fused compress-then-reduce over a whole (n, T) client stack in VMEM:
    per-row threshold search (the same 31-pass bitwise binary search as
    `_threshold_kernel`, vectorized over rows), the shared tie-break rule
    (`keep_mask_search`), the dense masked values, AND the local
    cross-client partial sum — one pass, one kernel."""
    v = v_ref[...]                                     # (n, T) f32 values
    a = jnp.abs(v)
    keys = jax.lax.bitcast_convert_type(a, jnp.int32)  # monotone for a ≥ 0
    tf = jax.lax.bitcast_convert_type(_row_threshold(keys, k), jnp.float32)
    out = jnp.where(keep_mask_search(a, tf, k), v, jnp.zeros_like(v))
    out_ref[...] = out
    s_ref[...] = jnp.sum(out, axis=0, keepdims=True)   # client-axis partial


@functools.partial(jax.jit, static_argnames=("k",))
def topk_compress_sum(v: jax.Array, k: int):
    """Exact |·|-Top-K of each row of f32 `v` (n, T) fused with the sum of
    the compressed rows: returns ``(dense (n, T), col_sum (T,))`` with
    ``col_sum == dense.sum(axis=0)``.

    The threshold is `topk_row_threshold`'s and the tie-break mask is
    `keep_mask`'s, so ``dense`` is bitwise the two-pass selection's output
    and ``col_sum`` is bitwise the XLA reduction of it — the fusion saves
    a pass over the stack, not an ulp (pinned by tests/test_kernels.py
    and tests/test_pallas_parity.py).  k is clamped to [1, T] like
    `topk_row_threshold`."""
    if v.dtype != jnp.float32:
        raise TypeError(
            f"topk_compress_sum runs its bitwise search on f32 bit "
            f"patterns, got {v.dtype}")
    n, T = v.shape
    kk = max(1, min(k, T))
    call = lambda interp, v: pl.pallas_call(
        functools.partial(_compress_sum_kernel, k=kk),
        out_shape=(jax.ShapeDtypeStruct((n, T), jnp.float32),
                   jax.ShapeDtypeStruct((1, T), jnp.float32)),
        interpret=interp,
    )(v)
    out, s = by_platform(call, v)
    return out, s[0]


@functools.partial(jax.jit, static_argnames=("k",))
def topk_threshold(x: jax.Array, k: int):
    """Global exact Top-K over a whole tensor (flattened): returns
    ``(compressed_dense, threshold, kept_count)`` with kept_count == min(k,
    numel) exactly (tie group broken by earliest index).  k ≤ 0 keeps
    nothing (threshold +inf)."""
    shape = x.shape
    flat = x.reshape(1, -1)
    if k <= 0:
        return (jnp.zeros_like(x), jnp.asarray(jnp.inf, jnp.float32),
                jnp.asarray(0, jnp.int32))
    kk = min(k, flat.shape[1])
    a32 = jnp.abs(flat).astype(jnp.float32)
    t = topk_row_threshold(a32, kk)
    mask = keep_mask(a32, t, kk)
    out = jnp.where(mask, flat, jnp.zeros_like(flat))
    return out.reshape(shape), t[0, 0], jnp.sum(mask)
