"""Two-sided basis transform A · gᵢ · B over a client stack as a Pallas
kernel (the BL-DNN rotation hot spot).

The pytree bases (`repro.core.basis.PerLayerSVDBasis` and the structured
DCT/Hadamard kinds) rotate every 2-D weight leaf of every client's gradient:
``(n, d1, d2)`` stacks hit ``Uᵀ g V`` (forward) and ``U c Vᵀ`` (backward)
each round.  On TPU the two products stay in VMEM: the grid walks
(client, row tile, column tile) of the output, the left product
``A[rows] @ gᵢ`` is computed once per (client, row tile) into a VMEM
scratch at the first column tile and reused by the others, and each step
multiplies it by one column tile of B.  A step holds one leaf, a row tile
of A and a column tile of B, so a 1024×1024 leaf fits the default VMEM
budget (the whole-factor layout it replaces did not).

The kernel computes ``(A @ gᵢ) @ B`` in the same association order as the
engine's default ``A @ g @ B`` (python ``@`` is left-associative), in f32;
tests/test_basis_ship.py compares it with the XLA path under
``REPRO_BL_PALLAS=1``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .platform import by_platform

#: output tile edge; dimensions that are not a multiple of it stay whole
_TILE = 128


def _tile(dim: int) -> int:
    return _TILE if dim % _TILE == 0 else dim


def _transform_kernel(a_ref, g_ref, b_ref, o_ref, t_ref):
    @pl.when(pl.program_id(2) == 0)
    def _left():
        t_ref[...] = jnp.dot(a_ref[...], g_ref[0],
                             preferred_element_type=jnp.float32)

    o_ref[0] = jnp.dot(t_ref[...], b_ref[...],
                       preferred_element_type=jnp.float32)


@jax.jit
def basis_transform(A: jax.Array, g: jax.Array, B: jax.Array) -> jax.Array:
    """``A @ g[i] @ B`` for every client i: (da, d1) × (n, d1, d2) ×
    (d2, db) → (n, da, db), tiled over the output (see the module
    docstring).  f32 only."""
    if g.ndim != 3:
        raise ValueError(f"expected a client-stacked (n, d1, d2) leaf, "
                         f"got shape {g.shape}")
    for name, x in (("A", A), ("g", g), ("B", B)):
        if x.dtype != jnp.float32:
            raise TypeError(f"basis_transform is f32-only, {name} is "
                            f"{x.dtype}")
    n, d1, d2 = g.shape
    da, db = A.shape[0], B.shape[1]
    if A.shape[1] != d1 or B.shape[0] != d2:
        raise ValueError(
            f"factor/leaf shape mismatch: A {A.shape} · g {g.shape} · "
            f"B {B.shape}")
    ta, tb = _tile(da), _tile(db)
    # block index 0 is spelled ``grid index * 0``: a python 0 is int64
    # under x64, which Mosaic refuses
    call = lambda interp, A, g, B: pl.pallas_call(
        _transform_kernel,
        grid=(n, da // ta, db // tb),
        in_specs=[
            pl.BlockSpec((ta, d1), lambda i, a, b: (a, a * 0)),
            pl.BlockSpec((1, d1, d2), lambda i, a, b: (i, i * 0, i * 0)),
            pl.BlockSpec((d2, tb), lambda i, a, b: (b * 0, b)),
        ],
        out_specs=pl.BlockSpec((1, ta, tb), lambda i, a, b: (i, a, b)),
        out_shape=jax.ShapeDtypeStruct((n, da, db), jnp.float32),
        scratch_shapes=[pltpu.VMEM((ta, d2), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interp,
    )(A, g, B)
    return by_platform(call, A, g, B)
