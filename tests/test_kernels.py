"""Per-kernel allclose tests against the ref.py oracles, swept over shapes
and dtypes (the kernels run interpreted on the CPU; their TPU compiles are
rehearsed in tests/test_tpu_compile.py)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import ops, ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.ssd_scan import ssd_scan
from repro.kernels.tiled_matmul import matmul
from repro.kernels.topk_threshold import topk_threshold

RNG = np.random.default_rng(0)


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else dict(rtol=2e-4, atol=2e-4)


# ----------------------------- matmul ---------------------------------------
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape", [(64, 64, 64), (300, 500, 200), (128, 1, 7),
                                   (1, 257, 129), (513, 128, 255)])
def test_matmul_sweep(shape, dtype):
    M, K, N = shape
    a = jnp.asarray(RNG.standard_normal((M, K)), dtype)
    b = jnp.asarray(RNG.standard_normal((K, N)), dtype)
    out = matmul(a, b, bm=128, bn=128, bk=128)
    want = ref.matmul_ref(a, b)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-2 if dtype == jnp.bfloat16 else 1e-4,
                               atol=2e-1 if dtype == jnp.bfloat16 else 1e-3)


@settings(max_examples=10, deadline=None)
@given(m=st.integers(1, 200), k=st.integers(1, 200), n=st.integers(1, 200))
def test_matmul_property(m, k, n):
    a = jnp.asarray(np.random.default_rng(m * k).standard_normal((m, k)), jnp.float32)
    b = jnp.asarray(np.random.default_rng(k * n + 1).standard_normal((k, n)), jnp.float32)
    out = matmul(a, b, bm=64, bn=64, bk=64)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref.matmul_ref(a, b)),
                               rtol=1e-4, atol=1e-3)


# ----------------------------- flash attention ------------------------------
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("cfg", [
    dict(BH=2, Sq=128, Sk=128, hd=64, causal=True, window=None),
    dict(BH=1, Sq=256, Sk=256, hd=32, causal=True, window=64),
    dict(BH=3, Sq=64, Sk=192, hd=64, causal=False, window=None),
    dict(BH=2, Sq=96, Sk=96, hd=128, causal=True, window=17),
])
def test_flash_attention_sweep(cfg, dtype):
    q = jnp.asarray(RNG.standard_normal((cfg["BH"], cfg["Sq"], cfg["hd"])), dtype)
    k = jnp.asarray(RNG.standard_normal((cfg["BH"], cfg["Sk"], cfg["hd"])), dtype)
    v = jnp.asarray(RNG.standard_normal((cfg["BH"], cfg["Sk"], cfg["hd"])), dtype)
    o = flash_attention(q, k, v, causal=cfg["causal"], window=cfg["window"],
                        bq=64, bk=64)
    want = ref.attention_ref(q, k, v, causal=cfg["causal"], window=cfg["window"])
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


def test_flash_gqa_wrapper_matches_blocked_model_attention():
    """ops.attention (GQA layout) vs the model's pure-jnp blocked attention."""
    from repro.models import layers as L
    B, S, H, KVH, hd = 2, 64, 4, 2, 32
    q = jnp.asarray(RNG.standard_normal((B, S, H, hd)), jnp.float32)
    k = jnp.asarray(RNG.standard_normal((B, S, KVH, hd)), jnp.float32)
    v = jnp.asarray(RNG.standard_normal((B, S, KVH, hd)), jnp.float32)
    o_kernel = ops.attention(q, k, v, causal=True, bq=32, bk=32)
    qg = q.reshape(B, S, KVH, H // KVH, hd)
    o_model = L._blocked_attn(qg, k, v, lambda qi, ki: ki <= qi, 32, None)
    np.testing.assert_allclose(np.asarray(o_kernel), np.asarray(o_model),
                               rtol=2e-4, atol=2e-4)


# ----------------------------- ssd scan -------------------------------------
@pytest.mark.parametrize("dtype", [jnp.float32])
@pytest.mark.parametrize("cfg", [
    dict(BH=2, S=64, hd=16, N=8, chunk=16),
    dict(BH=1, S=128, hd=32, N=16, chunk=32),
    dict(BH=4, S=96, hd=8, N=4, chunk=24),
    dict(BH=1, S=60, hd=16, N=8, chunk=32),  # chunk doesn't divide → shrink
])
def test_ssd_scan_sweep(cfg, dtype):
    rng = np.random.default_rng(cfg["S"])
    x = jnp.asarray(rng.standard_normal((cfg["BH"], cfg["S"], cfg["hd"])), dtype)
    dt = jnp.asarray(rng.random((cfg["BH"], cfg["S"])) * 0.5 + 0.01, jnp.float32)
    A = jnp.asarray(-rng.random(cfg["BH"]) - 0.1, jnp.float32)
    Bm = jnp.asarray(rng.standard_normal((cfg["BH"], cfg["S"], cfg["N"])), dtype)
    Cm = jnp.asarray(rng.standard_normal((cfg["BH"], cfg["S"], cfg["N"])), dtype)
    y = ssd_scan(x, dt, A, Bm, Cm, chunk=cfg["chunk"])
    want = ref.ssd_scan_ref(x, dt, A, Bm, Cm)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want),
                               rtol=1e-3, atol=1e-3)


def test_ssd_scan_matches_model_layer_math():
    """Kernel vs the model's _ssd_chunked (two independent implementations)."""
    from repro.models.layers import _ssd_chunked
    rng = np.random.default_rng(7)
    B, S, H, hd, N = 2, 64, 3, 16, 8
    xh = jnp.asarray(rng.standard_normal((B, S, H, hd)), jnp.float32)
    dt = jnp.asarray(rng.random((B, S, H)) * 0.5 + 0.01, jnp.float32)
    A = jnp.asarray(-rng.random(H) - 0.1, jnp.float32)
    Bm = jnp.asarray(rng.standard_normal((B, S, N)), jnp.float32)
    Cm = jnp.asarray(rng.standard_normal((B, S, N)), jnp.float32)
    y_model, _ = _ssd_chunked(xh, dt, A, Bm, Cm, chunk=16)
    # fold heads for the kernel: B,C shared across heads
    xf = xh.transpose(0, 2, 1, 3).reshape(B * H, S, hd)
    dtf = dt.transpose(0, 2, 1).reshape(B * H, S)
    Af = jnp.tile(A, B)
    Bf = jnp.repeat(Bm[:, None], H, 1).reshape(B * H, S, N)
    Cf = jnp.repeat(Cm[:, None], H, 1).reshape(B * H, S, N)
    y_kernel = ssd_scan(xf, dtf, Af, Bf, Cf, chunk=16)
    y_kernel = y_kernel.reshape(B, H, S, hd).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(y_kernel), np.asarray(y_model),
                               rtol=1e-3, atol=1e-3)


# ----------------------------- topk -----------------------------------------
@pytest.mark.parametrize("shape,k", [((64, 64), 10), ((100, 100), 50),
                                     ((33, 77), 1), ((128,), 100), ((16, 16, 16), 64)])
def test_topk_threshold_sweep(shape, k):
    """The bitwise-binary-search kernel finds the EXACT k-th largest |x|
    (in f32), and the shared tie-break mask keeps exactly k entries."""
    x = jnp.asarray(np.random.default_rng(k).standard_normal(shape), jnp.float32)
    out, t, kept = topk_threshold(x, k)
    n = int(np.prod(shape))
    kk = min(k, n)
    assert int(kept) == kk
    flat = np.abs(np.asarray(x)).ravel()
    # threshold is exactly the k-th largest magnitude
    assert float(t) == np.sort(flat)[-kk]
    # the kept set: everything strictly above t, none below t
    kept_mask = np.asarray(ref.topk_threshold_ref(x, t)).ravel() != 0
    out_mask = np.asarray(out).ravel() != 0
    assert out_mask[flat > float(t)].all()
    assert not out_mask[~kept_mask].any()


def test_topk_threshold_matches_xla_topk_bitwise():
    """Kernel threshold == `lax.top_k`'s k-th value bitwise — the property
    that makes REPRO_BL_PALLAS=1 selection trajectory-invariant."""
    from repro.kernels.topk_threshold import topk_row_threshold

    rng = np.random.default_rng(3)
    a = jnp.asarray(np.abs(rng.standard_normal((7, 333))), jnp.float32)
    for k in (1, 5, 332, 333):
        t_kernel = np.asarray(topk_row_threshold(a, k))
        t_xla = np.asarray(jax.lax.top_k(a, k)[0][:, -1:])
        np.testing.assert_array_equal(t_kernel, t_xla)


@pytest.mark.parametrize("T", [1, 7, 128, 333])
def test_keep_mask_search_equals_keep_mask(T):
    """The kernels' search tie-break selects exactly `keep_mask`'s entries
    on tie-heavy rows (few distinct magnitudes), for every k."""
    import jax

    from repro.kernels.topk_threshold import keep_mask, keep_mask_search

    rng = np.random.default_rng(T)
    a = jnp.asarray(rng.integers(0, 4, (9, T)), jnp.float32)
    for k in sorted({1, min(2, T), T // 2 + 1, T}):
        t = jax.lax.top_k(a, k)[0][:, -1:]
        np.testing.assert_array_equal(np.asarray(keep_mask_search(a, t, k)),
                                      np.asarray(keep_mask(a, t, k)))


def test_topk_compress_sum_breaks_ties_like_keep_mask():
    from repro.kernels.topk_threshold import keep_mask, topk_compress_sum

    rng = np.random.default_rng(5)
    v = jnp.asarray(rng.integers(-2, 3, (13, 200)), jnp.float32)
    for k in (1, 40, 199):
        dense, _ = topk_compress_sum(v, k)
        a = jnp.abs(v)
        t = jax.lax.top_k(a, k)[0][:, -1:]
        want = jnp.where(keep_mask(a, t, k), v, 0.0)
        np.testing.assert_array_equal(np.asarray(dense), np.asarray(want))


def test_topk_threshold_ties_and_zeros():
    tied = jnp.ones((10, 10), jnp.float32)
    out, t, kept = topk_threshold(tied, 7)
    assert int(kept) == 7 and float(t) == 1.0
    out0, t0, kept0 = topk_threshold(jnp.zeros((10, 10), jnp.float32), 7)
    # a zero tensor has threshold 0; the tie-break keeps the first 7 slots
    assert float(t0) == 0.0 and int(kept0) == 7
    # k = 0 keeps nothing (the 'send nothing' endpoint of a bits sweep)
    outz, tz, keptz = topk_threshold(tied, 0)
    assert int(keptz) == 0 and float(jnp.sum(jnp.abs(outz))) == 0.0


def test_topk_compress_sum_fuses_bitwise():
    """The fused compress-then-reduce kernel == the two-pass path (threshold
    → mask → XLA column sum) BITWISE, for edge and interior k — the property
    that lets the sharded engine's uplink pre-reduction ride the flag."""
    from repro.kernels.topk_threshold import (
        keep_mask, topk_compress_sum, topk_row_threshold)

    rng = np.random.default_rng(11)
    v = jnp.asarray(rng.standard_normal((6, 257)), jnp.float32)
    for k in (1, 13, 256, 257, 400):
        dense, s = topk_compress_sum(v, k)
        a = jnp.abs(v)
        kk = max(1, min(k, v.shape[1]))
        t = topk_row_threshold(a, kk)
        want = jnp.where(keep_mask(a, t, kk), v, jnp.zeros_like(v))
        np.testing.assert_array_equal(np.asarray(dense), np.asarray(want))
        np.testing.assert_array_equal(np.asarray(s),
                                      np.asarray(jnp.sum(want, axis=0)))
    with pytest.raises(TypeError, match="f32"):
        topk_compress_sum(v.astype(jnp.bfloat16), 3)


def test_topk_contraction_property():
    """Kernel output satisfies the paper's contraction inequality (Eq. 6)."""
    x = jnp.asarray(np.random.default_rng(1).standard_normal((64, 64)), jnp.float32)
    k = 200
    out, _, kept = topk_threshold(x, k)
    lhs = float(jnp.sum((x - out) ** 2))
    delta = k / x.size
    assert lhs <= (1 - delta) * float(jnp.sum(x ** 2)) + 1e-6


# ----------------------------- composite ops --------------------------------
def test_basis_project_matches_core_basis():
    """Kernel basis projection == core.DataOuterBasis.h coefficients."""
    from repro.core.basis import DataOuterBasis
    rng = np.random.default_rng(5)
    V = jnp.asarray(np.linalg.qr(rng.standard_normal((120, 20)))[0])
    Amat = rng.standard_normal((120, 120))
    Amat = jnp.asarray((Amat + Amat.T) / 2)
    basis = DataOuterBasis(V=V)
    want = np.asarray(basis.h(Amat))[:20, :20]
    got = np.asarray(ops.basis_project(V.astype(jnp.float32),
                                       Amat.astype(jnp.float32)))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


def test_glm_hessian_matches_core_glm():
    from repro.core import glm
    clients = glm.make_synthetic(seed=0, n_clients=1, m=64, d=48, r=16, lam=1e-2)
    c = clients[0]
    x = jnp.zeros(48, jnp.float64)
    w = glm.hess_diag_weights(c, x)
    want = np.asarray(glm.hess(c, x))
    got = np.asarray(ops.glm_hessian(jnp.asarray(c.A, jnp.float32),
                                     jnp.asarray(w, jnp.float32), 1e-2))
    np.testing.assert_allclose(got, want, rtol=1e-3, atol=1e-4)


def test_basis_project_batched_leading_dim():
    """Leading-batch-dim path (the batched BL engine's stacked-client layout)
    must agree per client with the 2-D kernel path and the einsum oracle."""
    rng = np.random.default_rng(7)
    V = jnp.asarray(
        np.stack([np.linalg.qr(rng.standard_normal((96, 24)))[0] for _ in range(4)]),
        jnp.float32,
    )
    A = jnp.asarray(rng.standard_normal((4, 96, 96)), jnp.float32)
    got = np.asarray(ops.basis_project(V, A, bm=32, bn=32, bk=32))
    assert got.shape == (4, 24, 24)
    want = np.asarray(jnp.einsum("ndr,nde,nes->nrs", V, A, V))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)
    for i in range(4):
        one = np.asarray(ops.basis_project(V[i], A[i], bm=32, bn=32, bk=32))
        np.testing.assert_allclose(got[i], one, rtol=1e-5, atol=1e-5)


def test_basis_project_batched_shared_basis():
    """A shared 2-D V broadcasts over the batch of matrices."""
    rng = np.random.default_rng(8)
    V = jnp.asarray(np.linalg.qr(rng.standard_normal((64, 16)))[0], jnp.float32)
    A = jnp.asarray(rng.standard_normal((3, 64, 64)), jnp.float32)
    got = np.asarray(ops.basis_project(V, A, bm=32, bn=32, bk=32))
    want = np.asarray(jnp.einsum("dr,nde,es->nrs", V, A, V))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)
