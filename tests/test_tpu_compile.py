"""Compile rehearsals for a described TPU v5e chip, at real widths.

Nothing here runs on a chip: each test lowers a piece of the main path for
one device of a described ``v5e:2x2`` topology and compiles it with the
TPU compiler that ships with jaxlib, which refuses what the chip would
refuse (an unsupported f64 decomposition, a block that is not a whole
tile, more VMEM than a kernel may use).  Where a Pallas kernel is
expected, the compiled text must hold a ``tpu_custom_call``: the kernel
was compiled, not interpreted.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process at a time may load the TPU library,
and under pytest-xdist every worker imports every test file.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.core import bl, glm, progcache
from repro.kernels import ops
from repro.kernels.basis_transform import basis_transform
from repro.kernels.topk_threshold import topk_compress_sum, topk_row_threshold


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without the chip; keep the cache out of it
    with progcache._compile_cache_off():
        yield topo


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def four_chips(topo):
    return Mesh(np.asarray(topo.devices), ("clients",))


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args, **static):
    return jax.jit(fn, static_argnames=tuple(static)).lower(
        *args, **static).compile()


def test_spd_solve_compiles_in_f64(one_chip):
    """The server Newton solve at fig1-xl width (d=1200), in float64: LU
    is refused there (f32/c64 only), the Cholesky helper is not."""
    A = _sds((1200, 1200), jnp.float64, one_chip)
    b = _sds((1200,), jnp.float64, one_chip)
    compiled = _compile(glm.spd_solve, A, b)
    assert compiled.memory_analysis() is not None


@pytest.mark.parametrize("kernel", ["row_threshold", "compress_sum"])
def test_topk_kernels_compile(one_chip, kernel):
    v = _sds((512, 1024), jnp.float32, one_chip)
    fn = topk_row_threshold if kernel == "row_threshold" else topk_compress_sum
    compiled = _compile(fn, v, k=1024)
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("shape", [(16, 12, 8), (2, 1024, 1024)],
                         ids=["12x8_leaves", "1024x1024_leaf"])
def test_basis_transform_compiles(one_chip, shape):
    n, d1, d2 = shape
    A = _sds((d1, d1), jnp.float32, one_chip)
    g = _sds(shape, jnp.float32, one_chip)
    B = _sds((d2, d2), jnp.float32, one_chip)
    compiled = _compile(basis_transform, A, g, B)
    assert "tpu_custom_call" in compiled.as_text()


def test_basis_project_compiles_at_fig1r1_widths(one_chip):
    """Γ = VᵀAV over a client stack as the engine calls it under
    ``REPRO_BL_PALLAS=1``: float64 operands, d=120, r=24."""
    V = _sds((8, 120, 24), jnp.float64, one_chip)
    A = _sds((8, 120, 120), jnp.float64, one_chip)
    compiled = _compile(ops.basis_project, V, A)
    assert "tpu_custom_call" in compiled.as_text()


def test_proj_mu_solve_compiles_in_a_sharded_program(four_chips):
    """BL1's server step inside a client-sharded scan over four chips, in
    float64: the Jacobi eigh and the eigen-solve compile there.  An f64
    Cholesky is refused in any program partitioned over more than one
    chip, with or without shard_map, a scan or the server-once cond."""
    def body(x, H):
        def step(c, _):
            g = jax.lax.all_gather(c, "clients", axis=0, tiled=True).sum(0)
            return c + bl.proj_mu_solve(*bl.proj_mu_eig(H, 1e-3), g)[None, :], None
        return jax.lax.scan(step, x, None, length=2)[0]

    f = jax.shard_map(body, mesh=four_chips, in_specs=(P("clients"), P()),
                      out_specs=P("clients"), check_vma=False)
    x = jax.ShapeDtypeStruct((8, 64), jnp.float64,
                             sharding=NamedSharding(four_chips, P("clients")))
    H = jax.ShapeDtypeStruct((64, 64), jnp.float64,
                             sharding=NamedSharding(four_chips, P()))
    assert "all-gather" in jax.jit(f).lower(x, H).compile().as_text()


def _server_step(ndev=1):
    def step(H, g):
        return bl.proj_mu_route(
            H, 1e-3, lambda P, *factors: bl.proj_mu_solve(P, *factors, g), ndev)
    return step


def test_certified_server_step_compiles_in_f64(one_chip):
    """BL1's server step at fig1-xl width (d=1200), in float64, on one
    chip: the Cholesky certificate, the cond, the certified branch's
    Cholesky solve and the Jacobi eigh branch compile into one program."""
    H = _sds((1200, 1200), jnp.float64, one_chip)
    g = _sds((1200,), jnp.float64, one_chip)
    compiled = _compile(_server_step(), H, g)
    text = compiled.as_text()
    assert "conditional" in text and "cholesky" in text
    assert compiled.memory_analysis() is not None


def test_server_route_takes_the_eigh_in_a_sharded_program(four_chips):
    """A reducer over four chips routes the server step to the eigh path,
    which compiles there (an f64 Cholesky would be refused)."""
    def body(x, H):
        def step(c, _):
            g = jax.lax.all_gather(c, "clients", axis=0, tiled=True).sum(0)
            x_new, _ = _server_step(ndev=4)(H, g)
            return c + x_new[None, :], None
        return jax.lax.scan(step, x, None, length=2)[0]

    f = jax.shard_map(body, mesh=four_chips, in_specs=(P("clients"), P()),
                      out_specs=P("clients"), check_vma=False)
    x = jax.ShapeDtypeStruct((8, 64), jnp.float64,
                             sharding=NamedSharding(four_chips, P("clients")))
    H = jax.ShapeDtypeStruct((64, 64), jnp.float64,
                             sharding=NamedSharding(four_chips, P()))
    assert "all-gather" in jax.jit(f).lower(x, H).compile().as_text()
