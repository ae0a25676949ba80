"""Retrace audit: the dispatch path traces each program body ONCE.

The invariant (ISSUE 10 acceptance): one trace per (spec, shapes) per
process and ZERO retraces across chunk boundaries and cohort epoch
boundaries, on every backend.  A retrace costs ~1000x the compiled
per-round dispatch, so a silent one is a serious perf regression — these
tests pin the counter deltas (`repro.core.rounds.trace_counts`), not
absolute counts, so they are immune to what earlier tests in the process
already traced.

The last test pins the cold-start subsystem's strongest form of the
invariant: a warm dispatch served from the program cache traces NOTHING —
the executable deserializes without ever running the Python body.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import batched, progcache, rounds
from repro.core.compressors import Identity, TopK

jax.config.update("jax_enable_x64", True)


@pytest.fixture(scope="module")
def problem():
    # dims deliberately distinct from every other test file's problem so
    # the first chunk here is a guaranteed fresh (spec, shapes) trace
    from repro.core import glm
    from repro.core.basis import orth_basis_from_data

    clients = glm.make_synthetic(seed=2, n_clients=5, m=20, d=14, r=5,
                                 lam=1e-3)
    bases = [orth_basis_from_data(c.A) for c in clients]
    x0 = jnp.zeros(14, jnp.float64)
    spec, batch, basisb = batched.bl2_setup(
        clients, bases, [TopK(k=5) for _ in clients],
        [Identity() for _ in clients], tau=2)
    return spec, batch, basisb, x0


def _delta(before, after, kind):
    return after.get(kind, 0) - before.get(kind, 0)


@pytest.mark.parametrize("sharded", [False, True],
                         ids=["fast", "fast+sharded"])
def test_one_trace_per_spec_zero_retraces_across_chunks(problem, sharded):
    spec, batch, basisb, x0 = problem
    root = jax.random.PRNGKey(0)
    carry = rounds.init_serve_carry(spec, batch, basisb, x0, sharded=sharded)

    before = rounds.trace_counts()
    carry, _ = rounds.run_chunk(spec, batch, basisb, x0, carry, 0, 4, root,
                                sharded=sharded)
    first = rounds.trace_counts()
    assert _delta(before, first, "chunk") == 1, \
        "first chunk at a fresh (spec, shapes) must trace exactly once"

    for t in (4, 8, 12):        # chunk AND epoch-of-work boundaries
        carry, _ = rounds.run_chunk(spec, batch, basisb, x0, carry, t, 4,
                                    root, sharded=sharded)
    after = rounds.trace_counts()
    assert _delta(first, after, "chunk") == 0, \
        f"retraced across chunk boundaries: {first} -> {after}"
    # the shape-only evaluations carry_client_flags runs are tagged apart
    # and must never be counted as real chunk traces
    assert _delta(first, after, "chunk/shape_eval") == 0


def test_zero_retraces_across_cohort_epochs():
    from repro.core import client_batch, cohort, compressors, specs

    d, m, n = 12, 8, 32
    bb = cohort.standard_basisb(d, n)
    spec = specs.BL2Spec(
        hess_comp=compressors.TopK(k=2 * d),
        model_comp=compressors.Identity(),
        alpha=1.0, eta=1.0, p=1.0, tau=8, init_exact=True,
        init_hess_bits=bb.init_coeff_bits_mean(True),
        basis_bits=bb.transmission_bits_mean(), block=False)
    store = client_batch.synthetic_store(0, n, m, d, lam=1e-3)
    # epoch = (n / cohort) * rounds_per_cohort = 4 rounds: every chunk
    # below crosses an epoch boundary (cohort swap + host scatter/gather)
    eng = cohort.CohortEngine(spec, store, x0=jnp.zeros(d, jnp.float64),
                              cohort=16, rounds_per_cohort=2,
                              root_key=jax.random.PRNGKey(0),
                              basis="standard")
    try:
        before = rounds.trace_counts()
        eng.run_chunk(0, 4)
        first = rounds.trace_counts()
        assert _delta(before, first, "cohort_chunk") == 1

        for t in (4, 8):
            eng.run_chunk(t, 4)
        after = rounds.trace_counts()
        assert _delta(first, after, "cohort_chunk") == 0, \
            f"retraced across epoch boundaries: {first} -> {after}"
    finally:
        eng.close()


def test_warm_cache_dispatch_traces_nothing(problem, tmp_path):
    """A cache-hit dispatch must deserialize, not trace: zero body traces
    for both the init and the chunk program."""
    spec, batch, basisb, x0 = problem
    root = jax.random.PRNGKey(1)
    progcache.activate(str(tmp_path / "pc"))
    try:
        rounds.clear_aot_memo()
        carry = rounds.init_serve_carry(spec, batch, basisb, x0)
        carry, ys_miss = rounds.run_chunk(spec, batch, basisb, x0, carry,
                                          0, 4, root)

        rounds.clear_aot_memo()      # next dispatch reloads from disk
        before = rounds.trace_counts()
        carry = rounds.init_serve_carry(spec, batch, basisb, x0)
        carry, ys_hit = rounds.run_chunk(spec, batch, basisb, x0, carry,
                                         0, 4, root)
        after = rounds.trace_counts()
        assert _delta(before, after, "chunk") == 0
        assert _delta(before, after, "init") == 0
        assert progcache.active().stats["hit"] >= 2
        np.testing.assert_array_equal(np.asarray(ys_miss[0]),
                                      np.asarray(ys_hit[0]))
    finally:
        progcache.deactivate()
        rounds.clear_aot_memo()
