"""Cohort-streaming engine (`repro.core.cohort`): the parity, invariance
and resume contracts that license the flat-in-n refactor.

The load-bearing pins:

  * **cohort == fleet is the stacked engine** — the engine factory
    (`repro.exp.engine.make_engine`) routes a store-backed cell whose
    cohort covers the fleet to `rounds.StackedEngine` over the whole fleet,
    bitwise `rounds.run_chunk` on both reducers (the sharded leg runs in a
    subprocess: the device count is locked at first jax init).
  * **chunk-boundary invariance** — any segmentation of `run_chunk` calls
    produces the same streams (per-round keys fold in the absolute round
    index; the cohort schedule is a pure function of the absolute epoch).
  * **kill -9 + resume is bit-exact** through the ckpt@2 ``host_state``
    payload (store rows, fleet aggregate totals, the epoch's frozen stats),
    mid-epoch or at an epoch boundary, in-process and through the CLI.

Also here: the `ClientBatch`/`TreeBatch` constructor validation added with
the streaming engine (a mis-shaped gather must fail loudly, not broadcast
into wrong per-client math), and the fig1-xxl registry scenario's shape.
"""
import json
import os
import signal
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import client_batch, cohort, compressors, rounds, specs
from repro.exp import artifacts

jax.config.update("jax_enable_x64", True)

D, M = 6, 8
KEY = jax.random.PRNGKey(7)
X0 = jnp.zeros(D, jnp.float64)


def _bl2(n, tau):
    bb = cohort.standard_basisb(D, n)
    return specs.BL2Spec(
        hess_comp=compressors.TopK(k=2 * D),
        model_comp=compressors.Identity(),
        alpha=1.0, eta=1.0, p=1.0, tau=tau, init_exact=True,
        init_hess_bits=bb.init_coeff_bits_mean(True),
        basis_bits=bb.transmission_bits_mean(), block=False)


def _store(n, seed=11):
    return client_batch.synthetic_store(seed, n, M, D)


def _engine(n, tau, cohort_size, seed=11, **kw):
    kw.setdefault("prefetch", False)
    return cohort.CohortEngine(
        _bl2(n, tau), _store(n, seed), X0, cohort=cohort_size,
        rounds_per_cohort=2, root_key=KEY, basis="standard", **kw)


def _assert_trees_equal(a, b, msg=""):
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb), msg
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                      err_msg=msg)


# --------------------------------------------------------------------------
# constructor validation (ClientBatch / TreeBatch)
# --------------------------------------------------------------------------
def test_clientbatch_rejects_unstacked_A():
    with pytest.raises(ValueError,
                       match=r"client-stacked \(n, m, d\); got shape"):
        client_batch.ClientBatch(A=jnp.zeros((4, 3)), b=jnp.zeros((4,)),
                                 lam=1e-3)


def test_clientbatch_rejects_mismatched_b():
    with pytest.raises(ValueError, match=r"shape \(n, m\) = A\.shape"):
        client_batch.ClientBatch(A=jnp.zeros((4, 3, 2)), b=jnp.zeros((4, 2)),
                                 lam=1e-3)
    # the error names both shapes so a bad gather is diagnosable on sight
    with pytest.raises(ValueError, match=r"\(4, 3\).*got \(3, 4\)"):
        client_batch.ClientBatch(A=jnp.zeros((4, 3, 2)), b=jnp.zeros((3, 4)),
                                 lam=1e-3)


def test_clientbatch_accepts_tracers():
    # validation must not fire on jit re-unflattens of abstract values
    out = jax.eval_shape(
        lambda A, b: client_batch.ClientBatch(A=A, b=b, lam=0.1).A,
        jax.ShapeDtypeStruct((4, 3, 2), jnp.float64),
        jax.ShapeDtypeStruct((4, 3), jnp.float64))
    assert out.shape == (4, 3, 2)


def test_treebatch_rejects_scalar_leaf():
    with pytest.raises(ValueError, match="leading client axis"):
        client_batch.TreeBatch(data={"w": np.zeros(()),
                                     "v": np.zeros((4, 2))}, n_clients=4)


def test_treebatch_rejects_disagreeing_client_axes():
    with pytest.raises(ValueError,
                       match="disagree on the leading client axis"):
        client_batch.TreeBatch(data={"w": np.zeros((4, 2)),
                                     "v": np.zeros((5, 2))}, n_clients=4)


def test_tree_batch_builder_validation():
    with pytest.raises(ValueError, match="at least one data leaf"):
        client_batch.tree_batch({})
    # tree_leaves orders dict keys, so "v" fixes n and "w" violates it
    with pytest.raises(ValueError, match="leading n_clients=5 axis"):
        client_batch.tree_batch({"w": np.zeros((4, 2)),
                                 "v": np.zeros((5, 2))})


def test_cohort_engine_constructor_validation():
    with pytest.raises(ValueError, match="rounds_per_cohort must be >= 1"):
        cohort.CohortEngine(_bl2(8, 8), _store(8), X0, cohort=4,
                            rounds_per_cohort=0, root_key=KEY)
    with pytest.raises(ValueError, match="cohort must be >= 1"):
        cohort.CohortEngine(_bl2(8, 8), _store(8), X0, cohort=0,
                            rounds_per_cohort=1, root_key=KEY)
    with pytest.raises(ValueError, match="not cohort-capable"):
        cohort.CohortEngine(object(), _store(8), X0, cohort=4,
                            rounds_per_cohort=1, root_key=KEY)
    with pytest.raises(ValueError, match="convention basis"):
        cohort.CohortEngine(_bl2(8, 8), _store(8), X0, cohort=8,
                            rounds_per_cohort=1, root_key=KEY,
                            basis="data_outer")


# --------------------------------------------------------------------------
# cohort == fleet: the factory's stacked engine, bitwise run_chunk
# --------------------------------------------------------------------------
def _fleet_engine(n, store, sharded=False):
    """The engine `make_engine` picks for a BL2 cell (the `_bl2` spec's
    fields) whose cohort covers a store-backed fleet of ``n``."""
    from repro.exp import engine, registry

    cell = registry.MethodCell(
        "BL2", "bl2", 6, basis="standard",
        hess_comp=registry.CompressorCfg(kind="topk", k=2 * D),
        model_comp=registry.CompressorCfg(kind="identity"), backend="cohort",
        params=(("tau", n), ("cohort", n), ("rounds_per_cohort", 2)))
    spec = registry.ProblemSpec(kind="synthetic_stream", n_clients=n, m=M,
                                d=D)
    exp = registry.Experiment(
        name="cohort-fleet", figure="extra", title="cohort == fleet",
        paper_ref="test", cells=(cell,), problem=spec)
    prob = engine.StreamProblem(spec=spec, store=store, x0=X0, x_star=None)
    return engine.make_engine(exp, cell, prob, root_key=KEY, sharded=sharded)


def test_full_mode_bitwise_parity_vmap():
    n = 32
    eng = _fleet_engine(n, _store(n))
    assert isinstance(eng, rounds.StackedEngine)
    spec = eng.spec
    batch = _store(n).gather_batch(np.arange(n))
    bb = cohort.standard_basisb(D, n)
    c0 = rounds.init_serve_carry(spec, batch, bb, X0)
    c1, ys1 = rounds.run_chunk(spec, batch, bb, X0, c0, 0, 6, KEY)

    # two calls: the engine must also be invariant to call segmentation
    ys2 = jax.tree.map(lambda *a: jnp.concatenate(a, 0),
                       eng.run_chunk(0, 3), eng.run_chunk(3, 3))
    _assert_trees_equal(ys1, ys2, "fleet-cohort streams != stacked streams")
    leaves, host = eng.checkpoint_view()
    assert host is None
    _assert_trees_equal(jax.tree_util.tree_leaves(c1), leaves,
                        "fleet-cohort carry != stacked carry")
    eng.close()


_SHARDED_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np
import jax, jax.numpy as jnp
from repro.core import cohort, client_batch, rounds, specs, compressors
from repro.exp import engine, registry

d, m = 6, 8
key = jax.random.PRNGKey(7)
x0 = jnp.zeros(d, jnp.float64)

def bl2(n, tau):
    bb = cohort.standard_basisb(d, n)
    return specs.BL2Spec(
        hess_comp=compressors.TopK(k=2 * d),
        model_comp=compressors.Identity(),
        alpha=1.0, eta=1.0, p=1.0, tau=tau, init_exact=True,
        init_hess_bits=bb.init_coeff_bits_mean(True),
        basis_bits=bb.transmission_bits_mean(), block=False)

def eq(a, b):
    return all(np.array_equal(np.asarray(x), np.asarray(y))
               for x, y in zip(jax.tree_util.tree_leaves(a),
                               jax.tree_util.tree_leaves(b)))

# a cohort that covers the fleet: the factory's stacked engine vs the
# stacked sharded run_chunk
n = 32
cell = registry.MethodCell(
    "BL2", "bl2", 6, basis="standard",
    hess_comp=registry.CompressorCfg(kind="topk", k=2 * d),
    model_comp=registry.CompressorCfg(kind="identity"), backend="cohort",
    params=(("tau", n), ("cohort", n), ("rounds_per_cohort", 2)))
pspec = registry.ProblemSpec(kind="synthetic_stream", n_clients=n, m=m, d=d)
exp = registry.Experiment(name="cohort-fleet", figure="extra",
                          title="cohort == fleet", paper_ref="test",
                          cells=(cell,), problem=pspec)
prob = engine.StreamProblem(spec=pspec,
                            store=client_batch.synthetic_store(11, n, m, d),
                            x0=x0, x_star=None)
eng = engine.make_engine(exp, cell, prob, root_key=key, sharded=True)
assert isinstance(eng, rounds.StackedEngine)
spec = eng.spec
batch = client_batch.synthetic_store(11, n, m, d).gather_batch(np.arange(n))
bb = cohort.standard_basisb(d, n)
c0 = rounds.init_serve_carry(spec, batch, bb, x0, sharded=True)
_, ys1 = rounds.run_chunk(spec, batch, bb, x0, c0, 0, 6, key, sharded=True)
ys2 = eng.run_chunk(0, 6)
eng.close()
print("FULL_SHARDED", eq(ys1, ys2), flush=True)

# streaming: sharded reducer bitwise == vmap reducer (exact mode)
n2 = 64
spec2 = bl2(n2, 16)
outs = []
for sharded in (False, True):
    e = cohort.CohortEngine(spec2, client_batch.synthetic_store(11, n2, m, d),
                            x0, cohort=16, rounds_per_cohort=2, root_key=key,
                            basis="standard", sharded=sharded, prefetch=False)
    outs.append(e.run_chunk(0, 8))
    e.close()
print("STREAM_SHARDED", eq(outs[0], outs[1]), flush=True)
"""


def test_sharded_parity_subprocess():
    """Both sharded pins in one 8-virtual-device child: a cohort that
    covers the fleet runs the factory's stacked engine, bitwise the stacked
    sharded `run_chunk`, and streaming vmap == streaming sharded (the exact
    fixed-order reducer contract)."""
    env = dict(os.environ, PYTHONPATH="src", JAX_PLATFORMS="cpu")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    r = subprocess.run([sys.executable, "-c", _SHARDED_SCRIPT], env=env,
                       cwd=repo, capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stdout + r.stderr[-2000:]
    assert "FULL_SHARDED True" in r.stdout, r.stdout
    assert "STREAM_SHARDED True" in r.stdout, r.stdout


# --------------------------------------------------------------------------
# chunk-boundary invariance
# --------------------------------------------------------------------------
def _run_segmented(segs, seed=11):
    eng = _engine(64, 16, 16, seed=seed)
    outs, t = [], 0
    for s in segs:
        outs.append(eng.run_chunk(t, s))
        t += s
    eng.close()
    if len(outs) == 1:
        return outs[0]
    return jax.tree.map(lambda *a: jnp.concatenate(a, 0), *outs)


def test_chunk_boundary_invariance():
    ref = _run_segmented([12])
    # boundaries landing mid-epoch, at epoch edges, and one-round calls
    _assert_trees_equal(ref, _run_segmented([1, 4, 3, 2, 2]),
                        "segmentation changed the trajectory")
    _assert_trees_equal(ref, _run_segmented([6, 6]),
                        "segmentation changed the trajectory")


@settings(max_examples=8, deadline=None)
@given(cuts=st.lists(st.integers(1, 11), min_size=0, max_size=3),
       seed=st.integers(0, 3))
def test_chunk_boundary_invariance_property(cuts, seed):
    """Property form: ANY sorted cut set of [0, 12) produces the reference
    streams (the deterministic test pins two hand-picked segmentations;
    this one searches the space)."""
    bounds = sorted(set(cuts)) + [12]
    segs, prev = [], 0
    for b in bounds:
        if b > prev:
            segs.append(b - prev)
            prev = b
    _assert_trees_equal(_run_segmented([12], seed=seed),
                        _run_segmented(segs, seed=seed),
                        f"segmentation {segs} changed the trajectory")


# --------------------------------------------------------------------------
# sampler
# --------------------------------------------------------------------------
def test_cohort_sampler_deterministic_and_unique():
    eng = _engine(64, 16, 16)
    i1 = eng.cohort_indices(3)
    assert np.array_equal(i1, eng.cohort_indices(3))
    assert np.unique(i1).size == 16 and i1.min() >= 0 and i1.max() < 64
    assert not np.array_equal(i1, eng.cohort_indices(4))
    # both sampler paths (rejection at c*8 <= n, permutation otherwise)
    big = _engine(64, 32, 32)
    j = big.cohort_indices(0)
    assert np.unique(j).size == 32
    eng.close()
    big.close()


# --------------------------------------------------------------------------
# checkpoint / restore
# --------------------------------------------------------------------------
@pytest.mark.parametrize("tck", [5, 6], ids=["mid_epoch", "epoch_boundary"])
def test_checkpoint_restore_bitwise(tck):
    e1 = _engine(64, 16, 16)
    e1.run_chunk(0, tck)
    leaves, host = e1.checkpoint_payload()
    assert any(k.startswith("store/") for k in host)
    assert any(k.startswith("frozen/") for k in host)
    treedef = jax.tree_util.tree_structure(e1.carry_template())
    tail_ref = e1.run_chunk(tck, 12 - tck)
    e1.close()

    e2 = _engine(64, 16, 16)
    carry = jax.tree_util.tree_unflatten(
        treedef, [jnp.asarray(l) for l in leaves])
    e2.restore(tck, carry, host)
    tail = e2.run_chunk(tck, 12 - tck)
    e2.close()
    _assert_trees_equal(tail_ref, tail, f"restore@{tck} diverged")


def test_restore_rejects_non_streaming_host_state():
    eng = _engine(64, 16, 16)
    template = eng.carry_template()
    with pytest.raises(ValueError, match="lacks.*frozen"):
        eng.restore(4, template, {})
    eng.close()


def test_ckpt_schema_v1_walked_past(tmp_path):
    """A pre-host-state ckpt@1 directory must not be adopted: the loader
    walks past the stale manifest to the newest valid @2 checkpoint (or
    None), instead of resuming without the engine's host plane."""
    artifacts.save_checkpoint(
        str(tmp_path), t=3, carry_leaves=[np.arange(4.0)],
        streams={"eval_x": np.zeros((3, 2))}, root_key=np.zeros(2, np.uint32),
        config_digest="dg", host_state={"store/z": np.ones((4, 2))})
    artifacts.save_checkpoint(
        str(tmp_path), t=9, carry_leaves=[np.arange(4.0) + 9],
        streams={"eval_x": np.zeros((9, 2))}, root_key=np.zeros(2, np.uint32),
        config_digest="dg")
    # downgrade the newest manifest to the retired @1 schema tag
    man = tmp_path / "ckpt-00000009.json"
    m = json.loads(man.read_text())
    m["schema"] = "repro.exp/ckpt@1"
    man.write_text(json.dumps(m))
    ck = artifacts.load_checkpoint(str(tmp_path), config_digest="dg")
    assert ck is not None and ck["t"] == 3
    assert set(ck["host_state"]) == {"store/z"}
    np.testing.assert_array_equal(ck["host_state"]["store/z"],
                                  np.ones((4, 2)))


# --------------------------------------------------------------------------
# serve CLI: kill -9 through ckpt@2
# --------------------------------------------------------------------------
_ENV = {"PYTHONPATH": "src", "PATH": os.environ.get("PATH", "/usr/bin:/bin"),
        "JAX_PLATFORMS": "cpu", "HOME": os.environ.get("HOME", "/tmp")}


def _serve_cli(ckpt_dir, *extra):
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return subprocess.run(
        [sys.executable, "-m", "repro.launch.fed_serve", "--exp",
         "cohort-smoke", "--cell", "BL2", "--seed", "2", "--max-rounds",
         "12", "--chunk", "3", "--ckpt-dir", str(ckpt_dir), *extra],
        env=_ENV, capture_output=True, text=True, timeout=900, cwd=repo)


def test_serve_cohort_kill9_resume_bitwise(tmp_path):
    """The acceptance scenario on the streaming engine: SIGKILL a serve of
    the cohort-smoke scenario mid-run (losing the in-flight chunk), restart,
    and the final record equals the uninterrupted reference — the ckpt@2
    host_state payload carried the store rows, totals and frozen stats."""
    ref_json = str(tmp_path / "ref.json")
    res_json = str(tmp_path / "res.json")
    r = _serve_cli(tmp_path / "ref", "--result", ref_json)
    assert r.returncode == 0, r.stdout + r.stderr[-2000:]

    r = _serve_cli(tmp_path / "crash", "--crash-after-round", "5")
    assert r.returncode == -signal.SIGKILL, (r.returncode, r.stderr[-500:])
    ts = [t for t, _ in artifacts.list_checkpoints(str(tmp_path / "crash"))]
    assert ts and max(ts) < 12      # the kill actually cost progress

    r = _serve_cli(tmp_path / "crash", "--result", res_json)
    assert r.returncode == 0, r.stdout + r.stderr[-2000:]
    assert "resumed from checkpoint" in r.stdout

    with open(ref_json) as f:
        ref = json.load(f)
    with open(res_json) as f:
        res = json.load(f)
    assert res["meta"]["resumed_from"] == max(ts)
    ref.pop("meta")
    res.pop("meta")
    assert ref == res   # bit-exact: gaps, events, every ledger leg


def test_serve_cohort_refuses_fault_plan_and_stacked_backend(tmp_path):
    from repro.core import faults
    from repro.launch import fed_serve

    with pytest.raises(SystemExit, match="fault"):
        fed_serve.serve(exp_name="cohort-smoke", cell_name="BL2",
                        ckpt_dir=str(tmp_path), max_rounds=2,
                        plan=faults.FaultPlan(n=96, dropout_p=0.5))
    with pytest.raises(SystemExit, match="cohort"):
        fed_serve.serve(exp_name="cohort-smoke", cell_name="BL2",
                        ckpt_dir=str(tmp_path), max_rounds=2,
                        backend="fast")


# --------------------------------------------------------------------------
# registry / engine integration
# --------------------------------------------------------------------------
def test_fig1_xxl_registered_at_streaming_scale():
    from repro.exp import get_experiment

    exp = get_experiment("fig1-xxl")
    assert exp.problem.kind == "synthetic_stream"
    assert exp.problem.n_clients >= 100_000
    assert "stream" in exp.tags
    for cell in exp.cells:
        params = cell.params_dict()
        assert cell.backend == "cohort"
        assert params["cohort"] <= 512


def test_run_cell_streams_cohort_smoke():
    from repro.exp import build_problem, get_experiment, run_cell

    exp = get_experiment("cohort-smoke")
    prob = build_problem(exp.problem)
    cell = exp.cell("BL2")
    h = run_cell(exp, cell, prob, steps=12)
    assert len(h.gaps) == 12
    assert h.gaps[-1] < h.gaps[0]
    assert h.up_bits[-1] > 0.0
    with pytest.raises(ValueError, match="cohort backends"):
        run_cell(exp, cell, prob, steps=2, backend="fast")
