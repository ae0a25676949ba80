"""Service-loop driver + checkpoint/restore bit-exactness.

The contract under test (ISSUE 6 acceptance): a serve run interrupted at
ANY point — graceful chunk boundary or kill -9 mid-run — and resumed from
its latest valid checkpoint produces a trajectory, `History.events` stream
and per-leg `CommLedger` bit accounting bit-exactly equal to the
uninterrupted run at the same seed, on both aggregation backends."""
import json
import os
import signal
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import batched, comm, faults, glm, rounds
from repro.core.compressors import Identity, TopK
from repro.exp import artifacts

jax.config.update("jax_enable_x64", True)


@pytest.fixture(scope="module")
def problem():
    clients = glm.make_synthetic(seed=0, n_clients=6, m=24, d=18, r=6,
                                 lam=1e-3)
    from repro.core.basis import orth_basis_from_data

    bases = [orth_basis_from_data(c.A) for c in clients]
    x0 = jnp.zeros(18, jnp.float64)
    spec, batch, basisb = batched.bl2_setup(
        clients, bases, [TopK(k=6) for _ in clients],
        [Identity() for _ in clients], tau=3)
    return spec, batch, basisb, x0


def _chunks(spec, batch, basisb, x0, carry, plan, t0, t1, chunk, root_key,
            sharded=False):
    """Drive [t0, t1) in `chunk`-round pieces; returns (carry, streams)."""
    xs, evs = [], []
    led = {leg: [] for leg in comm.CommLedger.LEGS}
    t = t0
    while t < t1:
        steps = min(chunk, t1 - t)
        avail = None if plan is None else plan.schedule(t, steps)[0]
        carry, ys = rounds.run_chunk(spec, batch, basisb, x0, carry, t,
                                     steps, root_key, avail=avail,
                                     sharded=sharded)
        xs.append(np.asarray(ys[0]))
        evs.append(np.asarray(ys[2]))
        for leg in comm.CommLedger.LEGS:
            led[leg].append(np.asarray(getattr(ys[1], leg)))
        t += steps
    return carry, (np.concatenate(xs),
                   {k: np.concatenate(v) for k, v in led.items()},
                   np.concatenate(evs))


def _assert_streams_equal(a, b):
    np.testing.assert_array_equal(a[0], b[0])          # trajectory
    for leg in comm.CommLedger.LEGS:                   # per-leg bits
        np.testing.assert_array_equal(a[1][leg], b[1][leg])
    np.testing.assert_array_equal(a[2], b[2])          # events


@pytest.mark.parametrize("sharded", [False, True],
                         ids=["vmap", "shard_map"])
def test_checkpoint_roundtrip_resume_bitwise(problem, tmp_path, sharded):
    """save → restore → run ≡ uninterrupted, on both reducers, including
    the CommLedger counters and the PRNG key riding the checkpoint."""
    spec, batch, basisb, x0 = problem
    root = jax.random.PRNGKey(5)
    plan = faults.FaultPlan(n=batch.n, dropout_p=0.3, seed=3)
    kw = dict(sharded=sharded)

    c0 = rounds.init_serve_carry(spec, batch, basisb, x0, **kw)
    _, ref = _chunks(spec, batch, basisb, x0, c0, plan, 0, 14, 14, root, **kw)

    # run 6 rounds, checkpoint through the artifact layer, restore, finish
    # (fresh carry: run_chunk DONATES its carry argument, so c0's buffers
    # died inside the reference run above)
    c0 = rounds.init_serve_carry(spec, batch, basisb, x0, **kw)
    mid, head = _chunks(spec, batch, basisb, x0, c0, plan, 0, 6, 3, root, **kw)
    artifacts.save_checkpoint(
        str(tmp_path), t=6,
        carry_leaves=[np.asarray(l) for l in jax.tree_util.tree_leaves(mid)],
        streams={"eval_x": head[0], "events": head[2],
                 **{f"led_{k}": v for k, v in head[1].items()}},
        root_key=np.asarray(root), config_digest="test")
    ck = artifacts.load_checkpoint(str(tmp_path), config_digest="test")
    assert ck is not None and ck["t"] == 6
    treedef = jax.tree_util.tree_structure(c0)
    restored = jax.tree_util.tree_unflatten(
        treedef, [jnp.asarray(l) for l in ck["carry_leaves"]])
    root_restored = jnp.asarray(ck["root_key"])
    np.testing.assert_array_equal(np.asarray(root), np.asarray(root_restored))

    _, tail = _chunks(spec, batch, basisb, x0, restored, plan, 6, 14, 5,
                      root_restored, **kw)
    resumed = (np.concatenate([ck["streams"]["eval_x"], tail[0]]),
               {k: np.concatenate([ck["streams"][f"led_{k}"], tail[1][k]])
                for k in comm.CommLedger.LEGS},
               np.concatenate([ck["streams"]["events"], tail[2]]))
    _assert_streams_equal(resumed, ref)


def test_vmap_and_sharded_serve_bitwise_equal(problem):
    """The exact=True cross-backend contract extends to the chunked driver:
    same chunks, same faults, bitwise-equal streams."""
    spec, batch, basisb, x0 = problem
    root = jax.random.PRNGKey(1)
    plan = faults.FaultPlan(n=batch.n, dropout_p=0.25,
                            outages=(faults.Outage(2, 3, 9),), seed=7)
    cv = rounds.init_serve_carry(spec, batch, basisb, x0, sharded=False)
    cs = rounds.init_serve_carry(spec, batch, basisb, x0, sharded=True)
    for lv, ls in zip(jax.tree_util.tree_leaves(cv),
                      jax.tree_util.tree_leaves(cs)):
        np.testing.assert_array_equal(np.asarray(lv), np.asarray(ls))
    _, sv = _chunks(spec, batch, basisb, x0, cv, plan, 0, 10, 4, root,
                    sharded=False)
    _, ss = _chunks(spec, batch, basisb, x0, cs, plan, 0, 10, 4, root,
                    sharded=True)
    _assert_streams_equal(sv, ss)


def test_commledger_snapshot_restore_bitwise():
    led = comm.CommLedger.create(hess_up=1.25, basis_ship=3e7)
    led = led.add(grad_up=0.1, model_down=7.0)
    snap = led.snapshot()
    back = comm.CommLedger.restore(snap)
    for leg in comm.CommLedger.LEGS:
        np.testing.assert_array_equal(np.asarray(getattr(led, leg)),
                                      np.asarray(getattr(back, leg)))
    with pytest.raises(ValueError, match="missing legs"):
        comm.CommLedger.restore({"hess_up": 0.0})


def test_load_checkpoint_skips_corrupt_and_mismatched(tmp_path):
    def save(t):
        artifacts.save_checkpoint(
            str(tmp_path), t=t, carry_leaves=[np.arange(3.0) + t],
            streams={"eval_x": np.zeros((t, 2))},
            root_key=np.zeros(2, np.uint32), config_digest="d1", keep=10)

    save(5)
    save(10)
    # newest payload torn mid-write → loader must fall back to t=5
    npz = os.path.join(str(tmp_path), "ckpt-00000010.npz")
    with open(npz, "r+b") as f:
        f.truncate(os.path.getsize(npz) // 2)
    ck = artifacts.load_checkpoint(str(tmp_path), config_digest="d1")
    assert ck is not None and ck["t"] == 5
    np.testing.assert_array_equal(ck["carry_leaves"][0], np.arange(3.0) + 5)
    # a different serve config must not resume from these checkpoints
    assert artifacts.load_checkpoint(str(tmp_path),
                                     config_digest="other") is None
    # empty dir → None
    assert artifacts.load_checkpoint(str(tmp_path / "void")) is None


def test_checkpoint_pruning(tmp_path):
    for t in (1, 2, 3, 4):
        artifacts.save_checkpoint(
            str(tmp_path), t=t, carry_leaves=[np.zeros(1)], streams={},
            root_key=np.zeros(2, np.uint32), config_digest="d", keep=2)
    assert [t for t, _ in artifacts.list_checkpoints(str(tmp_path))] == [3, 4]


# ---------------------------------------------------------------- fed_serve
def test_fed_serve_refuses_faults_on_synchronous_method(tmp_path):
    """bl1 models a fully synchronous fleet (supports_faults=False) —
    injecting a non-trivial fault plan must refuse, not silently ignore."""
    from repro.launch import fed_serve

    plan = faults.FaultPlan(n=10, dropout_p=0.5)
    with pytest.raises(SystemExit, match="synchronous"):
        fed_serve.serve(exp_name="fig1r1", cell_name="BL1",
                        ckpt_dir=str(tmp_path), plan=plan, max_rounds=2)


def test_fed_serve_inprocess_graceful_degradation(tmp_path):
    """Outage of most of the fleet → rounds degrade (events flagged), the
    loop keeps serving, and the record counts the degraded rounds."""
    from repro.launch import fed_serve

    plan = faults.FaultPlan(
        n=10, outages=tuple(faults.Outage(c, 2, 6) for c in range(9)))
    rec = fed_serve.serve(exp_name="fig4", cell_name="BL2_tau_half", seed=1,
                          chunk=4, max_rounds=8, ckpt_dir=str(tmp_path),
                          plan=plan, log=lambda *a: None)
    ev = rec["history"]["events"]
    assert len(ev) == 8
    assert all(isinstance(e, int) for e in ev)
    assert any(e & rounds.EVENT_DEGRADED for e in ev[2:6])
    assert ev[:2] == [0, 0] and ev[6:] == [0, 0]     # healthy outside window
    assert rec["degraded_rounds"] == sum(1 for e in ev if e)
    assert rec["schema"] == artifacts.SERVE_SCHEMA


def _bl1_experiment(mu):
    """A small BL1 cell whose learned H^k is the exact Hessian plus λI
    (Top-K keeps all r² coefficients, α = 1): sym(H^k) ⪰ λI = μI unless
    ``mu`` is set above its spectrum."""
    from repro.exp import registry

    cell = registry.MethodCell(
        "BL1", "bl1", 6, basis="data_outer",
        hess_comp=registry.CompressorCfg(kind="topk", k=36),
        model_comp=registry.CompressorCfg(kind="identity"),
        params=() if mu is None else (("mu", mu),))
    return registry.Experiment(
        name="serve_eigh_route", figure="extra", title="server eigh route",
        paper_ref="test", cells=(cell,),
        problem=registry.ProblemSpec(n_clients=6, m=24, d=18, r=6, lam=1e-3,
                                     newton_iters=12))


@pytest.mark.parametrize("mu", [None, 0.5], ids=["certified", "projected"])
def test_fed_serve_counts_server_eigh_rounds(tmp_path, monkeypatch, mu):
    """``meta.server_eigh`` counts the rounds whose server step took the
    eigendecomposition: none where H^k ⪰ μI, every one where μ sits above
    H^k's spectrum.  The trajectory stays in the reference backend's
    parity envelope (tests/test_batched_parity.py) either way, and the
    route bit is not a degradation."""
    from repro.exp import engine, registry
    from repro.launch import fed_serve

    exp = _bl1_experiment(mu)
    monkeypatch.setitem(registry.EXPERIMENT_REGISTRY, exp.name, exp)
    rec = fed_serve.serve(exp_name=exp.name, cell_name="BL1", chunk=4,
                          max_rounds=6, ckpt_dir=str(tmp_path),
                          log=lambda *a: None)
    eigh = sum(1 for e in rec["history"]["events"] if e & rounds.EVENT_EIGH)
    assert rec["meta"]["server_eigh"] == {"rounds": eigh, "of": 6}
    assert eigh == (0 if mu is None else 6)
    assert rec["degraded_rounds"] == 0
    ref = engine.run_cell(exp, exp.cell("BL1"), engine.build_problem(exp.problem),
                          backend="reference")
    np.testing.assert_allclose(rec["history"]["gaps"], ref.gaps, rtol=1e-9, atol=1e-8)
    np.testing.assert_allclose(rec["history"]["up_bits"], ref.up_bits, rtol=1e-12)


_ENV = {"PYTHONPATH": "src", "PATH": os.environ.get("PATH", "/usr/bin:/bin"),
        "JAX_PLATFORMS": "cpu", "HOME": os.environ.get("HOME", "/tmp")}


def _serve_cli(tmp, *extra):
    return subprocess.run(
        [sys.executable, "-m", "repro.launch.fed_serve", "--exp", "fig4",
         "--cell", "BL2_tau_half", "--seed", "3", "--max-rounds", "30",
         "--chunk", "6", "--dropout-p", "0.2", "--fault-seed", "11",
         *extra],
        env=_ENV, capture_output=True, text=True, timeout=900,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def test_fed_serve_kill9_resume_bitwise(tmp_path):
    """The acceptance scenario end-to-end through the CLI: SIGKILL mid-run
    (after round 14, losing the in-flight chunk), restart, and the final
    record — trajectory, events, per-leg bits — is byte-identical to the
    uninterrupted reference."""
    ref_json = str(tmp_path / "ref.json")
    res_json = str(tmp_path / "res.json")
    r = _serve_cli(tmp_path, "--ckpt-dir", str(tmp_path / "ref"),
                   "--result", ref_json)
    assert r.returncode == 0, r.stderr[-2000:]

    r = _serve_cli(tmp_path, "--ckpt-dir", str(tmp_path / "crash"),
                   "--crash-after-round", "14")
    assert r.returncode == -signal.SIGKILL, (r.returncode, r.stderr[-500:])
    # the kill must have actually cost progress: newest checkpoint < 30
    ts = [t for t, _ in artifacts.list_checkpoints(str(tmp_path / "crash"))]
    assert ts and max(ts) < 30

    r = _serve_cli(tmp_path, "--ckpt-dir", str(tmp_path / "crash"),
                   "--result", res_json)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "resumed from checkpoint" in r.stdout

    with open(ref_json) as f:
        ref = json.load(f)
    with open(res_json) as f:
        res = json.load(f)
    assert res["meta"]["resumed_from"] == max(ts)
    ref.pop("meta")
    res.pop("meta")
    assert ref == res    # bit-exact: gaps, events, every ledger leg


def test_schema_diff_validates_ckpt_dir(tmp_path):
    ckpt_dir = tmp_path / "ck"
    artifacts.save_checkpoint(
        str(ckpt_dir), t=3, carry_leaves=[np.zeros((2, 2))],
        streams={"eval_x": np.zeros((3, 2)), "events": np.zeros(3, np.int32)},
        root_key=np.zeros(2, np.uint32), config_digest="abc")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    tool = os.path.join(repo, "tools", "schema_diff.py")
    r = subprocess.run([sys.executable, tool, "--ckpt", str(ckpt_dir)],
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "ckpt schema ok" in r.stdout
    # corrupt the payload → digest mismatch must be reported
    npz = ckpt_dir / "ckpt-00000003.npz"
    npz.write_bytes(b"garbage")
    r = subprocess.run([sys.executable, tool, "--ckpt", str(ckpt_dir)],
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 1
    assert "sha256 mismatch" in r.stdout


# ------------------------------------------------------- compile cache / chip
_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_CACHE_SPY = r"""
import json, sys
import jax
dirs = []
_update = jax.config.update
def update(name, value):
    if name == "jax_compilation_cache_dir":
        dirs.append(value)
    return _update(name, value)
jax.config.update = update
from repro.launch import fed_serve
from repro.exp.__main__ import main as exp_main
out = sys.argv[1]
fed_serve.main(["--exp", "fig4", "--cell", "BL2_tau_half", "--max-rounds",
                "4", "--chunk", "2", "--ckpt-dir", out + "/ckpt"])
assert exp_main(["run", "--fig", "fig1r1", "--cell", "BL1", "--max-steps",
                 "2", "--out", out + "/res", "--artifacts",
                 out + "/res/exp"]) == 0
print("CACHE " + json.dumps({"set": dirs,
                             "dir": jax.config.jax_compilation_cache_dir}))
"""


def test_compile_cache_dir_comes_from_the_environment(tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set, fed_serve and the repro.exp CLI
    compile into that directory and no code path sets another."""
    xla = str(tmp_path / "xla")
    r = subprocess.run(
        [sys.executable, "-c", _CACHE_SPY, str(tmp_path)],
        env=dict(_ENV, JAX_COMPILATION_CACHE_DIR=xla), capture_output=True,
        text=True, timeout=900, cwd=_REPO)
    assert r.returncode == 0, r.stderr[-2000:]
    [line] = [ln for ln in r.stdout.splitlines() if ln.startswith("CACHE ")]
    got = json.loads(line[len("CACHE "):])
    assert got == {"set": [], "dir": xla}
    assert os.listdir(xla), "nothing was compiled into the cache"


def test_compile_cache_defaults_to_one_fixed_checkout_dir():
    script = ("import jax; from repro.core import progcache; "
              "print(progcache.enable_compile_cache()); "
              "print(jax.config.jax_compilation_cache_dir)")
    r = subprocess.run([sys.executable, "-c", script], env=_ENV,
                       capture_output=True, text=True, timeout=300,
                       cwd=_REPO)
    assert r.returncode == 0, r.stderr[-2000:]
    want = os.path.join(_REPO, ".jax_cache")
    assert r.stdout.split() == [want, want]


@pytest.mark.parametrize("where", ["checkout", "alone"])
def test_chip_smoke_refuses_without_a_tpu(tmp_path, where):
    """On the CPU, chip_smoke.py exits non-zero with a "no TPU" message and
    prints no result — in the checkout and as a lone file — before it
    builds any problem."""
    script = os.path.join(_REPO, "chip_smoke.py")
    cwd = _REPO
    if where == "alone":
        cwd = str(tmp_path)
        script = os.path.join(cwd, "chip_smoke.py")
        with open(os.path.join(_REPO, "chip_smoke.py")) as src, \
                open(script, "w") as dst:
            dst.write(src.read())
    r = subprocess.run([sys.executable, script], env=_ENV,
                       capture_output=True, text=True, timeout=300, cwd=cwd)
    assert r.returncode != 0
    assert "no TPU" in r.stderr
    assert r.stdout == ""
