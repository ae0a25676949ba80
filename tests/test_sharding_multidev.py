"""Multi-device sharding tests (subprocess: 8 virtual CPU devices).

Verifies that distributed execution is NUMERICALLY IDENTICAL to the
single-device reference — expert-parallel MoE vs the global dispatch path,
a sharded train step vs the unsharded one, and the round engine's
client-sharded aggregation backend (shard_map reducer) vs the single-device
fast path — the last one BITWISE.
"""
import subprocess
import sys


SCRIPT_MOE = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.models import layers as L
from repro.models.config import LayerSpec, ModelConfig, MoEConfig
from repro.sharding.rules import make_rules

cfg = ModelConfig(name="t", n_layers=2, d_model=64, n_heads=4, n_kv_heads=2,
                  d_ff=128, vocab_size=256,
                  group=(LayerSpec(ffn="moe"),),
                  moe=MoEConfig(n_experts=8, top_k=2, n_shared=1, d_expert=96,
                                capacity_factor=8.0))  # big cap: no drops
p = L.init_moe(jax.random.PRNGKey(0), cfg, jnp.float32)
rng = np.random.default_rng(0)
x = jnp.asarray(rng.standard_normal((4, 16, 64)), jnp.float32)

# reference: global path (rules=None)
ref, aux_ref = L.moe(p, x, cfg, None)

# distributed: 2 data x 4 model, expert-parallel path
mesh = jax.make_mesh((2, 4), ("data", "model"))
rules = make_rules(mesh, batch_size=4)
with mesh:
    xs = jax.device_put(x, NamedSharding(mesh, P("data", None, None)))
    ps = jax.tree.map(lambda a: jax.device_put(a, NamedSharding(mesh, P())), p)
    ps["wi"] = jax.device_put(p["wi"], NamedSharding(mesh, P("model", None, None)))
    ps["wg"] = jax.device_put(p["wg"], NamedSharding(mesh, P("model", None, None)))
    ps["wo"] = jax.device_put(p["wo"], NamedSharding(mesh, P("model", None, None)))
    out, aux = jax.jit(lambda pp, xx: L.moe(pp, xx, cfg, rules))(ps, xs)

err = float(jnp.abs(out - ref).max())
# aux is the mean of per-data-shard load-balance losses — close to but not
# bit-identical with the global one (documented local-aux convention)
auxerr = abs(float(aux) - float(aux_ref))
assert err < 2e-4, err
assert auxerr < 5e-3, auxerr
print("MOE_PARITY_OK", err, auxerr)
"""

SCRIPT_TRAIN = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs import get_config
from repro.models import model as M
from repro.models.steps import make_train_step
from repro.optim import adamw_init
from repro.sharding.rules import make_rules, param_specs

cfg = get_config("stablelm_12b").reduced()
params = M.init_params(jax.random.PRNGKey(0), cfg, jnp.float32)
opt = adamw_init(params)
rng = np.random.default_rng(1)
batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (8, 33)), jnp.int32)}

# single-device reference
_,_,m_ref = jax.jit(make_train_step(cfg, None, remat=False))(params, opt, batch)

mesh = jax.make_mesh((4, 2), ("data", "model"))
rules = make_rules(mesh, batch_size=8)
with mesh:
    specs = param_specs(params, cfg, rules)
    ps = jax.tree.map(jax.device_put, params, specs)
    os_ = adamw_init(ps)
    bs = {"tokens": jax.device_put(batch["tokens"], NamedSharding(mesh, P(("data",), None)))}
    _,_,m = jax.jit(make_train_step(cfg, rules, remat=True))(ps, os_, bs)

d = abs(float(m["loss"]) - float(m_ref["loss"]))
assert d < 5e-3, (float(m["loss"]), float(m_ref["loss"]))
print("TRAIN_PARITY_OK", d)
"""


SCRIPT_ROUND_ENGINE = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from repro.core import baselines, bl, glm
from repro.core.basis import orth_basis_from_data
from repro.core.compressors import Identity, TopK

clients = glm.make_synthetic(seed=0, n_clients=8, m=30, d=40, r=12, lam=1e-3)
x0 = jnp.zeros(40, jnp.float64)
xs = glm.newton_solve(clients, x0, 20)
bases = [orth_basis_from_data(c.A) for c in clients]
r = bases[0].r
n = 8
assert len(jax.devices()) == 8

runs = {
    # block-mode BL1, full-d BL2 with partial participation, PSD BL3, and
    # the Bernoulli-aggregation spec: every carry/reduction shape the
    # engine supports crosses the shard_map boundary here
    "bl1": lambda b: bl.bl1(clients, bases, [TopK(k=r)] * n, Identity(),
                            x0, xs, 12, backend=b),
    "bl2pp": lambda b: bl.bl2(clients, bases, [TopK(k=2 * r)] * n,
                              [Identity()] * n, x0, xs, 15, tau=3, seed=2,
                              backend=b),
    "bl3": lambda b: bl.bl3(clients, [Identity()] * n, [Identity()] * n,
                            x0, xs, 10, backend=b),
    "bag": lambda b: baselines.fednl_bag(clients, bases, [TopK(k=r)] * n,
                                         x0, xs, 12, q=0.5, seed=1, backend=b),
}
for name, run in runs.items():
    h_fast = run("fast")            # single-device: all 8 clients on dev 0
    h_sh = run("fast+sharded")      # 8 clients sharded 1-per-device
    assert h_sh.gaps == h_fast.gaps, (name, h_sh.gaps, h_fast.gaps)
    assert h_sh.up_bits == h_fast.up_bits, name
    assert h_sh.down_bits == h_fast.down_bits, name
# reference parity holds through the sharded backend too (deterministic,
# full-participation configs only — bl2pp/bag draw different PRNG streams)
for name in ("bl1", "bl3"):
    h_ref = runs[name]("reference")
    h_sh = runs[name]("fast+sharded")
    np.testing.assert_allclose(h_sh.gaps, h_ref.gaps, rtol=1e-9, atol=1e-8)
    np.testing.assert_allclose(h_sh.up_bits, h_ref.up_bits, rtol=1e-12)
print("ROUND_ENGINE_BITWISE_OK")
"""


SCRIPT_SERVE_CHUNKED = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from repro.core import batched, comm, faults, glm, rounds
from repro.core.compressors import Identity, TopK

clients = glm.make_synthetic(seed=0, n_clients=8, m=24, d=20, r=8, lam=1e-3)
from repro.core.basis import orth_basis_from_data
bases = [orth_basis_from_data(c.A) for c in clients]
x0 = jnp.zeros(20, jnp.float64)
spec, batch, basisb = batched.bl2_setup(
    clients, bases, [TopK(k=8)] * 8, [Identity()] * 8, tau=4)
assert len(jax.devices()) == 8
root = jax.random.PRNGKey(3)
plan = faults.FaultPlan(n=8, dropout_p=0.25,
                        outages=(faults.Outage(5, 4, 10),), seed=13)

def drive(sharded, chunk, t1=16):
    carry = rounds.init_serve_carry(spec, batch, basisb, x0, sharded=sharded)
    xs, evs, legs = [], [], {k: [] for k in comm.CommLedger.LEGS}
    t = 0
    while t < t1:
        steps = min(chunk, t1 - t)
        avail, _ = plan.schedule(t, steps)
        carry, ys = rounds.run_chunk(spec, batch, basisb, x0, carry, t,
                                     steps, root, avail=avail,
                                     sharded=sharded)
        xs.append(np.asarray(ys[0])); evs.append(np.asarray(ys[2]))
        for k in legs:
            legs[k].append(np.asarray(getattr(ys[1], k)))
        t += steps
    return (np.concatenate(xs), np.concatenate(evs),
            {k: np.concatenate(v) for k, v in legs.items()}, carry)

# 8-device chunked serve ≡ single-device, and chunk-size invariant — the
# resume contract (carry crosses the shard_map boundary between chunks)
v1 = drive(False, 16)      # vmap, one chunk
s1 = drive(True, 16)       # shard_map, one chunk
s2 = drive(True, 5)        # shard_map, resumed every 5 rounds
for a, b in ((s1, v1), (s2, v1)):
    np.testing.assert_array_equal(a[0], b[0])
    np.testing.assert_array_equal(a[1], b[1])
    for k in a[2]:
        np.testing.assert_array_equal(a[2][k], b[2][k])
for la, lb in zip(jax.tree_util.tree_leaves(s2[3]),
                  jax.tree_util.tree_leaves(v1[3])):
    np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))
print("SERVE_CHUNKED_MULTIDEV_OK")
"""


SCRIPT_APPROX = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from repro.core import baselines, bl, glm
from repro.core.basis import orth_basis_from_data
from repro.core.compressors import Identity, TopK

clients = glm.make_synthetic(seed=0, n_clients=8, m=30, d=40, r=12, lam=1e-3)
x0 = jnp.zeros(40, jnp.float64)
xs = glm.newton_solve(clients, x0, 20)
bases = [orth_basis_from_data(c.A) for c in clients]
r = bases[0].r
n = 8
assert len(jax.devices()) == 8

runs = {
    "bl1": lambda **kw: bl.bl1(clients, bases, [TopK(k=r)] * n, Identity(),
                               x0, xs, 12, **kw),
    "bl2pp": lambda **kw: bl.bl2(clients, bases, [TopK(k=2 * r)] * n,
                                 [Identity()] * n, x0, xs, 12, tau=3, seed=2,
                                 **kw),
    "bl3": lambda **kw: bl.bl3(clients, [Identity()] * n, [Identity()] * n,
                               x0, xs, 10, **kw),
    "bag": lambda **kw: baselines.fednl_bag(clients, bases, [TopK(k=r)] * n,
                                            x0, xs, 12, q=0.5, seed=1, **kw),
}
# exact=False swaps the fixed-order gather for ring collectives (psum /
# pmean per the spec's ReducePlan): reductions associate in ring order, so
# trajectories may drift by ulps — but over a pinned short horizon they
# must stay inside a tight envelope of the exact run, and the bit
# ACCOUNTING (sums of exactly-representable bit prices) must not move.
for name, run in runs.items():
    h_ex = run(backend="fast+sharded")               # exact=True default
    h_ap = run(backend="fast+sharded", exact=False)  # ring collectives
    np.testing.assert_allclose(h_ap.gaps, h_ex.gaps, rtol=1e-6, atol=1e-12,
                               err_msg=name)
    np.testing.assert_allclose(h_ap.up_bits, h_ex.up_bits, rtol=1e-9,
                               err_msg=name)
    np.testing.assert_allclose(h_ap.down_bits, h_ex.down_bits, rtol=1e-9,
                               err_msg=name)
print("APPROX_ENVELOPE_OK")
"""


SCRIPT_STREAM = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from repro.core import bl, glm
from repro.core.basis import orth_basis_from_data
from repro.core.compressors import Identity, TopK
from repro.core.rounds import StreamHook

clients = glm.make_synthetic(seed=0, n_clients=8, m=24, d=20, r=8, lam=1e-3)
x0 = jnp.zeros(20, jnp.float64)
xs = glm.newton_solve(clients, x0, 20)
bases = [orth_basis_from_data(c.A) for c in clients]
assert len(jax.devices()) == 8

seen = []
def cb(t, x, led):
    # host callback sees fully-gathered server state: the round index, the
    # replicated iterate, and the cumulative ledger
    seen.append((int(t), np.asarray(x).shape, float(np.asarray(led.hess_up))))

hook = StreamHook(every=2, callback=cb)
h1 = bl.bl1(clients, bases, [TopK(k=8)] * 8, Identity(), x0, xs, 5,
            backend="fast+sharded", stream=hook)
jax.effects_barrier()
h0 = bl.bl1(clients, bases, [TopK(k=8)] * 8, Identity(), x0, xs, 5,
            backend="fast+sharded")
assert [t for t, _, _ in seen] == [0, 2, 4], seen
assert all(shape == (20,) for _, shape, _ in seen), seen
hb = [b for _, _, b in seen]
assert hb == sorted(hb), seen             # cumulative ledger is monotone
assert h1.gaps == h0.gaps and h1.up_bits == h0.up_bits
print("STREAM_SHARDED_OK")
"""


def _run(script):
    # JAX_PLATFORMS=cpu: the child runs on virtual CPU devices (the
    # XLA_FLAGS in each script), never on an accelerator
    return subprocess.run([sys.executable, "-c", script], capture_output=True,
                          text=True, timeout=900,
                          env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
                               "JAX_PLATFORMS": "cpu"})


def test_expert_parallel_moe_matches_global_path():
    r = _run(SCRIPT_MOE)
    assert "MOE_PARITY_OK" in r.stdout, r.stdout + r.stderr[-3000:]


def test_sharded_train_step_matches_single_device():
    r = _run(SCRIPT_TRAIN)
    assert "TRAIN_PARITY_OK" in r.stdout, r.stdout + r.stderr[-3000:]


def test_round_engine_shard_map_reducer_bitwise():
    """Clients sharded over 8 devices reproduce the single-device fast-path
    histories BITWISE (gaps, uplink and downlink bits) for BL1/BL2/BL3 and
    the FedNL-BAG spec, and stay within reference parity."""
    r = _run(SCRIPT_ROUND_ENGINE)
    assert "ROUND_ENGINE_BITWISE_OK" in r.stdout, r.stdout + r.stderr[-3000:]


def test_serve_chunked_driver_multidev_bitwise():
    """The service-loop chunked driver on 8 devices — carry resumed across
    chunk boundaries through the shard_map program — is bitwise equal to the
    single-device single-chunk run under a non-trivial fault plan."""
    r = _run(SCRIPT_SERVE_CHUNKED)
    assert "SERVE_CHUNKED_MULTIDEV_OK" in r.stdout, r.stdout + r.stderr[-3000:]


def test_nonexact_collectives_stay_in_parity_envelope():
    """exact=False (ring psum/pmean per the spec's ReducePlan) on 8 devices
    tracks the exact fixed-order run within a ≤1e-6 relative envelope over
    a pinned horizon, for BL1/BL2/BL3 and FedNL-BAG, with unchanged bit
    accounting."""
    r = _run(SCRIPT_APPROX)
    assert "APPROX_ENVELOPE_OK" in r.stdout, r.stdout + r.stderr[-3000:]


def test_streamhook_mid_run_emission_on_8_devices():
    """The acceptance scenario for sharded streaming: a StreamHook attached
    to backend='fast+sharded' on 8 devices fires mid-run at its cadence
    with gathered server state, and the history it rode along is bitwise
    the hook-free run."""
    r = _run(SCRIPT_STREAM)
    assert "STREAM_SHARDED_OK" in r.stdout, r.stdout + r.stderr[-3000:]
