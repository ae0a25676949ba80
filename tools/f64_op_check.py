"""Float64 accuracy of the fig1-xl BL1 round path on a TPU, one op at a time.

    python tools/f64_op_check.py            # on a machine with a TPU
    python tools/f64_op_check.py --small    # tiny shapes, to try it out

Each op of the round-0 -> round-1 path (elementwise transcendentals, a
dense dot, the per-client SVD basis, gradients, coefficient einsums, the
factor reduction, the server's Jacobi eigh and its solves) runs on the
first JAX device and on the CPU backend of the same process with the
same inputs; each ``DIAG`` line prints their relative difference (or a
residual).  On a CPU-only machine both sides are the CPU and every
difference is zero.
"""
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "src"))
SMALL = "--small" in sys.argv[1:]

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import bl, client_batch, glm
from repro.core.basis import orth_basis_from_data

TPU = jax.devices()[0]
CPU = jax.devices("cpu")[0]
print("devices", TPU.platform, TPU.device_kind, flush=True)


def both(fn, *args):
    f = jax.jit(fn)
    out = {}
    for name, dev in (("tpu", TPU), ("cpu", CPU)):
        t0 = time.perf_counter()
        a = jax.device_put(args, dev)
        r = jax.block_until_ready(f(*a))
        out[name] = jax.tree_util.tree_map(np.asarray, r)
        out[name + "_s"] = time.perf_counter() - t0
    return out


def rel(a, b):
    a, b = np.asarray(a), np.asarray(b)
    return float(np.max(np.abs(a - b)) / max(np.max(np.abs(b)), 1e-300))


def say(name, val, extra=""):
    print(f"DIAG {name}: {val!r} {extra}", flush=True)


rng = np.random.default_rng(1)
n, m, d, r, lam = (16, 8, 60, 8, 1e-3) if SMALL else (512, 32, 1200, 32, 1e-3)
t0 = time.perf_counter()
clients = glm.make_synthetic(seed=0, n_clients=n, m=m, d=d, r=r, lam=lam)
A = np.stack([np.asarray(c.A) for c in clients])
b = np.stack([np.asarray(c.b) for c in clients])
say("data", A.shape, f"{time.perf_counter() - t0:.1f}s")

# 1. elementwise transcendentals
z = np.concatenate([np.linspace(-40, 40, 100001), rng.standard_normal(10000)])
o = both(lambda z: (glm.sigmoid(z), jnp.logaddexp(0.0, -z), jnp.tanh(z),
                    jnp.exp(-jnp.abs(z)), jnp.log1p(jnp.abs(z))), z)
for k, nm in enumerate(("sigmoid", "logaddexp", "tanh", "exp", "log1p")):
    say(f"elementwise {nm} rel err", rel(o["tpu"][k], o["cpu"][k]))
ex = 0.5 * (np.tanh(z / 2) + 1)
say("sigmoid tpu vs numpy", rel(o["tpu"][0], ex))

# 2. dense f64 matmul
X, Y = rng.standard_normal((d, d)), rng.standard_normal((d, d))
o = both(lambda X, Y: X @ Y, X, Y)
say("matmul d^3 rel err", rel(o["tpu"], o["cpu"]),
    f"elementwise-max-rel {float(np.max(np.abs(o['tpu'] - o['cpu']) / (np.abs(X) @ np.abs(Y)))):.3e}")
o = both(lambda x, y: x * y + x, X, Y)
say("mul-add rel err", rel(o["tpu"], o["cpu"]))

# 3. data_outer basis by SVD (the repo function, op by op, on each device)
for name, dev in (("tpu", TPU), ("cpu", CPU)):
    with jax.default_device(dev):
        Vs = [np.asarray(orth_basis_from_data(jnp.asarray(A[i])).V)
              for i in range(8)]
    if name == "tpu":
        Vt_ = Vs
    else:
        Vc_ = Vs
for i in range(3):
    Pt, Pc = Vt_[i] @ Vt_[i].T, Vc_[i] @ Vc_[i].T
    say(f"svd basis client {i}: r", (Vt_[i].shape[1], Vc_[i].shape[1]))
    say(f"svd basis client {i}: projector diff", float(np.max(np.abs(Pt - Pc))))
    say(f"svd basis client {i}: tpu ||VtV-I||", float(np.max(np.abs(Vt_[i].T @ Vt_[i] - np.eye(Vt_[i].shape[1])))))
    say(f"svd basis client {i}: cpu ||VtV-I||", float(np.max(np.abs(Vc_[i].T @ Vc_[i] - np.eye(Vc_[i].shape[1])))))
    say(f"svd basis client {i}: tpu row-space residual", rel(A[i] @ Pt, A[i]))
    say(f"svd basis client {i}: cpu row-space residual", rel(A[i] @ Pc, A[i]))

# host-exact basis for everything below (basis errors excluded)
V = np.stack([np.linalg.svd(A[i], full_matrices=False)[2].T for i in range(n)])
x = rng.standard_normal(d) * 0.05

o = both(lambda A, b, x: client_batch.grads(
    client_batch.ClientBatch(A=A, b=b, lam=lam), x), A, b, x)
say("grads rel err", rel(o["tpu"], o["cpu"]))
o = both(lambda A, b, x: client_batch.hess_weights(
    client_batch.ClientBatch(A=A, b=b, lam=lam), x), A, b, x)
say("hess_weights rel err", rel(o["tpu"], o["cpu"]))
o = both(lambda A, b, x: client_batch.global_loss(
    client_batch.ClientBatch(A=A, b=b, lam=lam), x), A, b, x)
say("global_loss rel err", rel(o["tpu"], o["cpu"]), f"{o['tpu']!r} {o['cpu']!r}")

o = both(lambda A, V: jnp.einsum("nmd,ndr->nmr", A, V), A, V)
say("AV einsum rel err", rel(o["tpu"], o["cpu"]))
AV = o["cpu"]
w = np.asarray(both(lambda A, b, x: client_batch.hess_weights(
    client_batch.ClientBatch(A=A, b=b, lam=lam), x), A, b, x)["cpu"])
o = both(lambda AV, w: jnp.einsum("nmr,nm,nms->nrs", AV, w, AV) / m, AV, w)
say("hess_coeff_block rel err", rel(o["tpu"], o["cpu"]))
G = o["cpu"]
Vt = np.swapaxes(V, 1, 2)
o = both(lambda G, Vt: jnp.einsum("nrd,nre->de",
                                  jnp.einsum("nsr,nsd->nrd", G, Vt), Vt) / n,
         G, Vt)
say("recon_mean rel err", rel(o["tpu"], o["cpu"]))
H = o["cpu"] + lam * np.eye(d)

# 4. the server step's eigensolve and solves on the real H
g = rng.standard_normal(d) * 1e-2
o = both(lambda S: bl._sym_eigh(S), H)
wt, Vt_e = o["tpu"]
wc, Vc_e = o["cpu"]
say("eigh eigenvalues rel err (vs cpu)", rel(wt, wc), f"min eig {wc.min():.3e} max {wc.max():.3e}")
say("eigh tpu residual ||SV-Vw||/||S||", rel(H @ Vt_e, Vt_e * wt))
say("eigh cpu residual ||SV-Vw||/||S||", rel(H @ Vc_e, Vc_e * wc))
say("eigh tpu ||VtV-I||", float(np.max(np.abs(Vt_e.T @ Vt_e - np.eye(d)))))
say("eigh cpu ||VtV-I||", float(np.max(np.abs(Vc_e.T @ Vc_e - np.eye(d)))))
mu = 1e-3
ex = np.linalg.solve(H, g)
for nm, fn in (
        ("proj_mu_solve (refined)", lambda H, g: bl.proj_mu_solve(
            *bl.proj_mu_eig(H, mu), g)),
        ("eig solve (unrefined)", lambda H, g: (lambda P, w, V: V @ ((V.T @ g) / w))(
            *bl.proj_mu_eig(H, mu))),
        ("spd_solve", lambda H, g: glm.spd_solve(H, g)),
        ("proj_mu P - S", lambda H, g: bl.proj_mu_eig(H, mu)[0] - (H + H.T) / 2)):
    o = both(fn, H, g)
    if nm.startswith("proj_mu P"):
        say(nm + " max abs (tpu, cpu)", (float(np.max(np.abs(o["tpu"]))), float(np.max(np.abs(o["cpu"])))))
        continue
    say(nm + " tpu vs numpy", rel(o["tpu"], ex), f"cpu vs numpy {rel(o['cpu'], ex):.3e} tpu {o['tpu_s']:.1f}s")
print("DIAG done", flush=True)
