"""Declarative method specs for the unified round engine (`repro.core.rounds`).

Each spec is a small frozen dataclass (hashable → static under jit) holding
the method's hyperparameters and three hooks consumed by the engine driver:

  * ``prepare(R, batch, basisb, x0)`` — per-run traced precomputation
    (typically a `CoeffLayout`);
  * ``init(R, env)``                 — the scan carry at round 0;
  * ``step(R, env, carry, rc)``      — one round (``rc`` is a
    `rounds.RoundCtx`: the round's PRNG key, the absolute round index and
    the fault layer's optional availability mask), returning
    ``(carry, (eval_x, ledger, event))``: the iterate the round is
    evaluated at, the cumulative `comm.CommLedger`, and the round's int32
    `rounds.EVENT_*` event bitmask (the engine turns the eval_x
    stream into f(x)−f* gaps outside the scan, the ledger stream into
    per-leg bit histories, and the event stream into `History.events` on
    the service loop).

Communication accounting is per-leg and declarative: compressors return
message `Counts`, specs price them with ``comm.price(comp.wire, counts)``
and charge the right ledger leg (`hess_up` / `grad_up` / `model_down`; the
one-time basis shipment sits on `basis_ship` from round 0).  No spec keeps
hand-maintained ``up = up + ...`` scalars.

All cross-client reductions go through the `Reducer` R, so every spec runs
unchanged on the single-device backend and on the client-sharded shard_map
backend.  The specs here are ports of the previously triplicated scan bodies
in `repro.core.batched` — parity with the op-by-op reference backend is
pinned by tests/test_batched_parity.py — plus one new method (FedNL with
Bernoulli aggregation, after "Distributed Newton-Type Methods with
Communication Compression and Bernoulli Aggregation", arXiv 2206.03588)
that exists to demonstrate that a new method is a ~50-line spec.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp

from . import client_batch, comm, glm
from .bl import (
    _psd_h_tilde,
    _psd_reconstruct_full,
    _psd_sum_matrix,
    proj_mu_route,
    proj_mu_solve,
)
from .comm import FLOAT_BITS, CommLedger
from .compressors import Compressor
from .rounds import (
    EVENT_ALL_DOWN,
    EVENT_DEGRADED,
    EVENT_EIGH,
    EVENT_NONE,
    Reducer,
    ReducePlan,
    coeff_layout,
    downlink_broadcast,
    global_grad,
    participation,
    refresh_due,
    shift_update,
    tree_shift_update,
    tree_shift_update_sum,
    xi_mask,
    xi_scalar,
)


def _sym_b(H):
    """(n, d, d) batched symmetrization."""
    return (H + jnp.transpose(H, (0, 2, 1))) / 2.0


def _fro_b(H):
    """(n, d, d) → (n,) Frobenius norms."""
    return jnp.sqrt(jnp.sum(H * H, axis=(1, 2)))


def _mv(Hb, xb):
    """(n, d, d) @ (n, d) → (n, d), batch-size-invariantly (see bmv)."""
    return client_batch.bmv(Hb, xb)


class MethodSpec:
    """Base hooks; subclasses are frozen dataclasses (static under jit)."""

    #: True for specs whose basis is a fleet-global pytree with no client
    #: axis (BL-DNN) — the sharded engine replicates it instead of sharding
    #: its leading dimension over the client mesh.
    basis_replicated = False

    #: True for specs whose round reacts to the fault layer's availability
    #: mask (`RoundCtx.avail`): the partial-participation methods (BL2/BL3)
    #: and the Bernoulli-lazy uplink (FedNL-BAG).  Specs modelling a fully
    #: synchronous fleet leave this False and `repro.launch.fed_serve`
    #: refuses to inject faults into them rather than silently ignoring
    #: the schedule.
    supports_faults = False

    #: Collective-mode selection for the sharded reducer's exact=False path
    #: (see `rounds.ReducePlan`).  The default psums every leg; specs with
    #: f32 payloads (BL-DNN) override toward pmean to keep local partials
    #: O(1).  Ignored entirely in exact mode.
    reduce_plan = ReducePlan()

    #: True for specs whose `step` runs correctly under the cohort-streaming
    #: engine (`repro.core.cohort`): every fleet reduction goes through a
    #: NAMED `reduce_tree` dict declared in `cohort_aggregates`, so the
    #: engine can maintain the absent clients' frozen contributions.  The
    #: natural cohort methods are the partial-participation ones (BL2/BL3,
    #: Alg. 2–3) and the Bernoulli-lazy uplink (FedNL-BAG).
    supports_cohort = False

    #: Names for the TOP-LEVEL elements of the carry tuple, in order — the
    #: streaming engine's handle for splitting the carry into host-resident
    #: client state (`ClientStore.state`) and resident server state, and for
    #: matching `cohort_aggregates` entries to carry leaves.
    carry_names: Tuple[str, ...] = ()

    def cohort_aggregates(self):
        """Fleet aggregates this spec's `step` reduces over RAW carry
        leaves: ``{aggregate_name: (carry_leaf_name, op)}`` with op in
        {"mean", "max"}.  For each ``mean`` entry the streaming engine
        incrementally maintains the fleet-wide sum of that carry leaf and
        hands the chunk program ``frozen[name] = sum over absent clients``;
        for ``max`` it computes the absent clients' max per epoch.
        Delta-style mean aggregates (absent clients contribute exactly 0)
        are NOT declared — a missing frozen entry is an implicit zero."""
        return {}

    def cohort_init_extras(self, R: Reducer, env, carry):
        """Per-client stacked arrays whose FLEET SUM feeds a derived piece
        of server init state (``{name: (n_local, ...) array}``).  The
        engine evaluates this slab-by-slab at fleet init, accumulates the
        sums, and passes them to `cohort_server_init`."""
        return {}

    def cohort_server_init(self, env, sums, n_total: int, carry):
        """Server carry elements that depend on a fleet reduction at init:
        ``{carry_name: value}`` computed from the accumulated
        `cohort_init_extras` sums.  Everything not named here keeps its
        per-slab `init` value (which must then be fleet-independent)."""
        return {}

    def prepare(self, R: Reducer, batch, basisb, x0):
        return None

    def init(self, R: Reducer, env):
        raise NotImplementedError

    def step(self, R: Reducer, env, carry, rc):
        raise NotImplementedError

    def eval_streams(self, batch, xs_t, f_star):
        """Post-scan evaluation of the whole trajectory: the ``xs_t`` the
        spec's ``step`` emitted (stacked over rounds) → a dict of named
        (steps,) streams, always containing ``"gap"`` (what `History.gaps`
        records).  Runs OUTSIDE the scan in one shared program on every
        aggregation backend — that is what keeps recorded histories
        bitwise-identical across backends.  The default is the GLM
        optimality gap f(x_t) − f*; pytree specs override (BL-DNN reports
        training error rate plus a loss stream)."""
        from .rounds import default_gap_stream

        return {"gap": default_gap_stream(batch, xs_t, f_star)}


# ==========================================================================
# BL1 — Algorithm 1
# ==========================================================================
@dataclasses.dataclass(frozen=True)
class BL1Spec(MethodSpec):
    hess_comp: Compressor
    model_comp: Compressor
    alpha: float
    eta: float
    p: float
    mu: float
    init_exact: bool
    grad_bits: float
    init_hess_bits: float
    basis_bits: float
    block: bool

    def prepare(self, R, batch, basisb, x0):
        return coeff_layout(R, batch, basisb, x0, self.block)

    def init(self, R, env):
        lay = env.extra
        x0 = env.x0
        L0 = lay.target_at(x0) if self.init_exact else jnp.zeros(lay.shape, x0.dtype)
        H0 = (R.mean(lay.recon(L0)) if lay.recon_mean is None
              else lay.recon_mean(L0)) + lay.ridge
        grad_w0 = global_grad(R, env.batch, x0)
        led0 = CommLedger.create(hess_up=self.init_hess_bits,
                                 basis_ship=self.basis_bits)
        return (x0, x0, L0, H0, grad_w0, jnp.asarray(True), led0)

    def step(self, R, env, carry, rc):
        key_t = rc.key
        z, w, L, H, grad_w, xi, led = carry
        lay = env.extra
        led_z = led  # the gap is evaluated at z, post-scan

        # client-side legs: gradients + Hessian-coefficient learning, then
        # ONE fused uplink reduction for the round (gradient stack, Hessian
        # shift reconstruction, and the bit accounting share a collective;
        # in block mode the shift reduces apart, as (n, r, d) factors)
        k_h, k_m, k_xi = jax.random.split(key_t, 3)
        S, L_n, counts = shift_update(
            lambda delta: self.hess_comp.compress(R.client_keys(k_h), delta),
            lay.target_at(z), L, self.alpha)
        up = {"grad_z": client_batch.grads(env.batch, z),
              "sbits": comm.price(self.hess_comp.wire, counts)}
        if lay.recon_mean is None:
            up["dH"] = lay.recon(self.alpha * S)
        red = R.reduce_tree(up)
        grad_z = red["grad_z"]
        H_n = H + (red["dH"] if lay.recon_mean is None
                   else lay.recon_mean(self.alpha * S))
        led = led.add(grad_up=jnp.where(xi, self.grad_bits, 0.0),
                      hess_up=red["sbits"])

        # gradient leg (both branches evaluated, selected by ξ)
        w_n = jnp.where(xi, z, w)
        grad_w_n = jnp.where(xi, grad_z, grad_w)

        # server model step (μ-projection + Newton solve computed once per
        # fleet, not once per shard) + compressed broadcast
        def server_step(H, grad_z, z, w, grad_w, xi):
            def solve(Hmu, *factors):
                g = jnp.where(xi, grad_z, Hmu @ (z - w) + grad_w)
                return z - proj_mu_solve(Hmu, *factors, g)
            return proj_mu_route(H, self.mu, solve, R.ndev)

        x_next, eigh = R.once(server_step, H, grad_z, z, w, grad_w, xi)
        v, vbits = self.model_comp(k_m, x_next - z)
        led = led.add(model_down=vbits)
        z_n = z + self.eta * v
        xi_n = xi_scalar(k_xi, self.p)
        ys = (z, led_z, jnp.int32(EVENT_EIGH) * eigh)
        return (z_n, w_n, L_n, H_n, grad_w_n, xi_n, led), ys


# ==========================================================================
# BL2 — Algorithm 2
# ==========================================================================
@dataclasses.dataclass(frozen=True)
class BL2Spec(MethodSpec):
    hess_comp: Compressor
    model_comp: Compressor
    alpha: float
    eta: float
    p: float
    tau: int
    init_exact: bool
    init_hess_bits: float
    basis_bits: float
    block: bool

    supports_faults = True        # partial participation absorbs dropouts
    supports_cohort = True        # Alg. 2: absent clients' state freezes
    carry_names = ("z", "w", "L", "Hi", "li", "gi", "led")

    def cohort_aggregates(self):
        # the server system is assembled from RAW per-client carry state
        # every round, so absent clients' frozen rows must keep
        # contributing their epoch-start values
        return {"H": ("Hi", "mean"), "l": ("li", "mean"), "g": ("gi", "mean")}

    def prepare(self, R, batch, basisb, x0):
        return coeff_layout(R, batch, basisb, x0, self.block)

    def init(self, R, env):
        lay = env.extra
        x0 = env.x0
        x0b = jnp.broadcast_to(x0, (R.n_local, env.batch.d))
        L0 = lay.target_at(x0) if self.init_exact else jnp.zeros(lay.shape, x0.dtype)
        Hi0 = lay.recon(L0) + lay.ridge
        li0 = _fro_b(_sym_b(Hi0) - client_batch.hess(env.batch, x0b))
        gi0 = (_mv(_sym_b(Hi0), x0b) + li0[:, None] * x0b
               - client_batch.grads(env.batch, x0b))
        led0 = CommLedger.create(hess_up=self.init_hess_bits,
                                 basis_ship=self.basis_bits)
        return (x0b, x0b, L0, Hi0, li0, gi0, led0)

    def step(self, R, env, carry, rc):
        key_t = rc.key
        z, w, L, Hi, li, gi, led = carry
        batch = env.batch
        d = batch.d
        lay = env.extra
        I = jnp.eye(d, dtype=env.x0.dtype)

        # one fused uplink collective for the server system, one solve per
        # fleet (shard 0) instead of one per shard
        red = R.reduce_tree({"H": Hi, "l": li, "g": gi})
        x_cur = R.once(
            lambda H, l_avg, g: glm.spd_solve(
                (H + H.T) / 2.0 + l_avg * I, g),
            red["H"], red["l"], red["g"])
        ys = (x_cur, led)  # gap evaluated at x_cur, outside the scan

        k_part, k_m, k_h, k_xi = jax.random.split(key_t, 4)
        part, pev = participation(R, k_part, self.tau, avail=rc.avail)

        # compressed model broadcast (participants only)
        z_n, dbits = downlink_broadcast(R, self.model_comp, k_m, z, x_cur,
                                        self.eta, part)
        led = led.add(model_down=dbits)

        # Hessian-coefficient learning
        S, L_plus, counts = shift_update(
            lambda delta: self.hess_comp.compress(R.client_keys(k_h), delta),
            lay.target_at(z_n), L, self.alpha)
        sbits = comm.price(self.hess_comp.wire, counts)
        L_n = jnp.where(part[:, None, None], L_plus, L)
        Hi_n = jnp.where(part[:, None, None], Hi + lay.recon(self.alpha * S), Hi)
        Hs_n = _sym_b(Hi_n)
        li_n = jnp.where(part, _fro_b(Hs_n - client_batch.hess(batch, z_n)), li)

        xi = xi_mask(R, k_xi, self.p) & part
        w_n = jnp.where(xi[:, None], z_n, w)
        # ξ=1: fresh g_i at the new w; ξ=0: server-reconstructed difference.
        # Non-participants: Hi_n = Hi and li_n = li exactly, so gi_recon = gi.
        gi_fresh = (_mv(Hs_n, w_n) + li_n[:, None] * w_n
                    - client_batch.grads(batch, w_n))
        gi_recon = gi + _mv(Hs_n - _sym_b(Hi), w) + (li_n - li)[:, None] * w
        gi_n = jnp.where(xi[:, None], gi_fresh, gi_recon)

        g_bits = jnp.where(xi, d * FLOAT_BITS, FLOAT_BITS + 1.0)
        bits = R.reduce_tree({"s": jnp.where(part, sbits, 0.0),
                              "g": jnp.where(part, g_bits, 0.0)}, "sum")
        led = led.add(hess_up=bits["s"] / R.n_total,
                      grad_up=bits["g"] / R.n_total)
        return (z_n, w_n, L_n, Hi_n, li_n, gi_n, led), (*ys, pev)


# ==========================================================================
# BL3 — Algorithm 3 (PSD basis of Example 5.1)
# ==========================================================================
@dataclasses.dataclass(frozen=True)
class BL3Spec(MethodSpec):
    hess_comp: Compressor
    model_comp: Compressor
    alpha: float
    eta: float
    p: float
    tau: int
    c: float
    option: int

    supports_faults = True        # partial participation absorbs dropouts
    supports_cohort = True        # Alg. 3: absent clients' state freezes
    carry_names = ("z", "w", "zprev", "L", "gam", "A", "C", "g1", "g2",
                   "beta", "led")

    def cohort_aggregates(self):
        return {"A": ("A", "mean"), "C": ("C", "mean"), "g1": ("g1", "mean"),
                "g2": ("g2", "mean"), "beta": ("beta", "max")}

    def prepare(self, R, batch, basisb, x0):
        return _psd_sum_matrix(batch.d, x0.dtype)

    def init(self, R, env):
        Ssum = env.extra
        x0b = jnp.broadcast_to(env.x0, (R.n_local, env.batch.d))
        L0 = jax.vmap(_psd_h_tilde)(client_batch.hess(env.batch, x0b))
        gam0 = jnp.maximum(self.c, jnp.max(jnp.abs(L0), axis=(1, 2)))
        A0 = jax.vmap(_psd_reconstruct_full)(L0) + 2.0 * gam0[:, None, None] * Ssum
        C0 = 2.0 * gam0[:, None, None] * Ssum
        # h̃(∇²f_i(w⁰)) = L⁰ at init, so β_i⁰ = 1 exactly (as the reference
        # backend's max over a ratio of identical matrices evaluates to)
        beta0 = jnp.ones((R.n_local,), env.x0.dtype)
        g1_0 = _mv(A0, x0b)
        g2_0 = _mv(C0, x0b) + client_batch.grads(env.batch, x0b)
        led0 = CommLedger.create(
            hess_up=(env.batch.d * (env.batch.d + 1) // 2) * FLOAT_BITS)
        return (x0b, x0b, x0b, L0, gam0, A0, C0, g1_0, g2_0, beta0, led0)

    def step(self, R, env, carry, rc):
        key_t = rc.key
        z, w, zprev, L, gam, A_i, C_i, g1, g2, beta_i, led = carry
        batch = env.batch
        d = batch.d
        Ssum = env.extra
        h_tilde = jax.vmap(_psd_h_tilde)
        recon_full = jax.vmap(_psd_reconstruct_full)

        # four means + the β max fused into one uplink collective; the
        # server system assembles and solves once per fleet (shard 0)
        red = R.reduce_tree(
            {"A": A_i, "C": C_i, "g1": g1, "g2": g2, "beta": beta_i},
            {"A": "mean", "C": "mean", "g1": "mean", "g2": "mean",
             "beta": "max"})
        x_cur = R.once(
            lambda beta, A, C, g1m, g2m: glm.qr_solve(
                beta * A - C, beta * g1m - g2m),
            red["beta"], red["A"], red["C"], red["g1"], red["g2"])
        ys = (x_cur, led)  # gap evaluated at x_cur, outside the scan

        k_part, k_m, k_h, k_xi = jax.random.split(key_t, 4)
        part, pev = participation(R, k_part, self.tau, avail=rc.avail)

        zprev_n = jnp.where(part[:, None], z, zprev)
        z_n, dbits = downlink_broadcast(R, self.model_comp, k_m, z, x_cur,
                                        self.eta, part)
        led = led.add(model_down=dbits)

        target = h_tilde(client_batch.hess(batch, z_n))
        S, L_plus, counts = shift_update(
            lambda delta: self.hess_comp.compress(R.client_keys(k_h), delta),
            target, L, self.alpha)
        sbits = comm.price(self.hess_comp.wire, counts)
        L_n = jnp.where(part[:, None, None], L_plus, L)
        gam_n = jnp.where(part,
                          jnp.maximum(self.c, jnp.max(jnp.abs(L_n), axis=(1, 2))),
                          gam)
        if self.option == 1:
            num = h_tilde(client_batch.hess(batch, zprev_n))
        else:
            num = target
        beta_cand = jnp.max(
            (num + 2.0 * gam_n[:, None, None]) / (L_n + 2.0 * gam_n[:, None, None]),
            axis=(1, 2),
        )
        beta_i_n = jnp.where(part, beta_cand, beta_i)
        dgam = (gam_n - gam)[:, None, None]
        A_n = jnp.where(part[:, None, None],
                        A_i + recon_full(L_n - L) + 2.0 * dgam * Ssum, A_i)
        C_n = jnp.where(part[:, None, None], C_i + 2.0 * dgam * Ssum, C_i)

        xi = xi_mask(R, k_xi, self.p) & part
        w_n = jnp.where(xi[:, None], z_n, w)
        g1_fresh = _mv(A_n, w_n)
        g2_fresh = _mv(C_n, w_n) + client_batch.grads(batch, w_n)
        # non-participants: A_n = A_i, C_n = C_i ⇒ recon branch keeps g1/g2
        g1_recon = g1 + _mv(A_n - A_i, w)
        g2_recon = g2 + _mv(C_n - C_i, w)
        g1_n = jnp.where(xi[:, None], g1_fresh, g1_recon)
        g2_n = jnp.where(xi[:, None], g2_fresh, g2_recon)

        # every PARTICIPANT's β_i^{k+1} reaches the server (one float,
        # billed with the Hessian leg; silent clients send nothing)
        g_bits = jnp.where(xi, 2.0 * d * FLOAT_BITS, 2.0 * FLOAT_BITS + 1.0)
        bits = R.reduce_tree(
            {"s": jnp.where(part, sbits + FLOAT_BITS, 0.0),
             "g": jnp.where(part, g_bits, 0.0)}, "sum")
        led = led.add(hess_up=bits["s"] / R.n_total,
                      grad_up=bits["g"] / R.n_total)
        carry_n = (z_n, w_n, zprev_n, L_n, gam_n, A_n, C_n, g1_n, g2_n,
                   beta_i_n, led)
        return carry_n, (*ys, pev)


# ==========================================================================
# Baselines: GD, DIANA, Newton
# ==========================================================================
@dataclasses.dataclass(frozen=True)
class GDSpec(MethodSpec):
    lr: float

    def init(self, R, env):
        return (env.x0, CommLedger.create())

    def step(self, R, env, carry, rc):
        x, led = carry
        x_n = x - self.lr * global_grad(R, env.batch, x)
        return ((x_n, led.add(grad_up=env.batch.d * FLOAT_BITS)),
                (x, led, jnp.int32(EVENT_NONE)))


@dataclasses.dataclass(frozen=True)
class DianaSpec(MethodSpec):
    comp: Compressor
    alpha_h: float
    lr: float

    def init(self, R, env):
        h0 = jnp.zeros((R.n_local, env.batch.d), env.x0.dtype)
        return (env.x0, h0, CommLedger.create())

    def step(self, R, env, carry, rc):
        x, h, led = carry
        gi = client_batch.grads(env.batch, x)
        q, counts = self.comp.compress(R.client_keys(rc.key), gi - h)
        bits = comm.price(self.comp.wire, counts)
        red = R.reduce_tree({"ghat": h + q, "bits": bits})
        h_n = h + self.alpha_h * q
        x_n = x - self.lr * red["ghat"]
        return ((x_n, h_n, led.add(grad_up=red["bits"])),
                (x, led, jnp.int32(EVENT_NONE)))


@dataclasses.dataclass(frozen=True)
class NewtonSpec(MethodSpec):
    hess_bits: float
    grad_bits: float
    basis_bits: float

    def init(self, R, env):
        return (env.x0, CommLedger.create(basis_ship=self.basis_bits))

    def step(self, R, env, carry, rc):
        x, led = carry
        batch = env.batch
        if env.basisb is None:
            Hc = client_batch.hess(batch, x)
        else:
            coef = client_batch.hess_coeff_target(env.basisb, batch, x)
            Hc = env.basisb.server_reconstruct(coef, batch.lam)
        red = R.reduce_tree({"H": Hc, "g": client_batch.grads(batch, x)})
        x_n = R.once(lambda H, g: x - glm.spd_solve(H, g),
                     red["H"], red["g"])
        return ((x_n, led.add(hess_up=self.hess_bits,
                              grad_up=self.grad_bits)),
                (x, led, jnp.int32(EVENT_NONE)))


# ==========================================================================
# FedNL-BAG — FedNL Hessian learning + Bernoulli gradient aggregation
# (the new-method-as-a-spec demonstration; arXiv 2206.03588's BAG mechanism)
# ==========================================================================
@dataclasses.dataclass(frozen=True)
class FedNLBAGSpec(MethodSpec):
    """Newton-type method with compressed Hessian learning and a
    Bernoulli-lazy gradient uplink: each round every client independently
    reports its exact local gradient with probability q; the server keeps
    the latest gradient per client (lazy aggregation — stale entries of
    silent clients are reused, which is the BAG mechanism's point) and
    takes the projected-Newton step with ĝ = mean of the gradient table.
    Staleness vanishes as the iterates converge, so the local Newton-type
    rate survives q < 1."""

    hess_comp: Compressor
    alpha: float
    q: float
    eta: float
    mu: float
    init_exact: bool
    init_hess_bits: float
    basis_bits: float
    block: bool

    supports_faults = True        # lazy table reuses silent clients' rows
    supports_cohort = True        # the lazy table IS frozen absent state
    carry_names = ("z", "L", "H", "gtab", "led")

    def cohort_aggregates(self):
        # ĝ is the mean of the RAW gradient table; absent clients' stale
        # rows keep contributing (exactly the BAG mechanism).  dH/sbits are
        # delta-style (absent clients contribute 0) — undeclared on purpose.
        return {"ghat": ("gtab", "mean")}

    def cohort_init_extras(self, R, env, carry):
        # H⁰ = mean_i recon(L⁰_i) + ridge is a fleet reduction; hand the
        # engine the per-client reconstructions to sum across slabs
        _, L0, _, _, _ = carry
        return {"recL": env.extra.recon(L0)}

    def cohort_server_init(self, env, sums, n_total, carry):
        return {"H": sums["recL"] / n_total + env.extra.ridge}

    def prepare(self, R, batch, basisb, x0):
        return coeff_layout(R, batch, basisb, x0, self.block)

    def init(self, R, env):
        lay = env.extra
        x0 = env.x0
        L0 = lay.target_at(x0) if self.init_exact else jnp.zeros(lay.shape, x0.dtype)
        H0 = R.mean(lay.recon(L0)) + lay.ridge
        gtab0 = client_batch.grads(env.batch, x0)  # exact init gradients
        led0 = CommLedger.create(hess_up=self.init_hess_bits,
                                 grad_up=env.batch.d * FLOAT_BITS,
                                 basis_ship=self.basis_bits)
        return (x0, L0, H0, gtab0, led0)

    def step(self, R, env, carry, rc):
        key_t = rc.key
        z, L, H, gtab, led = carry
        batch = env.batch
        lay = env.extra

        k_h, k_b = jax.random.split(key_t, 2)
        # Bernoulli-lazy aggregation: reporters refresh their table row.
        # Unavailable clients (fault layer) just stay silent — BAG's lazy
        # table reuses their stale rows, so dropouts degrade staleness
        # rather than correctness (the event stream records the outage).
        send = jax.random.bernoulli(k_b, self.q, (R.n,))
        if rc.avail is None:
            ev = jnp.int32(EVENT_NONE)
        else:
            n_av = jnp.sum(rc.avail)
            ev = (jnp.int32(EVENT_DEGRADED) * (n_av < R.n)
                  + jnp.int32(EVENT_ALL_DOWN) * (n_av == 0)).astype(jnp.int32)
            send = send & rc.avail
        send = R.shard(send)
        led_z = led  # the gap is evaluated at z, outside the scan
        gtab_n = jnp.where(send[:, None], client_batch.grads(batch, z), gtab)

        # FedNL Hessian-coefficient learning (same shift recursion as BL1);
        # both legs' payloads and bit accounting share one fused collective
        S, L_n, counts = shift_update(
            lambda delta: self.hess_comp.compress(R.client_keys(k_h), delta),
            lay.target_at(z), L, self.alpha)
        red = R.reduce_tree(
            {"ghat": gtab_n, "dH": lay.recon(self.alpha * S),
             "gbits": jnp.where(send, batch.d * FLOAT_BITS, 0.0),
             "sbits": comm.price(self.hess_comp.wire, counts)},
            {"ghat": "mean", "dH": "mean", "gbits": "sum", "sbits": "mean"})
        led = led.add(grad_up=red["gbits"] / R.n_total, hess_up=red["sbits"])
        H_n = H + red["dH"]

        # damped Newton step: η < 1 tempers the staleness feedback loop an
        # aggressive q would otherwise excite (η = 1 recovers FedNL when
        # q = 1); projected + solved once per fleet (shard 0)
        def server_step(H_n, ghat):
            return proj_mu_route(
                H_n, self.mu,
                lambda P, *factors: z - self.eta * proj_mu_solve(P, *factors, ghat),
                R.ndev)

        z_n, eigh = R.once(server_step, H_n, red["ghat"])
        ys = (z, led_z, ev | jnp.int32(EVENT_EIGH) * eigh)
        return (z_n, L_n, H_n, gtab_n, led), ys


# ==========================================================================
# BL-DNN — the paper's communication layer on parameter PYTREES
# (the beyond-paper deep-network workload; see repro.fed.bldnn for the
# public entry point, model builders and the experiment wiring)
# ==========================================================================
@dataclasses.dataclass(frozen=True)
class BasisRefreshPolicy:
    """Amortized basis shipment for specs that bill a shipped basis.

    ``rounds_per_refresh = 0`` (default) is the legacy ship-once policy:
    one shipment billed at round 0, reused forever.  ``T ≥ 1`` amortizes:
    the round-0 shipment is still billed in full, and at every later
    boundary (``rounds.refresh_due``: ``t % T == 0``, pure in the ABSOLUTE
    round index so chunking and checkpoint resume can't move it) the
    shipment is re-billed ONLY when the drift trigger fires — the previous
    round's fleet-mean rotated-coefficient energy leakage
    (1 − ‖compressed‖²/‖target‖² on the gradient leg) has reached
    ``drift_threshold``.  A threshold of 0 re-ships at every boundary
    (``T = 1`` then bills every round); a threshold > 1 never re-ships.

    Accounting-only by construction: the basis is numerically FIXED for
    the run (every client derives it from the shared initialization, so a
    "re-shipment" carries the same factors), which is what makes
    trajectories invariant to the policy — only the ``basis_ship`` ledger
    leg and the drift carry leaf change (pinned bitwise on both reducers
    in tests/test_basis_ship.py)."""

    rounds_per_refresh: int = 0
    drift_threshold: float = 0.0

    @property
    def amortized(self) -> bool:
        return self.rounds_per_refresh > 0

    def __post_init__(self):
        if self.rounds_per_refresh < 0:
            raise ValueError("rounds_per_refresh must be >= 0 "
                             f"(0 = ship once), got {self.rounds_per_refresh}")
        if self.drift_threshold < 0.0:
            raise ValueError("drift_threshold must be >= 0, got "
                             f"{self.drift_threshold}")


@dataclasses.dataclass(frozen=True)
class BLDNNSpec(MethodSpec):
    """Basis Learn + compressed-shift learning applied per layer of a DNN.

    The same round skeleton as the GLM specs, with every array generalized
    to a parameter *pytree* (leaves carry the engine's leading client
    axis):

      1. per-client gradients in the per-layer SVD basis (`env.basisb`, a
         `basis.PerLayerSVDBasis`; None ⇒ standard basis) go through the
         Alg. 1 shift recursion via `rounds.tree_shift_update` — one
         compressor per leaf (Top-K budgets scale with leaf size), per-leaf
         `Counts` priced and summed onto the ledger's ``grad_up`` leg;
      2. the curvature stream: clients learn a per-parameter Fisher
         diagonal (g², standard basis) through the identical recursion —
         the FedNL Hessian-learning loop with diag(F) standing in for
         ∇²f_i — billed on ``hess_up``; the server preconditions the
         aggregated update with it;
      3. the server step x ← x − lr·ĝ/(√F̂+ε) on the replicated params.

    DNN tensors ship as f32, so every leg is priced through
    `comm.with_float_bits(comp.wire, 32)` (index/entry widths untouched)
    and the (U_ℓ, V_ℓ) shipment bills on ``basis_ship`` — by default once
    at 32 bits/float, or at a compressed price via ``basis_ship_bits``
    (the `comm.price` of the quantized factors the engine actually
    rotates with), re-billed on the `BasisRefreshPolicy` schedule when
    ``refresh`` amortizes the shipment.

    ``loss_fn(params, client_data) -> scalar`` is the per-client loss;
    ``eval_fn(params, data) -> {"gap": ..., ...}`` produces the post-scan
    evaluation streams (BL-DNN reports training error rate as the gap — so
    the registered experiment's bits-to-tolerance IS bits-to-accuracy —
    plus a ``"loss"`` stream).  Both are static spec fields: specs holding
    different functions compile separate engine programs.
    """

    loss_fn: Callable
    eval_fn: Callable
    grad_comps: Tuple[Compressor, ...]
    fisher_comps: Tuple[Compressor, ...]
    alpha: float = 1.0            # shift learning rate (contractive ⇒ 1)
    fisher_alpha: float = 0.1
    lr: float = 1e-3
    eps: float = 1e-2
    precondition: bool = True
    #: bits one basis shipment costs on the wire.  None derives the legacy
    #: dense-f32 price (``ship_floats() × 32``); compressed shipments pass
    #: the `comm.price` of the quantized factors (see
    #: `basis.PerLayerSVDBasis.shipped` — `repro.fed.bldnn.run_bldnn`
    #: wires both sides: the quantized basis into the engine AND its exact
    #: price in here).
    basis_ship_bits: Optional[float] = None
    #: amortized re-shipment schedule; default is the legacy ship-once.
    refresh: BasisRefreshPolicy = BasisRefreshPolicy()

    basis_replicated = True       # PerLayerSVDBasis is fleet-global

    #: exact=False collectives: f32 coefficient/Fisher payloads travel as
    #: pmean (local partials stay O(1) in f32); the f64 bit accounting
    #: scalars psum (bit counts are integers in f64, so order-exact).
    reduce_plan = ReducePlan(dense="pmean", vector="pmean", scalar="psum")

    WIRE_FLOAT_BITS = 32          # DNN tensors are f32 on the wire

    def _bill(self, comps, auxs):
        """Per-client bits: per-leaf counts priced at the f32 wire, summed
        across leaves (one ledger leg per stream, never per leaf)."""
        return sum(
            comm.price(comm.with_float_bits(c.wire, self.WIRE_FLOAT_BITS), a)
            for c, a in zip(comps, auxs))

    def _ship_bits(self, env) -> float:
        """Bits of ONE basis shipment (round 0 and every fired refresh)."""
        if env.basisb is None:
            return 0.0
        if self.basis_ship_bits is not None:
            return float(self.basis_ship_bits)
        return env.basisb.ship_floats() * self.WIRE_FLOAT_BITS

    def init(self, R, env):
        params = env.x0
        stacked = lambda p: jnp.zeros((R.n_local,) + p.shape, jnp.float32)
        shift = jax.tree.map(stacked, params)   # complete basis ⇒ coeff
        fshift = jax.tree.map(stacked, params)  # shapes == param shapes
        server_f = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                                params)
        led0 = CommLedger.create(basis_ship=self._ship_bits(env))
        carry = (params, shift, fshift, server_f, led0)
        if self.refresh.amortized:
            # last round's fleet-mean rotated-coefficient energy leakage —
            # the drift trigger's input, replicated (no client axis) so it
            # checkpoints with the server state
            carry = carry + (jnp.zeros((), jnp.float64),)
        return carry

    def step(self, R, env, carry, rc):
        key_t = rc.key
        amortized = self.refresh.amortized
        if amortized:
            params, shift, fshift, server_f, led, drift = carry
        else:
            params, shift, fshift, server_f, led = carry
        ys = (params, led, jnp.int32(EVENT_NONE))  # evaluated post-scan
        data = env.batch.data                     # leaves (n_local, ...)
        basis = env.basisb

        # per-client gradients, rotated into the per-layer basis
        g = jax.vmap(jax.grad(self.loss_fn), in_axes=(None, 0))(params, data)
        coeff = g if basis is None else basis.rotate(g)

        k_g, k_f = jax.random.split(key_t)
        n_leaves = len(jax.tree_util.tree_leaves(params))
        gks = jax.random.split(k_g, n_leaves)
        S, shift_n, gauxs = tree_shift_update(
            lambda i, delta: self.grad_comps[i].compress(
                R.client_keys(gks[i]), delta),
            coeff, shift, self.alpha)
        gbits = self._bill(self.grad_comps, gauxs)

        if self.precondition:
            # the second-order leg: Fisher diagonal through the same
            # recursion (diagonal curvature lives in the standard basis),
            # driven through the fused compress-then-reduce codec — the
            # compressor also emits the local client-axis partial sum, so
            # the bandwidth-optimal sharded path reduces one payload-sized
            # tensor per leaf instead of the dense client stack
            ftarget = jax.tree.map(lambda gi: gi.astype(jnp.float32) ** 2, g)
            fks = jax.random.split(k_f, n_leaves)
            Fc, fshift_n, fauxs, fsums = tree_shift_update_sum(
                lambda i, delta: self.fisher_comps[i].compress_sum(
                    R.client_keys(fks[i]), delta),
                ftarget, fshift, self.fisher_alpha)
            fbits = self._bill(self.fisher_comps, fauxs)
        else:
            fshift_n = fshift
            fbits = jnp.zeros((R.n_local,), jnp.float64)

        # ONE fused uplink reduction for the round: every coefficient leaf
        # plus both bit-accounting legs (per dtype: f32 coeffs, f64 bits).
        # The server mirrors every client's recursion, so the aggregated
        # gradient estimate is the fleet mean of the UPDATED shifts.
        agg = {"coeff": shift_n, "gbits": gbits, "fbits": fbits}
        if amortized:
            # per-client rotated-coefficient energy leakage of this round's
            # gradient leg (1 − ‖C(Δ)‖²/‖Δ‖², clipped at 0 for unbiased
            # codecs that can overshoot); its fleet mean rides the SAME
            # fused collective as the bit legs, so both reducers produce
            # the identical drift scalar
            sq = lambda x: jnp.sum(jnp.square(x.astype(jnp.float64)),
                                   axis=tuple(range(1, x.ndim)))
            kept = sum(sq(s) for s in jax.tree_util.tree_leaves(S))
            total = sum(sq(c - s0)
                        for c, s0 in zip(jax.tree_util.tree_leaves(coeff),
                                         jax.tree_util.tree_leaves(shift)))
            safe = jnp.where(total > 0.0, total, 1.0)
            agg["drift"] = jnp.maximum(
                jnp.where(total > 0.0, 1.0 - kept / safe, 0.0), 0.0)
        red = R.reduce_tree(agg)
        coeff_mean = red["coeff"]
        g_hat = coeff_mean if basis is None else basis.unrotate(coeff_mean)

        if self.precondition:
            fmeans = R.tree_mean_presummed(Fc, fsums)
            server_f_n = jax.tree.map(
                lambda sf, fm: sf + self.fisher_alpha * fm, server_f, fmeans)
            update = jax.tree.map(
                lambda gh, sf: gh / (jnp.sqrt(jnp.maximum(sf, 0.0)) + self.eps),
                g_hat, server_f_n)
        else:
            server_f_n, update = server_f, g_hat

        params_n = jax.tree.map(
            lambda p, u: (p.astype(jnp.float32) - self.lr * u).astype(p.dtype),
            params, update)
        if amortized:
            # re-ship at refresh boundaries (pure in the absolute round
            # index — see rounds.refresh_due) when LAST round's drift has
            # reached the trigger; round 0's shipment is billed by init
            fire = (refresh_due(rc.t, self.refresh.rounds_per_refresh)
                    & (rc.t > 0)
                    & (drift >= self.refresh.drift_threshold))
            led = led.add(grad_up=red["gbits"], hess_up=red["fbits"],
                          basis_ship=jnp.where(fire, self._ship_bits(env),
                                               0.0))
            return (params_n, shift_n, fshift_n, server_f_n, led,
                    red["drift"]), ys
        led = led.add(grad_up=red["gbits"], hess_up=red["fbits"])
        return (params_n, shift_n, fshift_n, server_f_n, led), ys

    def eval_streams(self, batch, xs_t, f_star):
        """Vmapped whole-trajectory evaluation of `eval_fn` (one shared
        program on every backend); ``f_star`` is unused — DNN training has
        no reference optimum, the gap stream is the training error rate."""
        return jax.jit(jax.vmap(lambda p: self.eval_fn(p, batch.data)))(xs_t)
