"""BL-DNN: the paper's communication layer applied to deep-network training.

This is the labelled BEYOND-PAPER extension (docs/ARCHITECTURE.md §Layer 3):
the paper's exact second-order method needs d×d Hessians, impossible for
d ≥ 10⁹.  What *does* transfer is the communication mechanism, applied per
layer:

  1. **Basis Learn** — every 2-D weight's update is communicated in a fixed
     per-layer orthogonal basis (U_ℓ, V_ℓ) from the SVD of the initialization
     (shipped once; the server knows it — §2.3's recipe with the weight
     matrix playing the data-matrix role).  Gradient energy concentrates in
     the leading coefficients, so Top-K in the rotated space keeps more
     signal per bit than Top-K in the standard basis (tests/test_fed.py).
  2. **Compressed-difference learning with shifts** (the L_i^k recursion of
     Alg. 1 applied to gradients): client i sends C(γ_i − L_i); both sides
     update L_i ← L_i + αC(·).  Contractive compressors use α = 1
     (Assumption 4.6), unbiased ones α = 1/(ω+1) (Assumption 4.5).
  3. **Curvature learning** (the second-order part): clients learn a
     per-parameter Fisher-diagonal estimate through the same compressed
     recursion; the server preconditions the aggregated update — the FedNL
     Hessian-learning loop with diag(F) standing in for ∇²f_i.

The method itself is `repro.core.specs.BLDNNSpec` running on the unified
round engine (`repro.core.rounds`): per-client state is a parameter pytree
with a leading client axis, the shift recursion is the shared
`rounds.tree_shift_update` combinator, compressors come from the
`repro.core.compressors` registry (one per leaf, so Top-K budgets scale
with layer size — stochastic codecs like RTop-K work too), the basis is
the registered ``per_layer_svd`` kind (`repro.core.basis`), and every leg
bills onto the shared `comm.CommLedger` at the f32 wire.  Both aggregation
backends run it: `VmapReducer` on a single device (no mesh needed) and
`ShardMapReducer` with clients sharded over `CLIENT_AXIS` — bitwise
identical histories (tests/test_fed.py).

This module is the workload wiring: an MLP classifier assembled from
`repro.models.layers`, a synthetic fine-tuning-style classification fleet,
per-leaf compressor construction, and the public `run_bldnn` entry point
the `fig-dnn` experiment (`repro.exp.registry`) dispatches to.  It also
builds the served job (`make_lm_finetune`, `serve_setup`): fine-tuning a
`repro.configs` language model, cut to one chip's share, with its trunk
frozen and one attention layer federated (`specs.BLDNNSpec.frozen`), the
``granite-bldnn`` experiment that `repro.launch.fed_serve` serves.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import batched, comm, rounds, specs
from repro.core.basis import PerLayerSVDBasis, is_pytree_basis, make_bases
from repro.core.bl import History
from repro.core.client_batch import TreeBatch, tree_batch
from repro.core.compressors import Compressor, Identity, TopK, rtopk
from repro.models import layers as L

Params = Dict[str, Any]

_BACKENDS = ("fast", "fast+sharded")


@dataclasses.dataclass(frozen=True)
class BLDNNConfig:
    """BL-DNN hyperparameters (one frozen config → one `BLDNNSpec`)."""

    top_k_frac: float = 0.05       # per-leaf Top-K budget: k = max(1, ⌊frac·numel⌋)
    compressor: str = "topk"       # "topk" | "rtopk" | "identity"
    alpha: float = 1.0             # shift learning rate (contractive ⇒ 1)
    lr: float = 1e-3
    precondition: bool = True
    fisher_alpha: float = 0.1
    eps: float = 1e-2
    use_basis: bool = True
    #: which registered pytree basis (``per_layer_svd`` | ``dct_tree`` |
    #: ``hadamard_tree`` — the structured kinds ship zero floats)
    basis_kind: str = "per_layer_svd"
    #: shipment wire for the basis factors (comm.BasisShipSpec): per-float
    #: width (32/16 bf16/8 int8+scales) and top-|·| column sparsification
    ship_float_bits: int = 32
    ship_col_frac: float = 1.0
    #: amortized re-shipment (specs.BasisRefreshPolicy): 0 ships once;
    #: T ≥ 1 re-bills the shipment at t % T == 0 boundaries when the
    #: drift trigger (energy-leakage ≥ threshold) fires
    rounds_per_refresh: int = 0
    drift_threshold: float = 0.0


# --------------------------------------------------------------------------
# model: an MLP classifier assembled from the production layer library
# --------------------------------------------------------------------------
def init_mlp_classifier(key, d_in: int, width: int, classes: int,
                        spectral_decay: float = 0.0,
                        dtype=jnp.float32) -> Params:
    """Input projection → `models.layers` MLP block → class head.

    ``spectral_decay > 0`` re-spectralizes every 2-D weight to singular
    values exp(−i/decay) (energy concentrated in the leading directions, as
    pretrained-network weights are) — the regime where the per-layer SVD
    basis has structure to exploit.  0 keeps the plain random init.
    """
    ks = jax.random.split(key, 3)
    params = {
        "in": L._init(ks[0], (d_in, width), d_in ** -0.5, dtype),
        "mlp": L.init_mlp(ks[1], width, 2 * width, False, dtype),
        "out": L._init(ks[2], (width, classes), width ** -0.5, dtype),
    }
    if spectral_decay > 0.0:
        def respectralize(p):
            if p.ndim != 2 or min(p.shape) < 2:
                return p
            u, s, vt = jnp.linalg.svd(p.astype(jnp.float32),
                                      full_matrices=False)
            snew = jnp.exp(-jnp.arange(s.shape[0]) / spectral_decay)
            snew = snew * (jnp.linalg.norm(s) / jnp.linalg.norm(snew))
            return ((u * snew) @ vt).astype(p.dtype)
        params = jax.tree.map(respectralize, params)
    return params


def mlp_classifier_logits(params: Params, x: jax.Array) -> jax.Array:
    """(B, d_in) features → (B, classes) logits."""
    h = jnp.tanh(x @ params["in"])
    # the production MLP block operates on (batch, seq, d) activations
    h = h + L.mlp(params["mlp"], h[:, None, :], False, None)[:, 0, :]
    return h @ params["out"]


def make_loss_fn(classes: int):
    """Per-client mean softmax cross-entropy: (params, {"x", "y"}) → scalar."""
    del classes  # shapes carry it; kept for signature stability

    def loss_fn(params, data):
        logits = mlp_classifier_logits(params, data["x"])
        logp = jax.nn.log_softmax(logits)
        return -jnp.mean(jnp.take_along_axis(logp, data["y"][:, None],
                                             axis=1))
    return loss_fn


def make_eval_fn():
    """Fleet evaluation for `BLDNNSpec.eval_streams`: training error rate
    (the ``gap`` stream — so bits-to-tolerance IS bits-to-accuracy) plus
    the mean training loss as an extra ``loss`` stream."""

    def eval_fn(params, data):
        logits = jax.vmap(
            lambda xb: mlp_classifier_logits(params, xb))(data["x"])
        logp = jax.nn.log_softmax(logits)
        nll = -jnp.take_along_axis(logp, data["y"][..., None], axis=-1)
        err = jnp.mean((jnp.argmax(logits, -1) != data["y"])
                       .astype(jnp.float64))
        return {"gap": err, "loss": jnp.mean(nll).astype(jnp.float64)}
    return eval_fn


# --------------------------------------------------------------------------
# data: synthetic non-iid classification fleet (fine-tuning regime)
# --------------------------------------------------------------------------
def make_synthetic_classification(seed: int, n_clients: int, m: int, d: int,
                                  classes: int, width: int, r: int = 8,
                                  heterogeneity: float = 0.5,
                                  label_noise: float = 0.05,
                                  ) -> Tuple[TreeBatch, Params]:
    """A teacher-labelled classification fleet plus a near-teacher student —
    the §2.3 low-rank regime carried to a DNN.

    Client inputs live EXACTLY in a shared r-dimensional subspace span(P)
    (x = z Pᵀ, the DNN analogue of §2.3's "client rows span G_i"), so every
    input-layer gradient xᵀδ has its row space inside span(P) while being
    entrywise *dense* in standard coordinates.  The teacher's input layer
    is subspace-aligned (W_in = P M — what training on such data produces)
    and its deeper layers carry decaying spectra; the student is the
    teacher plus 40% perturbation whose input-layer component stays in the
    span.  Fine-tuning the student is therefore the regime BL-DNN targets:
    the per-layer SVD basis of W_in concentrates the (dense-looking)
    gradient into ~r·width leading coefficients, exactly as the paper's
    data basis concentrates Hessian coefficients.  Clients are non-iid
    (latent mean shifts scaled by `heterogeneity`); labels get
    `label_noise` uniform flips.

    Returns ``(batch, params0)``: the client-stacked `TreeBatch`
    ``{"x": (n, m, d), "y": (n, m)}`` and the student parameter pytree.
    """
    rng = np.random.default_rng(seed)
    kt, ks = jax.random.split(jax.random.PRNGKey(seed))
    P, _ = np.linalg.qr(rng.standard_normal((d, r)))      # shared subspace
    shifts = np.linspace(-1.0, 1.0, n_clients) * heterogeneity
    z = rng.standard_normal((n_clients, m, r)) + shifts[:, None, None]
    x = jnp.asarray(z @ P.T, jnp.float32)                 # rank-r rows

    teacher = init_mlp_classifier(kt, d, width, classes, spectral_decay=8.0)
    M = rng.standard_normal((r, width)) / np.sqrt(r)
    teacher["in"] = jnp.asarray(P @ M, jnp.float32)       # subspace-aligned
    logits = jax.vmap(lambda xb: mlp_classifier_logits(teacher, xb))(x)
    y = np.asarray(jnp.argmax(logits, -1))
    flip = rng.random((n_clients, m)) < label_noise
    y = np.where(flip, rng.integers(0, classes, (n_clients, m)), y)
    batch = tree_batch({"x": x, "y": jnp.asarray(y, jnp.int32)})

    # student: 60% teacher + 40% perturbation — near the task but not at
    # it (fine-tuning has work to do).  The input-layer perturbation stays
    # in span(P) (a model pretrained on this data distribution never grew
    # out-of-span input weights), so its SVD basis leads with span(P).
    fresh = init_mlp_classifier(ks, d, width, classes)
    fresh["in"] = jnp.asarray(P @ (P.T @ np.asarray(fresh["in"], np.float64)),
                              jnp.float32)
    student = jax.tree.map(lambda t, f: 0.6 * t + 0.4 * f, teacher, fresh)
    return batch, student


# --------------------------------------------------------------------------
# per-leaf compressors + the public entry point
# --------------------------------------------------------------------------
def leaf_compressors(kind: str, frac: float,
                     params: Params) -> Tuple[Compressor, ...]:
    """One registry compressor per parameter leaf, Top-K budgets scaled to
    the leaf: k_ℓ = max(1, ⌊frac·numel_ℓ⌋)."""
    comps = []
    for p in jax.tree_util.tree_leaves(params):
        k = max(1, int(frac * p.size))
        if kind == "identity":
            comps.append(Identity())
        elif kind == "topk":
            comps.append(TopK(k=k))
        elif kind == "rtopk":
            comps.append(rtopk(k))
        else:
            raise ValueError(
                f"unknown BL-DNN compressor kind {kind!r} "
                "(expected identity | topk | rtopk)")
    return tuple(comps)


def build_spec(loss_fn, eval_fn, params: Params, cfg: BLDNNConfig, *,
               basis_ship_bits: Optional[float] = None) -> specs.BLDNNSpec:
    """`BLDNNSpec` for a parameter tree under one `BLDNNConfig`.

    ``basis_ship_bits`` is the exact priced cost of one (possibly
    quantized) basis shipment; None keeps the legacy dense-f32 derivation
    from ``ship_floats()``."""
    comps = leaf_compressors(cfg.compressor, cfg.top_k_frac, params)
    return specs.BLDNNSpec(
        loss_fn=loss_fn, eval_fn=eval_fn,
        grad_comps=comps, fisher_comps=comps,
        alpha=cfg.alpha, fisher_alpha=cfg.fisher_alpha,
        lr=cfg.lr, eps=cfg.eps, precondition=cfg.precondition,
        basis_ship_bits=basis_ship_bits,
        refresh=specs.BasisRefreshPolicy(
            rounds_per_refresh=cfg.rounds_per_refresh,
            drift_threshold=cfg.drift_threshold))


def run_bldnn(loss_fn, eval_fn, params0: Params, batch: TreeBatch,
              steps: int, cfg: BLDNNConfig = BLDNNConfig(), *,
              seed: int = 0, backend: str = "fast", exact: bool = True,
              basis: Optional[PerLayerSVDBasis] = None,
              stream=None) -> History:
    """Train `steps` BL-DNN rounds on the unified round engine.

    Args:
      loss_fn: per-client loss ``(params, client_data) -> scalar``.
      eval_fn: fleet metrics ``(params, stacked_data) -> {"gap", ...}``
        (see `make_eval_fn`).
      params0: replicated initial parameter pytree.
      batch: client-stacked `TreeBatch` (leaves ``(n, ...)``).
      steps: communication rounds.
      cfg: hyperparameters; ``cfg.use_basis=False`` runs the standard
        basis (no rotations, zero shipment).
      seed: PRNG seed (stochastic compressors, per-round keys).
      backend: ``"fast"`` (single-device `VmapReducer`) or
        ``"fast+sharded"`` (clients over the mesh `CLIENT_AXIS`) — bitwise
        identical histories when ``exact``.
      exact: sharded aggregation parity (see `rounds.ShardMapReducer`).
        True gathers in fixed order (bitwise = single-device); False takes
        `BLDNNSpec.reduce_plan`'s ring collectives (pmean per dense/vector
        leg, psum for bit counters) — fewer bytes on the wire, reductions
        associate in ring order.  Ignored on the "fast" backend.
      basis: override the `per_layer_svd` basis (defaults to building it
        from ``params0`` via the basis registry).
      stream: optional `rounds.StreamHook` — chunk-boundary progress
        emission on either backend.

    Returns:
      `History` — ``gaps`` is the training error rate, ``metrics["loss"]``
      the loss stream, ``legs`` the per-leg `CommLedger` bit streams
      (gradient coefficients on ``grad_up``, the Fisher stream on
      ``hess_up``, the one-time (U_ℓ, V_ℓ) shipment on ``basis_ship``).
    """
    if backend not in _BACKENDS:
        raise ValueError(f"backend must be one of {_BACKENDS}, got {backend!r}")
    if cfg.use_basis and basis is None:
        if not is_pytree_basis(cfg.basis_kind):
            raise ValueError(
                f"BL-DNN needs a pytree basis, {cfg.basis_kind!r} is a "
                "d×d matrix basis (see basis.available_bases())")
        basis = make_bases(cfg.basis_kind, params0)
    if not cfg.use_basis:
        basis = None
    ship_bits = None
    if basis is not None:
        # the engine rotates with the basis AS SHIPPED: quantize the
        # factors per the shipment wire and bill their exact priced cost
        # (the default f32-dense spec is the identity at the legacy price;
        # structured zero-ship bases pass through at 0 bits)
        ship = comm.BasisShipSpec(float_bits=cfg.ship_float_bits,
                                  col_frac=cfg.ship_col_frac)
        basis, ship_bits = basis.shipped(ship)
    spec = build_spec(loss_fn, eval_fn, params0, cfg,
                      basis_ship_bits=ship_bits)
    keys = jax.random.split(jax.random.PRNGKey(seed), steps)
    evals, leds = rounds.run_rounds(
        spec, batch, basis, params0, 0.0, keys,
        sharded=(backend == "fast+sharded"), exact=exact, stream=stream)
    return batched._history(evals, leds)



# --------------------------------------------------------------------------
# fine-tuning a `repro.models` LM with a frozen trunk (the served BL-DNN job)
# --------------------------------------------------------------------------
#: leaves that a federated attention layer trains (flatten order: sorted)
ATTN_LEAVES = ("wk", "wo", "wq", "wv")
#: matrix views (n_stack, n_rows) of the stacked attention leaves:
#: ``(G, d, heads, hd)`` as G × ``(d, heads·hd)``, ``wo`` ``(G, heads, hd, d)``
#: as G × ``(heads·hd, d)``
ATTN_VIEWS = {"wk": (1, 1), "wo": (1, 2), "wq": (1, 1), "wv": (1, 1)}


def lm_model_config(model: str, n_layers: int, n_held: int, first_held: int,
                    vocab: int, overrides=()):
    """The cut `ModelConfig`: ``n_layers`` of the published pattern, the
    layer's expert share ``[first_held, first_held + n_held)`` and a
    vocabulary slice of ``vocab`` ids; ``overrides`` ((key, value) pairs,
    ``moe.``/``ssm.`` prefixes for the nested groups) shrink widths for the
    CPU tests only."""
    from repro.configs import get_config

    cfg = get_config(model)
    top, moe, ssm = {}, {}, {}
    for key, value in overrides:
        group, _, name = key.rpartition(".")
        {"": top, "moe": moe, "ssm": ssm}[group][name] = value
    moe = dataclasses.replace(cfg.moe, n_held=n_held, first_held=first_held,
                              **moe)
    return dataclasses.replace(
        cfg, n_layers=n_layers, vocab_size=vocab, moe=moe,
        ssm=dataclasses.replace(cfg.ssm, **ssm), **top)


def _leaf_std(name: str, shape) -> float:
    """Init scale of a weight matrix: fan_in^-1/2 over its input axes."""
    if name.endswith("attn/wo"):
        return float(shape[-3] * shape[-2]) ** -0.5
    if name.endswith(("attn/wq", "attn/wk", "attn/wv")):
        return float(shape[-3]) ** -0.5
    return float(shape[-2]) ** -0.5


def seeded_leaf(key, name: str, shape, dtype):
    """One parameter leaf by name, drawn from its own key: norm scales and
    D ones, A_log = log(1..H), dt_bias zeros, the embedding N(0, 0.02²), the
    conv N(0, 0.5²) and its bias N(0, 0.1²), every other weight
    N(0, 1/fan_in); drawn in float32, then cast."""
    last = name.rsplit("/", 1)[-1]
    if last in ("scale", "D"):
        return jnp.ones(shape, dtype)
    if last == "dt_bias":
        return jnp.zeros(shape, dtype)
    if last == "A_log":
        return jnp.broadcast_to(
            jnp.log(jnp.arange(1, shape[-1] + 1, dtype=jnp.float32)),
            shape).astype(dtype)
    std = {"embed": 0.02, "conv_w": 0.5, "conv_b": 0.1}.get(last)
    if std is None:
        std = _leaf_std(name, shape)
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def seeded_params(seed: int, cfg, dtype=jnp.bfloat16, keep=()) -> Params:
    """`repro.models` parameters of ``cfg`` drawn leaf by leaf, each from
    ``fold_in(PRNGKey(s), crc32(path))`` with s the seed reduced to 31 bits
    by NumPy's generator — a rule a plain reference can follow with no
    part of this program.  Leaves whose path is in ``keep`` stay float32."""
    import zlib

    from repro.models import model as M

    base = jax.random.PRNGKey(int(np.random.default_rng(seed).integers(2 ** 31)))
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        M.param_shapes(cfg, dtype))
    leaves = []
    for path, sds in flat:
        name = "/".join(str(k.key) for k in path)
        key = jax.random.fold_in(base, zlib.crc32(name.encode()))
        leaf_dtype = jnp.float32 if name in keep else sds.dtype
        leaves.append(seeded_leaf(key, name, sds.shape, leaf_dtype))
    return treedef.unflatten(leaves)


def zipf_tokens(seed: int, n_clients: int, length: int, vocab: int,
                exponent: float) -> np.ndarray:
    """(n_clients, length) token ids: ranks drawn Zipf(``exponent``) over
    the vocabulary, mapped through a permutation of its own per client
    (non-IID clients)."""
    rng = np.random.default_rng([seed, 1])
    p = np.arange(1, vocab + 1, dtype=np.float64) ** -exponent
    p /= p.sum()
    out = np.empty((n_clients, length), np.int32)
    for i in range(n_clients):
        perm = rng.permutation(vocab)
        out[i] = perm[rng.choice(vocab, size=length, p=p)]
    return out


def _attn_path(cfg) -> str:
    """The federated attention layer: the pattern's first attention mixer."""
    i = next(i for i, s in enumerate(cfg.group) if s.mixer == "attn")
    return f"layers/l{i}/attn"


def split_trunk(full: Params, cfg) -> Tuple[Params, Params]:
    """(federated attention leaves, trunk without them)."""
    _, li, _ = _attn_path(cfg).split("/")
    layer = dict(full["layers"][li])
    fed = layer.pop("attn")
    trunk = {**full, "layers": {**full["layers"], li: layer}}
    return fed, trunk


def merge_trunk(fed: Params, trunk: Params, cfg) -> Params:
    """The whole model: the federated leaves, cast to the trunk's dtype,
    back in their layer."""
    _, li, _ = _attn_path(cfg).split("/")
    dtype = trunk["embed"].dtype
    attn = jax.tree.map(lambda w: w.astype(dtype), fed)
    return {**trunk, "layers": {**trunk["layers"],
                                li: {**trunk["layers"][li], "attn": attn}}}


def make_lm_loss_fn(cfg):
    """Per-client next-token loss over the vocabulary slice:
    ``(fed, trunk, {"ids": (S+1,)}) -> (mean CE, (assigned, dropped))``.
    Activations in the trunk's dtype (bf16), norms, softmaxes, the SSM
    scan and the loss in float32; each layer is rematerialized on its own
    in the backward pass."""
    from repro.models import model as M

    def loss_fn(fed, trunk, data):
        ids = data["ids"]
        logits, _, _, counts = M.forward(
            merge_trunk(fed, trunk, cfg), cfg, None, ids[None, :-1],
            remat=False, remat_layers=True, return_counts=True)
        logp = jax.nn.log_softmax(logits[0].astype(jnp.float32), axis=-1)
        nll = -jnp.take_along_axis(logp, ids[1:, None], axis=-1)
        return jnp.mean(nll), counts
    return loss_fn


def make_lm_finetune(seed: int, model: str, n_layers: int, n_held: int,
                     first_held: int, vocab: int, n_clients: int, seq: int,
                     zipf: float, overrides=()):
    """(cfg, batch, params0, trunk): the cut model drawn from the seed
    (`seeded_params`; the federated attention leaves in float32, the trunk
    in bfloat16) and each client's ``seq + 1`` token ids (`zipf_tokens`)."""
    cfg = lm_model_config(model, n_layers, n_held, first_held, vocab,
                          overrides)
    attn = _attn_path(cfg)
    full = seeded_params(seed, cfg,
                         keep=tuple(f"{attn}/{w}" for w in ATTN_LEAVES))
    params0, trunk = split_trunk(full, cfg)
    ids = zipf_tokens(seed, n_clients, seq + 1, vocab, zipf)
    return cfg, tree_batch({"ids": jnp.asarray(ids)}), params0, trunk


def serve_setup(cell, prob):
    """(spec, batch, basis) of a frozen-trunk BL-DNN cell for the serve
    loop: the per-layer SVD basis of the federated leaves on their matrix
    views, shipped per the cell's wire, and its exact price."""
    if prob.trunk is None:
        raise SystemExit(
            f"fed_serve serves BL-DNN fine-tuning with a frozen trunk (an "
            f"lm_finetune problem); cell {cell.name!r} has none")
    params = cell.params_dict()
    params.pop("seed", None)        # the serve PRNG root comes from --seed
    cfg = BLDNNConfig(compressor=cell.hess_comp.kind,
                      use_basis=cell.basis is not None,
                      basis_kind=cell.basis or "per_layer_svd", **params)
    basis = None
    if cfg.use_basis:
        basis = make_bases(cfg.basis_kind, prob.params0, views=prob.views)
        basis, ship_bits = basis.shipped(comm.BasisShipSpec(
            float_bits=cfg.ship_float_bits, col_frac=cfg.ship_col_frac))
    spec = dataclasses.replace(
        build_spec(prob.loss_fn, None, prob.params0, cfg,
                   basis_ship_bits=ship_bits if basis is not None else None),
        frozen=True)
    return spec, prob.batch, basis
