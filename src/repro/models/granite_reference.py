"""Plain reference of BL-DNN fine-tuning of a Granite-4.0-H-Small cut with
its trunk frozen, independent of the program under test.

It builds the model and the clients' token ids from the run's seed by its
own copy of the rules the configuration states (each weight drawn from
``fold_in(PRNGKey(s), crc32(name))``, s the seed reduced to 31 bits by
NumPy's generator; norm scales and D ones, A_log = log(1..H), dt_bias 0, the
embedding N(0, 0.02²), the conv N(0, 0.5²) and its bias N(0, 0.1²), every
other weight N(0, 1/fan_in), drawn in float32 and cast to bfloat16, the
federated attention weights kept in float32; ids Zipf ranks over the
vocabulary slice through a permutation per client), and runs, in float32
under ``jax.default_matmul_precision("highest")``:

* the forward pass of HF ``granitemoehybrid`` as published: embeddings
  × 12; per layer ``h += 0.22·mixer(rmsnorm(h))`` and
  ``h += 0.22·(moe(rmsnorm(h)) + shared(rmsnorm(h)))``; logits
  ``rmsnorm(h)·embedᵀ / 16``.  Attention: GQA with no positional encoding,
  scores × attention_multiplier, causal.  Mamba-2: the SSM as a sequential
  recurrence over tokens (``s ← exp(Δ·A)s + Δ·x⊗B``, ``y = C·s + D·x``),
  not the chunked SSD; gated RMSNorm; conv with bias.  MoE: softmax over
  each token's top-k router logits; the layer's held experts computed on
  every token and weighted by the token's gate for them (zero where the
  token did not pick them); the experts not held here contribute nothing,
  in the program and here alike;
* the next-token loss over the vocabulary slice and its gradient with
  respect to the federated attention weights by `jax.grad`;
* BL-DNN's round: the complete SVD basis of each attention weight's matrix
  view (``(d, heads·hd)``; ``wo`` ``(heads·hd, d)``; NumPy's, on the
  host), the rotated
  gradients, per-leaf Top-K shift learning of the coefficients and of the
  Fisher diagonal g², the server's preconditioned step, and the ledger's
  legs (Top-K entries at 32 + 32 bits, the basis shipped once at 32 bits a
  float).

Clients are computed one at a time.  The layers before the federated one
do not depend on what is trained, so each client's hidden state at the
federated layer's input is computed once a run and every round's loss and
gradient start from it.  ``dtype`` keeps BL-DNN's state (parameters,
shifts, Fisher estimates) in another precision (the control); ``fault``
plants one of the faults the correctness check has to catch.
"""
from __future__ import annotations

import functools
import zlib

import jax
import jax.numpy as jnp
import numpy as np

FLOAT_BITS, INDEX_BITS = 32, 32
#: published widths (config.json of ibm-granite/granite-4.0-h-small)
GRANITE = {"d_model": 4096, "n_heads": 32, "n_kv_heads": 8, "head_dim": 128,
           "attention_multiplier": 0.0078125, "moe.n_experts": 72, "moe.top_k": 10,
           "moe.d_expert": 768, "moe.d_shared": 1536, "ssm.d_state": 128,
           "ssm.head_dim": 64, "ssm.chunk": 256, "expand": 2, "conv_width": 4,
           "embedding_multiplier": 12.0, "residual_multiplier": 0.22,
           "logits_scaling": 16.0, "eps": 1e-5}
#: one period of ``layer_types``: attention at offset 5
PATTERN = "MMMMMAMMMM"
ATTN = 5
FED = ("wk", "wo", "wq", "wv")
#: (n_stack, n_rows) matrix views of the federated leaves
VIEWS = {"wk": (1, 1), "wo": (1, 2), "wq": (1, 1), "wv": (1, 1)}
#: the sequential scan's segment: the backward pass keeps the state at
#: segment boundaries and recomputes inside a segment
SEGMENT = 256


def dims(problem: dict) -> dict:
    c = dict(GRANITE)
    c.update(dict(problem.get("overrides", ())))
    c.update(n_groups=problem["n_layers"] // len(PATTERN), n_held=problem["n_held"],
             first_held=problem["first_held"], vocab=problem["vocab"])
    c["d_inner"] = c["expand"] * c["d_model"]
    c["ssm_heads"] = c["d_inner"] // c["ssm.head_dim"]
    return c


def shapes(c: dict) -> dict:
    """{name: (shape, dtype)} of the whole model."""
    G, D, V = c["n_groups"], c["d_model"], c["vocab"]
    di, N, H = c["d_inner"], c["ssm.d_state"], c["ssm_heads"]
    nh, nkv, hd = c["n_heads"], c["n_kv_heads"], c["head_dim"]
    E, held, fe, fs = c["moe.n_experts"], c["n_held"], c["moe.d_expert"], c["moe.d_shared"]
    conv = di + 2 * N
    bf, f32 = jnp.bfloat16, jnp.float32
    out = {"embed": ((V, D), bf), "final_norm/scale": ((D,), bf)}
    for i, kind in enumerate(PATTERN):
        p = f"layers/l{i}/"
        out[p + "ln1/scale"] = ((G, D), bf)
        out[p + "ln2/scale"] = ((G, D), bf)
        if kind == "A":
            out.update({p + "attn/wq": ((G, D, nh, hd), bf), p + "attn/wk": ((G, D, nkv, hd), bf),
                        p + "attn/wv": ((G, D, nkv, hd), bf), p + "attn/wo": ((G, nh, hd, D), bf)})
        else:
            out.update({p + "mamba/in_proj": ((G, D, 2 * di + 2 * N + H), bf),
                        p + "mamba/conv_w": ((G, c["conv_width"], conv), bf),
                        p + "mamba/conv_b": ((G, conv), bf),
                        p + "mamba/A_log": ((G, H), f32), p + "mamba/D": ((G, H), f32),
                        p + "mamba/dt_bias": ((G, H), f32),
                        p + "mamba/norm/scale": ((G, di), bf),
                        p + "mamba/out_proj": ((G, di, D), bf)})
        out.update({p + "moe/router": ((G, D, E), f32),
                    p + "moe/wi": ((G, held, D, fe), bf), p + "moe/wg": ((G, held, D, fe), bf),
                    p + "moe/wo": ((G, held, fe, D), bf),
                    p + "moe/shared/wi": ((G, D, fs), bf), p + "moe/shared/wg": ((G, D, fs), bf),
                    p + "moe/shared/wo": ((G, fs, D), bf)})
    return out


def draw(seed: int, name: str, shape, dtype):
    base = jax.random.PRNGKey(int(np.random.default_rng(seed).integers(2 ** 31)))
    key = jax.random.fold_in(base, zlib.crc32(name.encode()))
    last = name.rsplit("/", 1)[-1]
    if last in ("scale", "D"):
        return jnp.ones(shape, dtype)
    if last == "dt_bias":
        return jnp.zeros(shape, dtype)
    if last == "A_log":
        return jnp.broadcast_to(jnp.log(jnp.arange(1, shape[-1] + 1, dtype=jnp.float32)),
                                shape).astype(dtype)
    std = {"embed": 0.02, "conv_w": 0.5, "conv_b": 0.1}.get(last)
    if std is None:
        if name.endswith("attn/wo"):
            std = float(shape[-3] * shape[-2]) ** -0.5
        elif name.endswith(("attn/wq", "attn/wk", "attn/wv")):
            std = float(shape[-3]) ** -0.5
        else:
            std = float(shape[-2]) ** -0.5
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def weights(seed: int, c: dict):
    """(trunk {name: array}, federated {leaf: float32 array})."""
    trunk, fed = {}, {}
    for name, (shape, dtype) in shapes(c).items():
        leaf = name.rsplit("/", 1)[-1]
        if name.startswith(f"layers/l{ATTN}/attn/"):
            fed[leaf] = draw(seed, name, shape, jnp.float32)
        else:
            trunk[name] = draw(seed, name, shape, dtype)
    return trunk, fed


def tokens(seed: int, n_clients: int, length: int, vocab: int, exponent: float):
    rng = np.random.default_rng([seed, 1])
    p = np.arange(1, vocab + 1, dtype=np.float64) ** -exponent
    p /= p.sum()
    out = np.empty((n_clients, length), np.int32)
    for i in range(n_clients):
        perm = rng.permutation(vocab)
        out[i] = perm[rng.choice(vocab, size=length, p=p)]
    return out


# ----------------------------------------------------------------- forward
def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _swiglu(x, wi, wg, wo):
    return (jax.nn.silu(x @ wg) * (x @ wi)) @ wo


def _attention(x, w, c):
    S = x.shape[0]
    nh, nkv, hd = c["n_heads"], c["n_kv_heads"], c["head_dim"]
    q = jnp.einsum("sd,dhk->hsk", x, w["wq"])
    k = jnp.einsum("sd,dhk->hsk", x, w["wk"])
    v = jnp.einsum("sd,dhk->hsk", x, w["wv"])
    causal = jnp.tril(jnp.ones((S, S), bool))

    def head(j):                       # query head j reads KV head j // (nh/nkv)
        kv = j // (nh // nkv)
        s = (q[j] @ k[kv].T) * c["attention_multiplier"]
        return jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1) @ v[kv]

    o = jax.lax.map(head, jnp.arange(nh))                     # (nh, S, hd)
    return jnp.einsum("hsk,hkd->sd", o, w["wo"])


def _ssm(xs, dt, A, B, C):
    """Sequential recurrence, token by token: (S, H, hd) outputs."""
    H, hd, N = xs.shape[1], xs.shape[2], B.shape[1]

    def tok(s, inp):
        x_t, dt_t, b_t, c_t = inp
        s = jnp.exp(dt_t * A)[:, None, None] * s + (dt_t[:, None] * x_t)[:, :, None] * b_t
        return s, jnp.sum(s * c_t, axis=-1)

    @jax.checkpoint
    def segment(s, inp):
        return jax.lax.scan(tok, s, inp)

    S = xs.shape[0]
    seg = min(SEGMENT, S)
    split = lambda a: a.reshape((S // seg, seg) + a.shape[1:])
    _, y = jax.lax.scan(segment, jnp.zeros((H, hd, N), jnp.float32),
                        (split(xs), split(dt), split(B), split(C)))
    return y.reshape(S, H, hd)


def _mamba(x, w, c):
    S = x.shape[0]
    di, N, H, W = c["d_inner"], c["ssm.d_state"], c["ssm_heads"], c["conv_width"]
    zxbcdt = x @ w["in_proj"]
    z, xbc, dt = zxbcdt[:, :di], zxbcdt[:, di:2 * di + 2 * N], zxbcdt[:, 2 * di + 2 * N:]
    padded = jnp.concatenate([jnp.zeros((W - 1, xbc.shape[1]), xbc.dtype), xbc])
    conv = sum(padded[i:i + S] * w["conv_w"][i] for i in range(W)) + w["conv_b"]
    conv = jax.nn.silu(conv)
    xs, B, C = conv[:, :di], conv[:, di:di + N], conv[:, di + N:]
    dt = jax.nn.softplus(dt + w["dt_bias"])
    xs = xs.reshape(S, H, di // H)
    y = _ssm(xs, dt, -jnp.exp(w["A_log"]), B, C) + xs * w["D"][:, None]
    y = _rms(y.reshape(S, di) * jax.nn.silu(z), w["norm/scale"], c["eps"])
    return y @ w["out_proj"]


def _moe(x, w, c):
    """The held experts' part of the layer: every held expert on every
    token, times the token's gate for it."""
    K, first, held = c["moe.top_k"], c["first_held"], c["n_held"]
    top, ids = jax.lax.top_k(x @ w["router"], K)
    gates = jax.nn.softmax(top, axis=-1)
    mine = ids[:, :, None] == (first + jnp.arange(held))[None, None, :]
    gate = jnp.sum(jnp.where(mine, gates[:, :, None], 0.0), axis=1)     # (S, held)

    def expert(total, e):
        return total + gate[:, e, None] * _swiglu(x, w["wi"][e], w["wg"][e], w["wo"][e]), None

    return jax.lax.scan(expert, jnp.zeros_like(x), jnp.arange(held))[0]


@functools.partial(jax.checkpoint, static_argnums=(2, 3))
def _layer(h, w, kind, c):
    """One layer (``c`` the widths as sorted items); its activations are
    recomputed in the backward pass, so the gradient holds one layer's at
    a time."""
    c = dict(c)
    f = {k: v.astype(jnp.float32) for k, v in w.items()}
    x = _rms(h, f["ln1/scale"], c["eps"])
    if kind == "A":
        out = _attention(x, {k: f["attn/" + k] for k in FED}, c)
    else:
        out = _mamba(x, {k: f["mamba/" + k] for k in (
            "in_proj", "conv_w", "conv_b", "A_log", "D", "dt_bias", "norm/scale",
            "out_proj")}, c)
    h = h + c["residual_multiplier"] * out
    x2 = _rms(h, f["ln2/scale"], c["eps"])
    moe = _moe(x2, {k: f["moe/" + k] for k in ("router", "wi", "wg", "wo")}, c)
    shared = _swiglu(x2, f["moe/shared/wi"], f["moe/shared/wg"], f["moe/shared/wo"])
    return h + c["residual_multiplier"] * (moe + shared)


def _layers(c):
    """(group, index, kind) of every layer in order; the federated layer
    of the first group is number ``ATTN``."""
    return [(g, i, kind) for g in range(c["n_groups"]) for i, kind in enumerate(PATTERN)]


def _run_layers(fed, trunk, h, c, layers):
    items = tuple(sorted(c.items()))
    for g, i, kind in layers:
        p = f"layers/l{i}/"
        layer = {name[len(p):]: w[g] for name, w in trunk.items() if name.startswith(p)}
        if kind == "A":
            layer.update({"attn/" + k: v[g] for k, v in fed.items()})
        h = _layer(h, layer, kind, items)
    return h


def prefix(trunk, ids, c):
    """The hidden state at the federated layer's input: the embeddings and
    the layers before it, which the training leaves unchanged."""
    h = trunk["embed"].astype(jnp.float32)[ids[:-1]] * c["embedding_multiplier"]
    return _run_layers({}, trunk, h, c, _layers(c)[:ATTN])


def suffix_loss(fed, trunk, h, ids, c, logits_scaling):
    """Mean next-token cross-entropy from the federated layer's input on
    (``logits_scaling`` an argument, so that a planted fault in it runs the
    same program)."""
    h = _run_layers(fed, trunk, h, c, _layers(c)[ATTN:])
    embed = trunk["embed"].astype(jnp.float32)
    logits = _rms(h, trunk["final_norm/scale"].astype(jnp.float32), c["eps"]) @ embed.T
    logp = jax.nn.log_softmax(logits / logits_scaling, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, ids[1:, None], axis=-1))


def client_loss(fed, trunk, ids, c):
    """Mean next-token cross-entropy of one client's ids."""
    return suffix_loss(fed, trunk, prefix(trunk, ids, c), ids, c, c["logits_scaling"])


@functools.lru_cache(maxsize=None)
def _programs(c_items: tuple):
    """The jitted prefix, and the suffix's loss and gradient, at one set of
    widths (one compile per process, whatever the seed, dtype or fault)."""
    c = dict(c_items)
    loss = lambda w, t, h, i, scale: suffix_loss(w, t, h, i, c, scale)
    return (jax.jit(lambda t, i: prefix(t, i, c)), jax.jit(jax.value_and_grad(loss)),
            jax.jit(loss))


# ------------------------------------------------------------------ BL-DNN
def _view(shape, n_stack, n_rows):
    return (int(np.prod(shape[:n_stack])), int(np.prod(shape[n_stack:n_stack + n_rows])),
            int(np.prod(shape[n_stack + n_rows:])))


def _topk(v, k):
    """v with all but its k largest-|·| entries zeroed (v flat)."""
    _, idx = jax.lax.top_k(jnp.abs(v), k)
    return jnp.zeros_like(v).at[idx].set(v[idx])


def run(seed: int, problem: dict, cell: dict, rounds: int, *, dtype=np.float32,
        fault: str | None = None) -> dict:
    """``rounds`` BL-DNN rounds from the seed's model.

    Returns ``iterates`` ({0, 1, rounds}: the federated weights, flattened
    in sorted leaf order, float64; under the key 1 those after round
    ``rounds`` too: the served job keeps no iterate before its checkpoint
    of round ``rounds``, so its first update is read there), ``gaps`` (the
    fleet-mean loss at rounds 0..rounds), ``legs(t)`` (each ledger leg's
    cumulative bits before round t, per client) and ``f_star`` 0 (no known
    optimum: the gaps are losses).  ``dtype``: the precision BL-DNN's state
    is kept in.  ``fault``: None; ``"frozen"`` (the step leaves the weights
    unchanged); ``"frozen_late"`` (the same from round 2 on: only the first
    update is applied); ``"altered"`` (the server applies 1.001 × its step);
    ``"half"`` (the server averages the first half of the clients only);
    ``"logits"`` (the logits are not divided by ``logits_scaling``)."""
    with jax.default_matmul_precision("highest"):
        return _run(seed, problem, cell, rounds, jnp.dtype(dtype), fault)


def _run(seed, problem, cell, rounds, state, fault):
    c = dims(problem)
    p = dict(cell.get("params", {}))
    frac = p.get("top_k_frac", 0.05)
    alpha, f_alpha = p.get("alpha", 1.0), p.get("fisher_alpha", 0.1)
    lr, eps = p.get("lr", 1e-3), p.get("eps", 1e-2)
    keep = lambda a: a.astype(state).astype(jnp.float32)
    n = problem["n_clients"]
    trunk, fed = weights(seed, c)
    fed = {k: keep(v) for k, v in fed.items()}
    ids = jnp.asarray(tokens(seed, n, problem["seq"] + 1, c["vocab"], problem["zipf"]))
    head, grad, loss = _programs(tuple(sorted(c.items())))
    hs = [head(trunk, ids[i]) for i in range(n)]
    scale = 1.0 if fault == "logits" else c["logits_scaling"]

    bases = {}
    for k in FED:
        shape = fed[k].shape
        u, _, vt = np.linalg.svd(np.asarray(fed[k], np.float32).reshape(_view(shape, *VIEWS[k])),
                                 full_matrices=True)
        bases[k] = (jnp.asarray(u), jnp.asarray(np.swapaxes(vt, -1, -2)))
    rotate = lambda k, g: (jnp.swapaxes(bases[k][0], -1, -2)
                           @ g.reshape(_view(g.shape, *VIEWS[k])) @ bases[k][1])
    unrotate = lambda k, cf, shape: (bases[k][0] @ cf @ jnp.swapaxes(bases[k][1], -1, -2)
                                     ).reshape(shape)
    sizes = {k: fed[k].size for k in FED}
    kk = {k: max(1, int(frac * sizes[k])) for k in FED}
    ship = sum(float(u.size + v.size) for u, v in bases.values()) * FLOAT_BITS
    per_round = sum(kk.values()) * (FLOAT_BITS + INDEX_BITS)

    shift = [{k: jnp.zeros(fed[k].shape[:1] + _view(fed[k].shape, *VIEWS[k])[1:])
              for k in FED} for _ in range(n)]
    fshift = [{k: jnp.zeros_like(fed[k]) for k in FED} for _ in range(n)]
    server_f = {k: jnp.zeros_like(fed[k]) for k in FED}
    flat = lambda w: np.concatenate([np.asarray(w[k], np.float64).ravel() for k in FED])
    iterates, gaps = {0: flat(fed)}, []
    agg = n // 2 if fault == "half" else n
    for t in range(rounds):
        losses, coeff_sum, fc_sum = [], None, None
        for i in range(n):
            li, g = grad(fed, trunk, hs[i], ids[i], scale)
            losses.append(float(li))
            for k in FED:
                d = rotate(k, g[k]) - shift[i][k]
                s = _topk(d.ravel(), kk[k]).reshape(d.shape)
                shift[i][k] = keep(shift[i][k] + alpha * s)
                fd = g[k] ** 2 - fshift[i][k]
                fs = _topk(fd.ravel(), kk[k]).reshape(fd.shape)
                fshift[i][k] = keep(fshift[i][k] + f_alpha * fs)
                if i < agg:
                    coeff_sum = {} if coeff_sum is None else coeff_sum
                    fc_sum = {} if fc_sum is None else fc_sum
                    coeff_sum[k] = coeff_sum.get(k, 0.0) + shift[i][k]
                    fc_sum[k] = fc_sum.get(k, 0.0) + fs
        gaps.append(float(np.mean(losses)))
        new = {}
        for k in FED:
            g_hat = unrotate(k, coeff_sum[k] / agg, fed[k].shape)
            server_f[k] = keep(server_f[k] + f_alpha * fc_sum[k] / agg)
            step = lr * g_hat / (jnp.sqrt(jnp.maximum(server_f[k], 0.0)) + eps)
            if fault == "altered":
                step = 1.001 * step
            frozen = fault == "frozen" or (fault == "frozen_late" and t > 0)
            new[k] = fed[k] if frozen else keep(fed[k] - step)
        fed = new
    iterates[1] = iterates[rounds] = flat(fed)
    gaps.append(float(np.mean([float(loss(fed, trunk, hs[i], ids[i], scale))
                               for i in range(n)])))

    def legs(ts):
        ts = np.asarray(ts, np.float64)
        return {"grad_up": ts * per_round, "hess_up": ts * per_round,
                "model_down": np.zeros_like(ts), "basis_ship": np.full_like(ts, ship)}

    return {"iterates": iterates, "gaps": np.asarray(gaps), "legs": legs, "f_star": 0.0}
