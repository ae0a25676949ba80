"""Model layers: RMSNorm, RoPE/M-RoPE, blocked GQA attention (+KV cache,
sliding window), gated/plain MLP, fine-grained MoE with shared experts, and
the Mamba2 SSD mixer (chunked scan for train/prefill, state update for
decode).

All functions are pure; parameters are nested dicts of arrays.  Activation
sharding constraints are applied through the `rules` object (see
repro.sharding.rules) and become no-ops when rules is None.

Memory discipline: attention over long sequences is computed in query blocks
via lax.scan (exact softmax per block row), bounding peak activation memory to
O(block · seq) instead of O(seq²) — required for prefill_32k to fit HBM.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .config import ModelConfig

Params = Dict[str, Any]

#: measurement mode: unroll every lax.scan so XLA cost_analysis (which counts
#: while-loop bodies ONCE) reports true whole-program costs.  Set only by the
#: dry-run cost extrapolation (launch/dryrun.lower_case_depth).
UNROLL_FOR_COSTS = False


def _init(key, shape, scale, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def shard(rules, x, *axes):
    """Apply a logical-axis sharding constraint (no-op without rules)."""
    if rules is None:
        return x
    return rules.constrain(x, axes)


def shard_residual(rules, h):
    """Residual stream: batch-sharded, replicated over `model`.

    (A sequence-parallel residual variant was tried and REFUTED — §Perf
    iteration log: under remat, every backward recompute re-gathers the
    seq-sharded activations, tripling all-gather bytes.  Sequence
    parallelism stays confined to the attention internals where it removes
    genuine redundancy — see _seq_parallel_attn.)"""
    if rules is None:
        return h
    return rules.constrain(h, ("batch", None, None))


# --------------------------------------------------------------------------
# Norm
# --------------------------------------------------------------------------
def init_rmsnorm(d, dtype):
    return {"scale": jnp.ones((d,), dtype)}


def rmsnorm(p, x, eps):
    h = x.astype(jnp.float32)
    h = h * jax.lax.rsqrt(jnp.mean(h * h, -1, keepdims=True) + eps)
    return (h * p["scale"].astype(jnp.float32)).astype(x.dtype)


# --------------------------------------------------------------------------
# RoPE / M-RoPE
# --------------------------------------------------------------------------
def rope_freqs(hd: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, hd // 2, dtype=jnp.float32) * 2.0 / hd))


def apply_rope(x: jax.Array, pos: jax.Array, theta: float, mrope: bool) -> jax.Array:
    """x: (B, S, H, hd).  pos: (B, S) or (3, B, S) for M-RoPE.

    M-RoPE (Qwen2-VL): the hd/2 frequency slots are split into 3 contiguous
    sections (temporal, height, width), each rotated by its own position
    component.  For text, all three components are equal and M-RoPE reduces
    to 1-D RoPE exactly.
    """
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)  # (hd/2,)
    if mrope:
        assert pos.ndim == 3
        nsec = hd // 2
        sec = np.array([nsec - 2 * (nsec // 3), nsec // 3, nsec // 3])
        comp_idx = np.repeat(np.arange(3), sec)              # static (hd/2,)
        p = pos.astype(jnp.float32)[comp_idx, :, :]          # (hd/2, B, S)
        ang = jnp.einsum("fbs,f->bsf", p, freqs)
    else:
        if pos.ndim == 3:
            pos = pos[0]
        ang = pos.astype(jnp.float32)[:, :, None] * freqs[None, None, :]  # (B,S,hd/2)
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return out.astype(x.dtype)


# --------------------------------------------------------------------------
# Attention
# --------------------------------------------------------------------------
def init_attention(key, cfg: ModelConfig, dtype, cross: bool = False) -> Params:
    d, hd = cfg.d_model, cfg.hd
    nh, nkv = cfg.n_heads, cfg.n_kv_heads
    ks = jax.random.split(key, 4)
    s = d ** -0.5
    return {
        "wq": _init(ks[0], (d, nh, hd), s, dtype),
        "wk": _init(ks[1], (d, nkv, hd), s, dtype),
        "wv": _init(ks[2], (d, nkv, hd), s, dtype),
        "wo": _init(ks[3], (nh, hd, d), (nh * hd) ** -0.5, dtype),
    }



def _dus_seq(buf, val, pos):
    """dynamic_update_slice along axis 1 with uniformly-typed int32 indices
    (robust to jax_enable_x64 being flipped on by the core test suite)."""
    z = jnp.zeros((), jnp.int32)
    p = jnp.asarray(pos, jnp.int32)
    return jax.lax.dynamic_update_slice(buf, val, (z, p, z, z))


def _blocked_attn(q, k, v, mask_fn, block: int, rules, q_pos0=0,
                  window: Optional[int] = None, scale: Optional[float] = None,
                  remat: bool = False):
    """Grouped-query blocked attention (no KV head materialization).

    q: (B, Sq, KVH, rep, hd);  k, v: (B, Sk, KVH, hd).
    Scans over query blocks; each block does an exact softmax over all keys
    with the (causal/window) mask from mask_fn(q_idx, k_idx).  q_pos0 offsets
    the query positions (sequence-parallel shards).

    Sliding-window layers (static `window`) slice each query block's K/V to
    the `block + window` stripe it can actually see instead of masking the
    full sequence — ~Sk/(block+window)× less attention compute/memory
    (§Perf gemma3 iteration 3).  ``scale`` multiplies the scores (None ⇒
    hd^-0.5).  ``remat`` recomputes each block's scores in the backward
    pass, which then holds one block's scores at a time.
    """
    B, Sq, KVH, rep, hd = q.shape
    Sk = k.shape[1]
    scale = hd ** -0.5 if scale is None else scale
    block = min(block, Sq)
    while Sq % block:  # largest divisor of Sq ≤ requested block
        block -= 1
    n_blocks = Sq // block
    qb = q.reshape(B, n_blocks, block, KVH, rep, hd).transpose(1, 0, 2, 3, 4, 5)

    windowed = window is not None and Sk > block + window
    if windowed:
        width = block + window
        k_use = jnp.pad(k, ((0, 0), (window, 0), (0, 0), (0, 0)))
        v_use = jnp.pad(v, ((0, 0), (window, 0), (0, 0), (0, 0)))
    else:
        k_use, v_use = k, v

    def body(carry, args):
        i, qi = args  # qi: (B, block, KVH, rep, hd)
        q_idx = q_pos0 + i * block + jnp.arange(block)
        if windowed:
            # padded coords: original position p lives at index p + window
            start = q_pos0 + i * block
            kk = jax.lax.dynamic_slice_in_dim(k_use, start, width, 1)
            vv = jax.lax.dynamic_slice_in_dim(v_use, start, width, 1)
            k_idx = start - window + jnp.arange(width)  # original positions
        else:
            kk, vv = k_use, v_use
            k_idx = jnp.arange(Sk)
        s = jnp.einsum("bqgrd,bkgd->bgrqk", qi.astype(jnp.float32) * scale,
                       kk.astype(jnp.float32))
        m = mask_fn(q_idx[:, None], k_idx[None, :])  # (block, kv_width)
        if windowed:
            m = m & (k_idx[None, :] >= 0)  # exclude front-pad rows
        s = jnp.where(m[None, None, None], s, -1e30)
        pr = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bgrqk,bkgd->bqgrd", pr.astype(vv.dtype), vv)
        return carry, o

    if remat:
        body = jax.checkpoint(body)
    if UNROLL_FOR_COSTS:
        outs = [body(None, (jnp.asarray(i), qb[i]))[1] for i in range(n_blocks)]
        ob = jnp.stack(outs)
    else:
        _, ob = jax.lax.scan(body, None, (jnp.arange(n_blocks), qb))
    out = ob.transpose(1, 0, 2, 3, 4, 5).reshape(B, Sq, KVH * rep, hd)
    return shard(rules, out, "batch", None, "heads", None)


def _seq_parallel_attn(qg, k, v, mask_fn, block: int, rules,
                       window: Optional[int] = None,
                       scale: Optional[float] = None):
    """Context-parallel blocked attention over the `model` axis (§Perf).

    Used when n_heads doesn't divide the model axis (gemma3: 8 heads,
    llama4: 40, whisper: 12 vs model=16): instead of replicating the whole
    attention 16×, queries shard over `model` on the SEQUENCE dim; the
    (small, GQA) K/V are all-gathered once per layer.  Exact — masks are
    offset by each shard's query-position base.
    """
    from jax.experimental.shard_map import shard_map
    from jax.sharding import PartitionSpec as P

    mesh = rules.mesh
    n_model = mesh.shape["model"]
    batch_ax = rules.amap["batch"]
    B, Sq, KVH, rep, hd = qg.shape
    Sq_loc = Sq // n_model

    def local(qg_l, k_l, v_l):
        kf = jax.lax.all_gather(k_l, "model", axis=1, tiled=True)
        vf = jax.lax.all_gather(v_l, "model", axis=1, tiled=True)
        off = jax.lax.axis_index("model") * Sq_loc
        return _blocked_attn(qg_l, kf, vf, mask_fn, block, None, q_pos0=off,
                             window=window, scale=scale)

    o = shard_map(
        local, mesh=mesh,
        in_specs=(P(batch_ax, "model", None, None, None),
                  P(batch_ax, "model", None, None),
                  P(batch_ax, "model", None, None)),
        out_specs=P(batch_ax, "model", None, None),
        check_rep=False,
    )(qg, k, v)
    return o


def attention(
    p: Params,
    x: jax.Array,
    cfg: ModelConfig,
    rules,
    pos: jax.Array,
    window: Optional[int] = None,
    cache: Optional[Tuple[jax.Array, jax.Array]] = None,
    cache_pos: Optional[jax.Array] = None,
    kv_override: Optional[Tuple[jax.Array, jax.Array]] = None,
    causal: bool = True,
    q_block: int = 1024,
    remat_blocks: bool = False,
) -> Tuple[jax.Array, Optional[Tuple[jax.Array, jax.Array]]]:
    """GQA attention.

    * train (cache=None): full-sequence blocked attention.
    * prefill (cache given, Sq>1): same, but also writes K/V into the cache
      (at `cache_pos`, or the last `window` tokens into the ring buffer for
      sliding-window layers) and returns the updated cache.
    * decode (cache given, Sq==1): single-token query attends to
      cache[: cache_pos+1] within the window; returns updated cache.
      Sliding-window layers keep a RING cache of size `window` — slot
      `pos % window` — so local layers never allocate the full sequence.
    * cross-attention: kv_override=(k, v) precomputed from encoder output
      (no RoPE, no mask).
    * window: static python int — sliding-window size (None ⇒ full).
    * ``cfg.rope`` False: no positional encoding (NoPE); scores are scaled
      by ``cfg.attention_multiplier`` where it is set.
    * remat_blocks: the backward pass recomputes each query block's scores
      (`_blocked_attn`).
    """
    B, Sq, D = x.shape
    nh, nkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    rep = nh // nkv
    scale = (hd ** -0.5 if cfg.attention_multiplier is None
             else cfg.attention_multiplier)

    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    q = shard(rules, q, "batch", None, "heads", None)

    if kv_override is not None:
        k, v = kv_override
        new_cache = None
        mask = lambda qi, ki: jnp.ones((qi.shape[0], ki.shape[1]), bool)
        qg = q.reshape(B, Sq, nkv, rep, hd)
        o = _blocked_attn(qg, k, v, mask, q_block, rules, scale=scale)
        out = jnp.einsum("bqhd,hdm->bqm", o, p["wo"])
        return shard(rules, out, "batch", None, None), None

    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    k = shard(rules, k, "batch", None, "kv_heads", None)
    v = shard(rules, v, "batch", None, "kv_heads", None)
    if cfg.rope:
        q = apply_rope(q, pos, cfg.rope_theta, cfg.mrope)
        k = apply_rope(k, pos, cfg.rope_theta, cfg.mrope)
    qg = q.reshape(B, Sq, nkv, rep, hd)

    new_cache = None
    if cache is not None:
        K, V = cache
        Sc = K.shape[1]  # ring size (== window) for sliding layers
        ring = window is not None and Sc <= window
        if Sq == 1:
            slot = cache_pos % Sc if ring else cache_pos
            K = _dus_seq(K, k.astype(K.dtype), slot)
            V = _dus_seq(V, v.astype(V.dtype), slot)
        else:  # prefill
            if Sq >= Sc:
                assert Sq % Sc == 0, (Sq, Sc)
                K = _dus_seq(K, k[:, Sq - Sc :].astype(K.dtype), 0)
                V = _dus_seq(V, v[:, Sq - Sc :].astype(V.dtype), 0)
            else:
                K = _dus_seq(K, k.astype(K.dtype), cache_pos)
                V = _dus_seq(V, v.astype(V.dtype), cache_pos)
        new_cache = (K, V)

    if cache is not None and Sq == 1:
        # decode: attend over the cache (possibly a ring buffer)
        K, V = new_cache
        Sk = K.shape[1]
        k_idx = jnp.arange(Sk)
        if window is not None and Sk <= window:
            # ring: slot s holds position cache_pos − ((cache_pos − s) mod Sk)
            pos_of = cache_pos - jnp.mod(cache_pos - k_idx, Sk)
            valid = pos_of >= 0
        else:
            valid = k_idx <= cache_pos
            if window is not None:
                valid = valid & (k_idx > cache_pos - window)
        s = jnp.einsum("bqgrd,bkgd->bgrqk", qg.astype(jnp.float32) * scale,
                       K.astype(jnp.float32))
        s = jnp.where(valid[None, None, None, None, :], s, -1e30)
        pr = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bgrqk,bkgd->bqgrd", pr.astype(V.dtype), V)
        o = o.reshape(B, 1, nh, hd)
    else:
        if causal:
            if window is not None:
                mask = lambda qi, ki: (ki <= qi) & (ki > qi - window)
            else:
                mask = lambda qi, ki: ki <= qi
        else:
            mask = lambda qi, ki: jnp.ones((qi.shape[0], ki.shape[1]), bool)
        n_model = rules.mesh.shape["model"] if rules is not None else 1
        if (rules is not None and nh % n_model != 0 and Sq % n_model == 0
                and Sq >= 4 * n_model and kv_override is None):
            # heads unshardable → sequence-parallel attention (see above)
            o = _seq_parallel_attn(qg, k, v, mask, q_block, rules,
                                   window=window, scale=scale)
        else:
            o = _blocked_attn(qg, k, v, mask, q_block, rules, window=window,
                              scale=scale, remat=remat_blocks)

    out = jnp.einsum("bqhd,hdm->bqm", o, p["wo"])
    return shard(rules, out, "batch", None, None), new_cache


# --------------------------------------------------------------------------
# MLP
# --------------------------------------------------------------------------
def init_mlp(key, d, f, gated, dtype):
    ks = jax.random.split(key, 3)
    if gated:
        return {
            "wi": _init(ks[0], (d, f), d**-0.5, dtype),
            "wg": _init(ks[1], (d, f), d**-0.5, dtype),
            "wo": _init(ks[2], (f, d), f**-0.5, dtype),
        }
    return {
        "wi": _init(ks[0], (d, f), d**-0.5, dtype),
        "wo": _init(ks[2], (f, d), f**-0.5, dtype),
    }


def mlp(p, x, gated, rules):
    h = jnp.einsum("bsd,df->bsf", x, p["wi"])
    if gated:
        g = jnp.einsum("bsd,df->bsf", x, p["wg"])
        h = jax.nn.silu(g) * h
    else:
        h = jax.nn.gelu(h)
    h = shard(rules, h, "batch", None, "ffn")
    return jnp.einsum("bsf,fd->bsd", h, p["wo"])


# --------------------------------------------------------------------------
# MoE (fine-grained, shared experts, top-k token-choice with capacity)
# --------------------------------------------------------------------------
def init_moe(key, cfg: ModelConfig, dtype):
    mc = cfg.moe
    d = cfg.d_model
    fe = mc.d_expert or cfg.d_ff
    ks = jax.random.split(key, 5)
    p = {
        "router": _init(ks[0], (d, mc.n_experts), d**-0.5, jnp.float32),
        "wi": _init(ks[1], (mc.n_experts, d, fe), d**-0.5, dtype),
        "wg": _init(ks[2], (mc.n_experts, d, fe), d**-0.5, dtype),
        "wo": _init(ks[3], (mc.n_experts, fe, d), fe**-0.5, dtype),
    }
    if mc.dropless:
        # the held experts only; the router keeps all n_experts outputs
        for name in ("wi", "wg", "wo"):
            p[name] = p[name][mc.first_held:mc.first_held + mc.held]
    if mc.d_shared:
        p["shared"] = init_mlp(ks[4], d, mc.d_shared, True, dtype)
    elif mc.n_shared:
        p["shared"] = init_mlp(ks[4], d, fe * mc.n_shared, True, dtype)
    return p


def _moe_expert_parallel(p, x, cfg: ModelConfig, rules) -> Tuple[jax.Array, jax.Array]:
    """Expert-parallel MoE dispatch via shard_map (§Perf iteration 1).

    Key observation: activations are REPLICATED over the `model` axis in our
    sharding scheme, so every model shard already holds all of its data
    shard's tokens.  Each shard therefore routes locally to its E/n_model
    experts, computes, and the per-expert partial outputs combine with ONE
    (B_loc·S·D) psum over `model` — no token all-to-all / all-gather at all.
    Measured on deepseek-moe train_4k: collective bytes 405 GB → see
    README.md §EXPERIMENTS."""
    from jax.experimental.shard_map import shard_map
    from jax.sharding import PartitionSpec as P

    mc = cfg.moe
    B, S, D = x.shape
    E, K = mc.n_experts, mc.top_k
    mesh = rules.mesh
    n_model = mesh.shape["model"]
    assert E % n_model == 0
    batch_ax = rules.amap["batch"]

    def local(xl, router, wi, wg, wo):
        Bl, Sl, _ = xl.shape
        T = Bl * Sl
        xt = xl.reshape(T, D)
        logits = xt.astype(jnp.float32) @ router
        probs = jax.nn.softmax(logits, -1)
        gate_vals, expert_ids = jax.lax.top_k(probs, K)
        gate_vals = gate_vals / jnp.clip(gate_vals.sum(-1, keepdims=True), 1e-9)

        me = probs.mean(0)
        ce = jnp.zeros((E,), jnp.float32).at[expert_ids.reshape(-1)].add(1.0) / (T * K)
        aux = E * jnp.sum(me * ce) * mc.router_aux_weight
        # per-data-shard (local) aux, averaged — standard local load-balance
        for ax in [a for a in mesh.axis_names if a != "model"]:
            aux = jax.lax.pmean(aux, ax)

        E_loc = wi.shape[0]
        my0 = jax.lax.axis_index("model") * E_loc
        cap = max(int(np.ceil(T * K / E * mc.capacity_factor)), K)

        # (E_loc, cap) token-index table — dispatch buffers stay E_loc·cap
        # sized instead of (T·K, D) (§Perf iteration 2: 12.8× smaller)
        flat_e = expert_ids.reshape(-1)
        local_e = jnp.where((flat_e >= my0) & (flat_e < my0 + E_loc),
                            flat_e - my0, E_loc)          # E_loc = not mine
        order = jnp.argsort(local_e, stable=True)
        sorted_e = local_e[order]
        pos = jnp.arange(T * K) - jnp.searchsorted(sorted_e, sorted_e, "left")
        keep = (sorted_e < E_loc) & (pos < cap)
        token_of = order // K
        e_cl = jnp.where(keep, sorted_e, 0)
        p_cl = jnp.where(keep, pos, cap)                  # cap = spill column
        idx_tbl = jnp.full((E_loc, cap + 1), T, jnp.int32).at[e_cl, p_cl].set(
            jnp.where(keep, token_of, T).astype(jnp.int32))[:, :cap]
        gate_tbl = jnp.zeros((E_loc, cap + 1), jnp.float32).at[e_cl, p_cl].set(
            jnp.where(keep, gate_vals.reshape(-1)[order], 0.0))[:, :cap]

        xt_pad = jnp.concatenate([xt, jnp.zeros((1, D), xt.dtype)], 0)
        xe = xt_pad[idx_tbl]                              # (E_loc, cap, D)
        h = jnp.einsum("ecd,edf->ecf", xe, wi)
        g = jnp.einsum("ecd,edf->ecf", xe, wg)
        ye = jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * h, wo)
        ye = ye * gate_tbl[:, :, None].astype(ye.dtype)
        out = jnp.zeros((T + 1, D), xl.dtype).at[idx_tbl.reshape(-1)].add(
            ye.reshape(E_loc * cap, D))[:T]
        out = jax.lax.psum(out, "model")   # combine expert partials
        return out.reshape(Bl, Sl, D), aux

    other_axes = [a for a in mesh.axis_names if a != "model"]
    bspec = P(batch_ax, None, None)
    out, aux = shard_map(
        local, mesh=mesh,
        in_specs=(bspec, P(), P("model", None, None), P("model", None, None),
                  P("model", None, None)),
        out_specs=(bspec, P()),
        check_rep=False,
    )(x, p["router"], p["wi"], p["wg"], p["wo"])

    if mc.n_shared:
        out = out + mlp(p["shared"], x, True, rules)
    return out, aux


def moe(p, x, cfg: ModelConfig, rules) -> Tuple[jax.Array, jax.Array]:
    """Token-choice top-k routing with per-expert capacity via sort-based
    dispatch (gather → grouped einsum → scatter-add).  Experts are sharded
    over the `model` axis (expert parallelism).  With sharding rules active,
    uses the expert-parallel shard_map path (zero dispatch collectives);
    without rules (single-device tests), the global argsort path below.
    Returns (out, aux_loss)."""
    if (rules is not None and cfg.moe.n_experts % rules.mesh.shape["model"] == 0
            and x.shape[0] * x.shape[1] >= 4096):
        # expert-parallel dispatch pays for its per-layer expert-weight
        # resharding only at prefill/train token counts; decode (1 token/seq)
        # keeps the global path (measured: llama4 decode coll 1.7→4.4 GB
        # regression with shard_map — gated out, §Perf)
        return _moe_expert_parallel(p, x, cfg, rules)
    mc = cfg.moe
    B, S, D = x.shape
    E, K = mc.n_experts, mc.top_k
    T = B * S
    xt = x.reshape(T, D)

    logits = (xt.astype(jnp.float32) @ p["router"])  # (T, E)
    probs = jax.nn.softmax(logits, -1)
    gate_vals, expert_ids = jax.lax.top_k(probs, K)   # (T, K)
    gate_vals = gate_vals / jnp.clip(gate_vals.sum(-1, keepdims=True), 1e-9)

    # load-balance aux loss (Switch-style)
    me = probs.mean(0)
    ce = jnp.zeros((E,), jnp.float32).at[expert_ids.reshape(-1)].add(1.0) / (T * K)
    aux = E * jnp.sum(me * ce) * mc.router_aux_weight

    cap = int(np.ceil(T * K / E * mc.capacity_factor))
    cap = max(cap, K)
    flat_e = expert_ids.reshape(-1)                   # (T*K,)
    # stable sort by expert id → contiguous expert groups
    order = jnp.argsort(flat_e, stable=True)          # (T*K,)
    sorted_e = flat_e[order]
    # position within expert group
    pos_in_e = jnp.arange(T * K) - jnp.searchsorted(sorted_e, sorted_e, side="left")
    keep = pos_in_e < cap
    token_of = order // K                              # source token per slot
    slot = sorted_e * cap + pos_in_e                   # target slot in (E*cap)
    slot = jnp.where(keep, slot, E * cap)              # overflow bucket

    gathered = jnp.zeros((E * cap + 1, D), x.dtype).at[slot].set(xt[token_of])
    xe = gathered[:-1].reshape(E, cap, D)
    xe = shard(rules, xe, "experts", None, None)

    h = jnp.einsum("ecd,edf->ecf", xe, p["wi"])
    g = jnp.einsum("ecd,edf->ecf", xe, p["wg"])
    h = jax.nn.silu(g) * h
    ye = jnp.einsum("ecf,efd->ecd", h, p["wo"])
    ye = shard(rules, ye, "experts", None, None)

    yflat = ye.reshape(E * cap, D)
    gates_sorted = gate_vals.reshape(-1)[order]
    contrib = jnp.where(keep[:, None], yflat[jnp.clip(slot, 0, E * cap - 1)]
                        * gates_sorted[:, None].astype(x.dtype), 0.0)
    out = jnp.zeros((T, D), x.dtype).at[token_of].add(contrib)

    if mc.n_shared:
        out = out + mlp(p["shared"], xt[None], True, rules)[0]
    return out.reshape(B, S, D), aux


def _held_rows(local, n_held: int, rows: int):
    """The first ``rows`` of the token–expert pairs sorted by held-expert
    id (pairs for experts not held here sort last): (order, group sizes
    clipped to ``rows``)."""
    order = jnp.argsort(local, stable=True)[:rows]
    sizes = jnp.bincount(local, length=n_held + 1)[:n_held].astype(jnp.int32)
    # a group that would run past `rows` is cut at it (only where the
    # pairs outnumber the rows, which `moe_dropless` never lets happen)
    ends = jnp.minimum(jnp.cumsum(sizes), rows)
    return order, jnp.diff(ends, prepend=0).astype(jnp.int32)


def moe_dropless(p, x, cfg: ModelConfig) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Dropless token-choice MoE over the experts this layer holds
    (Granite 4.0-H's ``block_sparse_moe`` as one expert-parallel share).

    The router keeps all ``n_experts`` outputs; each token takes its top-k
    router logits, and its gates are a softmax over those k logits.  The
    layer holds experts ``[first_held, first_held + n_held)`` and returns,
    for every token, the gated sum of its held experts' SwiGLU outputs,
    ``Σ_{e ∈ top-k ∩ held} g_e · W2_e (silu(W1_e x) ⊙ W3_e x)``: what the
    absent experts add is another chip's part of the sum.  No token is
    dropped: the pairs routed here are sorted by expert and run through
    grouped matmuls (`jax.lax.ragged_dot`) over a buffer of ``rows`` pairs,
    sized for the expected load with room to spare; a step whose load
    exceeds it takes a second branch whose buffer holds every pair that
    can be routed here (each token picks at most min(k, n_held) of them).
    The shared expert is not part of this layer (`_apply_layer`).

    Returns ``(out, counts)``: counts of the token–expert pairs routed to
    held experts (``assigned``) and of those left uncomputed
    (``dropped``, zero by construction)."""
    mc = cfg.moe
    B, S, D = x.shape
    E, K, held = mc.n_experts, mc.top_k, mc.held
    T = B * S
    xt = x.reshape(T, D)
    logits = xt.astype(jnp.float32) @ p["router"].astype(jnp.float32)
    top, ids = jax.lax.top_k(logits, K)
    gates = jax.nn.softmax(top, axis=-1)                    # (T, K)
    local = (ids - mc.first_held).reshape(-1)
    mine = (local >= 0) & (local < held)
    local = jnp.where(mine, local, held)                     # held ⇒ not here
    assigned = jnp.sum(mine.astype(jnp.int32))
    flat_gates = jnp.where(mine, gates.reshape(-1), 0.0)

    def run(rows: int):
        order, sizes = _held_rows(local, held, rows)
        tok = order // K
        # rows past the groups are left undefined by a TPU's ragged_dot,
        # forward and backward: select them out on both sides
        live = (jnp.arange(rows) < jnp.sum(sizes))[:, None]
        xe = jnp.where(live, xt[tok], 0)                      # (rows, D)
        h = jax.lax.ragged_dot(xe, p["wi"], sizes)
        g = jax.lax.ragged_dot(xe, p["wg"], sizes)
        ye = jax.lax.ragged_dot((jax.nn.silu(g) * h).astype(xe.dtype),
                                p["wo"], sizes)
        ye = jnp.where(live, ye.astype(jnp.float32), 0.0) * flat_gates[order][:, None]
        out = jnp.zeros((T, D), jnp.float32).at[tok].add(ye)
        return out.astype(x.dtype), assigned - jnp.sum(sizes)

    full = T * min(K, held)
    expected = -(-2 * T * K * held // E)                  # twice the mean load
    rows = min(full, max(128, -(-expected // 128) * 128))
    if rows == full:
        out, dropped = run(full)
    else:
        out, dropped = jax.lax.cond(assigned <= rows, lambda: run(rows),
                                    lambda: run(full))
    return out.reshape(B, S, D), {"assigned": assigned, "dropped": dropped}


# --------------------------------------------------------------------------
# Mamba2 (SSD)
# --------------------------------------------------------------------------
def init_mamba(key, cfg: ModelConfig, dtype):
    sc = cfg.ssm
    d = cfg.d_model
    di = cfg.d_inner
    nh = cfg.n_ssm_heads
    conv_dim = di + 2 * sc.d_state
    ks = jax.random.split(key, 5)
    return {
        "in_proj": _init(ks[0], (d, 2 * di + 2 * sc.d_state + nh), d**-0.5, dtype),
        "conv_w": _init(ks[1], (sc.conv_width, conv_dim), 0.5, dtype),
        **({"conv_b": _init(ks[2], (conv_dim,), 0.1, dtype)}
           if sc.conv_bias else {}),
        "A_log": jnp.zeros((nh,), jnp.float32) + jnp.log(jnp.arange(1, nh + 1, dtype=jnp.float32)),
        "D": jnp.ones((nh,), jnp.float32),
        "dt_bias": jnp.zeros((nh,), jnp.float32),
        "norm": init_rmsnorm(di, dtype),
        "out_proj": _init(ks[4], (di, d), di**-0.5, dtype),
    }


def _ssd_chunked(xh, dt, A, B_, C, chunk):
    """Mamba2 SSD forward (training/prefill).

    xh: (B, S, H, hd)   dt: (B, S, H)   A: (H,) < 0
    B_, C: (B, S, N)    (single SSM group shared across heads)
    Returns y: (B, S, H, hd) and final state (B, H, hd, N).

    Chunked state-space-duality: within a chunk, a masked quadratic form
    (MXU-friendly matmuls); across chunks, a sequential lax.scan over
    cumulative decay states.
    """
    Bsz, S, H, hd = xh.shape
    N = B_.shape[-1]
    nchunks = S // chunk
    assert S % chunk == 0, (S, chunk)

    xh = xh.reshape(Bsz, nchunks, chunk, H, hd)
    dt = dt.reshape(Bsz, nchunks, chunk, H)
    Bc = B_.reshape(Bsz, nchunks, chunk, N)
    Cc = C.reshape(Bsz, nchunks, chunk, N)

    dA = dt * A[None, None, None, :]                 # (B, n, c, H) ≤ 0
    cs = jnp.cumsum(dA, axis=2)                      # cumulative log-decay
    seg = cs[:, :, :, None, :] - cs[:, :, None, :, :]   # (B,n,c_q,c_k,H)
    idx = jnp.arange(chunk)
    causal = idx[:, None] >= idx[None, :]
    # mask BEFORE exp: exp of a masked huge positive would poison gradients
    seg = jnp.where(causal[None, None, :, :, None], seg, -1e30)
    L = jnp.exp(seg)

    # intra-chunk: y_intra[q] = Σ_k L[q,k] (C_q·B_k) dt_k x_k
    CB = jnp.einsum("bnqs,bnks->bnqk", Cc, Bc)       # (B,n,c,c)
    M = CB[:, :, :, :, None] * L                     # (B,n,q,k,H)
    y_intra = jnp.einsum("bnqkh,bnkh,bnkhd->bnqhd", M, dt, xh)

    # chunk summary states: S_n = Σ_k exp(cs_end − cs_k) dt_k B_k ⊗ x_k
    decay_to_end = jnp.exp(cs[:, :, -1:, :] - cs)    # (B,n,c,H)
    states = jnp.einsum("bnkh,bnkh,bnks,bnkhd->bnhds",
                        decay_to_end, dt, Bc, xh)    # (B,n,H,hd,N)
    chunk_decay = jnp.exp(cs[:, :, -1, :])           # (B,n,H)

    def scan_fn(s_prev, inp):
        st, dec = inp                                 # (B,H,hd,N), (B,H)
        s_new = s_prev * dec[:, :, None, None] + st
        return s_new, s_prev

    s0 = jnp.zeros((Bsz, H, hd, N), xh.dtype)
    xs_states = states.transpose(1, 0, 2, 3, 4)
    xs_decay = chunk_decay.transpose(1, 0, 2)
    if UNROLL_FOR_COSTS:
        s, s_ins = s0, []
        for i in range(nchunks):
            s, prev = scan_fn(s, (xs_states[i], xs_decay[i]))
            s_ins.append(prev)
        s_final, s_in = s, jnp.stack(s_ins)
    else:
        s_final, s_in = jax.lax.scan(scan_fn, s0, (xs_states, xs_decay))
    s_in = s_in.transpose(1, 0, 2, 3, 4)             # state entering each chunk

    # inter-chunk: y_inter[q] = exp(cs_q) C_q · S_in
    decay_from_start = jnp.exp(cs)                   # (B,n,c,H)
    y_inter = jnp.einsum("bnqs,bnhds,bnqh->bnqhd", Cc, s_in, decay_from_start)

    y = (y_intra + y_inter).reshape(Bsz, S, H, hd)
    return y, s_final


def mamba(
    p: Params,
    x: jax.Array,
    cfg: ModelConfig,
    rules,
    cache: Optional[Dict[str, jax.Array]] = None,
) -> Tuple[jax.Array, Optional[Dict[str, jax.Array]]]:
    """Mamba2 block.  cache = {"conv": (B, W-1, conv_dim), "ssm": (B,H,hd,N)}."""
    sc = cfg.ssm
    B, S, D = x.shape
    di = cfg.d_inner
    H = cfg.n_ssm_heads
    hd = sc.head_dim
    N = sc.d_state

    zxbcdt = jnp.einsum("bsd,de->bse", x, p["in_proj"])
    z, xraw, Bmat, Cmat, dt = jnp.split(
        zxbcdt, [di, 2 * di, 2 * di + N, 2 * di + 2 * N], axis=-1
    )
    conv_in = jnp.concatenate([xraw, Bmat, Cmat], -1)  # (B,S,conv_dim)
    conv_dim = conv_in.shape[-1]

    if cache is None:
        pad = jnp.zeros((B, sc.conv_width - 1, conv_dim), conv_in.dtype)
        seq = jnp.concatenate([pad, conv_in], 1)
        new_conv_state = seq[:, -(sc.conv_width - 1):, :] if sc.conv_width > 1 else None
    else:
        seq = jnp.concatenate([cache["conv"].astype(conv_in.dtype), conv_in], 1)
        new_conv_state = seq[:, -(sc.conv_width - 1):, :]

    # causal depthwise conv, width W
    conv = sum(
        seq[:, i : i + S, :] * p["conv_w"][i][None, None, :]
        for i in range(sc.conv_width)
    )
    if sc.conv_bias:
        conv = conv + p["conv_b"]
    conv = jax.nn.silu(conv)
    xc, Bc, Cc = jnp.split(conv, [di, di + N], axis=-1)
    xh = xc.reshape(B, S, H, hd)
    xh = shard(rules, xh, "batch", None, "heads", None)

    A = -jnp.exp(p["A_log"])                            # (H,)
    dt_s = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"][None, None, :])
    dt_s = shard(rules, dt_s, "batch", None, "heads")

    if cache is None or S > 1:
        chunk = min(sc.chunk, S)
        y, s_final = _ssd_chunked(
            xh.astype(jnp.float32), dt_s, A, Bc.astype(jnp.float32),
            Cc.astype(jnp.float32), chunk
        )
    else:
        # single-token decode: s = exp(dtA) s + dt B ⊗ x ; y = C·s
        s_prev = cache["ssm"].astype(jnp.float32)       # (B,H,hd,N)
        dec = jnp.exp(dt_s[:, 0] * A[None, :])          # (B,H)
        upd = jnp.einsum("bh,bn,bhd->bhdn", dt_s[:, 0], Bc[:, 0].astype(jnp.float32),
                         xh[:, 0].astype(jnp.float32))
        s_final = s_prev * dec[:, :, None, None] + upd
        y = jnp.einsum("bn,bhdn->bhd", Cc[:, 0].astype(jnp.float32), s_final)[:, None]
        y = y.reshape(B, 1, H, hd)

    y = y + xh.astype(jnp.float32) * p["D"][None, None, :, None]
    y = y.reshape(B, S, di).astype(x.dtype)
    y = rmsnorm(p["norm"], y * jax.nn.silu(z), cfg.norm_eps)
    out = jnp.einsum("bsd,de->bse", y, p["out_proj"])

    new_cache = None
    if cache is not None:
        new_cache = {
            "conv": (new_conv_state if new_conv_state is not None
                     else jnp.zeros((B, max(sc.conv_width - 1, 1), conv_dim), x.dtype)).astype(cache["conv"].dtype),
            "ssm": s_final.astype(jnp.float32),
        }
    return shard(rules, out, "batch", None, None), new_cache
