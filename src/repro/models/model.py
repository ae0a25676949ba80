"""Model assembly: parameter init, train/prefill/decode forwards.

Layers are stacked by *group*: identical `LayerSpec` groups scan over a
leading `n_groups` axis (small HLO, fast compile, remat-friendly); the
heterogeneity inside a group (e.g. Jamba's 1 attn : 7 mamba, Llama4's
dense/MoE interleave) is unrolled inside the scanned body.

Encoder–decoder (Whisper): encoder is a full-attention scan over stub frame
embeddings; every decoder layer adds cross-attention against the encoder
output.  VLM (Qwen2-VL): stub patch embeddings are concatenated in front of
the token embeddings and M-RoPE positions are used.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import layers as L
from .config import LayerSpec, ModelConfig

Params = Dict[str, Any]


# --------------------------------------------------------------------------
# Init
# --------------------------------------------------------------------------
def _init_layer(key, spec: LayerSpec, cfg: ModelConfig, dtype) -> Params:
    ks = jax.random.split(key, 4)
    p: Params = {"ln1": L.init_rmsnorm(cfg.d_model, dtype)}
    if spec.mixer == "attn":
        p["attn"] = L.init_attention(ks[0], cfg, dtype)
    else:
        p["mamba"] = L.init_mamba(ks[0], cfg, dtype)
    if cfg.n_enc_layers and spec.mixer == "attn":
        p["ln_x"] = L.init_rmsnorm(cfg.d_model, dtype)
        p["xattn"] = L.init_attention(ks[2], cfg, dtype, cross=True)
    if spec.ffn == "mlp":
        p["ln2"] = L.init_rmsnorm(cfg.d_model, dtype)
        p["mlp"] = L.init_mlp(ks[1], cfg.d_model, cfg.d_ff, cfg.mlp_gated, dtype)
    elif spec.ffn == "moe":
        p["ln2"] = L.init_rmsnorm(cfg.d_model, dtype)
        p["moe"] = L.init_moe(ks[1], cfg, dtype)
    return p


def _init_group(key, cfg: ModelConfig, dtype) -> Params:
    ks = jax.random.split(key, len(cfg.group))
    return {f"l{i}": _init_layer(ks[i], spec, cfg, dtype)
            for i, spec in enumerate(cfg.group)}


def init_params(key, cfg: ModelConfig, dtype=jnp.bfloat16) -> Params:
    ks = jax.random.split(key, 6)
    p: Params = {
        "embed": L._init(ks[0], (cfg.padded_vocab, cfg.d_model), 0.02, dtype),
        "final_norm": L.init_rmsnorm(cfg.d_model, dtype),
    }
    if not cfg.tie_embeddings:
        p["unembed"] = L._init(ks[1], (cfg.d_model, cfg.padded_vocab),
                               cfg.d_model**-0.5, dtype)
    # stacked decoder groups: every leaf gets a leading n_groups axis
    gkeys = jax.random.split(ks[2], cfg.n_groups)
    groups = [_init_group(k, cfg, dtype) for k in gkeys]
    p["layers"] = jax.tree.map(lambda *xs: jnp.stack(xs), *groups)
    if cfg.n_enc_layers:
        ekeys = jax.random.split(ks[3], cfg.n_enc_layers)
        enc_spec = LayerSpec(mixer="attn", ffn="mlp", window=None)
        enc_cfg = cfg  # same width
        encs = []
        for k in ekeys:
            kk = jax.random.split(k, 2)
            encs.append({
                "ln1": L.init_rmsnorm(cfg.d_model, dtype),
                "attn": L.init_attention(kk[0], cfg, dtype),
                "ln2": L.init_rmsnorm(cfg.d_model, dtype),
                "mlp": L.init_mlp(kk[1], cfg.d_model, cfg.d_ff, cfg.mlp_gated, dtype),
            })
        p["encoder"] = jax.tree.map(lambda *xs: jnp.stack(xs), *encs)
        p["enc_pos"] = L._init(ks[4], (cfg.enc_seq, cfg.d_model), 0.02, dtype)
        p["enc_norm"] = L.init_rmsnorm(cfg.d_model, dtype)
    return p


def param_shapes(cfg: ModelConfig, dtype=jnp.bfloat16):
    return jax.eval_shape(lambda k: init_params(k, cfg, dtype), jax.random.PRNGKey(0))


# --------------------------------------------------------------------------
# Cache
# --------------------------------------------------------------------------
def init_cache(cfg: ModelConfig, batch: int, max_seq: int, dtype=jnp.bfloat16) -> Params:
    """Per-group stacked decode caches (leading axis n_groups)."""
    def layer_cache(spec: LayerSpec):
        if spec.mixer == "attn":
            s = min(max_seq, spec.window) if spec.window else max_seq
            kvshape = (cfg.n_groups, batch, s, cfg.n_kv_heads, cfg.hd)
            return {"k": jnp.zeros(kvshape, dtype), "v": jnp.zeros(kvshape, dtype)}
        sc = cfg.ssm
        conv_dim = cfg.d_inner + 2 * sc.d_state
        return {
            "conv": jnp.zeros((cfg.n_groups, batch, sc.conv_width - 1, conv_dim), dtype),
            "ssm": jnp.zeros((cfg.n_groups, batch, cfg.n_ssm_heads, sc.head_dim,
                              sc.d_state), jnp.float32),
        }
    return {f"l{i}": layer_cache(s) for i, s in enumerate(cfg.group)}


def cache_shapes(cfg: ModelConfig, batch: int, max_seq: int, dtype=jnp.bfloat16):
    return jax.eval_shape(lambda: init_cache(cfg, batch, max_seq, dtype))


# --------------------------------------------------------------------------
# Forward
# --------------------------------------------------------------------------
def _apply_layer(
    lp: Params,
    spec: LayerSpec,
    cfg: ModelConfig,
    rules,
    h: jax.Array,
    pos: jax.Array,
    cache: Optional[Params],
    cache_pos,
    enc_out: Optional[jax.Array],
    remat_blocks: bool = False,
) -> Tuple[jax.Array, Optional[Params], jax.Array]:
    """One layer; returns ``(h, new_cache, aux)`` with ``aux`` the
    (router aux loss, token–expert pairs routed to held experts, pairs
    dropped) of a dropless expert layer, zeros elsewhere.

    Residual branches are scaled by ``cfg.residual_multiplier``
    (Granite 4.0-H: ``h ← h + 0.22·mixer(rmsnorm(h))`` and
    ``h ← h + 0.22·(moe(rmsnorm(h)) + shared(rmsnorm(h)))``); the mixer runs
    under the device scope ``attn`` or ``mamba`` and the expert layer under
    ``moe``."""
    zero = jnp.zeros((), jnp.int32)
    aux = (jnp.zeros((), jnp.float32), zero, zero)
    res = (lambda y: y) if cfg.residual_multiplier == 1.0 else (
        lambda y: y * jnp.asarray(cfg.residual_multiplier, y.dtype))
    x = L.rmsnorm(lp["ln1"], h, cfg.norm_eps)
    if spec.mixer == "attn":
        kv = (cache["k"], cache["v"]) if cache is not None else None
        with jax.named_scope("attn"):
            out, new_kv = L.attention(
                lp["attn"], x, cfg, rules, pos,
                window=spec.window,
                cache=kv, cache_pos=cache_pos, remat_blocks=remat_blocks,
            )
        new_cache = {"k": new_kv[0], "v": new_kv[1]} if (cache is not None) else None
    else:
        with jax.named_scope("mamba"):
            out, new_state = L.mamba(lp["mamba"], x, cfg, rules,
                                     cache=cache if cache is not None else None)
        new_cache = new_state if cache is not None else None
    h = h + res(out)

    if enc_out is not None and spec.mixer == "attn" and "xattn" in lp:
        xq = L.rmsnorm(lp["ln_x"], h, cfg.norm_eps)
        k = jnp.einsum("bsd,dhk->bshk", enc_out, lp["xattn"]["wk"])
        v = jnp.einsum("bsd,dhk->bshk", enc_out, lp["xattn"]["wv"])
        out, _ = L.attention(lp["xattn"], xq, cfg, rules, pos,
                             kv_override=(k, v), causal=False)
        h = h + out

    if spec.ffn == "mlp":
        x2 = L.rmsnorm(lp["ln2"], h, cfg.norm_eps)
        h = h + L.mlp(lp["mlp"], x2, cfg.mlp_gated, rules)
    elif spec.ffn == "moe":
        x2 = L.rmsnorm(lp["ln2"], h, cfg.norm_eps)
        with jax.named_scope("moe"):
            if cfg.moe.dropless:
                out, counts = L.moe_dropless(lp["moe"], x2, cfg)
                if "shared" in lp["moe"]:
                    out = out + L.mlp(lp["moe"]["shared"], x2, True, rules)
                aux = (aux[0], counts["assigned"], counts["dropped"])
            else:
                out, a = L.moe(lp["moe"], x2, cfg, rules)
                aux = (aux[0] + a, zero, zero)
        h = h + res(out)
    return h, new_cache, aux


def _run_encoder(p: Params, cfg: ModelConfig, rules, frames: jax.Array) -> jax.Array:
    """Whisper-style encoder over stub frame embeddings (B, enc_seq, D)."""
    h = frames + p["enc_pos"][None].astype(frames.dtype)
    pos = jnp.broadcast_to(jnp.arange(frames.shape[1], dtype=jnp.int32)[None],
                           frames.shape[:2])

    def body(h, ep):
        x = L.rmsnorm(ep["ln1"], h, cfg.norm_eps)
        out, _ = L.attention(ep["attn"], x, cfg, rules, pos, causal=False)
        h = h + out
        x2 = L.rmsnorm(ep["ln2"], h, cfg.norm_eps)
        h = h + L.mlp(ep["mlp"], x2, cfg.mlp_gated, rules)
        return h, None

    if L.UNROLL_FOR_COSTS:
        n_enc = jax.tree.leaves(p["encoder"])[0].shape[0]
        for i in range(n_enc):
            h, _ = body(h, jax.tree.map(lambda a: a[i], p["encoder"]))
    else:
        h, _ = jax.lax.scan(body, h, p["encoder"])
    return L.rmsnorm(p["enc_norm"], h, cfg.norm_eps)


def forward(
    p: Params,
    cfg: ModelConfig,
    rules,
    tokens: jax.Array,                    # (B, S) int32
    cache: Optional[Params] = None,       # stacked decode caches
    cache_pos=None,                       # scalar int32 (decode)
    prefix_embeds: Optional[jax.Array] = None,  # (B, P, D) VLM stub
    frames: Optional[jax.Array] = None,   # (B, enc_seq, D) audio stub
    remat: bool = True,
    return_hidden: bool = False,          # skip unembed (fused-CE train path)
    remat_layers: bool = False,
    return_counts: bool = False,
):
    """Returns (logits, new_cache, aux_loss), and with ``return_counts``
    also the dropless expert layers' summed ``(assigned, dropped)``
    token–expert pair counts.

    Modes: train (cache=None), prefill (cache given, S>1, cache_pos=0),
    decode (cache given, S==1, cache_pos=scalar position).
    ``remat_layers`` rematerializes each layer on its own in the backward
    pass (instead of a whole group), and each attention query block's
    scores, so a backward pass holds one layer's activations and one
    block's scores at a time.  Embeddings are scaled by
    ``cfg.embedding_multiplier`` (or by √d_model for tied embeddings
    without one); the head (final norm, unembedding, ÷ logits_scaling)
    runs under the device scope ``head``.
    """
    B, S = tokens.shape
    h = jnp.take(p["embed"], tokens, axis=0)
    if cfg.embedding_multiplier != 1.0:
        h = h * jnp.asarray(cfg.embedding_multiplier, h.dtype)
    elif cfg.tie_embeddings:
        h = h * jnp.asarray(cfg.d_model**0.5, h.dtype)
    if prefix_embeds is not None:
        h = jnp.concatenate([prefix_embeds.astype(h.dtype), h], axis=1)
    h = L.shard_residual(rules, h)
    Sfull = h.shape[1]
    decode = cache is not None and Sfull == 1

    if decode:
        pos = jnp.broadcast_to(jnp.asarray(cache_pos, jnp.int32), (B, 1))
    else:
        base = jnp.arange(Sfull, dtype=jnp.int32)[None]
        if cache_pos is not None:
            base = base + jnp.asarray(cache_pos, jnp.int32)
        pos = jnp.broadcast_to(base, (B, Sfull))
    if cfg.mrope:
        pos = jnp.broadcast_to(pos[None], (3,) + pos.shape)

    enc_out = _run_encoder(p, cfg, rules, frames) if cfg.n_enc_layers else None

    def group_body(h, xs):
        gp, gcache = xs
        new_caches = {}
        aux_total = (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.int32),
                     jnp.zeros((), jnp.int32))
        for i, spec in enumerate(cfg.group):
            lp = gp[f"l{i}"]
            lc = gcache[f"l{i}"] if gcache is not None else None
            layer = functools.partial(_apply_layer, spec=spec, cfg=cfg,
                                      rules=rules, cache_pos=cache_pos,
                                      enc_out=enc_out,
                                      remat_blocks=remat_layers)
            if remat_layers and cache is None:
                layer = jax.checkpoint(layer)
            h, nc, aux = layer(lp, h=h, pos=pos, cache=lc)
            h = L.shard_residual(rules, h)
            new_caches[f"l{i}"] = nc
            aux_total = tuple(a + b for a, b in zip(aux_total, aux))
        return h, (new_caches if gcache is not None else None, aux_total)

    body = group_body
    if remat and not remat_layers and cache is None:
        body = jax.checkpoint(group_body,
                              policy=jax.checkpoint_policies.nothing_saveable)

    if L.UNROLL_FOR_COSTS:
        auxs_l, caches_l = [], []
        for gi in range(cfg.n_groups):
            gp = jax.tree.map(lambda a: a[gi], p["layers"])
            gc = (jax.tree.map(lambda a: a[gi], cache)
                  if cache is not None else None)
            h, (nc, aux_g) = body(h, (gp, gc))
            auxs_l.append(aux_g)
            caches_l.append(nc)
        auxs = tuple(jnp.stack(a) for a in zip(*auxs_l))
        new_cache = (jax.tree.map(lambda *xs: jnp.stack(xs), *caches_l)
                     if cache is not None else None)
    elif cache is not None:
        h, (new_cache, auxs) = jax.lax.scan(body, h, (p["layers"], cache))
    else:
        h, (_, auxs) = jax.lax.scan(body, h, (p["layers"], None))
        new_cache = None

    if cache is not None and not decode:
        h = h[:, -1:, :]  # prefill: only last-position logits are needed
    counts = (jnp.sum(auxs[1]), jnp.sum(auxs[2]))
    with jax.named_scope("head"):
        h = L.rmsnorm(p["final_norm"], h, cfg.norm_eps)
        if return_hidden:
            out = (h, new_cache, jnp.sum(auxs[0]))
            return out + (counts,) if return_counts else out
        unemb = p["embed"].T if cfg.tie_embeddings else p["unembed"]
        if cfg.logits_scaling != 1.0:
            # Granite divides the logits by 16: keep them in f32
            logits = jnp.einsum("bsd,dv->bsv", h, unemb,
                                preferred_element_type=jnp.float32)
            logits = logits / cfg.logits_scaling
        else:
            logits = jnp.einsum("bsd,dv->bsv", h, unemb)
        logits = L.shard(rules, logits, "batch", None, "vocab")
        if cfg.padded_vocab != cfg.vocab_size:
            # mask padding slots so softmax/argmax never see them
            pad = jnp.arange(cfg.padded_vocab) >= cfg.vocab_size
            logits = logits + jnp.where(pad, -1e30, 0.0).astype(logits.dtype)
    out = (logits, new_cache, jnp.sum(auxs[0]))
    return out + (counts,) if return_counts else out
