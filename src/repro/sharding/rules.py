"""Logical-axis sharding rules: one place that decides how every parameter,
activation and cache tensor maps onto the (pod, data, model) mesh.

Scheme (baseline, see README.md §EXPERIMENTS for hillclimbed variants):

* batch            → (pod, data)      (data parallelism)
* attention heads, FFN hidden, MoE experts, vocab → model  (tensor/expert par.)
* parameters       → FSDP over data on the d_model-ish dimension, TP over model
* KV caches        → batch over data when it divides; the *sequence* dimension
  shards over model (flash-decode style seq-parallel attention) because most
  assigned configs have n_kv_heads < 16; for global_batch == 1 (long_500k) the
  sequence additionally shards over data.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


# Mesh axis that client-stacked federated state shards over (the round
# engine in repro.core.rounds and the BL-DNN layer in repro.fed.bldnn both
# map their leading n_clients axis onto it).
CLIENT_AXIS = "data"


def mesh_fingerprint(mesh: "Mesh") -> str:
    """Identity-free description of a mesh for program-cache keys
    (`repro.core.progcache`): axis names/sizes plus the device platform and
    kind.  Two processes building the same-shape mesh over the same device
    model produce the same string; device ordinals and hostnames are
    deliberately excluded (an executable compiled for device 0..7 loads
    fine on any same-kind 8-device world)."""
    axes = ",".join(f"{a}={mesh.shape[a]}" for a in mesh.axis_names)
    dev = mesh.devices.ravel()[0]
    return f"mesh({axes}|{dev.platform}:{dev.device_kind})"


def client_chunk_specs(carry_specs, basis_replicated: bool = False):
    """shard_map specs for the chunked round driver's body
    (`repro.core.rounds._chunk_body` — the one scan program behind
    `run_rounds`, `run_chunk` and, with three more arguments that
    `rounds._sharded_programs` appends, the cohort engine).

    Positional layout is (batch, basisb, x0, carry, ts, keys, avail) →
    (carry, (eval_x, ledger, events)).  The client-stacked pytrees
    (`ClientBatch`, `BatchedBasis`, `TreeBatch`) shard their leading
    client axis over CLIENT_AXIS; the scan carry crosses the shard_map
    boundary: ``carry_specs`` is the per-leaf spec pytree derived from
    `rounds.carry_client_flags` (client-stacked leaves shard over
    CLIENT_AXIS, server state is replicated).  Per-round keys and the
    fault-availability schedule ``avail`` (fleet-wide (steps, n)) are
    replicated, exactly like the participation draws; the history streams
    come back replicated (the P()s in the output tuple are pytree prefixes
    covering every ledger leg).

    ``basis_replicated=True`` replicates the basis argument instead of
    sharding it — pytree bases (`PerLayerSVDBasis`) are fleet-global with
    no client axis to shard (specs opt in via
    `MethodSpec.basis_replicated`)."""
    sharded = P(CLIENT_AXIS)
    in_specs = (sharded, P() if basis_replicated else sharded, P(),
                carry_specs, P(), P(), P())
    return in_specs, (carry_specs, (P(), P(), P()))


@dataclasses.dataclass
class Rules:
    mesh: Mesh
    amap: Dict[str, Any]  # logical axis → mesh axis (or tuple / None)

    def spec(self, axes) -> P:
        return P(*[self.amap.get(a) if a is not None else None for a in axes])

    def sharding(self, axes) -> NamedSharding:
        return NamedSharding(self.mesh, self.spec(axes))

    def constrain(self, x, axes):
        assert x.ndim == len(axes), (x.shape, axes)
        return jax.lax.with_sharding_constraint(x, self.sharding(axes))


def make_rules(
    mesh: Mesh,
    *,
    batch_size: Optional[int] = None,
    fsdp: bool = True,
    seq_parallel: bool = False,
) -> Rules:
    """Build rules for a mesh with axes ('data','model') or ('pod','data','model').

    batch_size (global) decides whether batch can shard over the data axes.
    """
    names = mesh.axis_names
    multi_pod = "pod" in names
    data_axes: Tuple[str, ...] = ("pod", "data") if multi_pod else ("data",)
    data_size = int(np.prod([mesh.shape[a] for a in data_axes]))
    batch_axes = data_axes
    kv_seq = None
    if batch_size is not None and batch_size < data_size:
        if batch_size == 1:
            batch_axes = None
            kv_seq = data_axes  # sequence takes over the idle data axes
        else:
            # shard over as many trailing data axes as divide the batch
            batch_axes = tuple(a for a in data_axes if batch_size % mesh.shape[a] == 0)[:1] or None
    amap = {
        "batch": batch_axes,
        "heads": "model",
        "kv_heads": None,       # most configs have kv < 16; see kv_seq instead
        "ffn": "model",
        "experts": "model",
        "vocab": "model",
        "kv_seq": kv_seq,       # extra data-axis seq sharding (long_500k)
        "fsdp": ("data" if fsdp else None),
        "model": "model",
        # sequence-parallel residual stream (archs whose head count doesn't
        # divide the model axis — gemma3/llama4/whisper; §Perf)
        "act_seq": ("model" if seq_parallel else None),
    }
    return Rules(mesh=mesh, amap=amap)


def wants_seq_parallel(cfg, mesh: Mesh) -> bool:
    m = mesh.shape["model"]
    specs = cfg.layer_specs()
    pure_attn = all(s.mixer == "attn" for s in specs)
    return pure_attn and cfg.n_heads % m != 0


# --------------------------------------------------------------------------
# Parameter / cache / optimizer specs by tree path
# --------------------------------------------------------------------------
def _param_spec_for(path: str, ndim: int, rules: Rules, cfg) -> P:
    f = rules.amap["fsdp"]
    m = "model"
    msize = rules.mesh.shape["model"]

    def fits(dim):  # only shard dims divisible by the mesh axis
        return dim % msize == 0

    # embed/unembed: vocab-only sharding.  2D (fsdp × vocab) sharding makes
    # the fused-CE backward contraction ambiguous and XLA all-gathers the
    # full (B,S,V) cotangent (13 GB for mamba2 train_4k) — measured in the
    # dry-run; vocab-only keeps dh as a cheap all-reduce partial.
    if path.endswith("unembed"):
        return P(None, m if fits(cfg.padded_vocab) else None)
    if path.endswith("embed") and ndim == 2:
        return P(m if fits(cfg.padded_vocab) else None, None)
    if path.endswith("enc_pos"):
        return P(None, None)
    # stacked layer params: leading axis = n_groups (or n_enc_layers)
    lead = (None,)
    name = path.split("/")[-1]
    if name in ("wq",):
        return P(*lead, f, m if fits(cfg.n_heads) else None, None)
    if name in ("wk", "wv"):
        return P(*lead, f, m if fits(cfg.n_kv_heads) else None, None)
    if name == "wo" and ndim == 4:
        return P(*lead, m if fits(cfg.n_heads) else None, None, f)
    if name in ("wi", "wg") and ndim == 3:   # dense MLP (G, D, F)
        return P(*lead, f, m)
    if name == "wo" and ndim == 3:           # dense MLP out (G, F, D)
        return P(*lead, m, f)
    if name in ("wi", "wg") and ndim == 4:   # MoE (G, E, D, F)
        mc = cfg.moe
        return P(*lead, m if fits(mc.n_experts) else None, f, None)
    if name == "wo" and ndim == 4:
        mc = cfg.moe
        return P(*lead, m if fits(mc.n_experts) else None, None, f)
    if name == "router":
        return P(*lead, None, None)
    if name == "in_proj":                    # mamba (G, D, E)
        return P(*lead, f, m)
    if name == "out_proj":                   # mamba (G, di, D)
        return P(*lead, m, f)
    if name == "conv_w":
        return P(*lead, None, m)
    if name in ("A_log", "D", "dt_bias"):
        return P(*lead, m if fits(cfg.n_ssm_heads) else None)
    # norms & everything else: replicated (tiny)
    return P(*([None] * ndim))


def _path_str(path) -> str:
    keys = []
    for k in path:
        if hasattr(k, "key"):
            keys.append(str(k.key))
        elif hasattr(k, "idx"):
            keys.append(str(k.idx))
    return "/".join(keys)


def _drop_indivisible(sp: P, shape, mesh: Mesh) -> P:
    """Replace any spec entry whose mesh-axis product doesn't divide the dim."""
    fixed = []
    for dim, entry in zip(shape, tuple(sp) + (None,) * (len(shape) - len(sp))):
        if entry is None:
            fixed.append(None)
            continue
        axes = entry if isinstance(entry, tuple) else (entry,)
        size = int(np.prod([mesh.shape[a] for a in axes]))
        fixed.append(entry if dim % size == 0 else None)
    return P(*fixed)


def param_specs(params_tree, cfg, rules: Rules):
    """NamedSharding tree matching the parameter pytree."""
    def f(path, leaf):
        ps = _path_str(path)
        sp = _param_spec_for(ps, len(leaf.shape), rules, cfg)
        # MoE expert wo vs attn wo: both ndim 4 — disambiguate by path
        if ps.split("/")[-1] == "wo" and len(leaf.shape) == 4:
            m = "model"
            fx = rules.amap["fsdp"]
            msize = rules.mesh.shape["model"]
            if "moe" in ps:
                ok = cfg.moe.n_experts % msize == 0
                sp = P(None, m if ok else None, None, fx)
            else:
                ok = cfg.n_heads % msize == 0
                sp = P(None, m if ok else None, None, fx)
        sp = _drop_indivisible(sp, leaf.shape, rules.mesh)
        return NamedSharding(rules.mesh, sp)
    return jax.tree_util.tree_map_with_path(f, params_tree)


def cache_specs(cache_tree, cfg, rules: Rules):
    """KV/SSM cache shardings.  Attn K/V: (G, B, S, KVH, hd) — batch over the
    batch axes, sequence over model (+ data when batch==1).  SSM states:
    (G, B, H, hd, N) — heads over model when divisible."""
    msize = rules.mesh.shape["model"]
    batch_ax = rules.amap["batch"]
    kvseq_extra = rules.amap["kv_seq"]

    def f(path, leaf):
        ps = _path_str(path)
        name = ps.split("/")[-1]
        if name in ("k", "v"):
            seq_axes = ("model",) if kvseq_extra is None else tuple(kvseq_extra) + ("model",)
            if leaf.shape[2] % int(np.prod([rules.mesh.shape[a] for a in seq_axes])) != 0:
                seq_axes = None
            sp = P(None, batch_ax, seq_axes, None, None)
        elif name == "ssm":
            ok = leaf.shape[2] % msize == 0
            sp = P(None, batch_ax, "model" if ok else None, None, None)
        elif name == "conv":
            sp = P(None, batch_ax, None, "model" if leaf.shape[3] % msize == 0 else None)
        else:
            sp = P(*([None] * len(leaf.shape)))
        return NamedSharding(rules.mesh, sp)
    return jax.tree_util.tree_map_with_path(f, cache_tree)


def batch_specs(rules: Rules):
    return NamedSharding(rules.mesh, P(rules.amap["batch"], None))
