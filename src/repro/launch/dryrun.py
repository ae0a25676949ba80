import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
"""Multi-pod dry-run (deliverable e).

For every (architecture × input shape) on the production mesh:
  jit(step).lower(*ShapeDtypeStructs).compile()
then record memory_analysis(), cost_analysis() and the collective byte totals
parsed from the optimized HLO — the raw material for the perf and roofline
notes in README.md §EXPERIMENTS.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch gemma3-4b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] [--out out.json]
"""
import argparse
import json
import re
import sys
import time
import traceback
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ARCH_IDS, get_config
from repro.core import progcache
from repro.launch.mesh import make_production_mesh
from repro.launch import shapes as SH
from repro.models import model as M
from repro.models.steps import make_prefill_step, make_serve_step, make_train_step
from repro.optim import adamw_init
from repro.sharding.rules import make_rules, param_specs, wants_seq_parallel

# ---------------------------------------------------------------------------
# HLO collective parsing
# ---------------------------------------------------------------------------
_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")
_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s64": 8, "u64": 8,
    "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1, "pred": 1,
    "f8e4m3fn": 1, "f8e5m2": 1,
}
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _tensor_bytes(shape_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(shape_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> Dict[str, Any]:
    """Sum output-operand sizes of every collective op in the optimized HLO.

    Counts the bytes that cross the interconnect once per op instance (the
    scan body appears once in HLO; XLA while-loops execute it n_groups times —
    we scale by the enclosing loop trip count when detectable)."""
    per_kind: Dict[str, float] = {k: 0.0 for k in _COLLECTIVES}
    counts: Dict[str, int] = {k: 0 for k in _COLLECTIVES}
    # map computation name -> body of while loops with trip counts
    trip_re = re.compile(r"trip_count=(\d+)")
    lines = hlo_text.splitlines()
    current_comp = ""
    comp_re = re.compile(r"^\s*%?([\w\.\-]+)\s*\(.*\)\s*->")
    # detect scan loop bodies: body computations referenced by while ops
    body_trips: Dict[str, int] = {}
    for ln in lines:
        if "while(" in ln and "body=" in ln:
            m = re.search(r"body=%?([\w\.\-]+)", ln)
            t = trip_re.search(ln)
            if m:
                body_trips[m.group(1)] = int(t.group(1)) if t else 1
    for ln in lines:
        mc = comp_re.match(ln)
        if mc and ("{" in ln or ln.rstrip().endswith("{")):
            current_comp = mc.group(1)
        for kind in _COLLECTIVES:
            if f" {kind}(" in ln or f"= {kind}(" in ln or kind + "-start" in ln:
                # output shape is the first shape on the line (lhs type)
                shape_part = ln.split("=")[0] + "=" + ln.split("=", 1)[1]
                b = _tensor_bytes(ln.split("=")[1].split(kind)[0]) or _tensor_bytes(ln)
                mult = body_trips.get(current_comp, 1)
                per_kind[kind] += b * mult
                counts[kind] += mult
                break
    per_kind_total = {k: v for k, v in per_kind.items()}
    return {
        "bytes_by_kind": per_kind_total,
        "counts": counts,
        "total_bytes": float(sum(per_kind_total.values())),
    }


# ---------------------------------------------------------------------------
# Lowering one (arch, shape, mesh)
# ---------------------------------------------------------------------------
def _compile_via_progcache(lowered, *key_bits):
    """``lowered.compile()`` routed through the active program cache
    (`repro.core.progcache`) when one is on: repeat dry-runs of the same
    (arch, shape, mesh, config) deserialize instead of recompiling — the
    analyses below (`memory_analysis`, `cost_analysis`, `as_text`) all work
    on deserialized executables.  With no cache active this IS
    ``lowered.compile()``.  Returns ``(compiled, status)``; status is None
    when uncached, else the cache outcome ("hit"/"miss")."""
    cache = progcache.active()
    if cache is None:
        return lowered.compile(), None
    return cache.load_or_compile(
        name="dryrun",
        key_parts=("dryrun", progcache.source_digest())
        + tuple(str(b) for b in key_bits),
        lower=lambda: lowered)


def lower_case(
    arch: str,
    shape_name: str,
    multi_pod: bool = False,
    compile_: bool = True,
    adam_dtype=jnp.bfloat16,
) -> Dict[str, Any]:
    cfg = get_config(arch)
    shape = SH.SHAPES[shape_name]
    ok, why = SH.shape_applicable(cfg, shape)
    if not ok:
        return {"arch": arch, "shape": shape_name, "status": "skipped", "reason": why}

    mesh = make_production_mesh(multi_pod=multi_pod)
    rules = make_rules(mesh, batch_size=shape.global_batch,
                       seq_parallel=wants_seq_parallel(cfg, mesh))
    t0 = time.time()

    pshapes = M.param_shapes(cfg, jnp.bfloat16)
    pspecs = param_specs(pshapes, cfg, rules)
    p_structs = jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        pshapes, pspecs)
    batch = SH.batch_struct(cfg, shape, rules)
    out: Dict[str, Any] = {"arch": arch, "shape": shape_name,
                           "mesh": "2x16x16" if multi_pod else "16x16"}

    with mesh:
        if shape.kind == "train":
            opt_shapes = jax.eval_shape(lambda p: adamw_init(p, adam_dtype), pshapes)
            opt_structs = jax.tree.map(
                lambda s, leafspec: jax.ShapeDtypeStruct(
                    s.shape, s.dtype, sharding=leafspec),
                opt_shapes,
                {"m": pspecs, "v": pspecs,
                 "step": NamedSharding(mesh, P())},
            )
            step = make_train_step(cfg, rules)
            # shardings are carried by the ShapeDtypeStructs themselves
            jitted = jax.jit(step, donate_argnums=(0, 1))
            lowered = jitted.lower(p_structs, opt_structs, batch)
        elif shape.kind == "prefill":
            cache = SH.cache_struct(cfg, shape, rules)
            step = make_prefill_step(cfg, rules)
            jitted = jax.jit(step, donate_argnums=(2,))
            lowered = jitted.lower(p_structs, batch, cache)
        else:  # decode
            cache = SH.cache_struct(cfg, shape, rules)
            step = make_serve_step(cfg, rules)
            jitted = jax.jit(step, donate_argnums=(2,))
            lowered = jitted.lower(p_structs, batch, cache, SH.pos_struct(rules))

        out["lower_s"] = round(time.time() - t0, 1)
        if not compile_:
            out["status"] = "lowered"
            return out
        t1 = time.time()
        from repro.models import layers as _layers
        # the cfg fingerprint keys depth-truncated variants
        # (`lower_case_depth` swaps the registry) apart from the full model
        compiled, pc_status = _compile_via_progcache(
            lowered, arch, shape_name, out["mesh"], shape.kind,
            jnp.dtype(adam_dtype).name, progcache.fingerprint(cfg),
            getattr(_layers, "UNROLL_FOR_COSTS", False))
        out["compile_s"] = round(time.time() - t1, 1)
        if pc_status is not None:
            out["progcache"] = pc_status

        mem = compiled.memory_analysis()
        out["memory"] = {
            "argument_size_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_size_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_size_bytes": getattr(mem, "temp_size_in_bytes", None),
            "generated_code_size_bytes": getattr(mem, "generated_code_size_in_bytes", None),
        }
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0]
        out["cost"] = {
            "flops": float(ca.get("flops", 0.0)),
            "bytes_accessed": float(ca.get("bytes accessed", ca.get("bytes_accessed", 0.0))),
        }
        hlo = compiled.as_text()
        out["collectives"] = collective_bytes(hlo)
        out["status"] = "ok"
    return out


def lower_case_depth(arch: str, shape_name: str, n_groups: int,
                     multi_pod: bool = False,
                     unroll: bool = True) -> Optional[Dict[str, Any]]:
    """lower_case with the layer stack truncated to n_groups groups (and the
    whisper encoder to n_groups layers) — used for cost extrapolation.

    unroll=True replaces every lax.scan with a Python loop during lowering:
    XLA's cost_analysis counts while-loop bodies ONCE (measured: flops flat
    in depth), so only fully-unrolled measurement programs report true
    costs.  Unrolling the full configs is intractable; unrolling G∈{1,2} is
    cheap, and cost(G) is affine in G.
    """
    import dataclasses as _dc
    from repro.configs import get_config as _gc
    from repro.models import layers as _L
    cfg = _gc(arch)
    short = _dc.replace(cfg, n_layers=len(cfg.group) * n_groups,
                        n_enc_layers=min(cfg.n_enc_layers, n_groups) if cfg.n_enc_layers else 0)
    # swap the registry lookup used by lower_case for this call
    g = globals()
    orig = g["get_config"]
    g["get_config"] = lambda name: short if name == arch else orig(name)
    _L.UNROLL_FOR_COSTS = unroll
    try:
        return lower_case(arch, shape_name, multi_pod=multi_pod)
    finally:
        g["get_config"] = orig
        _L.UNROLL_FOR_COSTS = False


def extrapolate_costs(arch: str, shape_name: str, full_groups: int,
                      enc_layers: int, multi_pod: bool = False) -> Optional[Dict[str, Any]]:
    """Corrected whole-model costs: XLA's cost_analysis counts while-loop
    bodies ONCE (not ×trip_count), so scan-stacked models under-report by
    ~n_groups.  cost(G) is affine in G ⇒ measure G=1,2 and extrapolate:
        total(G) = c1 + (G − 1) · (c2 − c1).
    (For whisper the encoder depth is scaled alongside, keeping affinity.)
    """
    r1 = lower_case_depth(arch, shape_name, 1, multi_pod)
    if r1.get("status") != "ok":
        return None
    r2 = lower_case_depth(arch, shape_name, 2, multi_pod)
    if r2.get("status") != "ok":
        return None

    def lin(f1, f2):
        return f1 + (full_groups - 1) * (f2 - f1)

    out = {
        "flops": lin(r1["cost"]["flops"], r2["cost"]["flops"]),
        "bytes_accessed": lin(r1["cost"]["bytes_accessed"],
                              r2["cost"]["bytes_accessed"]),
        "collective_bytes": lin(r1["collectives"]["total_bytes"],
                                r2["collectives"]["total_bytes"]),
        "method": "G1/G2 linear extrapolation",
    }
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", type=str, default=None)
    ap.add_argument("--shape", type=str, default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--no-compile", action="store_true")
    ap.add_argument("--extrapolate", action="store_true",
                    help="also compute loop-corrected costs via G=1/G=2 compiles")
    ap.add_argument("--out", type=str, default=None)
    ap.add_argument("--progcache-dir", type=str, default=None,
                    help="persist compiled dry-run programs here; repeat "
                         "runs deserialize instead of recompiling")
    args = ap.parse_args(argv)
    if args.progcache_dir:
        progcache.activate(args.progcache_dir)

    cases = []
    archs = ARCH_IDS if (args.all or not args.arch) else [args.arch]
    shapes = list(SH.SHAPES) if (args.all or not args.shape) else [args.shape]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    results = []
    for arch in archs:
        for shp in shapes:
            for mp in meshes:
                try:
                    r = lower_case(arch, shp, multi_pod=mp,
                                   compile_=not args.no_compile)
                    if args.extrapolate and r.get("status") == "ok":
                        cfg = get_config(arch)
                        corr = extrapolate_costs(arch, shp, cfg.n_groups,
                                                 cfg.n_enc_layers, mp)
                        if corr:
                            r["corrected"] = corr
                except Exception as e:
                    r = {"arch": arch, "shape": shp,
                         "mesh": "2x16x16" if mp else "16x16",
                         "status": "error", "error": f"{type(e).__name__}: {e}",
                         "trace": traceback.format_exc()[-2000:]}
                results.append(r)
                line = {k: v for k, v in r.items() if k not in ("trace",)}
                print(json.dumps(line), flush=True)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(results, f, indent=1)
    bad = [r for r in results if r["status"] == "error"]
    print(f"# {len(results)} cases, {len(bad)} errors", file=sys.stderr)
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
