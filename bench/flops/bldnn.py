"""Floating-point operations one round of frozen-trunk BL-DNN fine-tuning of
the Granite-4.0-H-Small cut needs, counted from the shapes and from the
expert layers' own counter: what the algorithm requires, not what XLA
emits (no rematerialization, no expert computed on a token that did not
pick it).  A multiply-add counts as 2.

Per client and token the forward pass takes, per Mamba-2 layer, the in/out
projections, the width-4 conv and the recurrence (5·H·hd·N: decay, outer
product and add, readout); per attention layer the q/k/v/o projections and
the causal score and value products; per layer the router and the shared
expert; the head (the tied unembedding).  Each token–expert pair routed to
a held expert adds that expert's SwiGLU (the serve records'
``moe_assignments_held``, summed over layers and clients).  The backward
pass runs from the loss to the federated attention layer: a frozen
weight's product needs one more (the input's gradient), the federated
projections one more (their weight gradient), the score products two.
Pairs are taken to be spread evenly over the layers.  Rotations:
``Uᵀ g V`` per client and leaf, and ``U c Vᵀ`` once.  The Top-K
selections and the server's elementwise step are not counted."""
from __future__ import annotations

import numpy as np

#: published widths (config.json of ibm-granite/granite-4.0-h-small)
GRANITE = {"d_model": 4096, "n_heads": 32, "n_kv_heads": 8, "head_dim": 128,
           "moe.n_experts": 72, "moe.top_k": 10, "moe.d_expert": 768, "moe.d_shared": 1536,
           "ssm.d_state": 128, "ssm.head_dim": 64, "expand": 2, "conv_width": 4}
PATTERN = "MMMMMAMMMM"
ATTN = 5


def widths(problem: dict) -> dict:
    c = dict(GRANITE)
    c.update(dict(problem.get("overrides", ())))
    c["d_inner"] = c["expand"] * c["d_model"]
    c["ssm_heads"] = c["d_inner"] // c["ssm.head_dim"]
    return c


def terms(problem: dict, assigned: float) -> dict:
    """FLOPs of a round by part; ``assigned``: token–expert pairs computed
    in a round (all clients, all layers)."""
    c = widths(problem)
    D, S, n, V = c["d_model"], problem["seq"], problem["n_clients"], problem["vocab"]
    G = problem["n_layers"] // len(PATTERN)
    di, N, H = c["d_inner"], c["ssm.d_state"], c["ssm_heads"]
    nh, nkv, hd = c["n_heads"], c["n_kv_heads"], c["head_dim"]
    proj = 2 * D * (nh + 2 * nkv) * hd + 2 * nh * hd * D
    scores = 2 * nh * hd * (S + 1)          # QKᵀ and PV over the causal half
    mamba = (2 * D * (2 * di + 2 * N + H) + 2 * di * D
             + 2 * c["conv_width"] * (di + 2 * N) + 5 * H * c["ssm.head_dim"] * N)
    ffn = 2 * D * c["moe.n_experts"] + 6 * D * c["moe.d_shared"]
    expert = 6 * D * c["moe.d_expert"]
    head = 2 * D * V
    layers = G * len(PATTERN)
    after = layers - ATTN                   # layers from the federated one on
    tokens = n * S
    fwd = (tokens * (G * (PATTERN.count("M") * mamba + PATTERN.count("A") * (proj + scores))
                     + layers * ffn + head) + assigned * expert)
    bwd = (tokens * ((after - G) * mamba + after * ffn + head + G * (proj + 2 * scores))
           + assigned * expert * after / layers)
    rows = {"wq": (D, nh * hd), "wk": (D, nkv * hd), "wv": (D, nkv * hd), "wo": (nh * hd, D)}
    rotate = G * sum(2 * r * r * k + 2 * r * k * k for r, k in rows.values())
    return {"oracle_forward": float(fwd), "oracle_backward": float(bwd),
            "basis": float((n + 1) * rotate)}


def _assigned(records) -> float | None:
    got = [r.get("meta", {}).get("moe_assignments_held") for r in records]
    got = [g for g in got if g is not None]
    return float(np.mean(got)) if got else None


def per_round(problem: dict, cell: dict, records) -> float | None:
    """FLOPs of an average round; None where no serve record counts the
    expert pairs."""
    assigned = _assigned(records)
    if assigned is None:
        return None
    return float(sum(terms(problem, assigned).values()))


def oracle_per_round(records) -> float | None:
    """FLOPs of a round's client oracle, forward and backward, from the
    newest record's problem and the records' counter."""
    assigned = _assigned(records)
    if assigned is None or not records:
        return None
    t = terms(records[-1]["config"]["problem"], assigned)
    return t["oracle_forward"] + t["oracle_backward"]
