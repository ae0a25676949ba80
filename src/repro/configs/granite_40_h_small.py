"""IBM Granite 4.0-H Small (32B-A9B) — hybrid Mamba-2 / NoPE GQA with a
72-expert MoE in every layer, at the published widths of
https://huggingface.co/ibm-granite/granite-4.0-h-small/blob/main/config.json
(HF ``granitemoehybrid``).

40 layers in periods of 10 (``layer_types``: attention at offsets 5, 15, 25,
35, Mamba-2 elsewhere), d_model 4096, tied embeddings over 100,352 ids.
With h the residual stream (HF ``GraniteMoeHybridDecoderLayer``):

    h₀ = 12 · embed(ids)                                  embedding_multiplier
    h ← h + 0.22 · mixer(rmsnorm(h))                      residual_multiplier
    h ← h + 0.22 · (moe(rmsnorm(h)) + shared(rmsnorm(h)))
    logits = rmsnorm(h) · embedᵀ / 16                     logits_scaling

* mixer, attention: 32 query and 8 KV heads of 128, no bias, no positional
  encoding (``position_embedding_type: nope``), scores × 1/128
  (``attention_multiplier``) in place of 1/√128;
* mixer, Mamba-2: 128 heads × 64, d_state 128, one B/C group, causal
  depthwise conv of width 4 with a bias (``mamba_conv_bias``), chunk 256,
  no projection bias, gated RMSNorm before ``out_proj``;
* moe: router 4096 → 72 without bias; each token takes its top-10 logits and
  softmaxes over those ten (``GraniteMoeTopKGating``); experts are SwiGLU of
  width 768 (``intermediate_size``); no token is dropped;
* shared: one always-on SwiGLU expert of width 1536
  (``shared_intermediate_size``);
* RMSNorm eps 1e-5 everywhere.

Points taken on inference, not stated by config.json: that
``intermediate_size`` is the width of one routed expert (the catalog's
note); that the gated RMSNorm of the Mamba mixer normalizes over the whole
inner width (one group); and the init of random weights, which the published
checkpoint replaces (this repo draws weights from a seed).
"""
from repro.models.config import LayerSpec, ModelConfig, MoEConfig, SSMConfig

_M = LayerSpec(mixer="mamba", ffn="moe")
_A = LayerSpec(mixer="attn", ffn="moe")

CONFIG = ModelConfig(
    name="granite-4.0-h-small",
    n_layers=40,
    d_model=4096,
    n_heads=32,
    n_kv_heads=8,
    head_dim=128,
    d_ff=768,
    vocab_size=100_352,
    group=(_M, _M, _M, _M, _M, _A, _M, _M, _M, _M),
    moe=MoEConfig(n_experts=72, top_k=10, d_expert=768, d_shared=1536,
                  dropless=True),
    ssm=SSMConfig(d_state=128, head_dim=64, expand=2, conv_width=4,
                  chunk=256, conv_bias=True),
    tie_embeddings=True,
    rope=False,
    embedding_multiplier=12.0,
    residual_multiplier=0.22,
    attention_multiplier=0.0078125,
    logits_scaling=16.0,
    norm_eps=1e-5,
    subquadratic=True,
    max_seq=131_072,
)
