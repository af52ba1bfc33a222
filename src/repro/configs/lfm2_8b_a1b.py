"""LFM2-8B-A1B — gated short convolutions and GQA over sparse experts
[hf:LiquidAI/LFM2-8B-A1B, config.json].

24L d_model=2048 vocab=65536, every block pre-norm (RMSNorm, eps 1e-5):
``h = x + mixer(RMSNorm(x))``, ``out = h + ffn(RMSNorm(h))``; a final
RMSNorm and a head tied to the (unscaled) embedding.

* conv (18 layers): ``[b, c, u] = split(h W_in, 3)`` (W_in d x 3d), ``v =
  b ⊙ u``, ``w_t = Σ_{i=0..2} K_i ⊙ v_{t-2+i}`` (depthwise causal, width 3,
  zero-padded, no bias), ``y = c ⊙ w``, output ``y W_out``
  (``models/conv.py``).  Decode keeps the last 2 rows of v.
* attn (6 layers, at 2, 6, 10, 14, 18, 21): q = RMSNorm_q(h W_q), k =
  RMSNorm_k(h W_k) over each head of 64; RoPE θ 1e6; causal GQA, 32 query
  heads over 8 KV heads, scale 1/8; W_o.  No biases.
* ffn of layers 0-1: SwiGLU of 7168.
* moe ffn (layers 2-23): s = σ(h W_r) in f32 (W_r d x 32); the selection is
  top-4 of s + b, b an expert bias that takes no gradient; each selected
  expert's weight is s_e / (Σ_selected s + 1e-6) · 1.0
  (``norm_topk_prob``, ``routed_scaling_factor`` 1); experts SwiGLU of
  1792; no shared expert, no auxiliary loss.
"""

from repro.models.config import LayerSpec, ModelConfig

LAYER_TYPES = ("conv", "conv", "full_attention", "conv", "conv", "conv",
               "full_attention", "conv", "conv", "conv", "full_attention",
               "conv", "conv", "conv", "full_attention", "conv", "conv",
               "conv", "full_attention", "conv", "conv", "full_attention",
               "conv", "conv")
N_DENSE = 2

CONFIG = ModelConfig(
    name="lfm2-8b-a1b",
    family="moe",
    n_layers=24,
    d_model=2048,
    n_heads=32,
    n_kv_heads=8,
    head_dim=64,
    d_ff=7168,
    vocab_size=65536,
    act="silu",
    norm_eps=1e-5,
    tie_embeddings=True,
    rope_theta=1_000_000.0,
    qk_norm=True,
    n_experts=32,
    router_experts=32,
    n_experts_per_tok=4,
    moe_d_ff=1792,
    router_aux_coef=0.0,
    router_score="sigmoid",
    expert_bias=True,
    conv_width=3,
    max_position=128_000,
    pattern=tuple(
        LayerSpec(kind="attn" if t == "full_attention" else "conv",
                  mlp="dense" if i < N_DENSE else "moe")
        for i, t in enumerate(LAYER_TYPES)),
)

# the benchmark's cut at smoke size: the leading dense layer once, then one
# period (attn, conv, conv, conv) of the MoE stack, 8 experts held
SMOKE = CONFIG.scaled(
    n_layers=5, d_model=64, n_heads=4, n_kv_heads=2, head_dim=16, d_ff=128,
    moe_d_ff=32, vocab_size=256, n_experts=8, router_experts=8,
    pattern=(LayerSpec("conv", "dense"), LayerSpec("attn", "moe"),
             LayerSpec("conv", "moe"), LayerSpec("conv", "moe"),
             LayerSpec("conv", "moe")),
)
