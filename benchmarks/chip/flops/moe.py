"""Operations and bytes of a DeepSeek-V2 decoder holding one chip's
share of its experts (MLA, a dense first layer, MoE layers with shared
experts), under masked training, counted from the configuration's
widths.

Conventions as in `flops/dense.py`: a multiply-add is 2 operations;
elementwise work (norms, rotary, softmax, routing, the mask's hash and
sigmoid) is not counted; bytes are the least the work must move through
HBM; weights and activations bfloat16 (2 B), scores float32 (4 B).  The
routed experts held here see the routed mean of rows: top_k / router
experts of the step's tokens each.
"""
from __future__ import annotations

BF16, F32 = 2, 4


def dims(cfg):
    """(d, heads, nope, rope, v, kv_rank, router width, held)."""
    return (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
            cfg["v_head_dim"], cfg["kv_lora_rank"], cfg["router_experts"],
            cfg["n_routed_experts"])


def layer_counts(cfg):
    n_dense = cfg["first_k_dense_replace"]
    return n_dense, cfg["num_hidden_layers"] - n_dense


def attn_leaves(cfg):
    """(K, N) of the masked MLA projections of one layer."""
    d, nh, nope, rope, vd, r, _, _ = dims(cfg)
    return [(d, nh * (nope + rope)),     # w_q
            (d, r + rope),               # w_dkv
            (r, nh * nope),              # w_uk
            (r, nh * vd),                # w_uv
            (nh * vd, d)]                # w_o


def swiglu_leaves(d, F):
    return [(d, F), (d, F), (F, d)]      # w_gate, w_up, w_down


def dense_leaves(cfg):
    """[(K, N, layers)] of every masked projection the dense kernels
    run: MLA in every layer, the dense MLP in the first layers, the
    shared experts (one SwiGLU of n_shared x the expert width) in the
    MoE layers."""
    d = cfg["hidden_size"]
    Ld, Lm = layer_counts(cfg)
    Fs = cfg["moe_intermediate_size"] * cfg["n_shared_experts"]
    return ([(K, N, Ld + Lm) for K, N in attn_leaves(cfg)]
            + [(K, N, Ld) for K, N in
               swiglu_leaves(d, cfg["intermediate_size"])]
            + [(K, N, Lm) for K, N in swiglu_leaves(d, Fs)])


def expert_leaves(cfg):
    """(K, N) of one routed expert's projections."""
    return swiglu_leaves(cfg["hidden_size"], cfg["moe_intermediate_size"])


def routed_rows(cfg, tokens):
    """Mean rows one routed expert sees among `tokens`."""
    _, _, _, _, _, _, E, _ = dims(cfg)
    return tokens * cfg["num_experts_per_tok"] / E


def masked_matmuls(cfg, traffic):
    """[(M, K, N, calls)] of the fused dense masked matmuls of one train
    step, once per cohort on its batch x seq rows."""
    M = traffic["batch"] * traffic["seq"]
    return [(M, K, N, L * traffic["cohorts"])
            for K, N, L in dense_leaves(cfg)]


def grouped_matmuls(cfg, traffic):
    """[(rows, K, N, calls)] of the grouped kernels of one train step,
    per held expert: the routed mean of rows, each held expert of each
    MoE layer once per cohort."""
    _, _, _, _, _, _, _, Eh = dims(cfg)
    _, Lm = layer_counts(cfg)
    rows = routed_rows(cfg, traffic["batch"] * traffic["seq"])
    return [(rows, K, N, Lm * Eh * traffic["cohorts"])
            for K, N in expert_leaves(cfg)]


def masked_leaf_sizes(cfg):
    """Parameters of each masked leaf (all its layers and experts)."""
    _, _, _, _, _, _, _, Eh = dims(cfg)
    _, Lm = layer_counts(cfg)
    return ([L * K * N for K, N, L in dense_leaves(cfg)]
            + [Lm * Eh * K * N for K, N in expert_leaves(cfg)])


def masked_params(cfg):
    """Masked parameters of one cohort (what a round samples)."""
    return sum(masked_leaf_sizes(cfg))


def float_bytes(cfg):
    """Bytes of one cohort's float leaves: the two bf16 tables over the
    vocabulary slice, and f32 norm scales (two per layer, the latent
    KV norm per layer, a final one) and routers."""
    d, _, _, _, _, r, E, _ = dims(cfg)
    L, V = cfg["num_hidden_layers"], cfg["vocab_size"]
    _, Lm = layer_counts(cfg)
    return 2 * V * d * BF16 + ((2 * L + 1) * d + L * r + Lm * d * E) * F32


def model_flops_per_token(cfg, seq):
    """Forward plus backward operations per trained token, recompute not
    counted: every projection, the router and the head at 6 operations
    a weight (forward, activation and weight gradients), the held
    experts at the routed mean (top_k x held / router experts
    evaluations a token), and causal MLA attention over a (seq + 1) / 2
    key mean, QK^T at nope + rope and PV at v per head."""
    d, nh, nope, rope, vd, _, E, Eh = dims(cfg)
    L, V = cfg["num_hidden_layers"], cfg["vocab_size"]
    _, Lm = layer_counts(cfg)
    dense = sum(L_ * K * N for K, N, L_ in dense_leaves(cfg))
    router = Lm * d * E
    held = Lm * cfg["num_experts_per_tok"] * Eh / E * sum(
        K * N for K, N in expert_leaves(cfg))
    head = d * V
    attn_fwd = 2 * nh * (nope + rope + vd) * (seq + 1) / 2
    return 6 * (dense + router + held + head) + 3 * L * attn_fwd
