"""Operations and bytes of a dense GQA decoder (InternLM2 layout) under
masked training, counted from the configuration's widths.

Conventions: a multiply-add is 2 operations; elementwise work (norms,
rotary, softmax, the mask's hash and sigmoid) is not counted; bytes are
the least the work must move through HBM.  Weights are bfloat16 (2 B),
scores and their momentum float32 (4 B), activations bfloat16 (2 B).
"""
from __future__ import annotations

BF16, F32 = 2, 4


def dims(cfg):
    d = cfg["hidden_size"]
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg.get("head_dim") or d // nh
    return d, nh, nkv, hd


def masked_leaves(cfg):
    """(K, N) of every masked projection of one layer."""
    d, nh, nkv, hd = dims(cfg)
    F = cfg["intermediate_size"]
    return [(d, nh * hd),        # w_q
            (d, nkv * hd),       # w_k
            (d, nkv * hd),       # w_v
            (nh * hd, d),        # w_o
            (d, F),              # w_gate
            (d, F),              # w_up
            (F, d)]              # w_down


def masked_matmuls(cfg, traffic):
    """[(M, K, N, calls)] of the fused masked matmuls of one train step:
    every masked projection of every layer, once per cohort, on the
    cohort's batch x seq rows."""
    M = traffic["batch"] * traffic["seq"]
    calls = cfg["num_hidden_layers"] * traffic["cohorts"]
    return [(M, K, N, calls) for K, N in masked_leaves(cfg)]


def masked_params(cfg):
    """Masked parameters of one cohort (what a round samples)."""
    return cfg["num_hidden_layers"] * sum(K * N for K, N in
                                          masked_leaves(cfg))


def float_bytes(cfg):
    """Bytes of one cohort's float leaves: two bf16 tables (embedding,
    output head) and f32 norm scales (two per layer and a final one)."""
    d, V, L = cfg["hidden_size"], cfg["vocab_size"], cfg["num_hidden_layers"]
    return 2 * V * d * BF16 + (2 * L + 1) * d * F32


def model_flops_per_token(cfg, seq):
    """Forward plus backward operations per trained token, recompute not
    counted.  Backward is twice the forward for every matmul (the
    activation gradient, and the gradient of its weight: the score
    gradient for masked projections, the table gradient for the head)."""
    d, nh, nkv, hd = dims(cfg)
    L, V = cfg["num_hidden_layers"], cfg["vocab_size"]
    proj = sum(K * N for K, N in masked_leaves(cfg))   # per layer
    head = d * V                                       # untied head
    # causal attention: token t attends t + 1 keys, (S + 1) / 2 on
    # average; QK^T and PV each cost 2 * nh * hd per key
    attn_fwd = 2 * 2 * nh * hd * (seq + 1) / 2
    return 6 * (L * proj + head) + 3 * L * attn_fwd


def masked_leaf_sizes(cfg):
    """Parameters of each masked leaf (all layers of one projection)."""
    return [cfg["num_hidden_layers"] * K * N for K, N in masked_leaves(cfg)]
