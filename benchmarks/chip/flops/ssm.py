"""Operations and bytes of a Mamba-2 language model under masked
training, counted from the configuration's widths.

Conventions as in `flops/dense.py`: a multiply-add is 2 operations;
elementwise work (norms, gates, softplus, exp of the decay, the mask's
hash) is not counted; bytes are the least the work must move through
HBM (bf16 weights and activations, f32 scores and momentum).
"""
from __future__ import annotations

BF16, F32 = 2, 4


def dims(cfg):
    d = cfg["d_model"]
    d_in = cfg["expand"] * d
    nh = d_in // cfg["headdim"]
    return d, d_in, nh, cfg["headdim"], cfg["d_state"], cfg["ngroups"]


def masked_leaves(cfg):
    """(K, N) of the masked projections of one layer (the conv kernel is
    masked too, but runs in its own kernel; see `conv_params`)."""
    d, d_in, nh, P, N, G = dims(cfg)
    return [(d, 2 * d_in + 2 * G * N + nh),   # w_in: z, x, B, C, dt
            (d_in, d)]                        # w_out


def conv_params(cfg):
    d, d_in, nh, P, N, G = dims(cfg)
    return cfg["d_conv"] * (d_in + 2 * G * N)


def masked_matmuls(cfg, traffic):
    M = traffic["batch"] * traffic["seq"]
    calls = cfg["n_layer"] * traffic["cohorts"]
    return [(M, K, N, calls) for K, N in masked_leaves(cfg)]


def masked_params(cfg):
    return cfg["n_layer"] * (sum(K * N for K, N in masked_leaves(cfg))
                             + conv_params(cfg))


def float_bytes(cfg):
    """One cohort's float leaves: the tied bf16 table, and per layer the
    f32 norm scale, gate-norm scale, A_log, D, dt bias and conv bias."""
    d, d_in, nh, P, N, G = dims(cfg)
    L, V = cfg["n_layer"], cfg["vocab_size_padded"]
    per_layer = d + d_in + 3 * nh + (d_in + 2 * G * N)
    return V * d * BF16 + (L * per_layer + d) * F32


def model_flops_per_token(cfg, seq):
    """Forward plus backward operations per trained token, recompute not
    counted (backward = twice forward for each term)."""
    d, d_in, nh, P, N, G = dims(cfg)
    L, V = cfg["n_layer"], cfg["vocab_size_padded"]
    proj = sum(K * N for K, N in masked_leaves(cfg))
    conv = cfg["d_conv"] * (d_in + 2 * G * N)   # depthwise taps
    # the state-space recurrence, per head: the state takes dt*x (x) B
    # (P*N multiply-adds) and the output contracts C with it (P*N)
    ssd = nh * 2 * (2 * P * N)
    head = d * V                                # tied output head
    return 6 * (L * (proj + conv) + head) + 3 * L * ssd


def masked_leaf_sizes(cfg):
    """Parameters of each masked leaf (all layers of one projection, and
    the conv kernels)."""
    L = cfg["n_layer"]
    return [L * K * N for K, N in masked_leaves(cfg)] + [L * conv_params(cfg)]
