"""Share of the masked-matmul kernels' roofline (kernels/masked_matmul.py):
the least time the window's fused masked matmuls could take on this chip
(for each call the larger of its operations over peak bf16 FLOP/s and its
least bytes over peak HBM bandwidth), over the device time of the
forward (`masked_matmul`), activation-gradient (`masked_matmul_dx`) and
score-gradient (`masked_matmul_ds`) kernel calls in the trace.

Per call on (M, K) x (K, N): 2*M*K*N operations each; least bytes read
and written: forward x, w, s in and y out; dx g, w, s in and dx out;
ds x, g, w, s in and ds out (bf16 activations and weights, f32 scores).
"""
KERNELS = ("masked_matmul", "masked_matmul_dx", "masked_matmul_ds")
BF16, F32 = 2, 4


def least_seconds(matmuls, peaks):
    flops, bw = peaks["bf16_flops_per_s"], peaks["hbm_bytes_per_s"]
    tot = 0.0
    for M, K, N, calls in matmuls:
        ops = 2.0 * M * K * N
        fwd = (M * K + K * N + M * N) * BF16 + K * N * F32
        dx = (M * N + K * N + M * K) * BF16 + K * N * F32
        ds = (M * K + M * N + K * N) * BF16 + 2 * K * N * F32
        tot += calls * sum(max(ops / flops, b / bw) for b in (fwd, dx, ds))
    return tot


def read(r):
    secs = r.trace.kernel_s(KERNELS)
    if secs <= 0 or not r.window.get("steps"):
        return None
    per_step = least_seconds(r.flops.masked_matmuls(r.config, r.traffic),
                             r.peaks)
    return 100.0 * r.window["steps"] * per_step / secs
