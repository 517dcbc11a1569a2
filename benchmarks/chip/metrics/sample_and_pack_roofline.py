"""Share of the fused sample-and-pack kernel's roofline (its call is
named `sample_and_pack`; kernels/masked_matmul.py): the least bytes it must move, every cohort's
f32 scores read and its packed uint32 words written, over peak HBM
bandwidth, per round of the window, over the kernel's device time.  The
kernel's arithmetic (hash, sigmoid, compare, pack) is not counted: the
bound is the bytes."""
F32, WORD = 4, 4


def read(r):
    secs = r.trace.kernel_s(("sample_and_pack",))
    rounds = r.window.get("rounds")
    if secs <= 0 or not rounds:
        return None
    C = r.traffic["cohorts"]
    nbytes = sum(C * (n * F32 + -(-n // 32) * WORD)
                 for n in r.flops.masked_leaf_sizes(r.config))
    return 100.0 * rounds * nbytes / r.peaks["hbm_bytes_per_s"] / secs
