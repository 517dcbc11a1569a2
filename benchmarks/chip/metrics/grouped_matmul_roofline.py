"""Share of the grouped masked-matmul kernels' roofline
(kernels/masked_matmul.py, the MoE experts held on the chip): the least
time the window's grouped calls could take on this chip over the device
time of the forward (`masked_matmul_grouped`), activation-gradient
(`masked_matmul_grouped_dx`) and score-gradient
(`masked_matmul_grouped_ds`) kernel calls in the trace.

The least time of a call is the larger of its operations over peak
bf16 FLOP/s and its least bytes over peak HBM bandwidth, summed over
the held experts it covers (`flops/<family>.grouped_matmuls`: per held
expert (rows, K, N, calls)).  Each held expert's `w` (bf16) and `s`
(f32) count once per call, plus its rows in and out, as the dense
kernels' reader counts them.  The rows are the routed mean, top_k /
router experts of the step's tokens: the routing of a run decides the
rows each expert really gets, and the kernels' padding of each group
to whole row tiles is not work.
"""
from benchmarks.chip.metrics.masked_matmul_roofline import least_seconds

KERNELS = ("masked_matmul_grouped", "masked_matmul_grouped_dx",
           "masked_matmul_grouped_ds")


def read(r):
    secs = r.trace.kernel_s(KERNELS)
    if secs <= 0 or not r.window.get("steps"):
        return None
    per_step = least_seconds(r.flops.grouped_matmuls(r.config, r.traffic),
                             r.peaks)
    return 100.0 * r.window["steps"] * per_step / secs
