"""The whole round's share of the chip's roofline (launch/steps
.make_round_step): the least time its work could take over the measured
time of a round (window / rounds).  The least time is the larger of its
operations over peak FLOP/s and its bytes over peak HBM bandwidth; the
round's arithmetic is elementwise and not counted, so the bytes bound
it.  Least bytes per round: every cohort's f32 scores read and written
back from the folded theta, its momentum written as zeros, and its float
leaves read and written back as their mean (`flops/<family>.py`),
whatever implements it."""
F32 = 4


def read(r):
    rounds = r.window.get("rounds")
    if not rounds:
        return None
    C = r.traffic["cohorts"]
    n = sum(r.flops.masked_leaf_sizes(r.config))
    nbytes = C * n * 3 * F32 + 2 * C * r.flops.float_bytes(r.config)
    least = nbytes / (r.peaks["hbm_bytes_per_s"] * r.cell.chips)
    return 100.0 * least / (r.window["window_s"] / rounds)
