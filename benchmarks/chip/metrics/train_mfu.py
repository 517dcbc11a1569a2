"""Model FLOP utilization of the training job: the model's forward and
backward operations per token (`flops/<family>.py`, recompute not
counted) times the window's trained tokens per second, over the chips'
peak bf16 FLOP/s.  Rounds in the window count as time, not work."""


def read(r):
    w = r.window
    if not w.get("tokens"):
        return None
    per_token = r.flops.model_flops_per_token(r.config, r.traffic["seq"])
    chips = r.cell.chips
    return (100.0 * per_token * w["tokens"] / w["window_s"]
            / (chips * r.peaks["bf16_flops_per_s"]))
