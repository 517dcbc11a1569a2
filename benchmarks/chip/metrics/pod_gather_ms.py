"""Device time per round of the cross-pod round's all-gather of the
packed mask words over the "pod" axis (launch/steps.make_round_step),
mean over the chips: every device op whose name is an all-gather, the
start and done halves of an asynchronous one included, summed over the
window's trace, over the chips and the rounds."""
import re

GATHER = re.compile(r"^all-gather(-start|-done)?$")


def read(r):
    t = r.trace
    rounds = r.window.get("rounds")
    names = [b for b in t.op_s if GATHER.match(b)]
    if not rounds or not names or not t.devices:
        return None
    return 1e3 * sum(t.op_s[b] for b in names) / t.devices / rounds
