"""The numbers that decide `correct`, each against its limit.

Training (the first steps of the timed train step, driven in set-up
through the window's own call and feed):
  loss_gap     largest |program loss - reference loss| / reference loss
               over the checked steps;
  grad_gap     the first step's score gradient as the optimizer holds it
               (momentum after one step), by the worst leaf: the gap of
               the two norms over the larger of the reference leaf's norm
               and the median leaf's;
  change_gap   the same measure for the change of every trained leaf
               (scores and float leaves) after the checked steps.  Leaves
               whose reference gradient is under a thousandth of the
               median leaf's are left out: they move by rounding alone.
The round (the timed round step on the state made from the seed):
  round_mismatch  parameters whose downlinked level is neither of the two
                  levels the stochastic 8-bit rounding may give to the
                  reference's theta, plus cohort rows that differ after
                  the broadcast;
  round_bits_gap  |measured uplink bits - the arithmetic coder's length
                  for the reference's masks|.

The reference runs after the window, once the program's state is freed.
"""
from __future__ import annotations

import sys

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip import harness as H
from benchmarks.chip.reference import common as R

F32 = jnp.float32


# ---------------------------------------------------------------------------
# What the program produced (read in set-up, kept on the host)
# ---------------------------------------------------------------------------


def _score_leaves(tree):
    """{path: leaf} of a program state tree's non-None leaves."""
    return {p: l for p, l in H.program_paths(tree) if l is not None}


@jax.jit
def _norms(tree):
    return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(F32))))
            for k, v in tree.items()}


def leaf_norms(tree):
    return {k: float(v) for k, v in
            _norms({"/".join(k): v for k, v in
                    _score_leaves(tree).items()}).items()}


def change_norms(cell, state, key):
    """Norms of every trained leaf's change from the state the seed made,
    made again by the same compiled generator."""
    vals, s0 = cell.leaf_maker(key)

    @jax.jit
    def run(scores, floats, vals, s0):
        out = {}
        for p, a in scores.items():
            out[p] = jnp.sqrt(jnp.sum(jnp.square(a - s0[p][None])))
        for p, a in floats.items():
            d = a.astype(F32) - vals[p].astype(F32)[None]
            out[p] = jnp.sqrt(jnp.sum(jnp.square(d)))
        return out

    got = run(_score_leaves(state["scores"]), _score_leaves(state["floats"]),
              {p: vals[p] for p in _score_leaves(state["floats"])}, s0)
    return {"/".join(p): float(v) for p, v in got.items()}


def capture_round(cell, new_scores):
    """The downlinked level of every parameter (from cohort 0's new
    scores) and the count of cohort entries that differ from cohort 0."""
    bits = cell.traffic["downlink_bits"]
    levels = (1 << bits) - 1

    @jax.jit
    def run(leaf):
        q = jnp.round(jax.nn.sigmoid(leaf[0]) * levels).astype(jnp.uint16)
        rows = jnp.sum((leaf[1:] != leaf[:1]).astype(jnp.int32))
        return q, rows

    levels_out, rows = {}, 0
    for p, leaf in _score_leaves(new_scores).items():
        q, r = run(leaf)
        levels_out[p] = np.asarray(q)
        rows += int(r)
    return levels_out, rows


# ---------------------------------------------------------------------------
# The reference
# ---------------------------------------------------------------------------


def reference_train(cell, seed, batches, act=F32, batch_rows=None,
                    steps=None):
    """Follow the program's first steps in plain float32.  `batches` are
    the (cohorts, batch, seq) token arrays the program was fed.
    Returns the per-step loss (mean over cohorts), the first step's
    score and float gradient norms, and the change norms after the
    last step."""
    fam, cfg, t = cell.family, cell.config, cell.traffic
    specs = fam.specs(cfg)
    mpaths = R.masked_paths(specs)
    C = t["cohorts"]
    vals, s0 = cell.leaf_maker(H.keys(seed)["params"])
    weights = {p: vals[p] for p in mpaths}
    f0 = {p: v for p, v in vals.items() if p not in weights}
    step = R.make_cohort_step(fam, cfg, specs, t, t["run_seed"],
                              act=act, batch_rows=batch_rows)
    copy = jax.jit(lambda t: jax.tree_util.tree_map(jnp.copy, t))
    st = [(copy(s0), copy(f0), {p: jnp.zeros_like(s0[p]) for p in mpaths})
          for _ in range(C)]
    losses, gsq = [], {}
    for i, tokens in enumerate(batches[:steps]):
        nll = []
        for c in range(C):
            s, f, m = st[c]
            s, f, m, l, gs, gf = step(s, f, m, weights,
                                      jnp.asarray(tokens[c]),
                                      jnp.int32(i), jnp.int32(c))
            st[c] = (s, f, m)
            nll.append(float(l))
            if i == 0:
                for p, g in list(gs.items()) + list(gf.items()):
                    gsq[p] = gsq.get(p, 0.0) + float(jnp.sum(
                        jnp.square(g.astype(F32))))
            del gs, gf
        losses.append(float(np.mean(nll)))
    delta = {}
    for p in list(s0) + list(f0):
        init = (s0 if p in s0 else f0)[p].astype(F32)
        tot = sum(float(jnp.sum(jnp.square(
            (st[c][0] if p in s0 else st[c][1])[p].astype(F32) - init)))
            for c in range(C))
        delta["/".join(p)] = tot ** 0.5
    grads = {"/".join(p): v ** 0.5 for p, v in gsq.items()}
    return {"losses": losses, "grads": grads, "delta": delta,
            "score_paths": ["/".join(p) for p in mpaths]}


def compare_train(prog, ref):
    """The three training numbers; `prog` has losses, grads (score
    leaves) and delta (all trained leaves)."""
    loss_gap = max(abs(a - b) / abs(b)
                   for a, b in zip(prog["losses"], ref["losses"]))
    sp = ref["score_paths"]
    grad_gap = R.worst_leaf_gap(prog["grads"],
                                {p: ref["grads"][p] for p in sp})
    med = float(np.median(list(ref["grads"].values())))
    moved = {p for p, g in ref["grads"].items() if g >= 1e-3 * med}
    change_gap = R.worst_leaf_gap(prog["delta"], ref["delta"], keep=moved)
    return {"loss_gap": loss_gap, "grad_gap": grad_gap,
            "change_gap": change_gap}


def print_leaves(prog, ref):
    """Each leaf's norms, program beside reference, on stderr."""
    for p in ref["delta"]:
        print(f"leaf {p}: change {prog['delta'][p]!r} (reference "
              f"{ref['delta'][p]!r}), first gradient "
              f"{prog['grads'].get(p, float('nan'))!r} (reference "
              f"{ref['grads'].get(p, float('nan'))!r})", file=sys.stderr)


def reference_round(cell, seed, sig_dtype=F32):
    """The reference's round on the state the seed made (step 0, one
    shard): {masked path: theta} and the coder's total uplink bits."""
    fam, cfg, t = cell.family, cell.config, cell.traffic
    specs = fam.specs(cfg)
    idx = R.leaf_index(specs)
    C = t["cohorts"]
    rs = t["run_seed"]
    s0 = cell.leaf_maker(H.keys(seed)["params"])[1]
    theta_of = jax.jit(R.round_theta, static_argnums=2)
    ones = np.zeros(C, np.int64)
    n_total, thetas = 0, {}
    for p in R.masked_paths(specs):
        seeds = jnp.stack([R.stream_seed(0, 0, idx[p], c, rs)
                           for c in range(C)])
        rows = jnp.broadcast_to(s0[p][None], (C,) + s0[p].shape)
        thetas[p], cnt = theta_of(rows, seeds, sig_dtype)
        ones += np.asarray(cnt, np.int64)
        n_total += int(np.prod(s0[p].shape))
    bits = sum(int(R.arithmetic_bits(jnp.int32(o), n_total)) for o in ones)
    return thetas, bits


def compare_round(cell, thetas, ref_bits, levels, rows, bits):
    """round_mismatch and round_bits_gap of a round's downlinked levels
    (`levels`, {path: array}), differing cohort entries (`rows`) and
    measured bits against the reference's thetas and bits."""
    nbits = cell.traffic["downlink_bits"]

    @jax.jit
    def bad(theta, q):
        lo, hi = R.downlink_support(theta, nbits)
        q = q.astype(F32)
        return jnp.sum(((q != lo) & (q != hi)).astype(jnp.int32))

    mismatch = rows + sum(int(bad(thetas[p], jnp.asarray(levels[p])))
                          for p in thetas)
    return {"round_mismatch": float(mismatch),
            "round_bits_gap": float(abs(bits - ref_bits))}
