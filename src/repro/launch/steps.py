"""Canonical lowered steps for the production mesh.

Federated mapping at pod scale (docs/DESIGN.md §2): a *cohort* (= FL client
site) is one pod (multi-pod mesh) or the whole pod (single-pod). Inside
a cohort, data-parallel slices share synchronized score updates (the
site's local cluster); ACROSS cohorts the ONLY traffic is the paper's
mask exchange at round boundaries — the slow inter-pod DCN link is
exactly the uplink the paper's 1-bit protocol compresses.

Lowered artifacts per training cell:
  * train_step  — one local mini-batch score update (no cross-pod comm)
  * round_step  — mask sample + (bitpacked) cross-pod aggregation
  * fedavg_step — float baseline: grads all-reduced across everything

Serving cells lower serve_step (one-token decode over a full KV cache).

State layout: scores/floats/opt carry a leading cohort axis C sharded
on "pod"; frozen weights have no cohort axis (same seed everywhere).

train_step runs the FUSED masked-execution path by default: the model
forward consumes `masking.MaskedLeaf` (w, s, seed) bundles and every
maskable leaf runs its fused kernel — `ops.masked_dense` for 2-D
projections, `ops.masked_dense_grouped` for stacked (E, K, N) MoE
expert weights, `ops.masked_conv1d` for depthwise conv kernels — so
the mask and the masked weights never exist in HBM on either pass,
for ANY maskable leaf shape (docs/DESIGN.md §3).
`REPRO_EFF_PATH=1` is the escape hatch: identical hash-stream masks,
but materialized through `masking.hash_effective` (the pre-fusion
reference semantics, for debugging/bisection).
"""
from __future__ import annotations

import dataclasses
import functools
import os
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.api import codecs as codecs_lib
from repro.api import payloads as plds
from repro.core import masking, regularizer, aggregation
from repro.core.masking import MaskedParams
from repro.kernels import ref as kref
from repro.launch import sharding as shd

Pytree = Any


def n_cohorts(mesh) -> int:
    return mesh.shape["pod"] if "pod" in mesh.axis_names else 1


@dataclasses.dataclass(frozen=True)
class StepConfig:
    lam: float = 1.0
    lr: float = 0.1
    float_lr: float = 0.01
    momentum: float = 0.9
    chunk_kv: Optional[int] = None   # chunked attention for long seq
    packed_masks: bool = True        # bitpacked cross-pod aggregation
    score_dtype: Any = jnp.float32
    microbatch: int = 1              # grad-accumulation chunks
    optimizer: str = "momentum"      # "momentum" | "adam" (scores)
    adam_eps: float = 1e-8
    downlink_bits: int = 0           # k-bit theta broadcast (0 = f32)
    seed: int = 17                   # run seed mixed into every mask
    #                                  stream (forward AND uplink) —
    #                                  plumbed from --seed in train.py
    mask_mode: str = "sample"        # "sample" (Bernoulli, fedpm*) |
    #                                  "threshold" (FedMask)
    tau: float = 0.5                 # threshold for mask_mode="threshold"


# sentinel "leaf index" for the downlink-quantizer key stream: far above
# any real leaf index, so `mask_stream_seed` cannot hand the quantizer a
# mask stream of the same (step, dev=0, cohort) coordinates
_DOWNLINK_STREAM_LEAF = 1 << 20


# ---------------------------------------------------------------------------
# State construction (shape-only friendly: works under jax.eval_shape)
# ---------------------------------------------------------------------------


def init_fed_state(key, api, spec: masking.MaskSpec, C: int,
                   score_dtype=jnp.float32, optimizer: str = "momentum"):
    params_like = api.init_params(key)
    mp = masking.init_masked(key, params_like, spec,
                             score_dtype=score_dtype)

    def rep(tree):  # add cohort axis
        return jax.tree_util.tree_map(
            lambda x: None if x is None else jnp.broadcast_to(
                x[None], (C,) + x.shape),
            tree, is_leaf=lambda x: x is None)

    scores = rep(mp.scores)
    zeros_like = lambda t: jax.tree_util.tree_map(
        lambda x: None if x is None else jnp.zeros_like(x), t,
        is_leaf=lambda x: x is None)
    state = {
        "scores": scores,
        "floats": rep(mp.floats),
        "weights": mp.weights,
        "opt_m": zeros_like(scores),
        "step": jnp.zeros((), jnp.int32),
    }
    if optimizer == "adam":
        state["opt_v"] = zeros_like(scores)
    return state


def fed_state_shardings(state_shapes, mesh):
    """Shardings for the federated state pytree (cohort axis -> pod)."""
    has_pod = "pod" in mesh.axis_names

    def score_like(tree):
        def one(path, leaf):
            if leaf is None:
                return None
            p = shd._path_str(path)
            # leading cohort axis (+ possibly a layer-stack axis after)
            sd = 1 + (0 if any(t in p.lower() for t in
                               ("embed", "final_norm", "lm_head",
                                "pos_embed")) else 1)
            sd = min(sd, max(len(leaf.shape) - 1, 0))
            ps = shd.param_spec(p, leaf.shape, mesh, scan_dims=sd)
            spec = list(ps) + [None] * (len(leaf.shape) - len(list(ps)))
            if has_pod:
                spec[0] = "pod"
            return jax.sharding.NamedSharding(
                mesh, jax.sharding.PartitionSpec(*spec))
        return jax.tree_util.tree_map_with_path(
            one, tree, is_leaf=lambda x: x is None)

    out = {
        "scores": score_like(state_shapes["scores"]),
        "floats": score_like(state_shapes["floats"]),
        "weights": shd.tree_param_shardings(state_shapes["weights"], mesh),
        "opt_m": score_like(state_shapes["opt_m"]),
        "step": shd.replicated(mesh),
    }
    if "opt_v" in state_shapes:
        out["opt_v"] = score_like(state_shapes["opt_v"])
    return out


# ---------------------------------------------------------------------------
# train_step: one local mini-batch update (no cross-pod traffic)
# ---------------------------------------------------------------------------


def _eff_path() -> bool:
    """REPRO_EFF_PATH=1 escape hatch (checked at trace time): train
    through materialized effective params (`masking.hash_effective`) —
    bit-identical hash-stream masks, pre-fusion memory behaviour."""
    return os.environ.get("REPRO_EFF_PATH", "") == "1"


def make_train_step(api, cfg: StepConfig, mesh=None, state_sh=None):
    """One local mini-batch score update on the fused masked-execution
    path: the forward consumes a `masked_forward_tree` whose maskable
    leaves run the fused kernels (dense / grouped-expert / conv) with
    scores as a first-class grad argument (STE custom-vjp), per-leaf
    seeds derived from
    (cfg.seed, step, leaf, cohort) by the SAME `mask_stream_seed`
    convention the round uplink samples with.

    With `mesh`/`state_sh` (a pod mesh whose other axes are 1, so each
    chip holds whole leaves), the step runs under shard_map: each chip
    trains its own cohorts with their global cohort numbers, and no
    traffic crosses chips but the reported loss's mean."""
    def cohort_loss(scores, floats, weights, batch, tick, cohort):
        mp = MaskedParams(weights, scores, floats)
        seed_fn = lambda i: masking.mask_stream_seed(
            tick, 0, i, cohort, run_seed=cfg.seed)
        build = (masking.hash_effective if _eff_path()
                 else masking.masked_forward_tree)
        params = build(mp, seed_fn, mode=cfg.mask_mode, tau=cfg.tau)
        out = api.forward(params, batch, chunk_kv=cfg.chunk_kv)
        with jax.named_scope("embed_head"):
            loss = api.loss(out, batch)
        with jax.named_scope("regularizer"):
            reg = regularizer.entropy_proxy(scores)
        return loss + cfg.lam * reg, (loss, reg)

    def train_step(state, batch):
        C = jax.tree_util.tree_leaves(state["scores"])[0].shape[0]
        first = (cohort_shard(mesh, C)[0] if mesh is not None
                 else jnp.int32(0))

        @jax.named_scope("optimizer")
        def update(scores, floats, opt_m, opt_v, gs, gf):
            """The score update (momentum, or Adam with `opt_v`) and the
            float leaves' SGD step."""
            if opt_v is not None:  # adam on scores
                b1, b2 = 0.9, 0.999
                new_m = jax.tree_util.tree_map(
                    lambda m, g: None if m is None else
                    (b1 * m + (1 - b1) * g).astype(m.dtype),
                    opt_m, gs, is_leaf=lambda x: x is None)
                new_v = jax.tree_util.tree_map(
                    lambda v, g: None if v is None else
                    (b2 * v + (1 - b2) * jnp.square(
                        g.astype(jnp.float32))).astype(v.dtype),
                    opt_v, gs, is_leaf=lambda x: x is None)
                t = (state["step"] + 1).astype(jnp.float32)
                bc1 = 1 - b1 ** t
                bc2 = 1 - b2 ** t
                scores = jax.tree_util.tree_map(
                    lambda s, m, v: None if s is None else
                    (s - cfg.lr * (m / bc1) / (jnp.sqrt(v / bc2)
                                               + cfg.adam_eps)
                     ).astype(s.dtype),
                    scores, new_m, new_v, is_leaf=lambda x: x is None)
            else:
                new_v = None
                new_m = jax.tree_util.tree_map(
                    lambda m, g: None if m is None else
                    (cfg.momentum * m + g).astype(m.dtype),
                    opt_m, gs, is_leaf=lambda x: x is None)
                scores = jax.tree_util.tree_map(
                    lambda s, m: None if s is None else
                    (s - cfg.lr * m).astype(s.dtype),
                    scores, new_m, is_leaf=lambda x: x is None)
            floats = jax.tree_util.tree_map(
                lambda f, g: None if f is None else
                (f - cfg.float_lr * g).astype(f.dtype),
                floats, gf, is_leaf=lambda x: x is None)
            return scores, floats, new_m, new_v

        def one(scores, floats, opt_m, opt_v, batch_c, idx):
            if cfg.microbatch > 1:
                M = cfg.microbatch
                mb = jax.tree_util.tree_map(
                    lambda b: b.reshape((M, b.shape[0] // M)
                                        + b.shape[1:]), batch_c)

                def acc(carry, xs):
                    gs_a, gf_a, loss_a = carry
                    b_i, t_i = xs
                    (tot, (l, r)), (g1, g2) = jax.value_and_grad(
                        cohort_loss, argnums=(0, 1), has_aux=True)(
                            scores, floats, state["weights"], b_i, t_i,
                            idx)
                    add = lambda a, g: None if a is None else a + g
                    gs_a = jax.tree_util.tree_map(
                        add, gs_a, g1, is_leaf=lambda x: x is None)
                    gf_a = jax.tree_util.tree_map(
                        add, gf_a, g2, is_leaf=lambda x: x is None)
                    return (gs_a, gf_a, loss_a + l), None

                zeros = lambda t: jax.tree_util.tree_map(
                    lambda x: None if x is None else
                    jnp.zeros(x.shape, jnp.float32), t,
                    is_leaf=lambda x: x is None)
                # one stream tick per microbatch so accumulation chunks
                # draw distinct masks
                ticks = state["step"] * M + jnp.arange(
                    M, dtype=jnp.int32)
                (gs, gf, loss), _ = jax.lax.scan(
                    acc, (zeros(scores), zeros(floats),
                          jnp.float32(0.0)), (mb, ticks))
                gs = jax.tree_util.tree_map(
                    lambda g: None if g is None else g / M, gs,
                    is_leaf=lambda x: x is None)
                gf = jax.tree_util.tree_map(
                    lambda g: None if g is None else g / M, gf,
                    is_leaf=lambda x: x is None)
                loss = loss / M
                reg = jnp.float32(0.0)
            else:
                (tot, (loss, reg)), (gs, gf) = jax.value_and_grad(
                    cohort_loss, argnums=(0, 1), has_aux=True)(
                        scores, floats, state["weights"], batch_c,
                        state["step"], idx)
            scores, floats, new_m, new_v = update(
                scores, floats, opt_m, opt_v, gs, gf)
            return scores, floats, new_m, new_v, loss

        has_v = "opt_v" in state
        opt_v_in = state.get("opt_v")
        if has_v:
            scores, floats, opt_m, opt_v, losses = jax.vmap(one)(
                state["scores"], state["floats"], state["opt_m"],
                opt_v_in, batch, first + jnp.arange(C))
        else:
            scores, floats, opt_m, opt_v, losses = jax.vmap(
                one, in_axes=(0, 0, 0, None, 0, 0))(
                state["scores"], state["floats"], state["opt_m"],
                None, batch, first + jnp.arange(C))
        new_state = dict(state, scores=scores, floats=floats, opt_m=opt_m,
                         step=state["step"] + 1)
        if has_v:
            new_state["opt_v"] = opt_v
        loss = jnp.mean(losses)
        if mesh is not None and "pod" in mesh.axis_names:
            loss = jax.lax.pmean(loss, "pod")
        return new_state, {"loss": loss}

    if mesh is None:
        return train_step
    assert all(mesh.shape[a] == 1 for a in mesh.axis_names if a != "pod"), \
        f"the train step holds whole leaves per chip: mesh {mesh.shape}"
    P = jax.sharding.PartitionSpec
    specs = _specs_of(state_sh)
    return jax.shard_map(train_step, mesh=mesh,
                         in_specs=(specs, P("pod")),
                         out_specs=(specs, {"loss": P()}),
                         check_vma=False)


# ---------------------------------------------------------------------------
# round_step: the paper's communication event (cross-pod mask exchange)
# ---------------------------------------------------------------------------


def _mask_stream_seeds(step, dev, leaf_idx: int, C: int,
                       run_seed=0, first=0) -> jax.Array:
    """Per-(run, round, shard, leaf, cohort) uint32 seeds for the
    counter-based mask sampler — one thin wrapper over the SHARED
    convention (`masking.mask_stream_seed`) the fused model forward
    derives its per-leaf seeds with, so a leaf's forward mask and its
    uplink `sample_and_pack` words come from one stream family.
    Cohorts are numbered globally from `first`; `dev` is the shard
    within a cohort."""
    return masking.mask_stream_seed(
        step, dev, leaf_idx,
        jnp.asarray(first, jnp.uint32) + jnp.arange(C, dtype=jnp.uint32),
        run_seed=run_seed)


def cohort_shard(mesh, cohorts_here: int):
    """(global number of the first of the `cohorts_here` cohorts this
    shard holds, the shard's index within its cohort) under shard_map
    over `mesh`: cohorts ride the "pod" axis, and the other axes divide
    one cohort's state.  So a round on a pod mesh draws each cohort's
    mask from the stream it draws without one."""
    shard = jnp.int32(0)
    for a in mesh.axis_names:
        if a != "pod":
            shard = shard * mesh.shape[a] + jax.lax.axis_index(a)
    pod = (jax.lax.axis_index("pod") if "pod" in mesh.axis_names
           else jnp.int32(0))
    return pod * cohorts_here, shard


def _specs_of(tree):
    return jax.tree_util.tree_map(
        lambda s: None if s is None else s.spec, tree,
        is_leaf=lambda x: x is None)


def make_round_step(api, cfg: StepConfig, mesh=None, state_sh=None,
                    codec=None):
    """Cross-pod mask exchange. When `mesh`/`state_sh` are given, the
    aggregation runs under shard_map with an EXPLICIT all_gather of the
    bit-packed uint32 words over the 'pod' axis — the wire carries
    exactly 1 bit/parameter/cohort (vs 16 for the bf16-psum baseline).
    Without a mesh (tests, 1-device), a plain jnp path is used.

    `codec` (name or `repro.api.codecs.Codec`, default the paper's
    arithmetic coder) meters the uplink: metrics carry ``bpp`` (eq. 13
    entropy bound), ``bpp_measured`` (the codec's pooled wire rate) and
    ``bits_measured`` / ``downlink_bits`` round totals for the
    CommLedger.  With ``cfg.downlink_bits > 0`` the post-round theta
    broadcast really goes through the stochastic k-bit quantizer
    (`aggregation.quantize_theta`) before scores are reset from it.
    """
    has_pod = mesh is not None and "pod" in mesh.axis_names
    if codec is None:
        codec = "arithmetic"
    if isinstance(codec, str):
        codec = codecs_lib.get_codec(codec)

    def _round_local(scores, floats, weights, opt_m, step, part=None):
        """Runs per-shard under shard_map (or globally w/o mesh).

        ``part`` is the round's participation vector (f32[C_global],
        1.0 = the cohort's uplink arrived, 0.0 = crashed/cut): the
        aggregation renormalizes the weighted mean over SURVIVORS
        (eq. 8 with dropped nodes renormalized out), and the metering
        only counts bits survivors actually put on the wire.  ``None``
        (a trace-time constant) keeps the original all-cohorts path
        bit-for-bit.

        Per-leaf uplink: the FUSED sample+pack kernel turns each
        cohort's score row straight into bit-packed uint32 words
        (scores -> hash -> Bernoulli -> words in one pass; the uint8
        mask never exists in HBM on the transport path), then the
        packed words ride `jax.lax.all_gather` over the 'pod' axis and
        reduce through `repro.api.payloads.mean_from_words` — the same
        transport code the host-sim round engine uses, so the two paths
        cannot drift.  The unpacked (bf16-psum) path samples the SAME
        counter-based hash streams in pure jnp (`kernels.ref`), so both
        paths see bit-identical masks.
        """
        pod_axis = "pod" if has_pod else None
        flat_s, tdef = jax.tree_util.tree_flatten(
            scores, is_leaf=lambda x: x is None)
        # survivor weights: normalized over the GLOBAL participation
        # vector; each shard also needs its local slice (its own
        # cohorts' alive flags) for float folds and metering
        Cl_loc = next((l.shape[0] for l in flat_s if l is not None), 1)
        if mesh is not None:
            # distinct hash stream per shard of a cohort (same seed
            # would give identical bits on every shard), cohorts
            # numbered globally
            first, dev = cohort_shard(mesh, Cl_loc)
        else:
            first, dev = 0, jnp.int32(0)

        if part is not None:
            wn_g = part / jnp.maximum(jnp.sum(part), 1.0)
            if pod_axis:
                off = jax.lax.axis_index(pod_axis) * Cl_loc
                alive_l = jax.lax.dynamic_slice(part, (off,), (Cl_loc,))
                wn_l = jax.lax.dynamic_slice(wn_g, (off,), (Cl_loc,))
            else:
                alive_l, wn_l = part, wn_g
        else:
            wn_g = alive_l = wn_l = None
        # metering accumulators: per-cohort one-counts via popcount of
        # the packed words (the uint8 masks where they exist anyway),
        # plus the pooled per-cohort streams for the codec meter
        words_exact = hasattr(codec, "measure_pooled_words")
        theta_flat = []
        ones_parts, word_parts, bit_parts = [], [], []
        n_pool, Cl_any = 0, 1
        for i, sl in enumerate(flat_s):
            if sl is None:
                theta_flat.append(None)
                continue
            Cl = Cl_any = sl.shape[0]
            body = sl.shape[1:]
            with jax.named_scope("uplink"):
                flat = sl.reshape(Cl, -1)
                n = flat.shape[1]
                seeds = _mask_stream_seeds(step, dev, i, Cl,
                                           run_seed=cfg.seed, first=first)
                if cfg.packed_masks:
                    words = aggregation.sample_and_pack_rows(
                        flat, seeds, use_kernel=True,
                        mode=cfg.mask_mode, tau=cfg.tau)   # (Cl, W) u32
                    ones_parts.append(jnp.sum(
                        jax.lax.population_count(words),
                        axis=1).astype(jnp.float32))
                    if words_exact:
                        word_parts.append(words)
                    else:  # codec needs gap structure, not just counts
                        bit_parts.append(jax.vmap(
                            lambda wd: aggregation.unpack_bits(wd, n)
                        )(words))
                else:
                    masks2 = (kref.threshold_rows(flat, cfg.tau)
                              if cfg.mask_mode == "threshold"
                              else kref.sample_rows(flat, seeds))
                    ones_parts.append(jnp.sum(
                        masks2.astype(jnp.float32), axis=1))
                    bit_parts.append(masks2)
            if cfg.packed_masks:
                words_all = words
                if pod_axis:
                    words_all = jax.lax.all_gather(words, pod_axis)
                    words_all = words_all.reshape(-1, words.shape[-1])
            with jax.named_scope("fold"):
                if cfg.packed_masks:
                    # wn_g rows follow the gather's pod-major cohort
                    # order, so the survivor-renormalized weighted mean
                    # drops in where the uniform mean was
                    theta = plds.mean_from_words(words_all, n,
                                                 weights=wn_g)
                elif part is None:
                    b = jnp.mean(masks2.astype(jnp.bfloat16), axis=0)
                    if pod_axis:
                        b = jax.lax.pmean(b, pod_axis)
                    theta = b.astype(jnp.float32)
                else:
                    b = jnp.tensordot(
                        wn_l, masks2.astype(jnp.float32), axes=(0, 0))
                    if pod_axis:
                        b = jax.lax.psum(b, pod_axis)
                    theta = b
                theta_flat.append(theta.reshape(body))
            n_pool += n
        theta = jax.tree_util.tree_unflatten(tdef, theta_flat)
        if cfg.downlink_bits:
            # the orphaned k-bit downlink, live: theta crosses the wire
            # stochastically quantized; the key derives from the run's
            # mask_stream_seed convention at the sentinel downlink slot
            # with dev=0 — every shard uses the same key, so cohorts
            # keep receiving identical broadcasts, and distinct
            # (run_seed, step) pairs quantize under distinct keys
            with jax.named_scope("downlink"):
                qkey = jax.random.PRNGKey(masking.mask_stream_seed(
                    step, 0, _DOWNLINK_STREAM_LEAF, 0, run_seed=cfg.seed))
                theta = aggregation.dequantize_theta(
                    aggregation.quantize_theta(theta, qkey,
                                               bits=cfg.downlink_bits),
                    bits=cfg.downlink_bits)
        with jax.named_scope("fold"):
            new_scores = jax.tree_util.tree_map(
                lambda t, s: None if t is None else jnp.broadcast_to(
                    masking.logit(t)[None], s.shape).astype(cfg.score_dtype),
                theta, scores, is_leaf=lambda x: x is None)
            if part is not None:
                # survivor-weighted float fold: dead cohorts' local floats
                # contribute zero weight, the psum renormalizes globally
                def _wavg(f):
                    if f is None:
                        return None
                    s = jnp.tensordot(wn_l, f.astype(jnp.float32),
                                      axes=(0, 0))
                    if has_pod:
                        s = jax.lax.psum(s, "pod")
                    return jnp.broadcast_to(s[None],
                                            f.shape).astype(f.dtype)
                new_floats = jax.tree_util.tree_map(
                    _wavg, floats, is_leaf=lambda x: x is None)
            elif has_pod:
                new_floats = jax.tree_util.tree_map(
                    lambda f: None if f is None else
                    (jax.lax.pmean(f.astype(jnp.float32), "pod")
                     ).astype(f.dtype),
                    floats, is_leaf=lambda x: x is None)
            else:
                new_floats = jax.tree_util.tree_map(
                    lambda f: None if f is None else jnp.broadcast_to(
                        jnp.mean(f.astype(jnp.float32), 0)[None],
                        f.shape).astype(f.dtype),
                    floats, is_leaf=lambda x: x is None)
            new_opt = jax.tree_util.tree_map(
                lambda m: None if m is None else jnp.zeros_like(m),
                opt_m, is_leaf=lambda x: x is None)
        # local bpp estimate (same value on every device up to shard
        # composition; cheap diagnostic) — the paper's eq. 13 meter,
        # computed from the popcounts so the packed path never
        # re-materializes the uint8 mask the fused kernel avoided
        with jax.named_scope("codec_meter"):
            if n_pool:
                ones_c = sum(ones_parts)                       # (Cl,)
                if part is None:
                    p1 = jnp.sum(ones_c) / jnp.float32(n_pool * Cl_any)
                else:  # survivors only: dead cohorts sent nothing
                    p1 = (jnp.sum(ones_c * alive_l)
                          / (jnp.float32(n_pool)
                             * jnp.maximum(jnp.sum(alive_l), 1.0)))
                bpp = regularizer.binary_entropy(p1)
            else:
                bpp = jnp.float32(0.0)
            # measured wire bits: pool every leaf's stream per cohort and
            # ask the codec — the same measure_* primitives the host-sim
            # engine meters payloads with.  Popcount-exact codecs (bitpack,
            # arithmetic) meter the packed words directly; others get the
            # unpacked pooled bits.  Each shard codes its own slice-stream;
            # the psum over EVERY mesh axis makes the returned value the
            # exact total of all shards' streams (and genuinely replicated,
            # as the out_spec declares).
            if word_parts:
                pooled = jnp.concatenate(word_parts, axis=1)
                per_cohort = jax.vmap(
                    lambda wr: codec.measure_pooled_words(wr, n_pool)
                )(pooled)
            elif bit_parts:
                pooled = jnp.concatenate(bit_parts, axis=1).astype(jnp.uint8)
                per_cohort = jax.vmap(codec.measure_pooled_bits)(pooled)
            else:
                per_cohort = jnp.zeros((1,), jnp.int32)
            per_cohort = per_cohort.astype(jnp.float32)
            if part is not None and per_cohort.shape[0] == Cl_loc:
                per_cohort = per_cohort * alive_l   # dead uplinks: 0 bits
            bits_total = jnp.sum(per_cohort)
            if mesh is not None:
                bits_total = jax.lax.psum(bits_total,
                                          tuple(mesh.axis_names))
        return new_scores, new_floats, new_opt, bpp, bits_total

    @jax.named_scope("fold")
    def _zero_v(st, out):
        if "opt_v" in st:
            out["opt_v"] = jax.tree_util.tree_map(
                lambda v: None if v is None else jnp.zeros_like(v),
                st["opt_v"], is_leaf=lambda x: x is None)
        return out

    def _comm_totals(state):
        """(cohorts, global mask params) from the static state shapes."""
        C, n = 1, 0
        for s in jax.tree_util.tree_leaves(
                state["scores"], is_leaf=lambda x: x is None):
            if s is None:
                continue
            C = s.shape[0]
            n += s.size // s.shape[0]
        return C, n

    def _comm_metrics(state, bpp, bits_total, n_alive=None):
        """``n_alive`` (traced survivor count) rescales the per-cohort
        denominators; None keeps the full-participation accounting."""
        C, n_glob = _comm_totals(state)
        dl_bpp = float(cfg.downlink_bits) if cfg.downlink_bits else 32.0
        eff = (jnp.float32(C) if n_alive is None
               else jnp.maximum(n_alive, 1.0))
        return {"bpp": bpp,
                "bpp_measured": bits_total / (jnp.float32(n_glob) * eff),
                "bits_measured": bits_total,
                "downlink_bpp": jnp.float32(dl_bpp),
                "downlink_bits": jnp.float32(dl_bpp * n_glob) * eff}

    def _as_part(participation):
        return (None if participation is None
                else jnp.asarray(participation).astype(jnp.float32))

    if mesh is None:
        def round_step(state, participation=None):
            part = _as_part(participation)
            sc, fl, om, bpp, bits_total = _round_local(
                state["scores"], state["floats"], state["weights"],
                state["opt_m"], state["step"], part)
            out = dict(state, scores=sc, floats=fl, opt_m=om,
                       step=state["step"] + 1)
            return _zero_v(state, out), _comm_metrics(
                state, bpp, bits_total,
                None if part is None else jnp.sum(part))
        return round_step

    in_specs = (_specs_of(state_sh["scores"]),
                _specs_of(state_sh["floats"]),
                _specs_of(state_sh["weights"]),
                _specs_of(state_sh["opt_m"]),
                jax.sharding.PartitionSpec())
    out_specs = (_specs_of(state_sh["scores"]),
                 _specs_of(state_sh["floats"]),
                 _specs_of(state_sh["opt_m"]),
                 jax.sharding.PartitionSpec(),
                 jax.sharding.PartitionSpec())
    mapped = jax.shard_map(_round_local, mesh=mesh, in_specs=in_specs,
                           out_specs=out_specs, check_vma=False)
    # participation variant: the vector is replicated (every shard
    # slices out its own cohorts); traced separately so the no-fault
    # path stays byte-identical to the original lowering
    mapped_part = jax.shard_map(
        lambda sc, fl, w, om, st, pt: _round_local(sc, fl, w, om, st,
                                                   pt),
        mesh=mesh, in_specs=in_specs + (jax.sharding.PartitionSpec(),),
        out_specs=out_specs, check_vma=False)

    def round_step(state, participation=None):
        part = _as_part(participation)
        if part is None:
            sc, fl, om, bpp, bits_total = mapped(
                state["scores"], state["floats"], state["weights"],
                state["opt_m"], state["step"])
            n_alive = None
        else:
            sc, fl, om, bpp, bits_total = mapped_part(
                state["scores"], state["floats"], state["weights"],
                state["opt_m"], state["step"], part)
            n_alive = jnp.sum(part)
        out = dict(state, scores=sc, floats=fl, opt_m=om,
                   step=state["step"] + 1)
        return _zero_v(state, out), _comm_metrics(state, bpp,
                                                  bits_total, n_alive)

    return round_step


# ---------------------------------------------------------------------------
# fedavg_step: the float reference (32-bit gradient all-reduce)
# ---------------------------------------------------------------------------


def make_fedavg_step(api, cfg: StepConfig):
    def loss_fn(params, batch):
        out = api.forward(params, batch, chunk_kv=cfg.chunk_kv)
        return api.loss(out, batch)

    def fedavg_step(state, batch):
        loss, grads = jax.value_and_grad(loss_fn)(state["params"], batch)
        opt_m = jax.tree_util.tree_map(
            lambda m, g: (cfg.momentum * m + g).astype(m.dtype),
            state["opt_m"], grads)
        params = jax.tree_util.tree_map(
            lambda p, m: (p - cfg.lr * m).astype(p.dtype),
            state["params"], opt_m)
        return dict(state, params=params, opt_m=opt_m,
                    step=state["step"] + 1), {"loss": loss}

    return fedavg_step


def init_fedavg_state(key, api):
    params = api.init_params(key)
    return {"params": params,
            "opt_m": jax.tree_util.tree_map(
                lambda x: jnp.zeros_like(x, jnp.float32), params),
            "step": jnp.zeros((), jnp.int32)}


def fedavg_state_shardings(state_shapes, mesh):
    return {"params": shd.tree_param_shardings(state_shapes["params"],
                                               mesh),
            "opt_m": shd.tree_param_shardings(state_shapes["opt_m"],
                                              mesh),
            "step": shd.replicated(mesh)}


# ---------------------------------------------------------------------------
# serve_step: one-token decode with full KV cache (deployed artifact)
# ---------------------------------------------------------------------------


def make_serve_step(api):
    def serve_step(params, cache, token, pos):
        return api.decode_step(params, cache, token, pos)
    return serve_step


def make_multi_serve_step(api):
    """Slot-major multi-tenant decode: one vmapped step over B batch
    slots, each carrying ITS OWN params tree (a gather over the
    freeze-cache's materialized trees), KV cache, current token, and
    position — the lockstep execution mode of
    `repro.runtime.serve_engine.ServeEngine`.

    Inputs are stacked with a leading slot axis: params/cache pytrees
    `(B, ...)`, token `(B, 1)` (inner per-slot batch of 1), pos `(B,)`
    — so slots at different sequence positions (prefill vs decode)
    advance in ONE dispatch.  Numerically equivalent to B independent
    `make_serve_step` calls but NOT bit-exact (batched-dot
    reassociation); the engine's default per-slot mode is the
    bit-identity contract (tests/test_serving.py).
    """
    def multi_serve_step(params, caches, tokens, poss):
        return jax.vmap(api.decode_step)(params, caches, tokens, poss)
    return multi_serve_step
