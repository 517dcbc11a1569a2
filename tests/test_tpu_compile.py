"""Compile the main path for a TPU v5e that is described, not attached.

The TPU compiler is installed with jax, so Mosaic refuses here what the
chip would refuse (casts it lacks, block shapes off the tiling, layouts
XLA and Mosaic disagree on, programs that do not fit the device) at no
chip time.  Nothing runs: these tests only lower and compile, at the
widths of internlm2-1.8b (d_model 2048, d_ff 8192, vocab 92544), and
check that each program holds its Pallas kernel (`tpu_custom_call`).

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker
imports this file.
"""
import pathlib
import re
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import bitpack as _bp
from repro.kernels import masked_matmul as _mm
from repro.kernels import ops

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from benchmarks.chip import scope_reduce as SR  # noqa: E402
from benchmarks.chip import trace_reduce as TR  # noqa: E402

M = 1024                       # batch 2 x seq 512
D, F, V = 2048, 8192, 92544    # internlm2-1.8b widths
EH, DE, FE = 8, 2048, 1408     # deepseek-v2-lite experts held, widths
HBM_BYTES = 16 * 2**30         # one v5e


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to rehearse
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip can be written to the persistent
    # cache but not read back without the chip: keep the cache out
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile()


def _dense_blocks(K, N, kernel):
    """The padding and blocks `ops.masked_dense` launches `kernel` with
    at M tokens: the forward and dx from `ops.dense_plan`, ds at
    bm=128."""
    Kp, Np = ops._round_up(K, 128), ops._round_up(N, 128)
    plan = ops.dense_plan(M, K, N)
    kw = dict(bm=plan.bm, bn=plan.bn, bk=plan.bk, interpret=False)
    if kernel == "ds":
        return Kp, Np, dict(kw, bm=128)
    return Kp, Np, dict(kw, n_logical=N)


def _grouped_blocks(K, N):
    """The padding and blocks `ops.masked_dense_grouped` launches with
    at the deepseek-v2-lite share cell: seq 8192, top-6 of 64, 8 of
    the experts held."""
    plan = ops.grouped_plan(8192 * 6, K, N, 8, 8192 * 6 / 64)
    Kp, Np = ops._round_up(K, 128), ops._round_up(N, 128)
    return Kp, Np, plan, dict(bm=plan.bm, bn=plan.bn, bk=plan.bk,
                              n_logical=N, interpret=False)


_LEAVES = {"up": (D, F), "down": (F, D), "head": (D, V)}
bf, f32, u32, i32 = jnp.bfloat16, jnp.float32, jnp.uint32, jnp.int32


@pytest.mark.parametrize("leaf", sorted(_LEAVES))
@pytest.mark.parametrize("kernel", ["fwd", "dx", "ds"])
def test_masked_matmul_compiles(one_chip, kernel, leaf):
    K, N = _LEAVES[leaf]
    Kp, Np, kw = _dense_blocks(K, N, kernel)
    w = [((Kp, Np), bf), ((Kp, Np), f32)]
    if kernel == "fwd":
        c = _compile(lambda x, w, s, sd, o: _mm.masked_matmul(
            x, w, s, sd, o, **kw), one_chip, ((M, Kp), bf), *w,
            ((), u32), ((), u32))
    elif kernel == "dx":
        c = _compile(lambda g, w, s, sd, o: _mm.masked_matmul_dx(
            g, w, s, sd, o, **kw), one_chip, ((M, Np), bf), *w,
            ((), u32), ((), u32))
    else:
        c = _compile(lambda x, g, w, s: _mm.masked_matmul_ds(
            x, g, w, s, **kw), one_chip, ((M, Kp), bf), ((M, Np), bf),
            *w)
    assert "tpu_custom_call" in c.as_text()


@pytest.mark.parametrize("mode", ["sample", "threshold"])
def test_sample_and_pack_compiles(one_chip, mode):
    c = _compile(lambda s, sd: _mm.sample_and_pack(
        s, sd, mode=mode, interpret=False), one_chip,
        ((2, D * F), f32), ((2,), u32))
    assert "tpu_custom_call" in c.as_text()


@pytest.mark.parametrize("kernel", ["pack", "unpack"])
def test_bitpack_compiles(one_chip, kernel):
    if kernel == "pack":
        c = _compile(lambda m: _bp.pack_bits(m, interpret=False),
                     one_chip, ((D * F,), jnp.uint8))
    else:
        c = _compile(lambda w: _bp.unpack_bits(w, D * F, interpret=False),
                     one_chip, ((D * F // 32,), u32))
    assert "tpu_custom_call" in c.as_text()


@pytest.mark.parametrize("kernel", ["fwd", "dx", "ds"])
@pytest.mark.parametrize("proj", ["up", "down"])
def test_grouped_compiles(one_chip, kernel, proj):
    """One deepseek-v2-lite expert projection of the share cell, 8 held
    experts x (2048 -> 1408) or (1408 -> 2048), over the routed rows'
    static buffer."""
    K, N = (DE, FE) if proj == "up" else (FE, DE)
    Kp, Np, plan, kw = _grouped_blocks(K, N)
    R = plan.tiles * plan.bm
    w = [((EH, Kp, Np), bf), ((EH, Kp, Np), f32)]
    lay = [((plan.tiles,), i32), ((1,), i32)]
    if kernel == "fwd":
        c = _compile(lambda x, w, s, sd, o, tg, nl:
                     _mm.masked_matmul_grouped(x, w, s, sd, o, tg, nl,
                                               **kw),
                     one_chip, ((R, Kp), bf), *w, ((EH,), u32),
                     ((EH,), u32), *lay)
    elif kernel == "dx":
        c = _compile(lambda g, w, s, sd, o, tg, nl:
                     _mm.masked_matmul_grouped_dx(g, w, s, sd, o, tg, nl,
                                                  **kw),
                     one_chip, ((R, Np), bf), *w, ((EH,), u32),
                     ((EH,), u32), *lay)
    else:
        del kw["n_logical"]
        c = _compile(lambda x, g, w, s, tg, nl:
                     _mm.masked_matmul_grouped_ds(x, g, w, s, tg, nl,
                                                  **kw),
                     one_chip, ((R, Kp), bf), ((R, Np), bf), *w, *lay)
    assert "tpu_custom_call" in c.as_text()


@pytest.mark.parametrize("step", ["train", "round"])
def test_step_compiles_and_fits(one_chip, monkeypatch, step):
    """The whole jitted step of `chip_smoke.py`'s cut (4 layers, 2
    cohorts, batch 2 x seq 512, state donated) compiles for one v5e,
    holds its kernels under their fixed names, and fits the chip's 16
    GiB."""
    from repro.configs import get_config
    from repro.core import masking
    from repro.launch import steps as steplib
    from repro.models import build_model
    # the kernels are compiled for the chip, not interpreted as this
    # CPU backend would choose
    monkeypatch.setattr(ops, "_use_interpret", lambda: False)
    api = build_model(get_config("internlm2-1.8b", layers=4))
    scfg = steplib.StepConfig(lr=0.3, downlink_bits=8)
    state = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                       sharding=one_chip),
        jax.eval_shape(lambda k: steplib.init_fed_state(
            k, api, masking.MaskSpec(), C=2), jax.random.PRNGKey(0)))
    if step == "train":
        fn = steplib.make_train_step(api, scfg)
        args = (state, {"tokens": jax.ShapeDtypeStruct(
            (2, 2, 512), jnp.int32, sharding=one_chip)})
    else:
        fn = steplib.make_round_step(api, scfg, codec="arithmetic")
        args = (state,)
    c = jax.jit(fn, donate_argnums=0).lower(*args).compile()
    text = c.as_text()
    assert "tpu_custom_call" in text
    # the kernels' calls are named by `pallas_call(name=...)`, whatever
    # scope calls them, and each kernel reader's pattern finds its own
    calls = {TR.base_name(n) for n in re.findall(
        r"%(\S+) = [^\n]*custom_call_target=\"tpu_custom_call\"", text)}
    kernels = (("masked_matmul", "masked_matmul_dx", "masked_matmul_ds")
               if step == "train" else ("sample_and_pack",))
    assert calls == set(kernels), calls
    found = TR.Summary(window_s=0, busy_s=0, op_s={}, op_count={},
                       gaps=[], devices=1, custom=calls)
    for k in kernels:
        assert found.kernels((k,)) == [k], (k, calls)
    assert not calls & set(SR.TRAIN_SCOPES + SR.ROUND_SCOPES), calls
    ma = c.memory_analysis()
    live = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    assert live < HBM_BYTES, live


def test_moe_share_train_step_compiles_and_fits(one_chip, monkeypatch):
    """The whole jitted train step of the deepseek-v2-lite-16b share
    cell (5 layers, 8 of 64 experts held, vocabulary 12800, 1 cohort x
    seq 8192, state donated; the cell's own program settings) compiles
    for one v5e, holds the dense and grouped kernels under their fixed
    names, and fits the chip's 16 GiB."""
    import dataclasses
    from benchmarks.chip import harness as H
    from benchmarks.chip.reference import moe as RM
    from repro.configs import get_config
    from repro.core import masking
    from repro.launch import steps as steplib
    from repro.models import build_model
    monkeypatch.setattr(ops, "_use_interpret", lambda: False)
    conf = H.load_json(H.HERE / "configs" / "deepseek-v2-lite-16b.json")
    arch = dataclasses.replace(get_config(conf["program_arch"]),
                               **RM.program_arch(conf))
    api = build_model(arch)
    scfg = steplib.StepConfig(lr=0.3, downlink_bits=8)
    state = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                       sharding=one_chip),
        jax.eval_shape(lambda k: steplib.init_fed_state(
            k, api, masking.MaskSpec(), C=1), jax.random.PRNGKey(0)))
    batch = {"tokens": jax.ShapeDtypeStruct((1, 1, 8192), jnp.int32,
                                            sharding=one_chip)}
    c = jax.jit(steplib.make_train_step(api, scfg),
                donate_argnums=0).lower(state, batch).compile()
    text = c.as_text()
    calls = {TR.base_name(n) for n in re.findall(
        r"%(\S+) = [^\n]*custom_call_target=\"tpu_custom_call\"", text)}
    assert calls == {"masked_matmul", "masked_matmul_dx",
                     "masked_matmul_ds", "masked_matmul_grouped",
                     "masked_matmul_grouped_dx",
                     "masked_matmul_grouped_ds"}, calls
    ma = c.memory_analysis()
    live = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    assert live < HBM_BYTES, live
