"""The phases the program names on the device (`jax.named_scope`), read
from the op_names of a tiny internlm2-shaped train step and round step
compiled on the CPU: every scope that the benchmark's phase reduction
(`benchmarks/chip/scope_reduce.py`) reads is in the program it belongs
to, and the program carries no scope outside that vocabulary."""
import pathlib
import re
import sys

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.core import masking
from repro.launch import steps as steplib
from repro.models import build_model

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from benchmarks.chip import scope_reduce as SR  # noqa: E402

VOCAB = {"train": set(SR.TRAIN_SCOPES), "round": set(SR.ROUND_SCOPES)}
# parts of a path that JAX itself writes: loops, calls, branches, and an
# einsum's subscripts
STRUCTURE = re.compile(r"^(while|body|cond|closed_call|branch_\d+_fun"
                       r"|[a-z,]+->[a-z]+)$")
_WRAP = re.compile(r"^(\w+)\((.*)\)$")
# the Pallas kernels' names (`pallas_call(name=...)`): interpreted on the
# CPU, a kernel's name heads the path of the ops it runs
KERNELS = {"masked_matmul", "masked_matmul_dx", "masked_matmul_ds",
           "sample_and_pack", "masked_matmul_grouped",
           "masked_matmul_grouped_dx", "masked_matmul_grouped_ds",
           "masked_conv1d", "masked_conv1d_ds", "pack_bits", "unpack_bits"}


@pytest.fixture(scope="module")
def op_names():
    api = build_model(get_config("internlm2-1.8b", smoke=True))
    scfg = steplib.StepConfig(lr=0.3, downlink_bits=8)
    state = jax.eval_shape(lambda k: steplib.init_fed_state(
        k, api, masking.MaskSpec(), C=2), jax.random.PRNGKey(0))
    batch = {"tokens": jax.ShapeDtypeStruct((2, 2, 16), jnp.int32)}
    out = {}
    for step, fn, args in (
            ("train", steplib.make_train_step(api, scfg), (state, batch)),
            ("round", steplib.make_round_step(api, scfg,
                                              codec="arithmetic"),
             (state,))):
        # compiled anew: the persistent cache would return a program
        # cached before its scopes changed, with the old op_names
        text = SR.fresh_text(jax.jit(fn, donate_argnums=0), *args)
        assert f"HloModule jit_{step}_step" in text
        out[step] = [n for names in re.findall(r'op_name="([^"]*)"', text)
                     for n in names.split(";")]
    return out


def own_names(op_name: str):
    """The names on an op_name's path that the step's own trace gives,
    transforms unwrapped: the path up to the first function it calls
    (`jit(...)`, whose own names are the library's), without the op."""
    parts = op_name.split("/")[:-1]
    if parts and parts[0].startswith("jit("):
        parts = parts[1:]
    for part in parts:
        while (m := _WRAP.match(part)) and m.group(1) != "jit":
            part = m.group(2)
        if part.startswith("jit(") or part in KERNELS:
            return
        if part and not STRUCTURE.match(part):
            yield part


@pytest.mark.parametrize("step", ["train", "round"])
def test_each_scope_is_in_its_program(op_names, step):
    found = {SR.scope_of(n, VOCAB["train"] | VOCAB["round"])
             for n in op_names[step]}
    assert VOCAB[step] <= found, VOCAB[step] - found
    other = VOCAB["round" if step == "train" else "train"]
    assert not found & other, found & other


@pytest.mark.parametrize("step", ["train", "round"])
def test_no_scope_outside_the_vocabulary(op_names, step):
    names = {p for n in op_names[step] for p in own_names(n)}
    assert names <= VOCAB[step], names - VOCAB[step]


def test_kernels_count_toward_the_block_that_calls_them(op_names):
    """A masked matmul's ops, forward and backward, carry the scope of
    the block that called the kernel, not the kernel's function name."""
    kern = [n for n in op_names["train"]
            if re.search(r"jit\(masked_matmul(_dx|_ds)?\)", n)]
    assert kern
    scopes = {SR.scope_of(n, VOCAB["train"]) for n in kern}
    assert scopes == {"attention", "mlp"}, scopes
    bwd = {SR.scope_of(n, VOCAB["train"]) for n in kern
           if "transpose(" in n}
    assert bwd == {"attention", "mlp"}, bwd


MOE_SCOPES = {"moe_router", "moe_dispatch", "moe_experts", "moe_shared",
              "moe_combine"}


@pytest.fixture(scope="module")
def moe_op_names():
    api = build_model(get_config("deepseek-v2-lite-16b", smoke=True))
    scfg = steplib.StepConfig(lr=0.3, downlink_bits=8)
    state = jax.eval_shape(lambda k: steplib.init_fed_state(
        k, api, masking.MaskSpec(), C=1), jax.random.PRNGKey(0))
    batch = {"tokens": jax.ShapeDtypeStruct((1, 2, 16), jnp.int32)}
    text = SR.fresh_text(jax.jit(steplib.make_train_step(api, scfg),
                                 donate_argnums=0), state, batch)
    return [n for names in re.findall(r'op_name="([^"]*)"', text)
            for n in names.split(";")]


def test_moe_block_names_its_phases(moe_op_names):
    """A compiled MoE train step (MLA, routed and shared experts) holds
    every MoE scope, MLA stays under `attention`, and the program
    carries no scope outside the train vocabulary and the MoE one."""
    vocab = VOCAB["train"] | MOE_SCOPES
    found = {SR.scope_of(n, vocab) for n in moe_op_names}
    assert MOE_SCOPES | {"attention", "attention_core", "mlp",
                         "embed_head", "optimizer"} <= found, found
    names = {p for n in moe_op_names for p in own_names(n)}
    assert names <= vocab, names - vocab
    grouped = [n for n in moe_op_names
               if re.search(r"jit\(masked_matmul_grouped(_dx|_ds)?\)", n)]
    assert grouped
    assert {SR.scope_of(n, vocab) for n in grouped} == {"moe_experts"}
