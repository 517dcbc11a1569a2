"""The phase reduction (`benchmarks/chip/scope_reduce.py`) on a trace
recorded on a TPU v5e by `record_scoped_trace.py`: three train steps and
one round of the program's own steps with their named phases, at
internlm2's smoke widths, eight device-to-host transfers, all under the
host span `window` (data/scoped.xplane.pb.gz)."""
import gzip
import pathlib
import re
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmarks.chip import scope_reduce as SR  # noqa: E402
from benchmarks.chip import trace_reduce as TR  # noqa: E402

TRACE = pathlib.Path(__file__).with_name("data") / "scoped.xplane.pb.gz"
VOCAB = SR.TRAIN_SCOPES + SR.ROUND_SCOPES
TRAIN, ROUND = "jit_train_step", "jit_round_step"


@pytest.fixture(scope="module")
def phases():
    return SR.reduce(str(TRACE), VOCAB)


@pytest.fixture(scope="module")
def progs():
    return {p.module: p for p in SR.programs(
        gzip.decompress(TRACE.read_bytes())).values()}


def test_calls_and_transfers_are_what_the_script_ran(phases):
    assert phases.module_calls == {TRAIN: 3, ROUND: 1}
    # each step's loss, then the round's five metrics, one by one
    assert phases.d2h == 3 + 5
    assert phases.unknown_ops == 0


@pytest.mark.parametrize("module,scopes", [(TRAIN, SR.TRAIN_SCOPES),
                                           (ROUND, SR.ROUND_SCOPES)])
def test_each_scope_is_found_in_its_module(phases, progs, module, scopes):
    named = {SR.scope_of(op, VOCAB) for op in progs[module].op_name.values()}
    assert named - {None} == set(scopes)
    timed = {s for (m, s), v in phases.scope_s.items()
             if m == module and v > 0}
    # XLA fuses the regularizer's passes over the scores into the
    # optimizer's update of each leaf, whose root is the optimizer's: its
    # time counts there
    assert timed - {None} == set(scopes) - {"regularizer"}


@pytest.mark.parametrize("module", [TRAIN, ROUND])
def test_phases_add_up_to_the_module(phases, module):
    parts = [v for (m, _), v in phases.scope_s.items() if m == module]
    assert sum(parts) == pytest.approx(phases.module_s[module], rel=1e-9)
    table = phases.table(VOCAB)[module]
    named = sum(v for k, v in table.items()
                if k not in ("calls", "op_ms"))
    assert named == pytest.approx(table["op_ms"], rel=1e-9)


def test_every_device_op_is_in_a_module(phases):
    from jax.profiler import ProfileData
    pd = ProfileData.from_serialized_xspace(
        gzip.decompress(TRACE.read_bytes()))
    ops = [ev for plane in pd.planes if plane.name == "/device:TPU:0"
           for line in plane.lines if line.name == "XLA Ops"
           for ev in line.events]
    total = sum(ev.duration_ns for ev in ops
                if TR.base_name(ev.name) not in TR.NESTING) * 1e-9
    assert sum(phases.module_s.values()) == pytest.approx(total, rel=1e-9)


def test_a_fusion_counts_toward_its_roots_scope(progs):
    """A fusion's op_name, and so its scope, is one of its own ops': its
    root's, one output's where the root is a tuple of several, or the
    converted op's where the root only converts."""
    root = other = 0
    for p in progs.values():
        for fusion, (r, members) in p.fusions.items():
            op = p.op_name[fusion]
            if not op:
                continue
            assert op in {p.op_name[m] for m in members}, fusion
            if op == p.op_name[r]:
                root += 1
            else:
                assert r.split(".")[0] in ("tuple", "convert", "bitcast",
                                           "convert_element_type"), fusion
                other += 1
    assert root > 2 * other > 0


def test_kernels_keep_their_names_under_scopes(progs):
    """Named kernels are found by the kernel readers' names, and their
    calls carry the scope of the block that called them."""
    calls = [(TR.base_name(n), op) for p in progs.values()
             for n, op in p.op_name.items() if "pallas_call" in op]
    assert {k for k, _ in calls} == {"masked_matmul", "masked_matmul_dx",
                                     "masked_matmul_ds", "sample_and_pack"}
    for k, op in calls:
        want = (("uplink",) if k == "sample_and_pack"
                else ("attention", "mlp"))
        assert SR.scope_of(op, VOCAB) in want, (k, op)


@pytest.mark.parametrize("op_name,scope", [
    ("jit(train_step)/vmap(transpose(jvp()))/while/body/closed_call/"
     "attention/attention_core/bqgrh,bkgh->bgrqk/dot_general",
     "attention_core"),
    ("jit(train_step)/vmap(transpose(jvp(embed_head)))/convert",
     "embed_head"),
    ("jit(train_step)/vmap(jvp())/while/body/closed_call/mlp/"
     "jit(masked_matmul)/pallas_call", "mlp"),
    ("jit(round_step)/downlink/jit(_uniform)/shift_right_logical",
     "downlink"),
    ("jit(train_step)/vmap(optimizer)/mul;jit(train_step)/add",
     "optimizer"),
    ("jit(train_step)/jit(mlp)/add", None),
    ("jit(train_step)/add", None),
    ("", None),
])
def test_scope_of_a_path(op_name, scope):
    assert SR.scope_of(op_name, VOCAB) == scope



def _without_metadata_plane(xspace: bytes) -> bytes:
    """The trace re-encoded without its `/host:metadata` plane."""
    out, i = bytearray(), 0
    while i < len(xspace):
        start = i
        key, i = SR._varint(xspace, i)
        length, i = SR._varint(xspace, i)      # every XSpace field is
        body, i = xspace[i:i + length], i + length   # length-delimited
        if not (key >> 3 == 1 and SR._first(body, 2) == b"/host:metadata"):
            out += xspace[start:i]
    return bytes(out)


def test_op_names_from_the_compiled_text_stand_in_for_the_protos(
        phases, progs, tmp_path):
    """A trace may lack a program's proto (the fedtrain cell's traces
    hold none of the TPU programs'): the same op_names, given by module,
    give the same phases."""
    path = tmp_path / "bare.xplane.pb"
    path.write_bytes(_without_metadata_plane(
        gzip.decompress(TRACE.read_bytes())))
    bare = SR.reduce(str(path), VOCAB)
    assert bare.unknown_ops > 0 and bare.scope_s.keys() == {
        (TRAIN, None), (ROUND, None)}
    given = SR.reduce(str(path), VOCAB, op_names={
        m: p.op_name for m, p in progs.items()})
    assert given.unknown_ops == 0
    assert given.scope_s == pytest.approx(phases.scope_s, rel=1e-12)


def test_op_names_of_a_compiled_program_text():
    import jax
    import jax.numpy as jnp

    def round_step(x):
        with jax.named_scope("fold"):
            y = jnp.sin(x) * 2.0
        return y + 1.0

    text = SR.fresh_text(jax.jit(round_step), jnp.zeros((8, 128)))
    module, names = SR.text_op_names(text)
    assert module == "jit_round_step"
    scopes = {SR.scope_of(op, VOCAB) for op in names.values()}
    assert "fold" in scopes and None in scopes
    assert all(re.fullmatch(r"[\w.\-]+", n) for n in names)
