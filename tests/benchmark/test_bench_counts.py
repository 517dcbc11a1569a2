"""The chip benchmark's yardstick on the CPU: operation and byte counts
against hand counts, the peaks table, and the per-cell file layout."""
import json
import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmarks.chip import harness as H  # noqa: E402
from benchmarks.chip.flops import dense, ssm  # noqa: E402

INTERNLM2 = H.load_json(H.HERE / "configs" / "internlm2-1.8b.json")
MAMBA2 = H.load_json(H.HERE / "configs" / "mamba2-370m.json")
FEDTRAIN = H.load_json(H.HERE / "traffic" / "fedtrain-r10.json")


def test_internlm2_leaves_by_hand():
    # q, o: 2048 x 2048; k, v: 2048 x 1024; gate, up: 2048 x 8192;
    # down: 8192 x 2048
    per_layer = (2 * 2048 * 2048 + 2 * 2048 * 1024 + 3 * 2048 * 8192)
    assert per_layer == 62_914_560
    assert dense.masked_params(INTERNLM2) == 4 * per_layer == 251_658_240
    assert sum(dense.masked_leaf_sizes(INTERNLM2)) == 251_658_240
    # two bf16 tables of 92544 x 2048, nine f32 norm scales of 2048
    assert dense.float_bytes(INTERNLM2) == 2 * 92544 * 2048 * 2 + 9 * 2048 * 4


def test_internlm2_model_flops_by_hand():
    proj = 4 * 62_914_560
    head = 2048 * 92544
    attn = 3 * 4 * (2 * 2 * 16 * 128 * 513 / 2)   # causal, seq 512
    want = 6 * (proj + head) + attn
    assert dense.model_flops_per_token(INTERNLM2, 512) == want
    # about 5.47 TFLOP per step of 2 cohorts x 2 x 512 tokens
    assert want * 2048 == pytest.approx(5.473e12, rel=1e-3)


def test_internlm2_masked_matmuls():
    mm = dense.masked_matmuls(INTERNLM2, FEDTRAIN)
    assert len(mm) == 7
    assert all(M == 1024 and calls == 8 for M, _, _, calls in mm)
    assert sorted((K, N) for _, K, N, _ in mm) == sorted(
        [(2048, 2048), (2048, 1024), (2048, 1024), (2048, 2048),
         (2048, 8192), (2048, 8192), (8192, 2048)])


def test_mamba2_leaves_by_hand():
    w_in = 1024 * (2 * 2048 + 2 * 128 + 32)     # z, x, B, C, dt
    w_out = 2048 * 1024
    conv = 4 * (2048 + 2 * 128)
    assert (w_in, w_out, conv) == (4_489_216, 2_097_152, 9_216)
    assert ssm.masked_params(MAMBA2) == 16 * (w_in + w_out + conv)
    assert ssm.masked_leaf_sizes(MAMBA2) == [16 * w_in, 16 * w_out,
                                             16 * conv]
    per_layer_f32 = 1024 + 2048 + 3 * 32 + 2304
    assert ssm.float_bytes(MAMBA2) == (50280 * 1024 * 2
                                       + (16 * per_layer_f32 + 1024) * 4)


def test_mamba2_model_flops_by_hand():
    proj = 4_489_216 + 2_097_152
    conv = 9_216
    ssd = 32 * 4 * 64 * 128           # 32 heads, 4*P*N each, forward
    head = 1024 * 50280
    want = 6 * (16 * (proj + conv) + head) + 3 * 16 * ssd
    assert ssm.model_flops_per_token(MAMBA2, 512) == want
    assert want == 992_428_032


def test_masked_matmul_roofline_counts():
    from benchmarks.chip.metrics import masked_matmul_roofline as mm
    peaks = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e12}
    # one (128 x 256) @ (256 x 512) call, compute-bound on these peaks:
    # 2*128*256*512 ops = 33.5 MFLOP each for fwd, dx and ds
    got = mm.least_seconds([(128, 256, 512, 1)], peaks)
    ops = 2 * 128 * 256 * 512
    fwd = (128 * 256 + 256 * 512 + 128 * 512) * 2 + 256 * 512 * 4
    ds = (128 * 256 + 128 * 512 + 256 * 512) * 2 + 2 * 256 * 512 * 4
    assert got == pytest.approx(2 * max(ops, fwd) / 1e12
                                + max(ops, ds) / 1e12)


def test_peaks_keyed_by_device_kind():
    run = H.load_file_module(H.HERE / "run.py", "chipbench_run")
    v5e = run.peaks_for("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    with pytest.raises(H.BenchError):
        run.peaks_for("TPU v9 imaginary")
    with pytest.raises(H.BenchError):
        run.peaks_for("cpu")


def test_every_cell_finds_its_files():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for w in bench["workloads"]:
        cell = H.load_cell(w["name"])
        assert cell.loop.Loop
        assert cell.family.specs(cell.config)
        assert cell.flops.model_flops_per_token
        assert cell.per_layer() and cell.end_to_end()
        for m in cell.per_layer():
            assert (H.HERE / "metrics" / f"{m['name']}.py").is_file()
        assert set(cell.limits) >= {"round_mismatch", "round_bits_gap"}


@pytest.mark.parametrize("config", ["internlm2-1.8b", "mamba2-370m"])
def test_cell_state_matches_the_program(config):
    """The benchmark's leaf table lays out exactly the program's state
    (shapes only: nothing is made), at the program's published widths
    with only the depth cut."""
    import dataclasses
    sys.path.insert(0, str(ROOT / "src"))
    from repro.configs import get_config
    cell = H.Cell(name=config, chips=1,
                  config=H.load_json(H.HERE / "configs" / f"{config}.json"),
                  traffic=FEDTRAIN, limits={}, bench={})
    api = H.program_model(cell)
    depth = api.cfg.n_layers
    assert api.cfg == dataclasses.replace(
        get_config(cell.config["program_arch"]), n_layers=depth)
    plan, shapes = H.launch_plan(cell)
    H.state_maker(cell, shapes)          # raises on any disagreement
    assert plan.step_fn is not None and plan.round_fn is not None
