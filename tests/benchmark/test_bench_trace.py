"""The trace reduction on a small trace recorded on a TPU v5e: three
jitted steps of the fused masked matmul (forward, dx, ds) and one
sample_and_pack, under the harness's host spans, with a 3 ms sleep in
each `make_batch` that leaves the device idle (data/small.xplane.pb)."""
import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmarks.chip import trace_reduce as TR  # noqa: E402

TRACE = pathlib.Path(__file__).with_name("data") / "small.xplane.pb"
SPANS = ("make_batch", "train_dispatch", "loss_to_host", "round_dispatch",
         "round_metrics_to_host")


@pytest.fixture(scope="module")
def summary():
    return TR.reduce(str(TRACE), spans=SPANS)


def test_busy_is_a_union_inside_the_window(summary):
    assert summary.devices == 1
    assert 0 < summary.busy_s < summary.window_s
    # ops may overlap, so their sum bounds the union from above
    assert sum(summary.op_s.values()) >= summary.busy_s * (1 - 1e-9)
    idle = sum(s for _, s in summary.gaps)
    assert idle + summary.busy_s == pytest.approx(summary.window_s,
                                                  rel=1e-6)


def test_kernels_are_found_by_name(summary):
    for k in ("masked_matmul", "masked_matmul_dx", "masked_matmul_ds"):
        assert summary.kernel_count((k,)) == 3, k
        assert summary.kernel_s((k,)) > 0
    assert summary.kernel_count(("sample_and_pack",)) == 1
    # a kernel is not found under a longer kernel's name, and XLA's own
    # ops are not kernels
    assert len(summary.kernels(("masked_matmul",))) == 1
    assert summary.kernels(("reduce",)) == []


def test_idle_gaps_are_named_for_host_spans(summary):
    by_span = summary.gap_by_span()
    # three 3 ms sleeps in make_batch leave the device idle
    assert by_span["make_batch"] >= 3 * 0.003
    assert set(by_span) <= set(SPANS) | {"other"}
    bd = TR.breakdown(summary)
    assert bd["idle_gaps"][0][0] == "make_batch"
    assert len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10


def test_union_and_gap_naming():
    assert TR._union([(5, 7), (0, 2), (1, 3), (6, 9)]) == [[0, 3], [5, 9]]
    host = [("window", 0, 100), ("make_batch", 10, 20),
            ("round_dispatch", 18, 40)]
    got = TR._name_gaps(host, [(10, 19), (19, 40), (50, 60)])
    assert [n for n, _ in got] == ["make_batch", "round_dispatch", "other"]
    assert [s for _, s in got] == pytest.approx([9e-9, 21e-9, 10e-9])
    assert TR.base_name("%masked_matmul_dx.79 = bf16[2]{0} custom-call("
                        "f32[2]{0} %x)") == "masked_matmul_dx"
    assert TR.base_name("fusion.3.1") == "fusion"
