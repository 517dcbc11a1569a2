"""The benchmark's MoE family and the pod round on the CPU: a tiny
DeepSeek-V2-shaped cell of its own (the Pallas kernels interpreted)
read correct, and not correct under the old capacity dispatch; the
grouped kernels' roofline and the pod all-gather's device time counted
by hand; the share configuration's counts by hand; the cross-pod round
cell on four CPU devices."""
import os
import pathlib
import subprocess
import sys
import types

import pytest

import bench_tiny as T

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmarks.chip import harness as H  # noqa: E402
from benchmarks.chip.flops import moe  # noqa: E402

YARN = {"beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 0.707,
        "mscale_all_dim": 0.707, "original_max_position_embeddings": 4096,
        "type": "yarn"}
TINY = dict(name="tiny-moe", family="moe",
            program_arch="deepseek-v2-lite-16b", hidden_size=64,
            intermediate_size=128, num_attention_heads=4, kv_lora_rank=32,
            qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
            first_k_dense_replace=1, num_hidden_layers=3,
            moe_intermediate_size=32, n_routed_experts=4, router_experts=8,
            n_shared_experts=2, num_experts_per_tok=3, vocab_size=256,
            rms_norm_eps=1e-6, rope_theta=10000, rope_scaling=YARN,
            program_settings={"remat": True, "attn_chunk": 16})
# sound runs of the program read loss_gap 6e-5 to 1.2e-4, grad_gap
# 2.4e-3 to 4.7e-3 and change_gap 2.4e-3 to 3.1e-3 here (seeds 5, 77,
# 2**31 + 11); the capacity dispatch reads change_gap 0.016 to 0.056,
# the float8 control grad_gap 0.017 to 0.063 and change_gap 0.018 to
# 0.039
LIMITS = {"loss_gap": 3e-4, "grad_gap": 0.01, "change_gap": 0.008,
          "round_mismatch": 0, "round_bits_gap": 0}
DSV2 = H.load_json(H.HERE / "configs" / "deepseek-v2-lite-16b.json")
FEDTRAIN_8K = H.load_json(H.HERE / "traffic" / "fedtrain-8k-r10.json")


def tiny_cell():
    t = H.load_json(H.HERE / "traffic" / "fedtrain-r10.json")
    t.update(batch=2, seq=32, round_every=2, stream_tokens=4096)
    return H.Cell(name="tiny", chips=1, config=TINY, traffic=t,
                  limits=LIMITS, bench=H.load_json(ROOT / "BENCHMARK.json"))


def capacity_route(factor=1.25):
    """The dispatch this layer replaced: each expert takes at most
    T * top_k * factor / E pairs, in token order; the rest are
    dropped."""
    import jax
    import jax.numpy as jnp
    from repro.models import layers as L
    real = L.moe_route

    def route(probs, top_k, n_held):
        gval, gidx, held = real(probs, top_k, n_held)
        Tn, E = probs.shape
        cap = max(int(Tn * top_k * factor / E), 4)
        oh = jax.nn.one_hot(gidx, E, dtype=jnp.int32)
        flat = oh.reshape(Tn * top_k, E)
        pos = jnp.sum((jnp.cumsum(flat, 0) - flat).reshape(Tn, top_k, E)
                      * oh, -1)
        return gval, gidx, held & (pos < cap)
    return route


def test_sound_moe_run_is_correct(monkeypatch):
    res = T.run_main(monkeypatch, tiny_cell())
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["compiles"]["window"] == 0, res["compiles"]


def test_capacity_dropping_run_is_not_correct(monkeypatch):
    import jax
    from repro.models import layers as L
    monkeypatch.setattr(L, "moe_route", capacity_route())
    # the layer block's traced form is cached across plans (remat)
    jax.clear_caches()
    res = T.run_main(monkeypatch, tiny_cell())
    assert not res["correct"], res["checks"]
    assert res["checks"]["change_gap"]["value"] > LIMITS["change_gap"]


def test_float8_control_is_not_correct():
    """The reference one precision below the configuration's (float8
    activations; bfloat16 scores in the round) reads outside the
    limits: the training numbers and the exact round alike."""
    from benchmarks.chip import control
    got = [nums for name, nums in control.readings(tiny_cell(), T.SEED)
           if name == "control"]
    train = next(n for n in got if "grad_gap" in n)
    rnd = next(n for n in got if "round_mismatch" in n)
    assert train["grad_gap"] > LIMITS["grad_gap"]
    assert train["change_gap"] > LIMITS["change_gap"]
    assert rnd["round_mismatch"] > LIMITS["round_mismatch"]


def test_share_counts_by_hand():
    # MLA: q 2048 x 3072, dkv 2048 x 576, uk and uv 512 x 2048, o
    # 2048 x 2048; dense MLP 3 x 2048 x 10944; 8 held experts and the
    # shared pair (2 x 1408 wide) of 3 x 2048 x 1408 each
    mla = 2048 * 3072 + 2048 * 576 + 2 * 512 * 2048 + 2048 * 2048
    assert mla == 13_762_560
    dense_mlp = 3 * 2048 * 10944
    held = 8 * 3 * 2048 * 1408
    shared = 3 * 2048 * 2816
    want = 5 * mla + dense_mlp + 4 * (held + shared)
    assert moe.masked_params(DSV2) == want == 482_082_816
    assert moe.float_bytes(DSV2) == (2 * 12800 * 2048 * 2
                                     + (11 * 2048 + 5 * 512
                                        + 4 * 2048 * 64) * 4)
    mm = moe.masked_matmuls(DSV2, FEDTRAIN_8K)
    assert len(mm) == 11 and all(M == 8192 for M, *_ in mm)
    assert sum(K * N * c for _, K, N, c in mm) == (5 * mla + dense_mlp
                                                   + 4 * shared)
    gm = moe.grouped_matmuls(DSV2, FEDTRAIN_8K)
    assert gm == [(768.0, 2048, 1408, 32), (768.0, 2048, 1408, 32),
                  (768.0, 1408, 2048, 32)]


def test_share_model_flops_by_hand():
    mla = 13_762_560
    dense = 5 * mla + 3 * 2048 * 10944 + 4 * 3 * 2048 * 2816
    router = 4 * 2048 * 64
    held = 4 * (6 * 8 / 64) * 3 * 2048 * 1408
    head = 2048 * 12800
    attn = 3 * 5 * (2 * 16 * (128 + 64 + 128) * 8193 / 2)
    want = 6 * (dense + router + held + head) + attn
    assert moe.model_flops_per_token(DSV2, 8192) == pytest.approx(want)
    # about 2.18 GFLOP a token, 17.8 TFLOP a step of 8192 tokens
    assert want == pytest.approx(2.177e9, rel=1e-3)


def _reading(**kw):
    return types.SimpleNamespace(**kw)


def test_grouped_matmul_roofline_by_hand():
    mod = H.load_file_module(H.HERE / "metrics"
                             / "grouped_matmul_roofline.py", "grm")
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    trace = types.SimpleNamespace(kernel_s=lambda names: 0.5)
    r = _reading(trace=trace, window={"steps": 10}, flops=moe,
                 config=DSV2, traffic=FEDTRAIN_8K, peaks=peaks)
    rows, calls = 768, 32
    tot = 0.0
    for K, N in ((2048, 1408), (2048, 1408), (1408, 2048)):
        ops = 2 * rows * K * N
        wb = K * N * 2 + K * N * 4             # w bf16, s f32
        fwd = wb + (rows * K + rows * N) * 2
        dx = wb + (rows * N + rows * K) * 2
        ds = fwd + K * N * 4                   # and ds out, f32
        tot += calls * sum(max(ops / 197e12, b / 819e9)
                           for b in (fwd, dx, ds))
    assert mod.read(r) == pytest.approx(100 * 10 * tot / 0.5)
    none = _reading(trace=types.SimpleNamespace(kernel_s=lambda n: 0.0),
                    window={"steps": 10})
    assert mod.read(none) is None


def test_pod_gather_ms_by_hand():
    mod = H.load_file_module(H.HERE / "metrics" / "pod_gather_ms.py",
                             "pgm")
    trace = types.SimpleNamespace(
        op_s={"all-gather-start": 0.004, "all-gather-done": 0.002,
              "all-gather": 0.002, "fusion": 1.0,
              "all-reduce": 0.5}, devices=4)
    r = _reading(trace=trace, window={"rounds": 10})
    assert mod.read(r) == pytest.approx(1e3 * 0.008 / 4 / 10)
    trace.op_s = {"fusion": 1.0}
    assert mod.read(r) is None


_POD = r"""
import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ["JAX_PLATFORMS"] = "cpu"
sys.path[:0] = [{root!r}, {root!r} + "/src", {root!r} + "/tests/benchmark"]
import dataclasses
import jax, jax.numpy as jnp
import bench_tiny as T
from benchmarks.chip import harness as H

class MP:
    def setattr(self, o, n, v): setattr(o, n, v)

t = H.load_json(H.HERE / "traffic" / "round-pod4.json")
def cell():
    return H.Cell(name="tiny", chips=4, config=T.CONFIGS["dense"],
                  traffic=t, limits={{"round_mismatch": 0,
                                      "round_bits_gap": 0}},
                  bench=H.load_json(T.ROOT / "BENCHMARK.json"))

def no_exchange(plan):
    def rnd(state):
        return plan.round_fn(state, jnp.array([1.0, 0.0, 0.0, 0.0]))
    return dataclasses.replace(plan, round_fn=rnd)

from benchmarks.chip.loops import podround
real = podround.pod_plan
for name, wrap in (("sound", None), ("no_exchange", no_exchange)):
    podround.pod_plan = (real if wrap is None else
                         lambda c, m: (lambda p, s: (wrap(p), s))(*real(c, m)))
    res = T.run_main(MP(), cell(), seconds=0.3)
    print(name, res["correct"], res["checks"]["round_mismatch"]["value"],
          res["device"]["count"], flush=True)
"""


def test_pod_round_cell_on_four_cpu_devices():
    """The cross-pod round cell's loop on a (4, 1, 1) mesh of CPU
    devices: the program's mesh round reads correct against the
    one-device reference, and a round that folds only cohort 0's mask
    does not."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", _POD.format(root=str(ROOT))],
                         capture_output=True, text=True, timeout=600,
                         env=env)
    assert out.returncode == 0, out.stderr[-3000:]
    lines = dict(ln.split(" ", 1) for ln in out.stdout.strip().splitlines())
    assert lines["sound"].split()[0] == "True", lines
    assert lines["sound"].split()[2] == "4", lines
    assert lines["no_exchange"].split()[0] == "False", lines
