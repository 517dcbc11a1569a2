"""Tiny cells for running the chip benchmark's loops on the CPU (the
Pallas kernels interpreted): the dense and SSM families at test widths,
the cells' own traffic mixes at a short sequence, and limits for the
numbers that decide `correct` at this size (sound runs of the program
read loss_gap ~2e-5, grad_gap ~5e-4 and change_gap ~2e-3 here)."""
import contextlib
import io
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from benchmarks.chip import harness as H  # noqa: E402

SEED = 2**31 + 11
CONFIGS = {
    "dense": dict(name="tiny-dense", family="dense",
                  program_arch="internlm2-1.8b", hidden_size=64,
                  intermediate_size=128, num_attention_heads=4,
                  num_key_value_heads=2, num_hidden_layers=2,
                  vocab_size=256, rms_norm_eps=1e-5, rope_theta=1e6),
    "ssm": dict(name="tiny-ssm", family="ssm", program_arch="mamba2-370m",
                d_model=64, n_layer=2, vocab_size=250,
                vocab_size_padded=256, d_state=16, d_conv=4, expand=2,
                headdim=16, ngroups=1, tie_embeddings=True),
}
LIMITS = {"loss_gap": 1e-4, "grad_gap": 3e-3, "change_gap": 6e-3,
          "round_mismatch": 0, "round_bits_gap": 0}


def cell(family: str, traffic: str) -> H.Cell:
    t = H.load_json(H.HERE / "traffic" / f"{traffic}.json")
    if t["loop"] == "fedtrain":
        t.update(batch=2, seq=32, round_every=2, stream_tokens=4096)
    limits = {k: v for k, v in LIMITS.items()
              if t["loop"] == "fedtrain" or k.startswith("round_")}
    return H.Cell(name="tiny", chips=1, config=CONFIGS[family], traffic=t,
                  limits=limits,
                  bench=H.load_json(ROOT / "BENCHMARK.json"))


def run_main(monkeypatch, tiny: H.Cell, wrap=None, seconds=0.5):
    """Drive `run.py`'s main for `tiny` on the CPU, past the look for a
    chip; `wrap(plan)` may break the plan's functions.  Returns the
    result line."""
    import jax
    run = H.load_file_module(H.HERE / "run.py", "chipbench_run_tiny")
    monkeypatch.setattr(H, "load_cell", lambda name: tiny)
    monkeypatch.setattr(H, "preflight", lambda chips: jax)
    monkeypatch.setattr(H, "enable_compile_cache", lambda: None)
    monkeypatch.setattr(run, "peaks_for", lambda kind: {})
    if wrap is not None:
        real = H.launch_plan

        def broken(cell):
            plan, shapes = real(cell)
            return wrap(plan), shapes
        monkeypatch.setattr(H, "launch_plan", broken)
    out, err = io.StringIO(), io.StringIO()
    with contextlib.redirect_stdout(out), contextlib.redirect_stderr(err):
        rc = run.main(["--workload", "tiny", "--seed", str(SEED),
                       "--seconds", str(seconds), "--trace", "0"])
    assert rc == 0, err.getvalue()
    return json.loads(out.getvalue().strip().splitlines()[-1])
