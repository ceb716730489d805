"""FLOP and byte counts against hand counts, the peaks table, and the
refusal to report from a CPU.

    PYTHONPATH=bench JAX_PLATFORMS=cpu python -m pytest bench/tests -q
"""
import importlib.util
import json
import os
import subprocess
import sys

import pytest

from harness import device, inputs, spec

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _reader(name):
    s = importlib.util.spec_from_file_location(
        name, os.path.join(BENCH, "metrics", f"{name}.py"))
    mod = importlib.util.module_from_spec(s)
    s.loader.exec_module(mod)
    return mod


def _config(name):
    with open(os.path.join(BENCH, "configs", f"{name}.json")) as f:
        return json.load(f)


def _traffic(name):
    with open(os.path.join(BENCH, "traffic", f"{name}.json")) as f:
        return json.load(f)


def test_param_counts():
    # embedding 6144 x 4096; a layer: q, o 4096 x 4096, k, v 4096 x 1024,
    # gate, up, down 4096 x 14336, two norms of 4096; the final norm
    layer = 2 * 4096 * 4096 + 2 * 4096 * 1024 + 3 * 4096 * 14336 + 2 * 4096
    assert inputs.param_count(_config("granite-8b-l1")) \
        == 6144 * 4096 + layer + 4096 == 243_281_920
    assert inputs.param_count(_config("granite-8b-l4")) \
        == 6144 * 4096 + 4 * layer + 4096 == 897_617_920


def test_model_flops():
    mfu = _reader("mfu")
    cfg, tr = _config("granite-8b-l1"), _traffic("w2-ls2-1x2048-snr5-pallas")
    model = spec.load_model(cfg)
    # 6 x (matmul weights, tied head included) x 2048 tokens, and causal
    # attention 6 x S^2 x H x hd per layer, for 2 workers x 2 local steps
    dense = 6 * (243_281_920 - 3 * 4096) * 2048
    attn = 6 * 2048 ** 2 * 32 * 128
    assert mfu.flops_per_round(model, cfg, tr) == 4 * (dense + attn)
    assert mfu.flops_per_round(model, cfg, tr) == pytest.approx(12.37e12, rel=1e-3)
    cfg4 = _config("granite-8b-l4")
    assert mfu.flops_per_round(model, cfg4, tr) == pytest.approx(45.8e12, rel=2e-3)


def test_round_bytes():
    roof = _reader("ota_round_roofline")
    D = 243_281_920
    assert roof.plane_bytes(2, D) == 44 * 2 * D
    assert roof.plane_bytes(2, D) / 1e9 == pytest.approx(21.4, abs=0.05)
    assert roof.min_bytes_per_round(2, D) == 44 * 2 * D + 8 * D


def test_peaks():
    assert device.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        device.peaks("TPU v9 imaginary")


def test_cpu_refuses():
    with pytest.raises(device.NoChip):
        device.chips(1)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "granite8b-l1-w2", "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "not a TPU" in p.stderr


def test_every_cell_resolves():
    bench = spec.load_benchmark()
    for w in bench["workloads"]:
        cell = spec.load_cell(w["name"], bench)
        assert cell.limits and set(cell.limits) <= {
            "loss", "inv_alpha", "dtheta1", "dTheta3_med", "lam3", "noise1"}
        for m in cell.per_layer:
            assert callable(spec.load_reader(m["name"]).read)
