"""``correct`` comes out true for the program and false for the control and
for each fault planted underneath the harness.

At a size a CPU test run holds (granite's registry layout at d_model 128,
one layer, 64-token rows; Pallas kernels in interpret mode), the harness's
run is driven past its chip check: set-up, a one-second window, the
reference, the verdict.  The one-chip traffic is held to the committed
limits of its cell, ``granite8b-l1-w2``; the mesh traffic, whose cell is
not in ``BENCHMARK.json``, to ``tiny-mesh-limits.json``.

    PYTHONPATH=bench JAX_PLATFORMS=cpu python -m pytest bench/tests -q
"""
import json
import os
import time

import jax
import pytest

from harness import compare, faults, main, spec

HERE = os.path.dirname(os.path.abspath(__file__))
SEED = 2 ** 40 + 12345


def _load(name):
    with open(os.path.join(HERE, name)) as f:
        return json.load(f)


def _cell(traffic, limits, chips):
    bench = spec.load_benchmark()
    tr = spec.load_traffic(traffic)
    tr["seq_len"] = 64
    return spec.Cell(name="tiny", chips=chips, config=_load("tiny.json"),
                     traffic=tr, limits=limits,
                     end_to_end=bench["end_to_end"],
                     per_layer=bench["per_layer"], run_seconds=1)


@pytest.fixture(scope="module")
def cell():
    return _cell("w2-ls2-1x2048-snr5-pallas",
                 spec.load_limits("granite8b-l1-w2"), 1)


@pytest.fixture(scope="module")
def mesh_cell():
    """The (1, 2, 2) mesh traffic on four virtual devices."""
    return _cell("w2-ls2-1x2048-snr5-mesh122",
                 _load("tiny-mesh-limits.json"), 4)


def _run(cell, wrap=None):
    return main.run_cell(cell, SEED, 1.0, False, jax.devices()[:cell.chips],
                         time.perf_counter(), wrap_step=wrap)


def test_program_is_correct(cell):
    out = _run(cell)
    assert out["correct"], out["checks"]
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert set(out["metrics"]) == {"round_s", "peak_hbm_gb", "setup_s"}
    assert list(out)[-1] == "checks"


@pytest.mark.parametrize("fault", sorted(faults.FAULTS))
def test_fault_is_caught(cell, fault):
    out = _run(cell, faults.FAULTS[fault])
    assert not out["correct"], out["checks"]


def test_control_is_caught(cell):
    """The reference in float8 (weights stored, matmul operands rounded)
    put in the program's place fails at least one number."""
    ctl = spec.load_reference(cell.traffic).run(
        cell.config, cell.traffic, SEED, main.CHECK_ROUNDS,
        store="float8_e4m3fn", operand_dtype="float8_e4m3fn")
    _, read = main.reference_readings(cell, ctl, SEED, None)
    ok, checks = compare.verdict(read, cell.limits)
    assert not ok, checks


def test_mesh_program_is_correct(mesh_cell):
    out = _run(mesh_cell)
    assert out["correct"], out["checks"]


@pytest.mark.parametrize("fault", sorted(faults.FAULTS)
                         + sorted(faults.MESH_FAULTS))
def test_mesh_fault_is_caught(mesh_cell, fault):
    wrap = {**faults.FAULTS, **faults.MESH_FAULTS}[fault]
    out = _run(mesh_cell, wrap)
    assert not out["correct"], out["checks"]


def test_mesh_control_is_caught(mesh_cell):
    devices = jax.devices()[:4]
    ctl = spec.load_reference(mesh_cell.traffic).run(
        mesh_cell.config, mesh_cell.traffic, SEED, main.CHECK_ROUNDS,
        store="float8_e4m3fn", operand_dtype="float8_e4m3fn",
        devices=devices, keep_theta1=True)
    _, read = main.reference_readings(mesh_cell, ctl, SEED, devices)
    ok, checks = compare.verdict(read, mesh_cell.limits)
    assert not ok, checks
