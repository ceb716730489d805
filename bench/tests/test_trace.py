"""The reduction from trace intervals to the per-layer numbers, on a
hand-built trace whose intervals are known.

    PYTHONPATH=bench JAX_PLATFORMS=cpu python -m pytest bench/tests -q
"""
import importlib.util
import os

import pytest

from harness import trace as tr
from harness.main import Context

METRICS = os.path.join(os.path.dirname(os.path.dirname(__file__)), "metrics")

MS = 1_000_000


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(METRICS, f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def two_devices():
    """A 100 ms window (host spans) on two devices.

    dev 0: fusion 0-30, train_step.4 (a round kernel) 25-40, all-reduce
           50-70 of which
           60-70 under fusion.2 (60-80): busy 0-40, 50-80 = 70 ms,
           collective alone 50-60 = 10 ms
    dev 1: fusion 10-20, all-gather-start 30-50 alone: busy 30 ms,
           collective alone 20 ms
    """
    dev0 = [(0, 30 * MS, "fusion.1"), (25 * MS, 40 * MS, "train_step.4"),
            (50 * MS, 70 * MS, "all-reduce.3"), (60 * MS, 80 * MS, "fusion.2")]
    dev1 = [(10 * MS, 20 * MS, "fusion.1"),
            (30 * MS, 50 * MS, "all-gather-start.1")]
    host = [(0, 5 * MS, "data"), (5 * MS, 42 * MS, "dispatch"),
            (42 * MS, 100 * MS, "readback")]
    return tr.Trace(devices={"/device:TPU:0": dev0, "/device:TPU:1": dev1},
                    host=host)


def test_union_measure_minus_gaps():
    u = tr.union([(5, 9, "a"), (0, 3, "b"), (2, 4, "c"), (9, 10, "d")])
    assert u == [(0, 4), (5, 10)]
    assert tr.measure(u) == 9
    assert tr.minus([(0, 10)], [(2, 3), (5, 7)]) == 7
    assert tr.minus([(0, 4), (6, 10)], [(3, 7)]) == 6
    assert tr.minus([(0, 4)], []) == 4
    assert tr.gaps([(2, 4), (6, 8)], 0, 10) == [(0, 2), (4, 6), (8, 10)]


def test_ops_named_by_instruction():
    """A TPU trace names an operation by its instruction's whole text."""
    assert tr.instruction(
        '%train_step.4 = f32[1,243281920]{1,0:T(1,128)} custom-call('
        'f32[1]{0:T(128)} %bitcast.31), custom_call_target="tpu_custom_call"'
    ) == "train_step.4"
    assert tr.instruction("%all-reduce.3 = f32[8]{0} all-reduce("
                          "f32[8]{0} %fusion.2)") == "all-reduce.3"
    assert tr.instruction("fusion.1") == "fusion.1"


def test_busy_window_and_exposed(two_devices):
    assert tr.window(two_devices) == (0, 100 * MS)
    assert tr.busy_ns(two_devices) == {"/device:TPU:0": 70 * MS,
                                       "/device:TPU:1": 30 * MS}
    coll = lambda n: bool(tr.COLLECTIVE.search(n))
    assert tr.exposed_ns(two_devices, coll) == {"/device:TPU:0": 10 * MS,
                                                "/device:TPU:1": 20 * MS}
    assert tr.op_time_ns(two_devices, lambda n: n == "fusion.1") == 40 * MS


def test_breakdown(two_devices):
    top = tr.top_ops(two_devices, 2)
    assert top[0] == ["fusion.1", 0.02]        # 40 ms over two devices
    gaps = tr.idle_gaps(two_devices, 3)
    # device 0 idles 40-50 (host in readback) and 80-100 (readback)
    assert gaps == [["readback", 0.02], ["readback", 0.01]]


#: two Mosaic calls of a compiled step: a round kernel on the packed
#: plane of D = 1000 parameters, and an attention kernel
PROGRAM = """\
  %flash_attention.3 = bf16[2,1,4,64,32]{4,3,2,1,0} custom-call(%a, %b), custom_call_target="tpu_custom_call", operand_layout_constraints={bf16[2,1,4,64,32]{4,3,2,1,0}}
  %fusion.7 = f32[2,1000]{1,0} fusion(%c), kind=kLoop, calls=%fused_computation.7
  %train_step.4 = f32[1,1000]{1,0:T(1,128)} custom-call(%d, %e), custom_call_target="tpu_custom_call", operand_layout_constraints={f32[1]{0}, f32[2,1000]{1,0}}
"""


def test_round_kernels_found_by_shape():
    roof = _reader("ota_round_roofline")
    assert set(tr.mosaic_calls(PROGRAM)) == {"flash_attention.3",
                                             "train_step.4"}
    assert roof.round_kernels(PROGRAM, 1000) == {"train_step.4"}
    assert roof.round_kernels(PROGRAM, 999) == set()


def _ctx(trace, **kw):
    base = dict(workload="w", config={}, traffic={"workers": 2,
                                                  "coherence_iters": 10},
                chips=2, peaks={"bf16_flops_per_s": 197e12,
                                "hbm_bytes_per_s": 819e9},
                model=None, n_params=1000, rounds=1, window_s=0.1,
                trace=trace, program_text=PROGRAM, trace_window_s=0.1,
                busy_s=0.05)
    base.update(kw)
    return Context(**base)


def test_readers(two_devices):
    ctx = _ctx(two_devices)
    assert _reader("idle_share").read(ctx) == pytest.approx(50.0)
    # the larger of 10 and 20 ms in a 100 ms window
    assert _reader("collective_exposed_share").read(ctx) == pytest.approx(20.0)
    roof = _reader("ota_round_roofline")
    need = roof.min_bytes_per_round(2, 1000)
    assert roof.read(ctx) == pytest.approx(100 * need / 819e9 / 0.015)


def test_readers_find_nothing():
    """A reader with nothing to read returns None, never 0."""
    empty = tr.Trace(devices={"/device:TPU:0": [(0, MS, "fusion.1")]},
                     host=[(0, 2 * MS, "dispatch")])
    ctx = _ctx(empty, chips=1)
    assert _reader("collective_exposed_share").read(ctx) is None
    assert _reader("ota_round_roofline").read(ctx) is None
    assert _reader("idle_share").read(_ctx(None)) is None
