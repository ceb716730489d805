"""Device time by layer scope: from the compiled step's HLO metadata and a
trace to the eight per-layer numbers, on hand-built HLO text and traces
whose intervals are known; and the scopes in the tiny cell's compiled
step.

    PYTHONPATH=bench JAX_PLATFORMS=cpu python -m pytest bench/tests -q
"""
import importlib.util
import json
import os

import jax
import pytest

from harness import scopes, spec
from harness import trace as tr
from harness.main import Context

HERE = os.path.dirname(os.path.abspath(__file__))
METRICS = os.path.join(os.path.dirname(HERE), "metrics")
MS = 1_000_000

#: reader -> the scope it reads
READERS = {"chan_step_ms": "chan_step", "local_ms": "local_steps",
           "penalty_ms": "penalty", "pack_ms": "ota_pack",
           "receive_ms": "ota_receive", "noise_ms": "ota_noise",
           "dual_ms": "ota_dual"}
TOP_READERS = ("chan_step_ms", "local_ms", "pack_ms", "receive_ms",
               "dual_ms")


def _reader(name):
    s = importlib.util.spec_from_file_location(
        name, os.path.join(METRICS, f"{name}.py"))
    mod = importlib.util.module_from_spec(s)
    s.loader.exec_module(mod)
    return mod


def _meta(op_name):
    return (f'metadata={{op_name="{op_name}" source_file="a.py" '
            f'source_line=1}}')


#: a step whose local-step ``while`` holds two body ops, the scope wrapped
#: in transforms, and three ops outside every scope, two of them with
#: names that hold a scope's name as a substring only
PROGRAM = f"""\
HloModule jit_train_step

%body.1 (p: (s32[], f32[8])) -> (s32[], f32[8]) {{
  %fusion.2 = f32[8]{{0}} fusion(%a), kind=kLoop, calls=%fc.2, {_meta("jit(train_step)/local_steps/while/body/vmap(transpose(jvp(fwd)))/dot_general")}
  %fusion.3 = f32[8]{{0}} fusion(%b), kind=kLoop, calls=%fc.3, {_meta("jit(train_step)/local_steps/while/body/closed_call/vmap(transpose(jvp(penalty)))/mul")}
  ROOT %tuple.4 = (s32[], f32[8]) tuple(%i, %fusion.3)
}}

%fused_computation.15 (param_0: bf16[8]) -> f32[1,8] {{
  %param_0 = bf16[8]{{0}} parameter(0)
  %convert.16 = f32[8]{{0}} convert(%param_0), {_meta("jit(train_step)/ota_pack/convert_element_type")}
  ROOT %bitcast.17 = f32[1,8]{{1,0}} bitcast(%convert.16)
}}

ENTRY %main.20 (a: f32[8]) -> (f32[8]) {{
  %fusion.5 = f32[2,1000]{{1,0}} fusion(%c), kind=kLoop, calls=%fc.5, {_meta("jit(train_step)/chan_step/jit(_normal)/mul")}
  %while.1 = (s32[], f32[8]) while(%t), condition=%cond.1, body=%body.1, {_meta("jit(train_step)/local_steps/while")}
  %copy-start.6 = (f32[2,1000]{{1,0}}, u32[]) copy-start(%x), {_meta("jit(train_step)/ota_pack/concatenate")}
  %train_step.7 = f32[1,1000]{{1,0}} custom-call(%d), custom_call_target="tpu_custom_call", {_meta("jit(train_step)/ota_receive/pallas_call")}
  %fusion.8 = f32[1000]{{0}} fusion(%e), kind=kLoop, calls=%fc.8, {_meta("jit(train_step)/ota_receive/ota_noise/jit(_normal)/mul")}
  %train_step.9 = f32[2,1000]{{1,0}} custom-call(%f), custom_call_target="tpu_custom_call", {_meta("jit(train_step)/ota_dual/pallas_call")}
  %fusion.10 = bf16[1000]{{0}} fusion(%g), kind=kLoop, calls=%fc.10, {_meta("jit(train_step)/convert_element_type")}
  %fusion.11 = f32[8]{{0}} fusion(%h), kind=kLoop, calls=%fc.11, {_meta("jit(train_step)/penalty_grad/mul")}
  %fusion.12 = f32[8]{{0}} fusion(%k), kind=kLoop, calls=%fc.12, {_meta("jit(step_channel_packed)/mul")}
  %bitcast.13 = f32[8]{{0}} bitcast(%fusion.12)
  %convert_bitcast_fusion.15 = f32[1,8]{{1,0}} fusion(%a), kind=kLoop, calls=%fused_computation.15
  ROOT %tuple.14 = (f32[8]) tuple(%bitcast.13)
}}
"""


def _device(shift_ms, chan_ms):
    """One device's round, in ms: chan_step for ``chan_ms``; the local-step
    ``while`` 30 (its body ops 13 + 13, penalty 13 of them); pack 5;
    receive 15 (noise 5 of them); dual 10; outside every scope 6."""
    c = chan_ms
    spans = [("fusion.5", 0, c), ("while.1", c, c + 30),
             ("fusion.2", c + 2, c + 15), ("fusion.3", c + 15, c + 28),
             ("copy-start.6", c + 30, c + 35),
             ("train_step.7", c + 35, c + 45), ("fusion.8", c + 45, c + 50),
             ("train_step.9", c + 50, c + 60), ("fusion.10", c + 60, c + 62),
             ("fusion.11", c + 62, c + 65), ("fusion.12", c + 65, c + 66)]
    return [((s + shift_ms) * MS, (e + shift_ms) * MS, n) for n, s, e in spans]


@pytest.fixture
def two_devices():
    """Device 0 spends 10 ms in chan_step, device 1 20 ms: busy 76 and
    86 ms."""
    return tr.Trace(devices={"/device:TPU:0": _device(0, 10),
                             "/device:TPU:1": _device(0, 20)},
                    host=[(0, 100 * MS, "dispatch")])


def _ctx(trace, program=PROGRAM, rounds=1):
    busy = tr.busy_ns(trace)
    return Context(workload="w", config={}, traffic={}, chips=2, peaks={},
                   model=None, n_params=1000, rounds=rounds, window_s=0.1,
                   trace=trace, program_text=program, trace_window_s=0.1,
                   busy_s=sum(busy.values()) / len(busy) / 1e9)


def test_op_names_parsed_from_hlo_text():
    names = scopes.op_names(PROGRAM)
    assert names["copy-start.6"] == "jit(train_step)/ota_pack/concatenate"
    assert names["fusion.3"].endswith("vmap(transpose(jvp(penalty)))/mul")
    assert "tuple.4" not in names and "bitcast.13" not in names
    # a fusion with no op_name of its own takes its fused instructions'
    assert names["convert_bitcast_fusion.15"] \
        == "jit(train_step)/ota_pack/convert_element_type"


@pytest.mark.parametrize("op_name,scope,want", [
    ("a/local_steps/while/body/vmap(transpose(jvp(penalty)))/mul",
     "penalty", True),
    ("a/local_steps/while/body/vmap(transpose(jvp(penalty)))/mul",
     "local_steps", True),
    ("jit(train_step)/ota_receive/ota_noise/mul", "ota_noise", True),
    ("local_steps", "local_steps", True),
    ("jit(train_step)/penalty_grad/mul", "penalty", False),
    ("jit(step_channel_packed)/mul", "chan_step", False),
    ("jit(train_step)/my_ota_pack/mul", "ota_pack", False),
])
def test_scope_is_a_whole_segment(op_name, scope, want):
    assert scopes.carries(op_name, scope) is want


def test_readers(two_devices):
    ctx = _ctx(two_devices)
    got = {r: _reader(r).read(ctx) for r in READERS}
    # chan_step averaged over the two devices; the while and its body
    # counted once; the penalty and noise inside their parents
    assert got == pytest.approx({"chan_step_ms": 15.0, "local_ms": 30.0,
                                 "penalty_ms": 13.0, "pack_ms": 5.0,
                                 "receive_ms": 15.0, "noise_ms": 5.0,
                                 "dual_ms": 10.0})
    # the convert and the two substring traps are outside every scope
    assert _reader("unscoped_ms").read(ctx) == pytest.approx(6.0)
    # per round: two rounds halve every number
    assert _reader("local_ms").read(_ctx(two_devices, rounds=2)) \
        == pytest.approx(15.0)


def test_top_level_and_unscoped_add_up_to_busy(two_devices):
    ctx = _ctx(two_devices)
    total = sum(_reader(r).read(ctx) for r in TOP_READERS) \
        + _reader("unscoped_ms").read(ctx)
    assert total == pytest.approx(ctx.busy_s * 1e3) == pytest.approx(81.0)


def test_a_scope_that_labels_nothing_reads_none(two_devices):
    # the dual update's op left out of the program: its scope labels
    # nothing, while the others still read
    program = PROGRAM.replace("jit(train_step)/ota_dual/pallas_call",
                              "jit(train_step)/pallas_call")
    ctx = _ctx(two_devices, program)
    assert _reader("dual_ms").read(ctx) is None
    assert _reader("receive_ms").read(ctx) == pytest.approx(15.0)
    # the scope labels an op, but none of its ops ran
    no_dual = tr.Trace(devices={d: [o for o in ops if o[2] != "train_step.9"]
                                for d, ops in two_devices.devices.items()},
                       host=two_devices.host)
    assert _reader("dual_ms").read(_ctx(no_dual)) is None
    # untraced
    untraced = _ctx(two_devices)
    untraced.trace = None
    assert all(_reader(r).read(untraced) is None
               for r in list(READERS) + ["unscoped_ms"])


def test_a_program_with_no_scopes(two_devices):
    """A program built before the scopes: no op is in any scope, and the
    whole busy time is unscoped."""
    program = "\n".join(l for l in PROGRAM.splitlines()
                        if "metadata" not in l)
    ctx = _ctx(two_devices, program)
    assert all(_reader(r).read(ctx) == 0.0 for r in READERS)
    assert _reader("unscoped_ms").read(ctx) == pytest.approx(81.0)


def _tiny_cell(traffic):
    with open(os.path.join(HERE, "tiny.json")) as f:
        config = json.load(f)
    tr_ = spec.load_traffic(traffic)
    tr_["seq_len"] = 64
    return config, tr_


@pytest.mark.parametrize("traffic,chips", [
    ("w2-ls2-1x2048-snr5-pallas", 1), ("w2-ls2-1x2048-snr5-mesh122", 4)])
def test_tiny_step_carries_every_scope(traffic, chips):
    """The tiny cell's compiled step, one device and the (1, 2, 2) mesh:
    every scope the readers read labels an instruction, and no
    instruction carries two top-level scopes."""
    config, tr_ = _tiny_cell(traffic)
    system = spec.load_system(tr_).System(config, tr_, jax.devices()[:chips])
    from repro.obs import LAYERS
    assert set(LAYERS) == set(scopes.TOP + scopes.NESTED)
    text = system.compile().as_text()
    for scope in READERS.values():
        assert scopes.labelled(text, scope), scope
    for op in scopes.op_names(text).values():
        assert sum(scopes.carries(op, s) for s in scopes.TOP) <= 1, op
