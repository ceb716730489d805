"""A new traffic mix, a new architecture and a cell's own per-layer metrics
are picked up from their files and entries, with no edit to any file the
benchmark already has.

    PYTHONPATH=bench JAX_PLATFORMS=cpu python -m pytest bench/tests -q
"""
import dataclasses
import json
import os
import shutil

import pytest

from harness import spec

#: the per-layer metrics of ``granite8b-l1-w2``, in ``BENCHMARK.json``'s
#: order
GRANITE_PER_LAYER = ["idle_share", "mfu", "ota_round_roofline", "local_ms",
                     "penalty_ms", "chan_step_ms", "pack_ms", "receive_ms",
                     "noise_ms", "dual_ms", "unscoped_ms"]

#: a plain model of a sparse-expert architecture, as a later change would
#: add it: only what ``model_config`` reads
MOE_MODEL = '''
PROGRAM_FIELDS = {"num_hidden_layers": "n_layers", "vocab_size": "vocab_size",
                  "num_experts": "n_experts",
                  "num_experts_per_tok": "n_experts_active",
                  "moe_intermediate_size": "moe_d_ff"}


def check(config):
    pass
'''

#: its configuration: qwen3-moe-30b-a3b, 4 of 48 layers, 8 of 128 experts
#: held here, an eighth of the vocabulary
MOE_CONFIG = {"name": "qwen3-moe-l4", "arch": "qwen3-moe-30b-a3b",
              "model_type": "moe_fixture", "hidden_size": 2048,
              "num_hidden_layers": 4, "vocab_size": 18992, "num_experts": 8,
              "num_experts_per_tok": 8, "moe_intermediate_size": 768,
              "torch_dtype": "bfloat16",
              "reduced": ["num_hidden_layers", "vocab_size", "num_experts"]}


def _fl_trainer(root=spec.ROOT):
    return spec.load_system({"system": "fl_trainer"}, str(root))


def _published(arch):
    """The program registry's entry."""
    _fl_trainer().import_program()
    from repro.models.registry import get_config
    return get_config(arch)


@pytest.fixture
def checkout(tmp_path):
    """A copy of the benchmark."""
    root = tmp_path / "checkout"
    shutil.copytree(spec.BENCH_DIR, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(spec.ROOT, "BENCHMARK.json"), root)
    return root


def _files(root):
    return {p: p.read_bytes() for p in root.rglob("*")
            if p.is_file() and "__pycache__" not in p.parts}


def _unchanged(before):
    for p, data in before.items():
        assert p.read_bytes() == data, f"{p} was edited"


def _add_cell(root, name, config="granite-8b-l1", opt_in=None):
    """Files and one workload entry for a new cell on the granite traffic
    at four workers; returns the benchmark with the entry."""
    traffic = json.loads((root / "bench" / "traffic" /
                          "w2-ls2-1x2048-snr5-pallas.json").read_text())
    traffic.update(workers=4, seq_len=1024, why="four workers, short rows")
    (root / "bench" / "traffic" / "w4-ls2-1x1024.json").write_text(
        json.dumps(traffic))
    (root / "bench" / "limits" / f"{name}.json").write_text(
        json.dumps({"loss": 1, "inv_alpha": 1, "dtheta1": 1,
                    "dTheta3_med": 1, "lam3": 1}))
    if opt_in is not None:
        (root / "bench" / "cells").mkdir(exist_ok=True)
        (root / "bench" / "cells" / f"{name}.json").write_text(
            json.dumps({"per_layer": opt_in}))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": name, "config": config,
                               "traffic": "w4-ls2-1x1024", "chips": 1,
                               "why": "four workers"})
    return bench


def test_new_traffic_file_is_found(checkout):
    before = _files(checkout)
    bench = _add_cell(checkout, "granite8b-l1-w4")
    cell = spec.load_cell("granite8b-l1-w4", bench, root=str(checkout))
    assert cell.traffic["workers"] == 4 and cell.traffic["seq_len"] == 1024
    assert cell.config["name"] == "granite-8b-l1"
    assert cell.root == str(checkout)
    # metrics without a ``workloads`` list apply to every cell, the new one
    # included; the roofline names the cells whose kernels it reads
    assert [m["name"] for m in cell.per_layer] == ["idle_share", "mfu"]
    _unchanged(before)


def test_new_architecture_is_found(checkout):
    """A sparse-expert configuration, its plain model, traffic and limits
    added as files, with entries: the sizes that the model's
    ``PROGRAM_FIELDS`` names reach the program's ``ModelConfig``, and
    every other field is the registry's."""
    before = _files(checkout)
    (checkout / "bench" / "models" / "moe_fixture.py").write_text(MOE_MODEL)
    (checkout / "bench" / "configs" / "qwen3-moe-l4.json").write_text(
        json.dumps(MOE_CONFIG))
    bench = _add_cell(checkout, "qwen3moe-l4-w4", config="qwen3-moe-l4")
    bench["configs"].append({"name": "qwen3-moe-l4",
                             "source": "https://huggingface.co/Qwen",
                             "file": "bench/configs/qwen3-moe-l4.json",
                             "reduced": MOE_CONFIG["reduced"],
                             "why": "sparse experts"})
    cell = spec.load_cell("qwen3moe-l4-w4", bench, root=str(checkout))
    assert spec.load_model(cell.config, cell.root).__file__ == str(
        checkout / "bench" / "models" / "moe_fixture.py")
    fl_trainer = _fl_trainer(cell.root)
    assert fl_trainer.__file__ == str(
        checkout / "bench" / "systems" / "fl_trainer.py")

    got = fl_trainer.model_config(cell.config, cell.root)
    published = _published("qwen3-moe-30b-a3b")
    assert (got.n_layers, got.vocab_size, got.n_experts,
            got.n_experts_active, got.moe_d_ff) == (4, 18992, 8, 8, 768)
    assert published.n_experts == 128
    for f in dataclasses.fields(published):
        if f.name not in ("n_layers", "vocab_size", "n_experts",
                          "n_experts_active", "moe_d_ff"):
            assert getattr(got, f.name) == getattr(published, f.name), f.name
    _unchanged(before)


def _config(path):
    with open(os.path.join(spec.BENCH_DIR, path)) as f:
        return json.load(f)


@pytest.mark.parametrize("path,fields", [
    ("configs/granite-8b-l1.json",
     dict(n_layers=1, vocab_size=6144, d_model=4096, d_ff=14336, n_heads=32,
          n_kv_heads=8, head_dim=128, rope_theta=10000.0, norm_eps=1e-6)),
    ("configs/granite-8b-l4.json",
     dict(n_layers=4, vocab_size=6144, d_model=4096, d_ff=14336, n_heads=32,
          n_kv_heads=8, head_dim=128, rope_theta=10000.0, norm_eps=1e-6)),
    ("tests/tiny.json",
     dict(n_layers=1, vocab_size=512, d_model=128, d_ff=256, n_heads=4,
          n_kv_heads=4, head_dim=32, rope_theta=10000.0, norm_eps=1e-6)),
])
def test_dense_model_config_is_pinned(path, fields):
    """The llama model's map gives the program the same granite it built
    from the nine dense keys before the map moved into the model file."""
    assert _fl_trainer().model_config(_config(path)) == dataclasses.replace(
        _published("granite-8b"), **fields)


def test_missing_key_is_named():
    config = _config("configs/granite-8b-l1.json")
    del config["head_dim"]
    with pytest.raises(spec.SpecError,
                       match=r"'head_dim'.*bench/models/llama\.py"):
        _fl_trainer().model_config(config)


def test_unknown_field_is_named(checkout):
    (checkout / "bench" / "models" / "moe_fixture.py").write_text(
        MOE_MODEL.replace('"n_experts"', '"n_expert"'))
    with pytest.raises(spec.SpecError,
                       match=r"bench/models/moe_fixture\.py.*'n_expert'"):
        _fl_trainer(checkout).model_config(MOE_CONFIG, str(checkout))


def test_cell_joins_listed_metrics(checkout):
    """A new cell's own file names listed per-layer metrics; it reports
    them beside those that apply to every cell, in ``BENCHMARK.json``'s
    order, and the listed cell's metrics stay as they were."""
    before = _files(checkout)
    bench = _add_cell(checkout, "granite8b-l1-w4",
                      opt_in=["receive_ms", "local_ms"])
    cell = spec.load_cell("granite8b-l1-w4", bench, root=str(checkout))
    assert [m["name"] for m in cell.per_layer] == [
        "idle_share", "mfu", "local_ms", "receive_ms"]
    listed = spec.load_cell("granite8b-l1-w2", bench, root=str(checkout))
    assert [m["name"] for m in listed.per_layer] == GRANITE_PER_LAYER
    assert [m["name"] for m in spec.load_cell("granite8b-l1-w2").per_layer] \
        == GRANITE_PER_LAYER
    _unchanged(before)


@pytest.mark.parametrize("name,why", [
    ("attn_ms", "no per-layer metric"), ("mfu", "no per-layer metric"),
    ("local_ms", "does not report")])
def test_cell_opt_in_is_checked(checkout, name, why):
    """An unknown name, a metric that applies to every cell already, and
    one whose end-to-end metric the cell does not report are refused."""
    bench = _add_cell(checkout, "granite8b-l1-w4", opt_in=[name])
    if why == "does not report":
        for m in bench["end_to_end"]:
            if m["name"] == "round_s":
                m["workloads"] = ["granite8b-l1-w2"]
    with pytest.raises(spec.SpecError, match=why):
        spec.load_cell("granite8b-l1-w4", bench, root=str(checkout))


def test_pieces_are_found_by_name():
    """The plain model, the program's system and the reference rounds are
    files named by the configuration's ``model_type`` and the traffic
    file's ``system`` and ``reference``, found under the cell's root: a
    new architecture arrives as a plain model (with its ``PROGRAM_FIELDS``)
    and a configuration, a new trainer as a system and a reference."""
    cell = spec.load_cell("granite8b-l1-w2")
    assert spec.load_model(cell.config).__file__ == os.path.join(
        spec.BENCH_DIR, "models", "llama.py")
    assert spec.load_system(cell.traffic).__file__ == os.path.join(
        spec.BENCH_DIR, "systems", "fl_trainer.py")
    assert spec.load_reference(cell.traffic).__file__ == os.path.join(
        spec.BENCH_DIR, "references", "afadmm_sgd.py")
    with pytest.raises(spec.SpecError, match="bench/models/mamba.py"):
        spec.load_model(dict(cell.config, model_type="mamba"))


def test_opted_in_metric_that_reads_nothing_fails(checkout):
    """The traced run's guard holds for a cell's own list as for a
    metric's: a metric that the cell names and that reads nothing fails
    the run, where an unlisted one is only left out."""
    from harness.main import Context, per_layer_metrics
    ctx = Context(workload="granite8b-l1-w4", config={}, traffic={},
                  chips=1, peaks={}, model=None, n_params=1, rounds=0,
                  window_s=1.0)
    bench = _add_cell(checkout, "granite8b-l1-w4")
    cell = spec.load_cell("granite8b-l1-w4", bench, root=str(checkout))
    assert per_layer_metrics(cell, ctx) == {}
    (checkout / "bench" / "cells").mkdir()
    (checkout / "bench" / "cells" / "granite8b-l1-w4.json").write_text(
        json.dumps({"per_layer": ["dual_ms"]}))
    cell = spec.load_cell("granite8b-l1-w4", bench, root=str(checkout))
    with pytest.raises(RuntimeError, match="dual_ms read nothing"):
        per_layer_metrics(cell, ctx)
