"""A new traffic mix is picked up from its file and an entry, with no edit
to any file the benchmark already has.

    PYTHONPATH=bench JAX_PLATFORMS=cpu python -m pytest bench/tests -q
"""
import json
import os
import shutil

import pytest

from harness import spec


def test_new_traffic_file_is_found(tmp_path):
    # a copy of the benchmark; only files are added to it
    root = tmp_path / "checkout"
    shutil.copytree(spec.BENCH_DIR, root / "bench")
    shutil.copy(os.path.join(spec.ROOT, "BENCHMARK.json"), root)
    before = {p: p.read_bytes() for p in root.rglob("*") if p.is_file()}

    traffic = json.loads((root / "bench" / "traffic" /
                          "w2-ls2-1x2048-snr5-pallas.json").read_text())
    traffic.update(workers=4, seq_len=1024, why="four workers, short rows")
    (root / "bench" / "traffic" / "w4-ls2-1x1024.json").write_text(
        json.dumps(traffic))
    (root / "bench" / "limits" / "granite8b-l1-w4.json").write_text(
        json.dumps({"loss": 1, "inv_alpha": 1, "dtheta1": 1,
                    "dTheta3_med": 1, "lam3": 1}))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": "granite8b-l1-w4",
                               "config": "granite-8b-l1",
                               "traffic": "w4-ls2-1x1024", "chips": 1,
                               "why": "four workers"})

    cell = spec.load_cell("granite8b-l1-w4", bench, root=str(root))
    assert cell.traffic["workers"] == 4 and cell.traffic["seq_len"] == 1024
    assert cell.config["name"] == "granite-8b-l1"
    # metrics without a ``workloads`` list apply to every cell, the new one
    # included; the roofline names the cells whose kernels it reads
    assert [m["name"] for m in cell.per_layer] == ["idle_share", "mfu"]
    for p, data in before.items():
        assert p.read_bytes() == data, f"{p} was edited"


def test_pieces_are_found_by_name():
    """The plain model, the program's system and the reference rounds are
    files named by the configuration's ``model_type`` and the traffic
    file's ``system`` and ``reference``: a new architecture or trainer
    arrives as a new file."""
    cell = spec.load_cell("granite8b-l1-w2")
    assert spec.load_model(cell.config).__file__ == os.path.join(
        spec.BENCH_DIR, "models", "llama.py")
    assert spec.load_system(cell.traffic).__file__ == os.path.join(
        spec.BENCH_DIR, "systems", "fl_trainer.py")
    assert spec.load_reference(cell.traffic).__file__ == os.path.join(
        spec.BENCH_DIR, "references", "afadmm_sgd.py")
    with pytest.raises(spec.SpecError, match="bench/models/mamba.py"):
        spec.load_model(dict(cell.config, model_type="mamba"))
