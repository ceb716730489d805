#!/usr/bin/env python3
"""Compile cells' steps for a described TPU v5e, without the chip.

    JAX_PLATFORMS=cpu python3 bench/tools/rehearse.py granite8b-l1-w2 ...

Builds each cell's trainer as a run does, places its state as a run does
(one described chip, or the cell's mesh over described chips), compiles
the step with the TPU compiler and prints ``memory_analysis`` per device
and whether Mosaic kernels (``tpu_custom_call``) are in it.  Nothing runs:
sizes only, never times.
"""
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
from jax.experimental import topologies  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from harness import spec  # noqa: E402


def main(names):
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    # the program asks the default backend whether to interpret its kernels
    jax.default_backend = lambda: "tpu"
    for name in names:
        cell = spec.load_cell(name)
        devices = topo.devices[:cell.chips]
        system = spec.load_system(cell.traffic).System(
            cell.config, cell.traffic, devices)
        if system.mesh is None:
            one = SingleDeviceSharding(devices[0])
            system.shardings = jax.tree.map(lambda _: one, system.shapes)
        t = time.perf_counter()
        step = system.compile()
        ma = step.memory_analysis()
        print(f"{name}: compiled for {len(devices)} described v5e chip(s) in "
              f"{time.perf_counter() - t:.1f} s; per device: arguments "
              f"{ma.argument_size_in_bytes / 1e9:.3f} GB, outputs "
              f"{ma.output_size_in_bytes / 1e9:.3f} GB, aliased "
              f"{ma.alias_size_in_bytes / 1e9:.3f} GB, temps "
              f"{ma.temp_size_in_bytes / 1e9:.3f} GB, code "
              f"{ma.generated_code_size_in_bytes / 1e6:.1f} MB; Mosaic "
              f"kernels: {'tpu_custom_call' in step.as_text()}", flush=True)
        if os.environ.get("REHEARSE_HLO"):
            with open(os.environ["REHEARSE_HLO"], "w") as f:
                f.write(step.as_text())
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
