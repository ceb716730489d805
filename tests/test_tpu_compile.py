"""Compile the main path's Pallas kernels for a TPU v5e without a chip.

The TPU compiler is installed with JAX and compiles for a described
``v5e:2x2`` topology, so these tests catch what interpret mode cannot: a
block shape Mosaic refuses, an op it cannot lower, a kernel that needs more
VMEM than the chip allows.  Each test compiles one kernel at the shapes of
``chip_smoke.py``'s main phase (granite-8b at published widths, one layer, a
6,144-row vocabulary slice, W=2, sequence 2,048) and checks that the
program holds a Mosaic kernel (``tpu_custom_call``); the OTA round's
kernels also compile at their default column tile, which does not divide D,
with no temp bytes: no plane is padded or copied.  The gated fading step
compiles to a loop (never a ``conditional``, which makes the compiler
reserve HBM for program overlays) that keeps the donated h planes in place.
Nothing runs.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and pytest-xdist workers all
import this file.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import admm_update, flash_attention, linear_scan
from repro.kernels import ota, ota_round, phy_population

#: packed parameter count of the main phase's model (granite-8b, 1 layer,
#: vocab 6,144): tests/test_tpu_compile.py::test_main_phase_dim pins it
MAIN_D = 243_281_920
MAIN_W = 2
SEQ = 2048


@pytest.fixture(scope="module")
def one_chip():
    # conftest.py keeps the compile cache off: a described chip's
    # executables could be written to it but never read back
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    return SingleDeviceSharding(topo.devices[0])


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_mosaic(fn, *args, donate=()):
    compiled = jax.jit(fn, donate_argnums=donate).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _assert_no_plane_copy(compiled):
    """The round kernels read and write the (W, D) planes where they lie:
    a pad to a tile multiple, or a copy around a kernel, would show as
    temp bytes (at MAIN_D a padded plane set does not fit the chip)."""
    assert compiled.memory_analysis().temp_size_in_bytes == 0


def test_main_phase_dim():
    import dataclasses
    import math

    from repro.models.registry import build_model, get_config
    cfg = dataclasses.replace(get_config("granite-8b"), n_layers=1,
                              vocab_size=6144)
    shapes = jax.eval_shape(build_model(cfg).init, jax.random.PRNGKey(0))
    assert sum(math.prod(l.shape) for l in jax.tree.leaves(shapes)) \
        == MAIN_D


def test_ota_round_stats(one_chip):
    planes = [_spec(one_chip, (MAIN_W, MAIN_D))] * 5
    _assert_no_plane_copy(_assert_mosaic(
        lambda *p: ota_round.ota_round_stats(*p, 0.5), *planes))


def test_ota_demodulate_dyn(one_chip):
    plane = _spec(one_chip, (MAIN_D,))
    _assert_no_plane_copy(_assert_mosaic(
        lambda y, z, p2, ia: ota.ota_demodulate_dyn(y, z, p2, ia),
        plane, plane, plane, _spec(one_chip, ())))


def test_ota_round_theta_fused_w256(one_chip):
    W, D = 256, 65536
    plane = _spec(one_chip, (W, D))

    def fn(t, lre, lim, hre, him, noise, mask, txre, txim, wre, wim):
        return ota_round.ota_round_theta(
            t, lre, lim, hre, him, noise, 1.0, 0.5, mask=mask,
            htx=(txre, txim), chan=(wre, wim, 0.9, 0.4359, jnp.float32(1)))

    _assert_mosaic(fn, *[plane] * 5, _spec(one_chip, (D,)),
                   _spec(one_chip, (W,)), *[plane] * 4)


def test_flash_attention_forward(one_chip):
    q = _spec(one_chip, (1, 32, SEQ, 128), jnp.bfloat16)
    _assert_mosaic(lambda q, k, v: flash_attention.flash_attention(q, k, v),
                   q, q, q)


def test_flash_attention_grad(one_chip):
    q = _spec(one_chip, (1, 32, SEQ, 128), jnp.bfloat16)

    def loss(q, k, v):
        o = flash_attention.flash_attention(q, k, v)
        return jnp.sum(o.astype(jnp.float32))

    _assert_mosaic(jax.grad(loss, argnums=(0, 1, 2)), q, q, q)


def test_linear_scan_forward(one_chip):
    a = _spec(one_chip, (1, SEQ, 4096))
    _assert_mosaic(lambda a, b: linear_scan.linear_scan(a, b), a, a)


def test_linear_scan_grad(one_chip):
    a = _spec(one_chip, (1, SEQ, 4096))
    grad = jax.grad(lambda a, b: jnp.sum(linear_scan.linear_scan(a, b)),
                    argnums=(0, 1))
    _assert_mosaic(grad, a, a)


def test_population_step(one_chip):
    plane = _spec(one_chip, (1 << 20,))

    def fn(*planes):
        return phy_population.population_step(
            *planes, 0.9, 0.4359, jnp.float32(1), 1.0, 10.0, 100.0, 3.0, 1.0)

    _assert_mosaic(fn, *[plane] * 12)


def test_admm_dual_update(one_chip):
    plane = _spec(one_chip, (MAIN_W, MAIN_D))
    _assert_no_plane_copy(_assert_mosaic(
        lambda lre, lim, hre, him, t, T: admm_update.admm_dual_update(
            lre, lim, hre, him, t, T, 0.5),
        *[plane] * 5, _spec(one_chip, (MAIN_D,)), donate=(0, 1)))


def test_gated_fading_step_keeps_h_in_place(one_chip):
    """The packed fading step at the main phase's size: the draw sits in a
    ``while`` loop, no ``conditional`` is compiled, and the donated (W, D)
    planes are aliased to the output with no copy of either."""
    import re

    from repro.core.channel import ChannelConfig
    from repro.core.cplx import Complex
    from repro.core.tree_ota import TreeChannel, step_channel_packed
    plane = _spec(one_chip, (MAIN_W, MAIN_D))
    chan = TreeChannel(h=Complex(plane, plane),
                       age=_spec(one_chip, (), jnp.int32))
    ccfg = ChannelConfig(n_workers=MAIN_W, coherence_iters=10)
    compiled = jax.jit(lambda k, c: step_channel_packed(k, c, ccfg),
                       donate_argnums=(1,)).lower(
        _spec(one_chip, (2,), jnp.uint32), chan).compile()
    text = compiled.as_text()
    assert " while(" in text and " conditional(" not in text
    shape = f"f32[{MAIN_W},{MAIN_D}]"
    assert not re.search(re.escape(shape) + r"\S* copy(-start)?\(", text)
    assert compiled.memory_analysis().alias_size_in_bytes \
        >= 2 * MAIN_W * MAIN_D * 4
