"""Column tiles of the worker-grid OTA kernels (``kernels/ota._block_cols``).

* The tile is the widest multiple of 128 lanes whose working set fits
  ``VMEM_TILE_BUDGET``, for every plane count the callers launch with:
  wider than 1,024 lanes at W ≤ 8, 768 at W = 256.
* The last tile may overhang the plane (n = k·bc + r): the stats kernel's
  y, Σ|h|² and energies match the jnp path, and the dual and demodulate
  kernels give the same bits as one full-width block, in interpret mode
  (which fills an overhanging read with NaN).
* The launch record (``repro.obs.profiling.grid_launches``) counts
  cdiv(n, bc) grid steps and no padded column.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import transport
from repro.core.channel import rayleigh
from repro.kernels import admm_update, ota, ota_round, phy_channel
from repro.kernels.ota import VMEM_TILE_BUDGET, _block_cols, vmem_block_cols
from repro.obs.profiling import grid_launches

KEY = jax.random.PRNGKey(0)
TOL = dict(rtol=1e-5, atol=1e-6)     # tests/test_fused_round.py's
RHO = 0.7

#: (W, d) planes per launch: receive 4, masked receive 5, round stats 5 to
#: 12 (mask, CSI, fused fading), dual 8 or 9, demodulate 4 (W = 1)
PLANES = [4, 5, 6, 8, 9, 12]


@pytest.fixture(autouse=True)
def _no_env_tile(monkeypatch):
    monkeypatch.delenv("REPRO_OTA_BLOCK_COLS", raising=False)


@pytest.mark.parametrize("n_planes", PLANES)
@pytest.mark.parametrize("W", [1, 2, 8, 256])
def test_tile_fits_vmem_budget(W, n_planes):
    bc = _block_cols(None, W, n_planes, 10 ** 9)
    w8 = -(-W // 8) * 8
    assert bc == vmem_block_cols(W, n_planes)
    assert bc % 128 == 0
    assert 3 * n_planes * w8 * bc * 4 <= VMEM_TILE_BUDGET
    # the widest such tile: 128 lanes more would not fit
    assert 3 * n_planes * w8 * (bc + 128) * 4 > VMEM_TILE_BUDGET
    if W <= 8:
        assert bc > 1024


def test_tile_at_the_callers_shapes():
    assert vmem_block_cols(2, 5) == 26112      # round stats, W = 2
    assert vmem_block_cols(2, 8) == 16384      # dual, W = 2
    assert vmem_block_cols(1, 4) == 32768      # demodulate
    assert vmem_block_cols(256, 5) == 768      # round stats, W = 256


def test_tile_overrides_and_narrow_planes(monkeypatch):
    assert _block_cols(512, 2, 5, 10 ** 6) == 512
    assert _block_cols(None, 2, 5, 300) == 300         # one full block
    monkeypatch.setenv("REPRO_OTA_BLOCK_COLS", "640")
    assert _block_cols(None, 2, 5, 10 ** 6) == 640
    assert _block_cols(256, 2, 5, 10 ** 6) == 256


def _planes(W, n, seed):
    k = jax.random.split(jax.random.fold_in(KEY, seed), 3)
    theta = jax.random.normal(k[0], (W, n), jnp.float32)
    return theta, rayleigh(k[1], (W, n)), rayleigh(k[2], (W, n))


#: a tile below the budget's (three steps and a remainder), and the
#: budget's own (two steps and a remainder)
TILES = [256, None]
#: remainders: none, a whole 128 lanes, and a width that is no multiple
#: of 128
REMS = [0, 128, 37]


def _n(bc, W, n_planes, rem):
    bc = bc or vmem_block_cols(W, n_planes)
    return bc, (3 if bc == 256 else 2) * bc + rem


@pytest.mark.parametrize("rem", REMS)
@pytest.mark.parametrize("tile", TILES)
def test_stats_ragged_last_block_matches_jnp(tile, rem):
    W = 2
    bc, n = _n(tile, W, 5, rem)
    theta, lam, h = _planes(W, n, rem)
    with grid_launches() as log:
        y, p2, e = ota_round.ota_round_stats(
            theta, lam.re, lam.im, h.re, h.im, RHO, block_cols=tile,
            interpret=True)
    assert log == [{"kernel": "ota_round_stats", "workers": W, "n": n,
                    "block_cols": bc, "steps": -(-n // bc),
                    "pad_cols": 0}]
    y0, p20, e0, _ = transport.ota_round_stats(theta, lam, h, RHO,
                                               backend="jnp")
    assert y.shape == p2.shape == (n,)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y0), **TOL)
    np.testing.assert_allclose(np.asarray(p2), np.asarray(p20), **TOL)
    np.testing.assert_allclose(np.asarray(e), np.asarray(e0), **TOL)


@pytest.mark.parametrize("rem", REMS)
@pytest.mark.parametrize("tile", TILES)
def test_dual_ragged_last_block_is_bitwise(tile, rem):
    W = 2
    bc, n = _n(tile, W, 9, rem)
    theta, lam, h = _planes(W, n, 10 + rem)
    Theta = jnp.mean(theta, axis=0) + 0.25
    nz = jax.random.normal(jax.random.fold_in(KEY, 99), (W, n))
    args = (lam.re, lam.im, h.re, h.im, theta, Theta, RHO, nz)
    with grid_launches() as log:
        got = admm_update.admm_dual_update(*args, block_cols=tile,
                                           interpret=True)
    assert [(l["steps"], l["pad_cols"]) for l in log] == [(-(-n // bc), 0)]
    one = admm_update.admm_dual_update(*args, block_cols=n, interpret=True)
    r = theta - Theta
    want = (lam.re + RHO * (h.re * r - nz), lam.im + RHO * h.im * r)
    for g, o, w in zip(got, one, want):
        assert g.shape == (W, n)
        np.testing.assert_array_equal(np.asarray(g), np.asarray(o))
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), **TOL)


@pytest.mark.parametrize("rem", REMS)
@pytest.mark.parametrize("tile", TILES)
def test_demodulate_ragged_last_block_is_bitwise(tile, rem):
    bc, n = _n(tile, 1, 4, rem)
    k = jax.random.split(jax.random.fold_in(KEY, 20 + rem), 3)
    y, z = jax.random.normal(k[0], (n,)), jax.random.normal(k[1], (n,))
    p2 = jnp.abs(jax.random.normal(k[2], (n,))) + 0.05
    ia = jnp.float32(0.37)
    with grid_launches() as log:
        got = ota.ota_demodulate_dyn(y, z, p2, ia, block_cols=tile,
                                     interpret=True)
    assert [(l["steps"], l["pad_cols"]) for l in log] == [(-(-n // bc), 0)]
    one = ota.ota_demodulate_dyn(y, z, p2, ia, block_cols=n, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(one))
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray((y + z * ia) / p2), **TOL)


@pytest.mark.parametrize("masked", [False, True])
def test_receive_ragged_last_block_is_bitwise(masked):
    W, n = 3, 3 * 256 + 37
    theta, s, h = _planes(W, n, 30)
    z = jax.random.normal(jax.random.fold_in(KEY, 31), (n,))
    mask = jnp.array([True, False, True])
    if masked:
        fn = lambda bc: phy_channel.ota_receive_masked(
            s.re, s.im, h.re, h.im, mask, z, 0.5, block_cols=bc,
            interpret=True)
    else:
        fn = lambda bc: ota.ota_receive(s.re, s.im, h.re, h.im, z, 0.5,
                                        block_cols=bc, interpret=True)
    with grid_launches() as log:
        got = fn(256)
    assert [(l["steps"], l["pad_cols"]) for l in log] == [(4, 0)]
    np.testing.assert_array_equal(np.asarray(got), np.asarray(fn(n)))


def test_launch_records_nest():
    W, n = 2, 1000
    theta, lam, h = _planes(W, n, 40)
    with grid_launches() as outer:
        with grid_launches() as inner:
            ota_round.ota_round_stats(theta, lam.re, lam.im, h.re, h.im,
                                      RHO, interpret=True)
        assert len(inner) == 1
    assert outer == inner
