"""The coherence gate of the block-fading steps (``core.channel.gated_redraw``).

Every gated redraw goes through one helper that makes the draw the body of
a loop of one trip on a coherence boundary and none off it: a round off
the boundary makes no draw and passes ``h`` through.  Contracts pinned
here:

* values: over 25 rounds at ``coherence_iters`` 10, each gated step equals
  the ungated ``cwhere(redraw, fresh, h)`` formula written below, bitwise,
  in ``h`` and ``age`` (the packed, leafwise, flat-block and phy steps; phy
  at rho 0 and 0.8, on jnp and on the Pallas kernel);
* structure: the PRNG primitives sit only in the loop's body, and ``h``
  is the loop's carry; under ``vmap`` a per-lane predicate gives the
  formula's values lane by lane;
* mesh: the reduced granite trainer on a (1, 2, 2) mesh crosses a boundary
  and its ``h`` equals the formula bitwise;
* counter: with telemetry on, ``obs/chan_redraw`` is 1.0 on exactly the
  boundary rounds; with it off, the key is absent.
"""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.extend.core import ClosedJaxpr, Jaxpr

from repro.core import cplx
from repro.core.channel import (ChannelBlock, ChannelConfig, rayleigh,
                                step_channel)
from repro.core.cplx import Complex
from repro.core.tree_ota import (TreeChannel, step_channel_packed,
                                 step_channel_tree)
from repro.phy.fading import correlated_step, innovation_scale

KEY = jax.random.PRNGKey(0)
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
W, D, COH, ROUNDS = 3, 1024 + 37, 10, 25
RNG_PRIMS = {"random_bits", "random_split", "random_fold_in",
             "threefry2x32"}


def _is_cplx(x):
    return isinstance(x, Complex)


def _draw_one(key, h, rho):
    """The ungated update of one plane: the fresh draw, or the AR(1) mix."""
    w = rayleigh(key, h.re.shape)
    if rho == 0.0:
        return w
    s = innovation_scale(rho)
    return Complex(rho * h.re + s * w.re, rho * h.im + s * w.im)


def _draw_kernel(key, h, rho):
    """The ungated Pallas AR(1) kernel on the fresh innovations (its mix
    matches the jnp one to 1e-6, ``test_phy.test_fading_step_kernel_parity``;
    the gate must not move it by a bit)."""
    from repro.core.transport import _interpret
    from repro.kernels.phy_channel import fading_step
    w = rayleigh(key, h.re.shape)
    re, im = fading_step(h.re.reshape(-1), h.im.reshape(-1),
                         w.re.reshape(-1), w.im.reshape(-1), rho,
                         innovation_scale(rho), interpret=_interpret())
    return Complex(re.reshape(h.re.shape), im.reshape(h.im.shape))


def _draw_leaves(key, h, rho):
    """Leafwise: one key per leaf, split from the round key."""
    leaves, tdef = jax.tree_util.tree_flatten(h, is_leaf=_is_cplx)
    keys = jax.random.split(key, len(leaves))
    return jax.tree_util.tree_unflatten(
        tdef, [_draw_one(k, l, rho) for k, l in zip(keys, leaves)])


@jax.jit
def _formula_age(age):
    age = age + 1
    redraw = age >= COH
    return redraw, jnp.where(redraw, jnp.zeros((), jnp.int32), age)


def _formula(draw, rho):
    """The ungated step: draw every round, keep the draw on a boundary."""
    def step(key, h, age):
        redraw, age_new = _formula_age(age)
        fresh = draw(key, h, rho)
        h_new = jax.tree.map(lambda a, b: cplx.cwhere(redraw, a, b),
                             fresh, h, is_leaf=_is_cplx)
        return h_new, age_new
    return step


def _ccfg():
    return ChannelConfig(n_workers=W, coherence_iters=COH)


def _packed(key, h, age):
    chan, _ = step_channel_packed(key, TreeChannel(h=h, age=age), _ccfg())
    return chan.h, chan.age


def _tree(key, h, age):
    chan, _ = step_channel_tree(key, TreeChannel(h=h, age=age), _ccfg())
    return chan.h, chan.age


def _block(key, h, age):
    zeros = jnp.zeros(h.re.shape, jnp.bool_)
    blk = step_channel(key, ChannelBlock(h=h, h_prev=h, changed=zeros,
                                         age=age), _ccfg())
    return blk.h, blk.age


def _phy(rho, backend):
    def step(key, h, age):
        h_new, age_new, _ = correlated_step(key, h, age, rho, COH,
                                            backend=backend)
        return h_new, age_new
    return step


def _h0(tree):
    if tree:
        return {"a": rayleigh(jax.random.fold_in(KEY, 97), (W, 5, 7)),
                "b": rayleigh(jax.random.fold_in(KEY, 98), (W, 9))}
    return rayleigh(jax.random.fold_in(KEY, 99), (W, D))


SITES = {
    "packed": (_packed, _draw_one, 0.0, False),
    "tree": (_tree, _draw_leaves, 0.0, True),
    "block": (_block, _draw_one, 0.0, False),
    "phy-jnp-rho0": (_phy(0.0, "jnp"), _draw_one, 0.0, False),
    "phy-jnp-rho0.8": (_phy(0.8, "jnp"), _draw_one, 0.8, False),
    "phy-pallas-rho0": (_phy(0.0, "pallas"), _draw_kernel, 0.0, False),
    "phy-pallas-rho0.8": (_phy(0.8, "pallas"), _draw_kernel, 0.8, False),
}


def _assert_same(got, want, what):
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w),
                                      err_msg=what)


@pytest.mark.parametrize("site", sorted(SITES))
def test_gated_step_equals_cwhere_formula_bitwise(site):
    gated, draw, rho, tree = SITES[site]
    gated = jax.jit(gated)
    formula = jax.jit(_formula(draw, rho))
    h = h_want = _h0(tree)
    age = age_want = jnp.zeros((), jnp.int32)
    redraws = []
    for r in range(1, ROUNDS + 1):
        k = jax.random.fold_in(KEY, r)
        h_prev = h
        h, age = gated(k, h, age)
        h_want, age_want = formula(k, h_want, age_want)
        _assert_same(h, h_want, f"{site} h, round {r}")
        assert int(age) == int(age_want), (site, r)
        moved = any(not np.array_equal(np.asarray(a), np.asarray(b))
                    for a, b in zip(jax.tree.leaves(h),
                                    jax.tree.leaves(h_prev)))
        if moved:
            redraws.append(r)
    assert redraws == [10, 20], (site, redraws)


def test_vmapped_gate_with_per_lane_predicate_equals_formula():
    """Under vmap a lane on its boundary redraws and a lane off it keeps
    its h, bitwise as the ungated formula."""
    keys = jax.random.split(jax.random.fold_in(KEY, 3), 2)
    h = Complex(jnp.stack([_h0(False).re] * 2), jnp.stack([_h0(False).im] * 2))
    age = jnp.asarray([COH - 1, COH - 3], jnp.int32)
    got = jax.jit(jax.vmap(_packed))(keys, h, age)
    want = jax.jit(jax.vmap(_formula(_draw_one, 0.0)))(keys, h, age)
    _assert_same(got, want, "vmapped gate")
    assert not np.array_equal(np.asarray(got[0].re[0]), np.asarray(h.re[0]))
    np.testing.assert_array_equal(np.asarray(got[0].re[1]),
                                  np.asarray(h.re[1]))


def _subjaxprs(eqn):
    for v in eqn.params.values():
        for x in (v if isinstance(v, (tuple, list)) else (v,)):
            if isinstance(x, ClosedJaxpr):
                yield x.jaxpr
            elif isinstance(x, Jaxpr):
                yield x


def _prims(jaxpr):
    """Every primitive in ``jaxpr`` and the jaxprs it calls."""
    out = set()
    for eqn in jaxpr.eqns:
        out.add(eqn.primitive.name)
        for sub in _subjaxprs(eqn):
            out |= _prims(sub)
    return out


@pytest.mark.parametrize("site", sorted(SITES))
def test_draw_lives_only_in_the_loop_body(site):
    """The round's one gate loop carries (key, h); its body holds every
    PRNG primitive, and neither its trip test nor the rest of the step
    holds any."""
    gated, _, _, tree = SITES[site]
    h = _h0(tree)
    top = jax.make_jaxpr(gated)(KEY, h, jnp.zeros((), jnp.int32)).jaxpr
    loops = [e for e in top.eqns if e.primitive.name == "while"]
    assert len(loops) == 1, [e.primitive.name for e in top.eqns]
    gate = loops[0]
    assert _prims(gate.params["body_jaxpr"].jaxpr) & RNG_PRIMS, site
    assert not _prims(gate.params["cond_jaxpr"].jaxpr) & RNG_PRIMS, site
    rest = set()
    for e in top.eqns:
        if e is not gate:
            rest.add(e.primitive.name)
            for sub in _subjaxprs(e):
                rest |= _prims(sub)
    assert not rest & RNG_PRIMS, (site, rest & RNG_PRIMS)
    # h rides in the carry: every leaf of h is an input of the loop
    h_vars = top.invars[1:1 + len(jax.tree.leaves(h))]
    assert set(map(id, h_vars)) <= set(map(id, gate.invars))


def test_python_bool_gate_decides_at_trace_time():
    """A Python-bool redraw traces no loop: held, no draw at all; redrawn,
    the plain eager draw, bitwise."""
    from repro.phy.fading import gauss_markov_step
    h = _h0(False)
    k = jax.random.fold_in(KEY, 5)
    held = jax.make_jaxpr(lambda k, h: gauss_markov_step(k, h, 0.0, False))(
        k, h).jaxpr
    assert not _prims(held) & RNG_PRIMS and "while" not in _prims(held)
    _assert_same(gauss_markov_step(k, h, 0.0, True), rayleigh(k, (W, D)),
                 "redraw=True")


@pytest.mark.parametrize("mode", ["replicated", "sketched"])
def test_chan_redraw_counter_marks_boundary_rounds(mode):
    """Telemetry on: obs/chan_redraw is 1.0 on rounds 10 and 20 of 25 and
    0.0 elsewhere.  Telemetry off: no such key."""
    from repro.core.admm import AdmmConfig
    from repro.models import get_model
    from repro.train.llm_trainer import FLConfig, make_fl_train
    m = get_model("granite-8b", reduced=True)
    batch = {"tokens": jax.random.randint(KEY, (2, 1, 8), 0,
                                          m.cfg.vocab_size)}
    ccfg = ChannelConfig(n_workers=2, snr_db=5.0, coherence_iters=COH)
    for telemetry in (True, None):
        init_fn, step = make_fl_train(
            m, FLConfig(mode=mode, n_workers=2, local_steps=1,
                        sketch_ratio=16, telemetry=telemetry),
            AdmmConfig(rho=0.5), ccfg)
        step = jax.jit(step)
        st = init_fn(KEY)
        seen = []
        for r in range(1, ROUNDS + 1):
            st, ms = step(st, batch, jax.random.fold_in(KEY, r))
            seen.append(ms.get("obs/chan_redraw"))
        if telemetry:
            on = [r for r, v in enumerate(seen, 1) if float(v) == 1.0]
            assert on == [10, 20], on
            assert all(float(v) in (0.0, 1.0) for v in seen)
        else:
            assert all(v is None for v in seen)


_MESH_SCRIPT = r"""
import jax, jax.numpy as jnp
import numpy as np
from repro.core import cplx
from repro.core.admm import AdmmConfig
from repro.core.channel import ChannelConfig, rayleigh
from repro.models import get_model
from repro.models.sharding import axis_rules
from repro.train.llm_trainer import FLConfig, make_fl_train

assert jax.device_count() == 4, jax.devices()
KEY = jax.random.PRNGKey(0)
mesh = jax.sharding.Mesh(np.array(jax.devices()).reshape(1, 2, 2),
                         ("data", "fsdp", "model"))
m = get_model("granite-8b", reduced=True)
W, COH = 2, 5
batch = {"tokens": jax.random.randint(KEY, (W, 1, 8), 0, m.cfg.vocab_size)}
init_fn, train_step = make_fl_train(
    m, FLConfig(n_workers=W, local_steps=1),
    AdmmConfig(rho=0.5), ChannelConfig(n_workers=W, coherence_iters=COH),
    mesh=mesh)
st = init_fn(KEY)
h, age = st.chan.h, jnp.zeros((), jnp.int32)


@jax.jit
def formula(key, h, age):
    # the ungated step on the trainer's channel key: draw every round,
    # keep the draw on a boundary
    age = age + 1
    redraw = age >= COH
    fresh = rayleigh(jax.random.split(key)[0], h.re.shape)
    return (cplx.cwhere(redraw, fresh, h),
            jnp.where(redraw, jnp.zeros((), jnp.int32), age), redraw)


redraws = []
with mesh:
    with axis_rules(mesh):
        step = jax.jit(train_step)
        for r in range(1, 13):
            key = jax.random.fold_in(KEY, r)
            st, _ = step(st, batch, key)
            h, age, redraw = formula(key, h, age)
            np.testing.assert_array_equal(np.asarray(st.chan.h.re),
                                          np.asarray(h.re), err_msg=str(r))
            np.testing.assert_array_equal(np.asarray(st.chan.h.im),
                                          np.asarray(h.im), err_msg=str(r))
            assert int(st.chan.age) == int(age), r
            if bool(redraw):
                redraws.append(r)
assert redraws == [5, 10], redraws
assert len(st.chan.h.re.sharding.device_set) == 4, st.chan.h.re.sharding
print("MESH_GATE_BITWISE_OK")
"""


def test_mesh_trainer_gate_equals_formula_bitwise():
    env = dict(os.environ,
               PYTHONPATH=os.path.join(REPO, "src"),
               JAX_PLATFORMS="cpu",
               XLA_FLAGS=(os.environ.get("XLA_FLAGS", "")
                          + " --xla_force_host_platform_device_count=4"
                          ).strip())
    proc = subprocess.run([sys.executable, "-c", _MESH_SCRIPT], env=env,
                          capture_output=True, text=True, timeout=540,
                          cwd=REPO)
    assert "MESH_GATE_BITWISE_OK" in proc.stdout, \
        (proc.stdout + proc.stderr)[-4000:]
