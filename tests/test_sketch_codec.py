"""Standalone count-sketch codec contract (A-FADMM-CS, paper Sec. 6).

The codec is the ONLY thing the sketched trainer trusts: these tests pin
it independently of any trainer/transport plumbing —

* golden bucket/sign draws under fixed keys (both the materialised
  `SketchPlan` and the storage-free hashed codec), so a JAX version bump
  or an accidental sign-construction change cannot silently re-key every
  sketched checkpoint;
* linearity of encode (the property OTA superposition relies on: the sum
  of encoded worker deltas IS the encode of the summed delta);
* unbiasedness of decode∘encode, Monte-Carlo over keys/seeds;
* `encode_decode_gain` golden value;
* shard-local encode inside `shard_map` on a REAL (1, 2) model-parallel
  mesh preserves the parameter sharding and psums to the global codec
  (subprocess: tier-1 pins a single device, see test_shard_local.py).
"""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.sketch import (SketchPlan, bucket_of, decode, decode_packed,
                               decode_shard_local, encode, encode_decode_gain,
                               encode_packed, encode_shard_local, sign_of)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
KEY = jax.random.PRNGKey(42)


# ---------------------------------------------------------------------------
# golden draws — fixed key/seed, exact values
# ---------------------------------------------------------------------------

#: SketchPlan.build(PRNGKey(42), d=16, d_s=4) — bernoulli sign construction
_GOLD_BUCKET = [0, 1, 1, 2, 1, 1, 3, 2, 2, 3, 3, 0, 3, 1, 0, 1]
_GOLD_SIGN = [-1., -1., 1., 1., 1., 1., 1., -1., 1., 1., -1., 1.,
              -1., -1., 1., -1.]

#: hashed codec: bucket_of/sign_of(arange(12), d_s=4, seed=17)
_GOLD_HBUCKET = [2, 2, 2, 0, 1, 0, 1, 2, 1, 2, 2, 1]
_GOLD_HSIGN = [1., 1., -1., 1., 1., 1., 1., 1., 1., 1., -1., 1.]


def test_sketchplan_build_golden_values():
    """The sign draw is pinned to the bernoulli construction (no
    `jax.random.rademacher` fallback): these exact values are the codec."""
    p = SketchPlan.build(KEY, 16, 4)
    np.testing.assert_array_equal(np.asarray(p.bucket), _GOLD_BUCKET)
    np.testing.assert_array_equal(np.asarray(p.sign), _GOLD_SIGN)
    assert p.sign.dtype == jnp.float32 and p.bucket.dtype == jnp.int32


def test_hashed_codec_golden_values():
    idx = jnp.arange(12, dtype=jnp.uint32)
    np.testing.assert_array_equal(np.asarray(bucket_of(idx, 4, 17)),
                                  _GOLD_HBUCKET)
    np.testing.assert_array_equal(np.asarray(sign_of(idx, 17)), _GOLD_HSIGN)


def test_encode_decode_gain_golden():
    p = SketchPlan.build(KEY, 4096, 256)
    assert encode_decode_gain(p) == 1.0 + 4096 / 256 == 17.0


# ---------------------------------------------------------------------------
# algebraic contract
# ---------------------------------------------------------------------------

def test_encode_linearity():
    """encode(a·u + b·v) == a·encode(u) + b·encode(v) — what lets OTA
    superposition aggregate worker sketches in the analog sum."""
    d, d_s = 96, 16
    u = jax.random.normal(jax.random.fold_in(KEY, 1), (d,))
    v = jax.random.normal(jax.random.fold_in(KEY, 2), (d,))
    p = SketchPlan.build(KEY, d, d_s)
    np.testing.assert_allclose(
        np.asarray(encode(p, 2.0 * u - 3.0 * v)),
        np.asarray(2.0 * encode(p, u) - 3.0 * encode(p, v)),
        rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(encode_packed(2.0 * u - 3.0 * v, d_s, seed=9)),
        np.asarray(2.0 * encode_packed(u, d_s, seed=9)
                   - 3.0 * encode_packed(v, d_s, seed=9)),
        rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("codec", ["plan", "hashed"])
def test_decode_encode_unbiased_monte_carlo(codec):
    """E_key[decode(encode(v))] == v: collisions carry random independent
    signs, so their expectation cancels — the transposed-sketch estimator
    is unbiased and the sketched consensus converges to the true delta."""
    d, d_s, n_mc = 48, 12, 4000
    v = jax.random.normal(jax.random.fold_in(KEY, 3), (d,))

    if codec == "plan":
        def one(k):
            p = SketchPlan.build(k, d, d_s)
            return decode(p, encode(p, v))
        est = jnp.mean(jax.vmap(one)(jax.random.split(KEY, n_mc)), axis=0)
    else:
        def one(seed):
            return decode_packed(encode_packed(v, d_s, seed=seed), d, seed=seed)
        est = jnp.mean(jax.vmap(one)(jnp.arange(n_mc)), axis=0)

    # MC std of each coord ~ sqrt((d/d_s)) * |v| / sqrt(n_mc) ~ 0.03
    np.testing.assert_allclose(np.asarray(est), np.asarray(v), atol=0.25)
    assert float(jnp.mean(jnp.abs(est - v))) < 0.08


def test_shard_local_codec_is_global_codec_flat():
    """encode_shard_local with the identity index map IS encode_packed, and
    masked positions contribute nothing."""
    d, d_s = 40, 8
    v = jax.random.normal(jax.random.fold_in(KEY, 4), (3, d))
    idx = jnp.arange(d, dtype=jnp.uint32)
    ones = jnp.ones((d,), bool)
    np.testing.assert_allclose(
        np.asarray(encode_shard_local(v, idx, ones, d_s, seed=5)),
        np.asarray(encode_packed(v, d_s, seed=5)), rtol=1e-6, atol=1e-6)
    # split in halves with disjoint index ranges -> partial sketches psum
    half = d // 2
    parts = (encode_shard_local(v[..., :half], idx[:half], ones[:half],
                                d_s, seed=5)
             + encode_shard_local(v[..., half:], idx[half:], ones[half:],
                                  d_s, seed=5))
    np.testing.assert_allclose(np.asarray(parts),
                               np.asarray(encode_packed(v, d_s, seed=5)),
                               rtol=1e-6, atol=1e-6)
    # a masked position is invisible to encode and decodes to exactly 0
    mask = ones.at[7].set(False)
    vz = v.at[..., 7].set(0.0)
    np.testing.assert_array_equal(
        np.asarray(encode_shard_local(v, idx, mask, d_s, seed=5)),
        np.asarray(encode_shard_local(vz, idx, mask, d_s, seed=5)))
    s = jax.random.normal(KEY, (d_s,))
    assert float(jnp.abs(decode_shard_local(s, idx, mask, seed=5)[7])) == 0.0


# ---------------------------------------------------------------------------
# sharding preservation on a real (1, 2) mesh — subprocess (tier-1 pins
# one device; jax locks the device count at first backend init)
# ---------------------------------------------------------------------------

_MESH_SCRIPT = r"""
import jax, jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.core.packing import (build_shard_packspec, pack, pack_shard_local,
                                shard_perm_local, shard_valid_mask,
                                unpack_shard_local)
from repro.core.sketch import (decode_shard_local, encode_packed,
                               encode_shard_local)

assert jax.device_count() == 2, jax.devices()
KEY = jax.random.PRNGKey(0)
mesh = jax.sharding.Mesh(np.array(jax.devices()).reshape(1, 2),
                         ("data", "model"))

theta = {"wq": jax.random.normal(KEY, (4, 8)),
         "wo": jax.random.normal(jax.random.fold_in(KEY, 1), (8, 4)),
         "b": jax.random.normal(jax.random.fold_in(KEY, 2), (5,))}
dims = [None, 0, 1]                      # sorted keys: b, wo, wq
ss = build_shard_packspec(theta, dims, 2)
d_s = 16
specs = {"wq": P(None, "model"), "wo": P("model", None), "b": P()}
put = {k: jax.device_put(v, NamedSharding(mesh, specs[k]))
       for k, v in theta.items()}


def enc_body(t):
    jm = jax.lax.axis_index("model")
    buf = pack_shard_local(ss, t, jm)
    s = encode_shard_local(buf, shard_perm_local(ss, jm),
                           shard_valid_mask(ss, jm), d_s, 17)
    return jax.lax.psum(s, "model")


in_specs = ({k: specs[k] for k in theta},)
enc = jax.jit(jax.shard_map(enc_body, mesh=mesh, in_specs=in_specs,
                            out_specs=P(), check_vma=False))
s = enc(put)
want = encode_packed(pack(ss.spec, theta), d_s, 17)
np.testing.assert_allclose(np.asarray(s), np.asarray(want),
                           rtol=1e-6, atol=1e-6)
print("ENC_GLOBAL_PARITY_OK")


def dec_body(sk):
    jm = jax.lax.axis_index("model")
    perm, valid = shard_perm_local(ss, jm), shard_valid_mask(ss, jm)
    buf = decode_shard_local(sk, perm, valid, 17)
    from repro.core.packing import rep_segment_perm
    rseg = None
    if ss.rep_size:
        rperm = rep_segment_perm(ss)
        rvalid = jnp.arange(ss.rep_pad) < ss.rep_size
        rseg = decode_shard_local(sk, rperm, rvalid, 17)
    return unpack_shard_local(ss, buf, rseg, cast=False)


dec = jax.jit(jax.shard_map(dec_body, mesh=mesh, in_specs=(P(),),
                            out_specs={k: specs[k] for k in theta},
                            check_vma=False))
out = dec(s)
# decoded tree keeps the model-parallel parameter sharding (no all-gather)
for k in theta:
    assert out[k].sharding.is_equivalent_to(
        NamedSharding(mesh, specs[k]), out[k].ndim), (k, out[k].sharding)
    assert out[k].shape == theta[k].shape
print("DEC_SHARDING_PRESERVED_OK")

# and bitwise matches the host-side global decode
from repro.core.sketch import decode_packed
from repro.core.packing import unpack
host = unpack(ss.spec, decode_packed(s, ss.spec.d, 17), cast=False)
for k in theta:
    np.testing.assert_array_equal(np.asarray(out[k]), np.asarray(host[k]))
print("DEC_GLOBAL_PARITY_OK")
"""


def test_shard_local_codec_on_two_device_mesh():
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=2",
               JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(REPO, "src"))
    r = subprocess.run([sys.executable, "-c", _MESH_SCRIPT], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stdout + r.stderr
    for marker in ("ENC_GLOBAL_PARITY_OK", "DEC_SHARDING_PRESERVED_OK",
                   "DEC_GLOBAL_PARITY_OK"):
        assert marker in r.stdout


# ---------------------------------------------------------------------------
# trainer-side sketch sizing (satellite: _sketch_dim regression)
# ---------------------------------------------------------------------------

def test_sketch_dim_validates_ratio():
    from repro.train.llm_trainer import _sketch_dim
    assert _sketch_dim(1000, 10) == 100
    assert _sketch_dim(1001, 10) == 101          # ceil, not floor
    assert _sketch_dim(16, 1000) == 8            # floor of 8 buckets
    assert _sketch_dim(7, 1) == 8
    for bad in (0, -1, -32):
        with pytest.raises(ValueError, match="sketch_ratio"):
            _sketch_dim(1000, bad)
