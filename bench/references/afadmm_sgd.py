"""Plain reference of a cell's first rounds of A-FADMM training with local
SGD steps.

Written from the configuration file and the algorithm, in ``jax.numpy`` and
float32 at ``HIGHEST`` matmul precision, with no kernel, no packing code
and nothing of the program imported.  The model is the plain one of the
configuration's ``model_type`` (``bench/models``).  It takes its weights,
fading, tokens and round keys from ``harness.inputs`` and the seed, as the
program's state is made, never from the program.

One round, for W workers with weights θ_w, duals λ_w, fading h_w, the
global model Θ, penalty ρ and local rate η:

* local steps: θ_w ← θ_w − η (∇f_w(θ_w) + Re{h_w λ_w*} + ρ|h_w|²(θ_w − Θ)),
  ``local_steps`` times; the round's loss is the workers' mean loss at the
  last local step;
* each worker sends s_w = h_w* θ_w + λ_w*/ρ; with E_w = Σ|s_w|² and the
  budget P·D, 1/α = max_w sqrt(E_w / (P·D));
* the server receives Θ = (Σ_w Re{h_w s_w} + z/α) / Σ_w |h_w|², z real
  Gaussian noise of variance N0/(2T) per element;
* duals: λ_w ← λ_w + ρ h_w (θ_w − Θ); Θ is stored in the weights' type.

The weights are stored in the type the configuration states (bfloat16):
the reference rounds θ and Θ to it where the configuration keeps them.
The control (``store``/``matmul`` of a lower precision) is this code with
the weights stored, and every matmul's operands rounded, in that type.

The receiver noise and the fading live on the packed index space: leaf
after leaf in the order of their paths, each raveled row-major.  That is
the convention the noise and fading draws of the configuration follow.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, Optional

import jax
import jax.numpy as jnp

from harness import inputs, spec


# ---------------------------------------------------------------------------
# A-FADMM rounds
# ---------------------------------------------------------------------------

def run(config, traffic, seed: int, n_rounds: int = 3,
        store=None, operand_dtype=None, devices=None,
        theta1_program: Optional[Dict[str, object]] = None,
        keep_theta1: bool = False) -> dict:
    """The first ``n_rounds`` rounds from ``seed``.

    Returns per-round ``losses`` and ``inv_alpha``, and per-leaf norms:
    ``dtheta1`` of the workers' weights' change in round 1, ``dTheta3`` of
    the global model's change and ``lam3`` of the duals after
    ``n_rounds``.  ``store``/``operand_dtype`` give the control.

    ``devices`` (more than one) spreads every array over them, each on its
    last dimension where that divides.  With ``theta1_program`` (the
    program's Θ after round 1, per leaf) it also returns ``noise1``: per
    leaf, the mean square of the receiver noise that the program's Θ and
    the reference's own Θ imply, ``(Θ − y/S)·S·α`` with the reference's
    y, S and α; a statistic of the noise that does not depend on how a
    program lays its noise draw out over the parameters.  ``keep_theta1``
    returns its own Θ after round 1 on the host (``Theta1``), for the
    control put in the program's place."""
    if n_rounds >= traffic["coherence_iters"]:
        raise ValueError("the reference follows rounds within the first "
                         "coherence block (no fading redraw)")
    model = spec.load_model(config)
    lv = model.leaves(config)
    offs, D = inputs.offsets(lv)
    store = jnp.dtype(store or config["torch_dtype"])
    operand = None
    if operand_dtype is not None:
        od = jnp.dtype(operand_dtype)
        operand = lambda a: a.astype(od).astype(jnp.float32)
    W, steps = traffic["workers"], traffic["local_steps"]
    lr, rho = traffic["local_lr"], traffic["rho"]
    ch = traffic["channel"]
    power_per_elem = 10.0 ** (traffic["snr_db"] / 10.0) * ch["noise_psd"] \
        * ch["subcarrier_hz"]
    noise_std = math.sqrt(ch["noise_psd"] / ch["slot_seconds"] / 2.0)
    key = inputs.seed_key(seed)
    paths = [p for p, _, _ in lv]
    shapes = {p: sh for p, sh, _ in lv}
    lay = _layout(devices)

    # every program takes the seed's key as an argument, so that it is
    # compiled once for all seeds and found in the cache after that
    @functools.partial(jax.jit, out_shardings=(
        {p: lay((W,) + shapes[p]) for p in paths},
        {p: lay(shapes[p]) for p in paths}) if lay(()) else None)
    def init(key):
        ws = [inputs.worker_weights(key, lv, w) for w in range(W)]
        theta = {p: jnp.stack([ws[w][p] for w in range(W)]).astype(store)
                 for p in paths}
        Theta = {p: jnp.mean(theta[p].astype(jnp.float32), 0).astype(store)
                 for p in paths}
        return theta, Theta

    def fading(key):
        """The packed draw, then its leaves: a program that slices one
        leaf out of the draw still makes the whole draw's temporaries."""
        sh = lay((W, D))
        planes = jax.jit(lambda k: inputs.fading(k, W, D),
                         out_shardings=(sh, sh) if sh else None)(key)
        leaf = lambda x: {p: x[:, o:o + n].reshape((W,) + shapes[p])
                          for p, (o, n) in offs.items()}
        sh = {p: lay((W,) + shapes[p]) for p in paths}
        return jax.jit(lambda hre, him: (leaf(hre), leaf(him)),
                       out_shardings=(sh, sh) if lay(()) else None)(*planes)

    @functools.partial(jax.jit, donate_argnums=(0,))
    def local_step(theta, Theta, lam, h, tokens, w):
        pick = lambda t: {p: t[p][w] for p in paths}
        tw = {p: theta[p][w].astype(jnp.float32) for p in paths}
        loss, g = jax.value_and_grad(model.loss_fn)(tw, tokens, config, operand)
        lre, lim, hre, him = pick(lam[0]), pick(lam[1]), pick(h[0]), pick(h[1])
        new = {}
        for p in paths:
            pen = (hre[p] * lre[p] + him[p] * lim[p]
                   + rho * (hre[p] ** 2 + him[p] ** 2)
                   * (tw[p] - Theta[p].astype(jnp.float32)))
            new[p] = theta[p].at[w].set(
                (tw[p] - lr * (g[p] + pen)).astype(store))
        return new, loss

    def signal(theta, lam, h, p):
        t = theta[p].astype(jnp.float32)
        return t, h[0][p] * t + lam[0][p] / rho, -h[1][p] * t - lam[1][p] / rho

    @jax.jit
    def power(theta, lam, h):
        """1/α from the workers' energies (a program of its own, so the
        signals are not kept for the receive)."""
        energy = 0.0
        for p in paths:
            _, sre, sim = signal(theta, lam, h, p)
            energy = energy + jnp.sum((sre ** 2 + sim ** 2).reshape(W, -1), 1)
        return jnp.max(jnp.sqrt(energy / (power_per_elem * D)))

    @functools.partial(jax.jit, donate_argnums=(1, 2))
    def receive(theta, Theta, lam, h, key, inv_alpha):
        _, kn = jax.random.split(key)
        kr, _ = jax.random.split(kn)
        z = jax.random.normal(kr, (D,), jnp.float32) * noise_std
        Theta_new, lre, lim = {}, {}, {}
        for (p, s, _) in lv:
            o, n = offs[p]
            t, sre, sim = signal(theta, lam, h, p)
            y = jnp.sum(h[0][p] * sre - h[1][p] * sim, 0)
            s2 = jnp.sum(h[0][p] ** 2 + h[1][p] ** 2, 0)
            T = (y + z[o:o + n].reshape(s) * inv_alpha) / jnp.maximum(s2,
                                                                       1e-12)
            lre[p] = lam[0][p] + rho * h[0][p] * (t - T)
            lim[p] = lam[1][p] + rho * h[1][p] * (t - T)
            Theta_new[p] = T.astype(store)
        return Theta_new, (lre, lim)

    @jax.jit
    def dtheta_norms(theta, key):
        ws = [inputs.worker_weights(key, lv, w) for w in range(W)]
        return {p: jnp.sqrt(sum(jnp.sum(
            (theta[p][w].astype(jnp.float32)
             - ws[w][p].astype(store).astype(jnp.float32)) ** 2)
            for w in range(W))) for p in paths}

    @jax.jit
    def noise_var(theta, h, inv_alpha, Theta1):
        """Round 1 (zero duals): per leaf, the mean square of
        (Θ1 − y/S)·S/(1/α), the receiver noise Θ1 implies."""
        out = {}
        for p in paths:
            t = theta[p].astype(jnp.float32)
            s2 = jnp.sum(h[0][p] ** 2 + h[1][p] ** 2, 0)
            y = jnp.sum((h[0][p] ** 2 + h[1][p] ** 2) * t, 0)
            z = (Theta1[p].astype(jnp.float32) * s2 - y) / inv_alpha
            out[p] = jnp.mean(z * z)
        return out

    @jax.jit
    def round3_norms(Theta, lam, key):
        ws = [inputs.worker_weights(key, lv, w) for w in range(W)]
        T0 = {p: jnp.mean(jnp.stack([ws[w][p].astype(store) for w in
                                     range(W)]).astype(jnp.float32), 0)
              .astype(store) for p in paths}
        dT = {p: jnp.sqrt(jnp.sum((Theta[p].astype(jnp.float32)
                                   - T0[p].astype(jnp.float32)) ** 2))
              for p in paths}
        ln = {p: jnp.sqrt(jnp.sum(lam[0][p] ** 2) + jnp.sum(lam[1][p] ** 2))
              for p in paths}
        return dT, ln

    with jax.default_matmul_precision("highest"):
        theta, Theta = init(key)
        h = fading(key)
        zeros = jax.jit(lambda t: {p: jnp.zeros_like(x) for p, x in t.items()})
        lam = (zeros(h[0]), zeros(h[0]))
        tokens = inputs.round_tokens(key, traffic, config["vocab_size"],
                                     n_rounds)
        keys = inputs.round_keys(key, n_rounds)
        losses, inv_alpha, dtheta1 = [], [], None
        for r in range(n_rounds):
            for _ in range(steps):
                step_losses = []
                for w in range(W):
                    theta, l = local_step(theta, Theta, lam, h, tokens[r, w],
                                          jnp.int32(w))
                    step_losses.append(l)
            losses.append(float(sum(step_losses) / W))
            ia = power(theta, lam, h)
            Theta, lam = receive(theta, Theta, lam, h, keys[r], ia)
            inv_alpha.append(float(ia))
            if r == 0:
                dtheta1 = jax.device_get(dtheta_norms(theta, key))
                if keep_theta1:
                    theta1 = jax.device_get(Theta)
                if theta1_program is not None:
                    got = {p: jnp.asarray(v) for p, v in
                           theta1_program.items()}
                    noise1 = jax.device_get((noise_var(theta, h, ia, Theta),
                                             noise_var(theta, h, ia, got)))
        dTheta3, lam3 = jax.device_get(round3_norms(Theta, lam, key))
    f = lambda d: {p: float(v) for p, v in d.items()}
    out = dict(losses=losses, inv_alpha=inv_alpha, dtheta1=f(dtheta1),
               dTheta3=f(dTheta3), lam3=f(lam3))
    if theta1_program is not None:
        out["noise1_ref"], out["noise1_prog"] = f(noise1[0]), f(noise1[1])
    if keep_theta1:
        out["Theta1"] = theta1
    return out


def _layout(devices):
    """``shape -> sharding`` over ``devices`` on the last dimension where
    it divides (None: one device, or no such dimension)."""
    if not devices or len(devices) < 2:
        return lambda shape: None
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    mesh = Mesh(list(devices), ("x",))
    n = len(devices)

    def lay(shape):
        if not shape:
            return NamedSharding(mesh, P())
        if shape[-1] % n:
            return NamedSharding(mesh, P())
        return NamedSharding(mesh, P(*([None] * (len(shape) - 1) + ["x"])))
    return lay
