"""The system under test: the program's A-FADMM LLM trainer
(``repro.train.llm_trainer.make_fl_train``), built for a cell.

From the program the benchmark takes the model registry, the trainer and
its state's structure; the state's values come from ``harness.inputs`` and
the seed.  The window drives the trainer's ``train_step``, jitted with
donation, one dispatch per round, as the training loop of
``repro.launch.train`` does.  A traffic file names this system with
``"system": "fl_trainer"``.
"""
from __future__ import annotations

import contextlib
import dataclasses
import os
import sys
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp

from harness import inputs, spec

#: configuration keys and the registry fields they set
_FIELDS = {"num_hidden_layers": "n_layers", "vocab_size": "vocab_size",
           "hidden_size": "d_model", "intermediate_size": "d_ff",
           "num_attention_heads": "n_heads",
           "num_key_value_heads": "n_kv_heads", "head_dim": "head_dim",
           "rope_theta": "rope_theta", "rms_norm_eps": "norm_eps"}


def import_program():
    src = os.path.join(spec.ROOT, "src")
    if src not in sys.path:
        sys.path.insert(0, src)


@contextlib.contextmanager
def _kernels(on: bool):
    """``REPRO_USE_PALLAS`` is read while the step is traced (attention)."""
    old = os.environ.get("REPRO_USE_PALLAS")
    os.environ["REPRO_USE_PALLAS"] = "1" if on else "0"
    try:
        yield
    finally:
        if old is None:
            os.environ.pop("REPRO_USE_PALLAS", None)
        else:
            os.environ["REPRO_USE_PALLAS"] = old


def model_config(config):
    """The registry's ``config['arch']`` with every size the file states
    taken from the file; the architecture's other choices (family, MLP,
    head) are the registry's."""
    import_program()
    from repro.models.registry import get_config
    spec.load_model(config).check(config)
    return dataclasses.replace(get_config(config["arch"]), **{
        field: config[key] for key, field in _FIELDS.items()})


class System:
    """The compiled step of one cell, with the state it starts from.

    ``wrap_step`` (tests only) wraps the program's ``train_step`` before it
    is jitted, to plant a fault underneath the harness."""

    def __init__(self, config, traffic, devices,
                 wrap_step: Optional[Callable] = None):
        import_program()
        from repro.core.admm import AdmmConfig
        from repro.core.channel import ChannelConfig
        from repro.models.registry import build_model
        from repro.train.llm_trainer import FLConfig, make_fl_train

        self.config, self.traffic = config, traffic
        self.ref_model = spec.load_model(config)
        W = traffic["workers"]
        self.model = build_model(model_config(config))
        ch = traffic["channel"]
        flcfg = FLConfig(mode=traffic["mode"], n_workers=W,
                         local_steps=traffic["local_steps"],
                         local_lr=traffic["local_lr"],
                         local_optimizer=traffic["local_optimizer"],
                         transport_backend=traffic["transport"])
        ccfg = ChannelConfig(n_workers=W, snr_db=traffic["snr_db"],
                             coherence_iters=traffic["coherence_iters"],
                             noise_psd=ch["noise_psd"],
                             subcarrier_hz=ch["subcarrier_hz"],
                             slot_seconds=ch["slot_seconds"])
        self.mesh = None
        if traffic["mesh"]:
            from repro.launch.mesh import make_mesh
            axes = tuple(traffic["mesh"])
            self.mesh = make_mesh(tuple(traffic["mesh"][a] for a in axes),
                                  axes, devices=devices)
        self._flash = traffic["flash_attention"]
        with _kernels(self._flash):
            self.init_fn, train_step = make_fl_train(
                self.model, flcfg, AdmmConfig(rho=traffic["rho"]), ccfg,
                mesh=self.mesh)
        self.train_step = wrap_step(train_step) if wrap_step else train_step
        self.shapes = jax.eval_shape(self.init_fn, jax.random.PRNGKey(0))
        self.shardings = self._shardings()
        self._check_layout()

    # -- placement ---------------------------------------------------------

    def _rules(self):
        if self.mesh is None:
            return contextlib.nullcontext()
        from repro.models.sharding import axis_rules
        return axis_rules(self.mesh)

    def _shardings(self):
        """Parameter trees as ``repro.launch.shardings`` places them, the
        packed planes over the (fsdp, model) shard grid, scalars
        replicated; None on one device."""
        if self.mesh is None:
            return None
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.launch.shardings import tree_pspecs
        s, cfg, mesh = self.shapes, self.model.cfg, self.mesh

        def params(tree, worker_dim):
            return tree_pspecs(tree, cfg, mesh, worker_dim=worker_dim,
                               fsdp=True, multi_pod=False)

        plane = lambda x: P("data", ("fsdp", "model")) if x.ndim == 2 else P()
        spec = s._replace(
            theta=params(s.theta, True), Theta=params(s.Theta, False),
            lam=jax.tree.map(plane, s.lam), chan=jax.tree.map(plane, s.chan),
            opt=jax.tree.map(lambda _: P(), s.opt), step=P())
        return jax.tree.map(lambda p: NamedSharding(mesh, p), spec,
                            is_leaf=lambda x: isinstance(x, P))

    def _check_layout(self):
        """The program's weights are the reference's, and (on one device)
        its packed planes follow the reference's packed order."""
        got = {inputs.path_str(p): (tuple(l.shape[1:]), l.dtype)
               for p, l in jax.tree_util.tree_leaves_with_path(
                   self.shapes.theta)}
        want = {p: (s, d) for p, s, d in self.ref_model.leaves(self.config)}
        if got != want:
            raise ValueError(f"the program's weights {got} are not the "
                             f"configuration's {want}")
        if self.mesh is None:
            from repro.core.packing import build_packspec
            spec = build_packspec(self.shapes.theta, batch_dims=1)
            offs, D = inputs.offsets(self.ref_model.leaves(self.config))
            if list(spec.offsets) != [offs[p][0] for p in sorted(offs)] \
                    or spec.d != D:
                raise ValueError("the program's packed order is not the "
                                 "reference's")

    # -- the state ---------------------------------------------------------

    def _leaves(self):
        return [(inputs.path_str(p), tuple(l.shape[1:]), l.dtype)
                for p, l in jax.tree_util.tree_leaves_with_path(
                    self.shapes.theta)]

    def _weights(self, key):
        s, W, lv = self.shapes, self.traffic["workers"], self._leaves()
        ws = [inputs.worker_weights(key, lv, w) for w in range(W)]
        flat = [jnp.stack([ws[w][p] for w in range(W)]) for p, _, _ in lv]
        theta = jax.tree_util.tree_unflatten(
            jax.tree_util.tree_structure(s.theta), flat)
        Theta = jax.tree.map(
            lambda l: jnp.mean(l.astype(jnp.float32), 0).astype(l.dtype), theta)
        return theta, Theta

    def make_state(self, key: jax.Array):
        """The state at round 0, from the seed: independent worker weights,
        their mean as Θ, zero duals and one fading block.  On one device
        it is one jitted call and the fading is drawn over the packed
        (W, D) planes; on a mesh the fading is drawn in the weights' own
        layout (the same values, leaf by leaf) and packed into the
        shard-local planes by the program's packer, one plane at a time so
        that set-up does not outgrow the step's memory."""
        s, W = self.shapes, self.traffic["workers"]
        cplx = type(s.lam)

        def rest(theta, Theta, hre, him):
            return s._replace(
                theta=theta, Theta=Theta,
                lam=cplx(jnp.zeros_like(hre), jnp.zeros_like(him)),
                chan=s.chan._replace(h=cplx(hre, him),
                                     age=jnp.zeros((), jnp.int32)),
                opt=jax.tree.map(lambda l: jnp.zeros(l.shape, l.dtype), s.opt),
                step=jnp.zeros((), jnp.int32))

        # the seed's key is an argument of every program, never a constant
        # in it, so that one compile serves every seed
        if self.mesh is None:
            def make(key):
                theta, Theta = self._weights(key)
                hre, him = inputs.fading(key, W, s.lam.re.shape[1])
                return rest(theta, Theta, hre, him)
            return jax.jit(make)(key)
        sh = self.shardings
        with self._rules():
            theta, Theta = jax.jit(self._weights, out_shardings=(
                sh.theta, sh.Theta))(key)
            planes = [self._pack_plane(key, part) for part in (0, 1)]
            return jax.jit(rest, out_shardings=sh, donate_argnums=(0, 1, 2, 3))(
                theta, Theta, *planes)

    def _shard_spec(self):
        from repro.core.packing import build_shard_packspec
        from repro.launch.shardings import shard_dims_2d
        mesh, n = self.mesh, dict(self.mesh.shape)
        mdims, fdims = shard_dims_2d(self.shapes.theta, self.model.cfg, mesh,
                                     multi_pod=False)
        return build_shard_packspec(self.shapes.theta, mdims, n["model"],
                                    batch_dims=1, fsdp_dims=fdims,
                                    n_fsdp=n["fsdp"])

    def _pack_plane(self, key, part: int):
        """One fading plane (0: re, 1: im) drawn leaf by leaf from the
        canonical packed draw and packed shard-locally on the mesh."""
        from jax.sharding import PartitionSpec as P
        from repro.core.packing import pack_shard_local
        from repro.core.tree_ota import _shard_theta_specs
        W, lv = self.traffic["workers"], self._leaves()
        offs, D = inputs.offsets(lv)
        sspec, mesh = self._shard_spec(), self.mesh
        tree_specs = _shard_theta_specs(sspec, "data", "model",
                                        worker_dim=True)

        def draw(key, o, n, shape):
            # one leaf's columns of the packed draw
            plane = inputs.fading(key, W, D)[part]
            return plane[:, o:o + n].reshape((W,) + shape)

        def body(tree):
            j = jax.lax.axis_index("fsdp") * sspec.n_model \
                + jax.lax.axis_index("model")
            return pack_shard_local(sspec, tree, j)

        specs = jax.tree_util.tree_leaves(
            tree_specs, is_leaf=lambda x: isinstance(x, P))
        flat = [jax.jit(draw, static_argnums=(1, 2, 3), out_shardings=(
            jax.sharding.NamedSharding(mesh, sp)))(key, *offs[p], sh)
            for (p, sh, _), sp in zip(lv, specs)]
        tree = jax.tree_util.tree_unflatten(
            jax.tree_util.tree_structure(self.shapes.theta), flat)
        pack = jax.shard_map(body, mesh=mesh, in_specs=(tree_specs,),
                             out_specs=P("data", ("fsdp", "model")),
                             check_vma=False)
        return jax.jit(pack, donate_argnums=(0,))(tree)

    # -- the step ----------------------------------------------------------

    def compile(self):
        """Lower and compile the step for the cell's batch; returns it."""
        tr = self.traffic
        batch = {"tokens": jax.ShapeDtypeStruct(
            (tr["workers"], tr["batch_per_worker"], tr["seq_len"]),
            jnp.int32)}
        key = jax.ShapeDtypeStruct((2,), jnp.uint32)
        shapes, kw = self.shapes, {}
        if self.shardings is not None:
            shapes = jax.tree.map(
                lambda x, sh: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                                   sharding=sh),
                self.shapes, self.shardings)
            kw = dict(in_shardings=(self.shardings, None, None),
                      out_shardings=(self.shardings, None))
        with _kernels(self._flash), self._rules():
            lowered = jax.jit(self.train_step, donate_argnums=(0,),
                              **kw).lower(shapes, batch, key)
        return lowered.compile()

    # -- the feed and the readings ----------------------------------------

    def feed(self, key: jax.Array, n: int):
        """``n`` rounds' batches and round keys from the seed's key: each
        a list of ``n`` device arrays, drawn before the state is made so
        that the draw's temporaries come and go first."""
        tr, V = self.traffic, self.config["vocab_size"]
        tokens = _split(jax.jit(lambda k: inputs.round_tokens(k, tr, V, n))(
            key), n)
        keys = _split(jax.jit(lambda k: inputs.round_keys(k, n))(key), n)
        return [{"tokens": t} for t in tokens], keys

    def readings(self, key: jax.Array, state, r: int, last: int,
                 numbers) -> Dict[str, Any]:
        """What round ``r`` (from 1) of the first ``last`` gives the
        comparison with the reference, for the cell's ``numbers``."""
        out: Dict[str, Any] = {}
        if r == 1:
            out["dtheta1"] = self.theta_change(key, state)
            if "noise1" in numbers:
                out["Theta1"] = self.global_model(state)
        if r == last and {"dTheta3_med", "lam3"} & set(numbers):
            changes = self.model_change(key, state)
            out["dTheta3"], out["lam3"] = changes["dTheta"], changes["lam"]
        return out


    def theta_change(self, key: jax.Array, state) -> Dict[str, float]:
        """Per-leaf norm of the workers' weights' change since round 0."""
        W = self.traffic["workers"]
        lv = self.ref_model.leaves(self.config)

        def norms(theta, key):
            ws = [inputs.worker_weights(key, lv, w) for w in range(W)]
            got = {inputs.path_str(p): l for p, l in
                   jax.tree_util.tree_leaves_with_path(theta)}
            return {p: jnp.sqrt(sum(jnp.sum(
                (got[p][w].astype(jnp.float32)
                 - ws[w][p].astype(jnp.float32)) ** 2) for w in range(W)))
                for p, _, _ in lv}

        with self._rules():
            out = jax.jit(norms)(state.theta, key)
        return {p: float(v) for p, v in jax.device_get(out).items()}

    def global_model(self, state) -> Dict[str, Any]:
        """Θ, per leaf, on the host."""
        return {inputs.path_str(p): l for p, l in
                jax.tree_util.tree_leaves_with_path(
                    jax.device_get(state.Theta))}

    def model_change(self, key: jax.Array, state) -> Dict[str, Dict[str, float]]:
        """Per-leaf norms of Θ's change since round 0 (``dTheta``) and of
        the duals (``lam``), which start at zero."""
        W = self.traffic["workers"]
        lv = self.ref_model.leaves(self.config)
        offs, _ = inputs.offsets(lv)

        def norms(Theta, lam, key):
            ws = [inputs.worker_weights(key, lv, w) for w in range(W)]
            got = {inputs.path_str(p): l for p, l in
                   jax.tree_util.tree_leaves_with_path(Theta)}
            dT, ln = {}, {}
            for p, _, dtype in lv:
                T0 = jnp.mean(jnp.stack([ws[w][p] for w in range(W)])
                              .astype(jnp.float32), 0).astype(dtype)
                dT[p] = jnp.sqrt(jnp.sum((got[p].astype(jnp.float32)
                                          - T0.astype(jnp.float32)) ** 2))
                o, n = offs[p]
                ln[p] = jnp.sqrt(jnp.sum(lam.re[:, o:o + n] ** 2)
                                 + jnp.sum(lam.im[:, o:o + n] ** 2))
            return dT, ln

        with self._rules():
            dT, ln = jax.device_get(jax.jit(norms)(state.Theta, state.lam,
                                                   key))
        f = lambda d: {p: float(v) for p, v in d.items()}
        return {"dTheta": f(dT), "lam": f(ln)}


def _split(stacked, n: int) -> List[Any]:
    return list(jax.jit(lambda a: tuple(a[i] for i in range(n)))(stacked))
