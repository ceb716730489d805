"""Minimal functional optimizers (SGD / Adam) for the local primal steps.

We deliberately do not depend on optax (offline container); these match the
textbook updates and are pytree-polymorphic.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, NamedTuple, Tuple

import jax
import jax.numpy as jnp

Array = jax.Array
PyTree = Any


class OptState(NamedTuple):
    mu: PyTree     # first moment (None for sgd)
    nu: PyTree     # second moment (None for sgd)
    count: Array


@dataclasses.dataclass(frozen=True)
class Optimizer:
    init: Callable[[PyTree], OptState]
    update: Callable[[PyTree, OptState, PyTree], Tuple[PyTree, OptState]]


def sgd(learning_rate: float) -> Optimizer:
    """Plain SGD: keeps no moments (the replicated trainer holds one
    optimizer state per worker, so a moment tree would cost a full model
    copy per worker)."""
    def init(params: PyTree) -> OptState:
        return OptState(mu=None, nu=None, count=jnp.zeros((), jnp.int32))

    def update(grads, state, params):
        new_params = jax.tree.map(lambda p, g: p - learning_rate * g,
                                  params, grads)
        return new_params, state._replace(count=state.count + 1)

    return Optimizer(init=init, update=update)


def adam(learning_rate: float, b1: float = 0.9, b2: float = 0.999,
         eps: float = 1e-8) -> Optimizer:
    def init(params: PyTree) -> OptState:
        z = jax.tree.map(jnp.zeros_like, params)
        return OptState(mu=z, nu=jax.tree.map(jnp.zeros_like, params),
                        count=jnp.zeros((), jnp.int32))

    def update(grads, state, params):
        count = state.count + 1
        mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, state.mu, grads)
        nu = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, state.nu, grads)
        c = count.astype(jnp.float32)
        mhat_s = 1.0 / (1 - b1 ** c)
        vhat_s = 1.0 / (1 - b2 ** c)
        new_params = jax.tree.map(
            lambda p, m, v: p - learning_rate * (m * mhat_s) /
            (jnp.sqrt(v * vhat_s) + eps),
            params, mu, nu)
        return new_params, OptState(mu=mu, nu=nu, count=count)

    return Optimizer(init=init, update=update)
