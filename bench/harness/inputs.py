"""Everything a run feeds the program, made from ``--seed`` alone.

The same seed gives the same weights, fading, tokens and round keys, for
the program and for the reference alike; neither takes them from the
other.  Each stream is a ``fold_in`` of the seed's key with a tag of its
own, and each weight leaf is drawn from its path, so the draw does not
depend on the order in which a tree is walked.
"""
from __future__ import annotations

import math
import zlib
from typing import Dict, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

THETA, FADING, TOKENS, ROUNDS = 1, 2, 3, 4


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any whole number below 2**64."""
    if not 0 <= seed < 2 ** 64:
        raise ValueError(f"--seed must be in [0, 2**64), got {seed}")
    k = jax.random.PRNGKey(0)
    k = jax.random.fold_in(k, np.uint32(seed & 0xFFFFFFFF))
    return jax.random.fold_in(k, np.uint32(seed >> 32))


def stream(key: jax.Array, tag: int) -> jax.Array:
    return jax.random.fold_in(key, tag)


def path_str(path) -> str:
    """``embed/table`` for a tree path of dict keys."""
    parts = []
    for p in path:
        parts.append(str(getattr(p, "key", getattr(p, "name", p))))
    return "/".join(parts)


def offsets(leaves: Sequence[Tuple[str, tuple, object]]
            ) -> Tuple[Dict[str, Tuple[int, int]], int]:
    """``{path: (offset, size)}`` on the packed index space (leaf after
    leaf in the order given, each raveled row-major), and its D."""
    out, off = {}, 0
    for p, s, _ in leaves:
        n = math.prod(s)
        out[p] = (off, n)
        off += n
    return out, off


def param_count(config, root=None) -> int:
    from harness import spec
    model = spec.load_model(config, root or spec.ROOT)
    return offsets(model.leaves(config))[1]


def leaf_value(key: jax.Array, path: str, shape: Tuple[int, ...],
               dtype) -> jax.Array:
    """One worker's initial value of the weight at ``path``: norm scales are
    ones, biases zeros, the embedding table N(0, 1/d_model), every other
    matrix N(0, 1/fan_in) with fan_in its second-to-last dimension."""
    name = path.rsplit("/", 1)[-1]
    if name == "scale":
        return jnp.ones(shape, dtype)
    if name == "b":
        return jnp.zeros(shape, dtype)
    k = jax.random.fold_in(key, zlib.crc32(path.encode()) & 0x7FFFFFFF)
    fan_in = shape[-1] if name == "table" else shape[-2]
    return (jax.random.normal(k, shape, jnp.float32)
            * fan_in ** -0.5).astype(dtype)


def worker_weights(key: jax.Array, leaves: Sequence[Tuple[str, tuple, object]],
                   worker: int) -> Dict[str, jax.Array]:
    """``{path: value}`` of one worker's weights; ``leaves`` lists
    ``(path, per-worker shape, dtype)``.  Workers start from independent
    draws, as the trainer's own init does."""
    kw = jax.random.fold_in(stream(key, THETA), worker)
    return {p: leaf_value(kw, p, s, d) for p, s, d in leaves}


def rayleigh(key: jax.Array, shape: Tuple[int, ...]):
    """CN(0, 1) fading: (re, im), each N(0, 1/2)."""
    kr, ki = jax.random.split(key)
    s = jnp.sqrt(jnp.float32(0.5))
    return (jax.random.normal(kr, shape, jnp.float32) * s,
            jax.random.normal(ki, shape, jnp.float32) * s)


def fading(key: jax.Array, n_workers: int, d: int):
    """The initial fading block over the packed ``(W, d)`` index space."""
    return rayleigh(stream(key, FADING), (n_workers, d))


def token_dataset(key: jax.Array, n_sequences: int, seq_len: int,
                  vocab_size: int, n_workers: int = 1,
                  skew: float = 2.0) -> jax.Array:
    """Synthetic token streams with per-worker unigram skew (non-IID FL).

    Each worker samples from a Zipf-tempered unigram distribution over a
    worker-specific random permutation of the vocabulary, so local losses
    disagree.  Returns ``(n_workers, n_sequences, seq_len)`` int32.
    """
    ranks = jnp.arange(1, vocab_size + 1, dtype=jnp.float32)
    base_logits = -skew * jnp.log(ranks)

    def one_worker(k):
        kp, ks = jax.random.split(k)
        perm = jax.random.permutation(kp, vocab_size)
        logits = base_logits[jnp.argsort(perm)]
        return jax.random.categorical(ks, logits,
                                      shape=(n_sequences, seq_len))

    keys = jax.random.split(key, n_workers)
    return jax.vmap(one_worker)(keys).astype(jnp.int32)


def round_tokens(key: jax.Array, traffic, vocab_size: int,
                 n_rounds: int) -> jax.Array:
    """``(n_rounds, W, B, S)``: round r's batch, worker-major."""
    W, B, S = (traffic["workers"], traffic["batch_per_worker"],
               traffic["seq_len"])
    t = token_dataset(stream(key, TOKENS), n_rounds * B, S, vocab_size,
                      n_workers=W, skew=traffic["token_skew"])
    return t.reshape(W, n_rounds, B, S).transpose(1, 0, 2, 3)


def round_keys(key: jax.Array, n_rounds: int) -> jax.Array:
    """``(n_rounds, 2)``: the key handed to round r's step."""
    k = stream(key, ROUNDS)
    return jax.vmap(lambda i: jax.random.fold_in(k, i))(
        jnp.arange(n_rounds, dtype=jnp.uint32))
