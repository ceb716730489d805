"""Plain model of a ``model_type: llama`` configuration.

Written from the configuration file, in ``jax.numpy`` and float32 at
``HIGHEST`` matmul precision, with no kernel and nothing of the program
imported: RMSNorm, rotary position embedding on half-split heads,
grouped-query causal attention, a SiLU-gated MLP, a final RMSNorm and an
output head tied to the embedding table; the loss is the mean next-token
cross-entropy.  Besides the loss, the weights' paths and shapes and the
model FLOPs that ``mfu`` counts.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST

#: configuration keys and the program's ``ModelConfig`` fields they set;
#: the registry's entry for the configuration's ``arch`` gives the rest
PROGRAM_FIELDS = {"num_hidden_layers": "n_layers", "vocab_size": "vocab_size",
                  "hidden_size": "d_model", "intermediate_size": "d_ff",
                  "num_attention_heads": "n_heads",
                  "num_key_value_heads": "n_kv_heads", "head_dim": "head_dim",
                  "rope_theta": "rope_theta", "rms_norm_eps": "norm_eps"}


def check(config) -> None:
    """The configurations this model describes."""
    if config["tie_word_embeddings"] is not True \
            or config["hidden_act"] != "silu":
        raise ValueError(f"{config['name']}: the llama model here has a tied "
                         "head and a SiLU-gated MLP")


def leaves(config) -> List[Tuple[str, tuple, object]]:
    """``(path, per-worker shape, stored dtype)`` of every weight, in the
    packed order (paths sorted)."""
    d, f = config["hidden_size"], config["intermediate_size"]
    h, kv, hd = (config["num_attention_heads"],
                 config["num_key_value_heads"], config["head_dim"])
    L, V = config["num_hidden_layers"], config["vocab_size"]
    dtype = jnp.dtype(config["torch_dtype"])
    shapes = {
        "embed/table": (V, d),
        "final_norm/scale": (d,),
        "layers/attn/wk/w": (L, d, kv * hd),
        "layers/attn/wo/w": (L, h * hd, d),
        "layers/attn/wq/w": (L, d, h * hd),
        "layers/attn/wv/w": (L, d, kv * hd),
        "layers/ln1/scale": (L, d),
        "layers/ln2/scale": (L, d),
        "layers/mlp/down/w": (L, f, d),
        "layers/mlp/gate/w": (L, d, f),
        "layers/mlp/up/w": (L, d, f),
    }
    return [(p, shapes[p], dtype) for p in sorted(shapes)]


def matmul_params(config) -> int:
    """Weights that enter a matmul, the tied output head included."""
    d, f = config["hidden_size"], config["intermediate_size"]
    h, kv, hd = (config["num_attention_heads"],
                 config["num_key_value_heads"], config["head_dim"])
    per_layer = d * hd * (2 * h + 2 * kv) + 3 * d * f
    return config["num_hidden_layers"] * per_layer \
        + config["vocab_size"] * d


def train_flops(config, seq_len: int) -> float:
    """Model FLOPs of one training step on one sequence: forward and
    backward (3x forward) of every matmul weight, 2 FLOPs per multiply-add,
    and causal attention's score and value products over the lower
    triangle.  Recomputation (remat) is not counted."""
    S = seq_len
    dense = 6.0 * matmul_params(config) * S
    # QK^T and PV over the causal triangle: 2 x (2 S^2/2 H hd) forward
    attn = 3.0 * 2.0 * S * S * config["num_attention_heads"] \
        * config["head_dim"] * config["num_hidden_layers"]
    return dense + attn


def _rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """x: (B, S, H, hd), rotated by position over half-split pairs."""
    S, hd = x.shape[1], x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs   # (S, hd/2)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def loss_fn(p: Dict[str, jax.Array], tokens: jax.Array, config,
            operand=None) -> jax.Array:
    """Mean next-token cross-entropy of one worker's batch ``(B, S)``.

    ``p`` holds float32 weights; ``operand`` rounds every matmul operand
    (the control's lower precision), None keeps float32."""
    q = operand or (lambda a: a)

    def mm(spec, a, b):
        return jnp.einsum(spec, q(a), q(b), precision=HIGHEST)

    H, KV, hd = (config["num_attention_heads"],
                 config["num_key_value_heads"], config["head_dim"])
    eps, theta = config["rms_norm_eps"], config["rope_theta"]
    B, S = tokens.shape
    x = p["embed/table"][tokens]
    causal = jnp.tril(jnp.ones((S, S), bool))

    @jax.checkpoint         # one layer's activations live at a time
    def layer(x, lp):
        w = lambda name: lp[name]
        a = _rmsnorm(x, w("ln1/scale"), eps)
        qh = _rope(mm("bsd,de->bse", a, w("attn/wq/w")).reshape(B, S, H, hd),
                   theta)
        kh = _rope(mm("bsd,de->bse", a, w("attn/wk/w")).reshape(B, S, KV, hd),
                   theta)
        vh = mm("bsd,de->bse", a, w("attn/wv/w")).reshape(B, S, KV, hd)
        kh = jnp.repeat(kh, H // KV, axis=2)        # head i reads kv i // G
        vh = jnp.repeat(vh, H // KV, axis=2)
        s = mm("bshd,bthd->bhst", qh, kh) * hd ** -0.5
        s = jnp.where(causal, s, -jnp.inf)
        o = mm("bhst,bthd->bshd", jax.nn.softmax(s, -1), vh)
        x = x + mm("bse,ed->bsd", o.reshape(B, S, H * hd), w("attn/wo/w"))
        a = _rmsnorm(x, w("ln2/scale"), eps)
        g = mm("bsd,df->bsf", a, w("mlp/gate/w"))
        u = mm("bsd,df->bsf", a, w("mlp/up/w"))
        return x + mm("bsf,fd->bsd", jax.nn.silu(g) * u, w("mlp/down/w"))

    names = [k[len("layers/"):] for k in p if k.startswith("layers/")]
    for l in range(config["num_hidden_layers"]):
        x = layer(x, {k: p[f"layers/{k}"][l] for k in names})
    x = _rmsnorm(x, p["final_norm/scale"], eps)
    logits = mm("bsd,vd->bsv", x, p["embed/table"])
    logp = jax.nn.log_softmax(logits[:, :-1], -1)
    ll = jnp.take_along_axis(logp, tokens[:, 1:, None], -1)[..., 0]
    return -jnp.mean(ll)
