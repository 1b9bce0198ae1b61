"""Plain float32 reference of the GPT-2 decoder (Radford et al. 2019;
huggingface.co/openai-community/gpt2-medium): forward pass and next-token
cross-entropy in straightforward `jax.numpy`, dense attention with the
whole score matrix, no kernels, no mixed precision, nothing from the
program.

Departures from the published model are the configuration's
(`departures` in benchmark/configs/gpt2-medium.json): ReLU in the
feed-forward, an output head of its own, no dropout.  `params` holds the
weights by layer (benchmark/models/gpt2.param_names says which program
variable is which).
"""

import jax
import jax.numpy as jnp


def _layer_norm(x, scale_bias, eps):
    scale, bias = scale_bias
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * scale + bias


def _dense(x, weight_bias):
    w, b = weight_bias
    return x @ w + b


def _attention(cfg, qkv):
    batch, seq, _ = qkv.shape
    heads = cfg["n_head"]
    q, k, v = (t.reshape(batch, seq, heads, -1).transpose(0, 2, 1, 3)
               for t in jnp.split(qkv, 3, axis=-1))
    scores = q @ k.transpose(0, 1, 3, 2) / jnp.sqrt(
        jnp.float32(q.shape[-1]))
    causal = jnp.tril(jnp.ones((seq, seq), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    out = jax.nn.softmax(scores, axis=-1) @ v
    return out.transpose(0, 2, 1, 3).reshape(batch, seq, -1)


def logits(cfg, params, tokens, positions):
    params = jax.tree_util.tree_map(
        lambda a: jnp.asarray(a, jnp.float32), params)
    eps = cfg["layer_norm_epsilon"]
    x = params["wte"][tokens] + params["wpe"][positions]
    for block in params["blocks"]:
        h = _layer_norm(x, block["ln_1"], eps)
        x = x + _dense(_attention(cfg, _dense(h, block["qkv"])),
                       block["proj"])
        h = _layer_norm(x, block["ln_2"], eps)
        x = x + _dense(jax.nn.relu(_dense(h, block["fc_1"])),
                       block["fc_2"])
    return _dense(_layer_norm(x, params["ln_f"], eps), params["head"])


def loss(cfg, params, feeds):
    """Mean next-token cross-entropy over every position."""
    with jax.default_matmul_precision("highest"):
        z = logits(cfg, params, feeds["tokens"], feeds["positions"])
        logp = jax.nn.log_softmax(z, axis=-1)
        picked = jnp.take_along_axis(
            logp, feeds["targets"].astype(jnp.int32), axis=-1)
        return -jnp.mean(picked)
