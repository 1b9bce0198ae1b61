"""What a generation cell's served tokens are held to: the plain float32
GPT-2 decoder (Radford et al. 2019; as benchmark/reference/gpt2.py, with
the configuration's departures: ReLU, an output head of its own) run once
over each prompt with the tokens the system served after it, dense
attention over the whole sequence, no cache, no kernels, highest matmul
precision, nothing from the program.

A greedy token is right when no other token's logit is above its own, and
with seeded weights the top two lie close often enough that rounding
picks the other one now and then.  So the number compared is the *gap*:
by how much the reference's logit of the served token lies below the
reference's best at that position, 0 where they agree.  `gaps` gives it
for every served position.
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
    rows, seq, _ = qkv.shape
    heads = cfg["n_head"]
    q, k, v = (t.reshape(rows, seq, heads, -1).transpose(0, 2, 1, 3)
               for t in jnp.split(qkv, 3, axis=-1))
    scores = q @ k.transpose(0, 1, 3, 2) / jnp.sqrt(
        jnp.float32(q.shape[-1]))
    causal = jnp.tril(jnp.ones((seq, seq), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    out = jax.nn.softmax(scores, axis=-1) @ v
    return out.transpose(0, 2, 1, 3).reshape(rows, seq, -1)


def logits(cfg, params, tokens, first):
    """Float32 logits `[rows, seq - first, vocab]` at positions `first`
    and after of `tokens` `[rows, seq]`, which stand at positions
    0..seq-1."""
    params = jax.tree_util.tree_map(
        lambda a: jnp.asarray(a, jnp.float32), params)
    eps = cfg["layer_norm_epsilon"]
    seq = tokens.shape[1]
    with jax.default_matmul_precision("highest"):
        x = params["wte"][tokens] + params["wpe"][jnp.arange(seq)]
        for block in params["blocks"]:
            h = _layer_norm(x, block["ln_1"], eps)
            x = x + _dense(_attention(cfg, _dense(h, block["qkv"])),
                           block["proj"])
            h = _layer_norm(x, block["ln_2"], eps)
            x = x + _dense(jax.nn.relu(_dense(h, block["fc_1"])),
                           block["fc_2"])
        x = _layer_norm(x[:, first:], params["ln_f"], eps)
        return _dense(x, params["head"])


def gaps(cfg, params, prompt, served):
    """`[rows, served length]` float32: at every served position, how far
    the reference's logit of the served token lies below the reference's
    best.

    The served token i was chosen from the logits at position
    prompt_len - 1 + i, whose input is the prompt and the served tokens
    before it."""
    tokens = jnp.concatenate([prompt, served], axis=1)
    first, count = prompt.shape[1] - 1, served.shape[1]
    z = logits(cfg, params, tokens, first)[:, :count]
    picked = jnp.take_along_axis(z, served[..., None], axis=-1)
    return jnp.max(z, axis=-1) - picked[..., 0]
