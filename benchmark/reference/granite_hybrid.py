"""Plain float32 reference of granite-4.0-h-micro
(huggingface.co/ibm-granite/granite-4.0-h-micro, `model_type`
granitemoehybrid; the state-space mixer is Mamba-2, Dao & Gu,
"Transformers are SSMs", arXiv:2405.21060): forward pass and training
loss in straightforward `jax.numpy`.  The recurrence is a literal
`lax.scan` over the positions with the [heads, head_dim, d_state] state
(no chunks, no decay mask: another algorithm than the program's, so that
a shared mistake cannot hide), the convolution four shifted adds,
attention the whole score matrix with the key/value heads indexed, not
repeated; no mixed precision, no kernel, nothing imported from the
program.

The model.  Tokens [batch, seq], `E` the [vocab, hidden] embedding, no
bias but the convolution's, no positions ("nope"):

    x = embedding_multiplier * E[tokens]
    every layer:  x <- x + residual_multiplier * mixer(norm_1(x))
                  [g | u] = norm_2(x) W_in    (the first half the gate)
                  x <- x + residual_multiplier * (silu(g) * u) W_out
    z = norm_f(x) E^T / logits_scaling;   loss = mean_n CE(z_n, target_n)

`layer_types` says which mixer a layer has.  "mamba", on h:

    [z | xBC | dt] = h W_in_proj       widths d_inner, d_inner + 2 N, H
    xBC_t <- silu(b_c + sum_{j<K} w_c[:, j] xBC_{t-(K-1)+j})   zeros before 0
    [x | B | C] = xBC                  widths d_inner (H heads of P), N, N
    dt <- softplus(dt + dt_bias);  A = -exp(A_log)
    per head:  S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T   (S [P, N], S_-1 = 0)
               y_t = S_t C_t + D x_t
    y <- norm_g(y * silu(z))           over the whole d_inner, learned scale
    mixer = y W_out_proj

"attention": q = h W_q (heads of head_dim), k = h W_k, v = h W_v
(`num_key_value_heads` heads), no rotation; query head i reads key/value
head i // (heads / kv heads); scores times `attention_multiplier` (not
1 / sqrt(head_dim)), causal softmax; mixer = attn W_o.

Departures from the source and sizes it does not fix are the
configuration's (`assumed` and `departures` in
benchmark/configs/granite-4.0-h-micro.json).  One group (`mamba_n_groups`
1): every head reads the same B and C.  `params`: {"embed", "blocks":
[{"norm_1", mixer's weights, "norm_2", "w_in", "w_out"}], "norm_f"},
the mixer's weights {"in_proj", "conv_w" [channels, K], "conv_b",
"dt_bias", "a_log", "d", "norm_g", "out_proj"} or {"wq", "wk", "wv",
"wo"}, matrices as [in, out].
"""

import jax
import jax.numpy as jnp


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * scale


def causal_conv(x, w, b):
    """silu(b + sum_j w[:, j] x_{t-(K-1)+j}) over x [batch, seq,
    channels], zeros before position 0: K shifted adds."""
    width = w.shape[1]
    seq = x.shape[1]
    padded = jnp.pad(x, ((0, 0), (width - 1, 0), (0, 0)))
    out = b
    for j in range(width):
        out = out + padded[:, j:j + seq] * w[:, j]
    return jax.nn.silu(out)


def recurrence(x, dt, a, b, c, d_skip, segment=None):
    """y [batch, seq, heads, head_dim] of the selective scan, one
    position after another: x [batch, seq, heads, head_dim], dt [batch,
    seq, heads] (after the softplus), a [heads] (negative), b and c
    [batch, seq, d_state], d_skip [heads].  With `segment`, the same
    steps in the same order as a scan over segments of that many
    positions, each under `jax.checkpoint`: its gradient then keeps one
    state a segment and not one a position (8.6 GB at 4096 positions of
    64 x 64 x 128)."""
    def step(state, inputs):
        x_t, dt_t, b_t, c_t = inputs
        state = jnp.exp(dt_t * a)[..., None, None] * state \
            + (dt_t[..., None] * x_t)[..., None] * b_t[:, None, None, :]
        return state, jnp.einsum("bhpn,bn->bhp", state, c_t)

    batch, seq, heads, dim = x.shape
    start = jnp.zeros((batch, heads, dim, b.shape[-1]), x.dtype)
    by_position = tuple(jnp.moveaxis(t, 1, 0) for t in (x, dt, b, c))
    if segment is None:
        _, y = jax.lax.scan(step, start, by_position)
    else:
        walk = jax.checkpoint(lambda s, part: jax.lax.scan(step, s, part))
        _, y = jax.lax.scan(walk, start, tuple(
            t.reshape(seq // segment, segment, *t.shape[1:])
            for t in by_position))
        y = y.reshape(seq, *y.shape[2:])
    return jnp.moveaxis(y, 0, 1) + d_skip[:, None] * x


def mamba_mixer(cfg, block, h):
    batch, seq, _ = h.shape
    heads, dim = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    state = cfg["mamba_d_state"]
    inner = heads * dim
    proj = h @ block["in_proj"]
    z, xbc, dt = (proj[..., :inner], proj[..., inner:2 * inner + 2 * state],
                  proj[..., 2 * inner + 2 * state:])
    xbc = causal_conv(xbc, block["conv_w"], block["conv_b"])
    x, b, c = (xbc[..., :inner], xbc[..., inner:inner + state],
               xbc[..., inner + state:])
    y = recurrence(x.reshape(batch, seq, heads, dim),
                   jax.nn.softplus(dt + block["dt_bias"]),
                   -jnp.exp(block["a_log"]), b, c, block["d"])
    y = rms_norm(y.reshape(batch, seq, inner) * jax.nn.silu(z),
                 block["norm_g"], cfg["rms_norm_eps"])
    return y @ block["out_proj"]


def attention_mixer(cfg, block, h):
    batch, seq, _ = h.shape
    heads, kv_heads = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    q = (h @ block["wq"]).reshape(batch, seq, kv_heads, heads // kv_heads,
                                  -1)
    k = (h @ block["wk"]).reshape(batch, seq, kv_heads, -1)
    v = (h @ block["wv"]).reshape(batch, seq, kv_heads, -1)
    # query head g * (heads / kv_heads) + r reads key/value head g
    scores = jnp.einsum("bqgrd,bkgd->bgrqk", q, k) \
        * cfg["attention_multiplier"]
    causal = jnp.tril(jnp.ones((seq, seq), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    out = jnp.einsum("bgrqk,bkgd->bqgrd", jax.nn.softmax(scores, axis=-1),
                     v)
    return out.reshape(batch, seq, -1) @ block["wo"]


MIXERS = {"mamba": mamba_mixer, "attention": attention_mixer}


def hidden(cfg, params, tokens, dtype=jnp.float32):
    """The residual stream after the last layer's norm, [batch, seq,
    hidden].  `dtype` is float32 for the reference; a narrower one
    (weights, activations, sums and the loss all in it) is how a
    comparison's tolerance is shown to tell precisions apart."""
    params = jax.tree_util.tree_map(lambda a: jnp.asarray(a, dtype), params)
    eps, res = cfg["rms_norm_eps"], cfg["residual_multiplier"]
    x = cfg["embedding_multiplier"] * params["embed"][tokens]
    for kind, block in zip(cfg["layer_types"], params["blocks"]):
        x = x + res * MIXERS[kind](
            cfg, block, rms_norm(x, block["norm_1"], eps))
        gate_up = rms_norm(x, block["norm_2"], eps) @ block["w_in"]
        width = gate_up.shape[-1] // 2
        x = x + res * ((jax.nn.silu(gate_up[..., :width])
                        * gate_up[..., width:]) @ block["w_out"])
    return rms_norm(x, params["norm_f"], eps), params["embed"]


def logits(cfg, params, tokens, last=None, dtype=jnp.float32):
    """[batch, seq, vocab]; with `last`, of the last `last` positions
    only (the whole context is still read)."""
    with jax.default_matmul_precision("highest"):
        x, embed = hidden(cfg, params, tokens, dtype)
        if last is not None:
            x = x[:, -last:]
        return x @ embed.T / cfg["logits_scaling"]


def loss(cfg, params, feeds, dtype=jnp.float32):
    """Mean cross-entropy of every position's next token."""
    z = logits(cfg, params, feeds["tokens"], dtype=dtype)
    targets = feeds["targets"].astype(jnp.int32)
    return jnp.mean(-jnp.take_along_axis(
        jax.nn.log_softmax(z, axis=-1), targets, axis=-1))
