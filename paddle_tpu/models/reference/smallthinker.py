"""Plain float32 reference of SmallThinker-21BA3B-Instruct
(huggingface.co/PowerInfer/SmallThinker-21BA3B-Instruct, config.json;
the family's description: window and full attention mixed, the full
layers without positions, sparse ReGLU experts, the router placed before
attention): forward pass and training loss in straightforward
`jax.numpy`, attention as whole rows of scores under a mask (a block of
queries at a time, so that 16,384 positions fit a chip's memory beside a
training program's state; the arithmetic of a row is the dense one),
every held expert applied densely to every token and weighted by the
routing afterwards, no ordering, no grouped product, no kernel, no mixed
precision, nothing imported from the program.

The model.  Pre-norm decoder layers, no bias anywhere, no q/k norm.
With x [batch, seq, hidden], for layer l:

    u   = norm_1(x)
    r   = u W_r                          (the router reads u: before attention)
    idx = top_k(r);  p = softmax(r[idx])
    q, k, v = u W_q, u W_k, u W_v
    if rope_layout[l]: q, k = rope(q), rope(k)     (rotate-half, theta)
    query head h reads key/value head h // (heads / kv heads); / sqrt(head_dim)
    query i sees keys j <= i, and j > i - window where sliding_window_layout[l]
    a   = x + attn W_o
    s   = norm_2(a)
    y   = sum_j p_j W_down[e] (relu(s W_gate[e]) * (s W_up[e])),  e = idx_j
    x  <- a + y

then z = norm_f(x) W_head and the loss is the mean cross-entropy: no
auxiliary loss.  softmax over the chosen logits is the softmax over all
experts, its `top_k` largest, renormalised
(`moe_primary_router_apply_softmax`, `norm_topk_prob`).

One chip's share.  Where the configuration holds `moe_num_primary_experts`
of the `scored_experts` its router scores, from `first_expert` on, the
sum over j runs over the held e alone: what the absent experts would add
is another chip's, here as in the program, and the partial result goes
on.  A sliced vocabulary is a smaller vocabulary.

What the configuration does not fix is read from `cfg` beside it and
listed under `assumed` in benchmark/configs/smallthinker-21b-a3b.json:
`router_reads` ("input_layernorm": u; "post_attention_layernorm" would
be s, the reading this one was chosen over), `hidden_act` ("relu").
`params` holds the weights by layer: {"embed", "blocks": [{"norm_1",
"wq", "wk", "wv", "wo", "norm_2", "router", "w_gate", "w_up",
"w_down"}], "norm_f", "head"}, matrices as [in, out], an expert stack as
[held experts, in, out].
"""

import math

import jax
import jax.numpy as jnp

QUERY_BLOCK = 512


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * scale


def rope(x, positions, theta):
    """x [batch, seq, heads, head_dim] turned at `positions` [batch, seq]:
    x cos + rotate_half(x) sin, the two halves of a head paired."""
    dim = x.shape[-1]
    inv_freq = 1.0 / theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32)
                               / dim)
    angles = positions[..., None, None].astype(jnp.float32) * inv_freq
    angles = jnp.concatenate([angles, angles], axis=-1)
    x1, x2 = x[..., :dim // 2], x[..., dim // 2:]
    rotated = jnp.concatenate([-x2, x1], axis=-1)
    return (x * jnp.cos(angles) + rotated * jnp.sin(angles)).astype(x.dtype)


def masked_attention(q, k, v, window):
    """softmax(q k^T / sqrt(d) under the mask) v for q [batch, seq,
    heads, d] and k, v [batch, seq, kv heads, d]: query i sees keys
    j <= i, and with `window` > 0 only those with j > i - window.  Whole
    rows of scores, `QUERY_BLOCK` queries at a time."""
    batch, seq, heads, d = q.shape
    group = heads // k.shape[2]
    k, v = (jnp.repeat(a, group, axis=2) for a in (k, v))
    block = math.gcd(seq, QUERY_BLOCK)
    keys = jnp.arange(seq)

    # a block's scores are made again for its gradient, not kept: 32
    # blocks of [heads, 512, 16384] float32 would not fit a chip
    @jax.checkpoint
    def rows(start):
        queries = start + jnp.arange(block)
        qb = jax.lax.dynamic_slice_in_dim(q, start, block, axis=1)
        scores = jnp.einsum("bqhd,bkhd->bhqk", qb, k) / math.sqrt(d)
        seen = keys[None, :] <= queries[:, None]
        if window:
            seen &= keys[None, :] > queries[:, None] - window
        scores = jnp.where(seen, scores, -jnp.inf)
        return jnp.einsum("bhqk,bkhd->bqhd",
                          jax.nn.softmax(scores, axis=-1), v)

    out = jax.lax.map(rows, jnp.arange(0, seq, block))
    return jnp.moveaxis(out, 0, 1).reshape(batch, seq, heads * d)


def attention(cfg, block, u, positions, layer):
    """The attention sub-layer's output (before the residual) of layer
    `layer` for u [batch, seq, hidden], already normed."""
    batch, seq, _ = u.shape
    d = cfg["head_dim"]
    q = (u @ block["wq"]).reshape(batch, seq, cfg["num_attention_heads"], d)
    k, v = ((u @ block[w]).reshape(batch, seq, cfg["num_key_value_heads"], d)
            for w in ("wk", "wv"))
    if cfg["rope_layout"][layer]:
        q = rope(q, positions, cfg["rope_theta"])
        k = rope(k, positions, cfg["rope_theta"])
    window = cfg["sliding_window_size"] \
        if cfg["sliding_window_layout"][layer] else 0
    return masked_attention(q, k, v, window) @ block["wo"]


def route(cfg, block, r, indices=None):
    """(logits, p, indices) for the router's input r [tokens, hidden]:
    the `moe_num_active_primary_experts` largest logits (the reference's
    own, or `indices` [tokens, top_k] where given) and the softmax over
    them."""
    logits = r @ block["router"]
    if indices is None:
        indices = jax.lax.top_k(
            logits, cfg["moe_num_active_primary_experts"])[1]
    chosen = jnp.take_along_axis(logits, indices, axis=1)
    return logits, jax.nn.softmax(chosen, axis=-1), indices


def experts(cfg, block, s, p, indices):
    """sum_j p_j expert_{idx_j}(s) over the held experts for s [tokens,
    hidden]: every held expert applied to every token, weighted by the
    token's p of it where it is among the token's chosen and by zero
    elsewhere."""
    act = {"relu": jax.nn.relu, "silu": jax.nn.silu}[cfg["hidden_act"]]
    held = cfg["first_expert"] + jnp.arange(block["w_gate"].shape[0])
    weights = jnp.sum((indices[:, :, None] == held) * p[:, :, None], axis=1)

    def add_expert(y, expert):
        w_gate, w_up, w_down, weight = expert
        hidden = act(s @ w_gate) * (s @ w_up)
        return y + weight[:, None] * (hidden @ w_down), None

    # one expert after another (a scan, so that they compile as one)
    y, _ = jax.lax.scan(add_expert, jnp.zeros_like(s), (
        block["w_gate"], block["w_up"], block["w_down"], weights.T))
    return y


def layer(cfg, block, x, positions, i, indices=None):
    """Layer `i` on x [batch, seq, hidden]: (x after it, the attention
    sub-layer's output, what the experts read, the expert layer's
    output, the router's logits, the experts chosen)."""
    eps = cfg["rms_norm_eps"]
    u = rms_norm(x, block["norm_1"], eps)
    a = attention(cfg, block, u, positions, i)
    x = x + a
    s = rms_norm(x, block["norm_2"], eps)
    reads = {"input_layernorm": u, "post_attention_layernorm": s}[
        cfg["router_reads"]]
    flat = s.reshape(-1, s.shape[-1])
    logits, p, idx = route(cfg, block, reads.reshape(flat.shape), indices)
    y = experts(cfg, block, flat, p, idx)
    return x + y.reshape(x.shape), a, flat, y, logits, idx


def forward(cfg, params, tokens, indices=None, positions=None,
            dtype=jnp.float32):
    """{"logits" [batch, seq, vocab] and per layer, in lists,
    "attn_out" [batch, seq, hidden] (the attention sub-layer's output),
    "moe_in" and "moe_out" [tokens, hidden], "router_logits" [tokens,
    scored experts], "indices" [tokens, top_k]}.  `indices`, one
    [tokens, top_k] array a layer, takes the place of the reference's
    own top-k.  `dtype` is float32 for the reference; a narrower one
    (weights, activations, sums and the loss all in it) is how a
    comparison's tolerance is shown to tell precisions apart.  A layer's
    activations are made again for its gradient, not kept: four layers
    of float32 at 16,384 positions do not fit a chip beside the weights'
    gradients."""
    params = jax.tree_util.tree_map(lambda a: jnp.asarray(a, dtype), params)
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(tokens.shape[1]),
                                     tokens.shape)
    x = params["embed"][tokens]
    keys = ("attn_out", "moe_in", "moe_out", "router_logits", "indices")
    out = {key: [] for key in keys}
    for i, block in enumerate(params["blocks"]):
        x, *parts = jax.checkpoint(
            lambda block, x, idx, i=i: layer(cfg, block, x, positions, i,
                                             idx))(
            block, x, None if indices is None else indices[i])
        for key, part in zip(keys, parts):
            out[key].append(part)
    out["logits"] = rms_norm(x, params["norm_f"], cfg["rms_norm_eps"]) \
        @ params["head"]
    return out


def loss_terms(cfg, params, feeds, indices=None, dtype=jnp.float32):
    """{"loss"} and what `forward` gives."""
    with jax.default_matmul_precision("highest"):
        out = forward(cfg, params, feeds["tokens"], indices,
                      feeds.get("positions"), dtype)
        targets = feeds["targets"].astype(jnp.int32)
        out["loss"] = jnp.mean(-jnp.take_along_axis(
            jax.nn.log_softmax(out["logits"], axis=-1), targets, axis=-1))
        return out


def loss(cfg, params, feeds):
    """Mean cross-entropy over the positions."""
    return loss_terms(cfg, params, feeds)["loss"]
