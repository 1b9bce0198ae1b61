"""Plain float32 reference of one chip's share of K-EXAONE-236B-A23B
(huggingface.co/LGAI-EXAONE/K-EXAONE-236B-A23B, `model_type`
`exaone_moe`): the full-sequence forward pass in straightforward
`jax.numpy`, the whole score matrix under a mask (the window is a mask,
there is no cache), every key/value head repeated for its group of query
heads, every held expert applied densely to every token and masked by
the routing weights, highest matmul precision, nothing imported from the
program.

The model.  With x [batch, seq, hidden], RMSNorms N with a scale each,
no bias anywhere, a pre-norm block (*assumed*: config.json does not say;
EXAONE 4.0's 1.2B model is post-norm, its hybrid 32B and the MoE config's
DeepSeek-V3 keys point to the pre-norm block, and the issue fixes it):

    a = x + Attn_l(N_in(x))
    y = a + F_l(N_pre_mlp(a))

Attn_l, for u = N_in(x), `num_attention_heads` query heads and
`num_key_value_heads` key/value heads of `head_dim`:

    q = u W_q, k = u W_k, v = u W_v, split into heads
    q, k: RMSNorm over each head's head_dim values, one learned
          [head_dim] scale for q and one for k a layer   (*assumed*:
          EXAONE 4.0's released modeling file)
    on a `sliding_attention` layer: q, k = rope(q), rope(k), rotate-half
          over all head_dim values, base `rope_theta`; on a
          `full_attention` layer no positions at all      (*assumed*:
          EXAONE 4.0's hybrid-attention convention)
    query head j reads key/value head j // (heads / kv heads)
    s_ij = q_i . k_j / sqrt(head_dim) for j <= i and, on a
          `sliding_attention` layer, i - j < `sliding_window` (a query
          sees itself and the window - 1 positions before it:
          *assumed*, the inclusive edge of the released mask)
    o = softmax(s) v;  Attn = [o_1 .. o_H] W_o

F_l is the gated-SiLU feed-forward (silu(u W_g) * (u W_u)) W_d of width
`intermediate_size` where `mlp_layer_types[l]` is "dense", else

    s = sigmoid(u W_r) over the scored experts, in float32
    e_j, j < top_k: the largest of s + b   (b: the selection bias,
          *assumed*: the DeepSeek-V3 gate whose keys this config has;
          n_group = topk_group = 1 limit nothing)
    w_j = routed_scaling_factor * s_{e_j} / (sum_j s_{e_j} + 1e-20)
    F(u) = E_shared(u) + sum_j w_j E_{e_j}(u)

of which a share holds the experts `held = (first, count)`: the sum then
runs over the j whose e_j lies in first .. first + count - 1
(`shared=False` leaves the replicated shared expert out, for adding
shares up).  After the last layer z = N_f(x) W_head over the rows of the
vocabulary the share holds.  The prediction module
(`num_nextn_predict_layers`) is not part of the forward.

`params`: {"embed" [vocab, hidden], "blocks": [{"input_norm", "wq"
[hidden, heads * head_dim], "wk", "wv" [hidden, kv heads * head_dim],
"q_norm", "k_norm" [head_dim], "wo", "pre_mlp_norm", then "ffn_in"
[hidden, 2 * width] (gate columns first) and "ffn_out" for a dense
layer, or "shared_in", "shared_out", "router" [hidden, scored],
"router_bias" [scored], "w_gate", "w_up" [count, hidden, width],
"w_down" [count, width, hidden]}], "norm_f", "head" [hidden, vocab]},
matrices as [in, out].  `cfg` has the source's keys ("layer_types",
"mlp_layer_types", "sliding_window", "rope_parameters", ...).
"""

import math

import jax
import jax.numpy as jnp

WINDOW = "sliding_attention"


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * scale


def rope(x, positions, theta):
    """x [batch, seq, heads, dim] turned at `positions` [seq]: x cos +
    rotate_half(x) sin, the two halves of a head paired."""
    dim = x.shape[-1]
    inv_freq = 1.0 / theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32)
                               / dim)
    angles = positions[:, None, None].astype(jnp.float32) * inv_freq
    angles = jnp.concatenate([angles, angles], axis=-1)
    x1, x2 = x[..., :dim // 2], x[..., dim // 2:]
    rotated = jnp.concatenate([-x2, x1], axis=-1)
    return x * jnp.cos(angles) + rotated * jnp.sin(angles)


def gated(u, w_in, w_out):
    gate, up = jnp.split(u @ w_in, 2, axis=-1)
    return (jax.nn.silu(gate) * up) @ w_out


def attention(cfg, block, h, kind):
    """(Attn(h), k, v) of h [batch, seq, hidden] on a layer of `kind`:
    the window as a mask, every key/value head repeated; k (normed, and
    rotated on a window layer) and v [batch, seq, kv heads, head_dim] are
    what a cache of the layer would hold."""
    batch, seq, _ = h.shape
    heads, kv_heads = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dim, eps = cfg["head_dim"], cfg["rms_norm_eps"]
    positions = jnp.arange(seq)
    q = rms_norm((h @ block["wq"]).reshape(batch, seq, heads, dim),
                 block["q_norm"], eps)
    k = rms_norm((h @ block["wk"]).reshape(batch, seq, kv_heads, dim),
                 block["k_norm"], eps)
    v = (h @ block["wv"]).reshape(batch, seq, kv_heads, dim)
    keep = positions[None, :] <= positions[:, None]
    if kind == WINDOW:
        theta = float(cfg["rope_parameters"]["rope_theta"])
        q, k = rope(q, positions, theta), rope(k, positions, theta)
        keep &= positions[:, None] - positions[None, :] \
            < cfg["sliding_window"]
    k_all, v_all = (jnp.repeat(t, heads // kv_heads, axis=2)
                    for t in (k, v))
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k_all) / math.sqrt(dim)
    scores = jnp.where(keep, scores, -jnp.inf)
    out = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1),
                     v_all)
    return out.reshape(batch, seq, -1) @ block["wo"], k, v


def route(cfg, block, u, indices=None):
    """(weights [tokens, scored], indices [tokens, top_k]) of u [tokens,
    hidden]: a token's weight of each scored expert, 0 where it is not
    among its chosen (the reference's own choice, by s + b, or `indices`
    where a caller hands it a routing); the weights read s."""
    scores = jax.nn.sigmoid(u @ block["router"])
    if indices is None:
        indices = jax.lax.top_k(scores + block["router_bias"],
                                cfg["num_experts_per_tok"])[1]
    top = jnp.take_along_axis(scores, indices, axis=1)
    if cfg["norm_topk_prob"]:
        top = top / (jnp.sum(top, axis=-1, keepdims=True) + 1e-20)
    top = top * cfg["routed_scaling_factor"]
    hot = indices[..., None] == jnp.arange(scores.shape[-1])
    return jnp.sum(jnp.where(hot, top[..., None], 0.0), axis=1), indices


def routed(cfg, block, u, first=0, indices=None):
    """The held experts' part of the routed sum for u [tokens, hidden]:
    every held expert applied to every token, one after another (a
    scan), weighted by the token's weight of it."""
    weights, indices = route(cfg, block, u, indices)
    count = block["w_gate"].shape[0]
    held = jax.lax.dynamic_slice_in_dim(weights, first, count, axis=1)

    def add_expert(m, expert):
        w_gate, w_up, w_down, weight = expert
        hidden = jax.nn.silu(u @ w_gate) * (u @ w_up)
        return m + weight[:, None] * (hidden @ w_down), None

    m, _ = jax.lax.scan(add_expert, jnp.zeros_like(u), (
        block["w_gate"], block["w_up"], block["w_down"], held.T))
    return m, indices


def feed_forward(cfg, block, u, first=0, shared=True, indices=None):
    """(F(u), indices or None) for u [tokens, hidden]."""
    if "ffn_in" in block:
        return gated(u, block["ffn_in"], block["ffn_out"]), None
    m, indices = routed(cfg, block, u, first, indices)
    if shared:
        m = m + gated(u, block["shared_in"], block["shared_out"])
    return m, indices


def layer(cfg, block, x, kind, first=0, indices=None):
    """(y, the attention sub-layer's output, indices, k, v) of one
    decoder layer of `kind`."""
    eps = cfg["rms_norm_eps"]
    o, k, v = attention(cfg, block, rms_norm(x, block["input_norm"], eps),
                        kind)
    a = x + o
    u = rms_norm(a, block["pre_mlp_norm"], eps)
    f, indices = feed_forward(cfg, block, u.reshape(-1, u.shape[-1]), first,
                              indices=indices)
    return a + f.reshape(a.shape), o, indices, k, v


def forward(cfg, params, tokens, held=None):
    """{"logits" [batch, seq, vocab], "hidden": [L] each layer's output,
    "attn": [L] each attention sub-layer's output, "indices": [L] the
    experts chosen [tokens, top_k] (None for a dense layer), "keys",
    "values": [L] what `attention` gives beside its output} for local
    token ids `tokens` [batch, seq]; `held` = (first, count) says which
    of the scored experts `params` holds (default: the first
    `w_gate.shape[0]`)."""
    params = jax.tree_util.tree_map(
        lambda a: jnp.asarray(a, jnp.float32), params)
    first = 0
    if held is not None:
        first = held[0]
        for block in params["blocks"]:
            if "w_gate" in block and block["w_gate"].shape[0] != held[1]:
                raise ValueError("params hold %d experts, `held` says %d"
                                 % (block["w_gate"].shape[0], held[1]))
    out = {"hidden": [], "attn": [], "indices": [], "keys": [],
           "values": []}
    with jax.default_matmul_precision("highest"):
        x = params["embed"][tokens]
        for block, kind in zip(params["blocks"], cfg["layer_types"]):
            x, o, indices, k, v = layer(cfg, block, x, kind, first)
            for key, value in (("hidden", x), ("attn", o), ("keys", k),
                               ("indices", indices), ("values", v)):
                out[key].append(value)
        out["logits"] = rms_norm(x, params["norm_f"],
                                 cfg["rms_norm_eps"]) @ params["head"]
    return out


def session(cfg, found, extent):
    """{"k_cache_<i>", "v_cache_<i>": [batch, kv heads, slots, head_dim],
    "pos": [batch]}: the caches a decode step continues from after the
    sequence `forward` gave `found` for, as a prefill would hand them
    over.  A `full_attention` layer's hold position p in slot p of
    `extent`; a `sliding_attention` layer's are rings of `sliding_window`
    slots, the last `sliding_window` positions, position p in slot p mod
    `sliding_window`."""
    import numpy as np

    window = cfg["sliding_window"]
    out = {}
    for i, kind in enumerate(cfg["layer_types"]):
        for which, made in (("k", found["keys"][i]),
                            ("v", found["values"][i])):
            made = np.asarray(made).transpose(0, 2, 1, 3)
            batch, heads, seq, dim = made.shape
            slots = window if kind == WINDOW else extent
            cache = np.zeros((batch, heads, slots, dim), np.float32)
            kept = np.arange(max(seq - slots, 0), seq)
            cache[:, :, kept % slots] = made[:, :, kept]
            out["%s_cache_%d" % (which, i)] = cache
    out["pos"] = np.full((batch,), seq, np.int64)
    return out
