"""Plain float32 reference of one chip's share of openPangu-Ultra-MoE-718B
(huggingface.co/FreedomIntelligence/openPangu-Ultra-MoE-718B; the
DeepSeek-V3 family's block, arXiv:2412.19437, with sandwich norms):
the full-sequence forward pass in straightforward `jax.numpy`, attention
with every head's keys and values made from the latents and the whole
score matrix (nothing absorbed, no cache), every held expert applied
densely to every token and masked by the routing weights, highest matmul
precision, nothing imported from the program.

The model.  With x [batch, seq, hidden], RMSNorms N with a scale each,
no bias anywhere:

    a = x + N_post_attn(MLA(N_in(x)))
    y = a + N_post_mlp(F(N_pre_mlp(a)))

MLA, for h = N_in(x), per head i of `num_attention_heads`:

    c_q = N_q(h W_dq)                          [q_lora_rank]
    q_i = [c_q W_uq_nope,i | rope(c_q W_uq_rope,i)]
    [c | r] = h W_dkv;  c = N_kv(c);  r = rope(r)   [kv_lora_rank | rope]
    k_i = [c W_uk,i | r],  v_i = c W_uv,i      (r is shared by the heads)
    o_i = softmax_causal(q_i k_i^T / sqrt(nope + rope)) v_i
    MLA = [o_1 .. o_H] W_o

rope is the rotate-half form at base `rope_theta`.  F is the gated-SiLU
feed-forward (silu(u W_g) * (u W_u)) W_d of width `intermediate_size` in
the first `first_k_dense_replace` layers, and in the others the expert
layer

    s = sigmoid(u W_r) over the `n_routed_experts` scored
    (s_j, e_j), j < top_k: the largest
    w_j = routed_scaling_factor * s_j / (sum_j s_j + 1e-20)
    F(u) = E_shared(u) + sum_j w_j E_{e_j}(u)

of which a share holds the experts `held = (first, count)`: the sum then
runs over the j whose e_j lies in first .. first + count - 1, and what
it gives is this share's part of the layer (the shared expert is
replicated, so it is whole in every share: `shared=False` leaves it out,
for adding shares up).  After the last layer z = N_f(x) W_head over the
rows of the vocabulary the share holds; token ids are local to them.

`params`: {"embed" [vocab, hidden], "blocks": [{"input_norm", "w_dq",
"q_norm", "w_uq_nope", "w_uq_rope", "w_dkv", "kv_norm", "w_uk", "w_uv",
"wo", "post_attn_norm", "pre_mlp_norm", then "ffn_in" [hidden, 2 *
width] (gate columns first) and "ffn_out" for a dense layer, or
"shared_in", "shared_out", "router" [hidden, scored], "w_gate", "w_up"
[count, hidden, width], "w_down" [count, width, hidden] for an expert
layer, and "post_mlp_norm"}], "norm_f", "head" [hidden, vocab]},
matrices as [in, out].  `cfg` has the source's keys.
"""

import math

import jax
import jax.numpy as jnp


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


def attention(cfg, block, h):
    """MLA of h [batch, seq, hidden], every head's keys and values made
    from the latents."""
    batch, seq, _ = h.shape
    heads, eps = cfg["num_attention_heads"], cfg["rms_norm_eps"]
    theta = cfg["rope_theta"]
    positions = jnp.arange(seq)
    c_q = rms_norm(h @ block["w_dq"], block["q_norm"], eps)
    q_nope = (c_q @ block["w_uq_nope"]).reshape(batch, seq, heads, -1)
    q_rope = rope((c_q @ block["w_uq_rope"]).reshape(batch, seq, heads, -1),
                  positions, theta)
    latent = cfg["kv_lora_rank"]
    ckv = h @ block["w_dkv"]
    c = rms_norm(ckv[..., :latent], block["kv_norm"], eps)
    r = rope(ckv[..., latent:][:, :, None, :], positions, theta)
    k_nope = (c @ block["w_uk"]).reshape(batch, seq, heads, -1)
    v = (c @ block["w_uv"]).reshape(batch, seq, heads, -1)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(r, k_nope.shape[:3] + r.shape[3:])],
        axis=-1)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(q.shape[-1])
    causal = jnp.tril(jnp.ones((seq, seq), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    out = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1), v)
    return out.reshape(batch, seq, -1) @ block["wo"]


def route(cfg, block, u, indices=None):
    """(weights [tokens, scored], indices [tokens, top_k], scores) of u
    [tokens, hidden]: a token's weight of each scored expert, 0 where it
    is not among its `top_k` (the reference's own, or `indices` where
    given, weighted by the reference's scores of them)."""
    scores = jax.nn.sigmoid(u @ block["router"])
    if indices is None:
        top, indices = jax.lax.top_k(scores, cfg["num_experts_per_tok"])
    else:
        top = jnp.take_along_axis(scores, indices, axis=1)
    if cfg["norm_topk_prob"]:
        top = top / (jnp.sum(top, axis=-1, keepdims=True) + 1e-20)
    top = top * cfg["routed_scaling_factor"]
    hot = indices[..., None] == jnp.arange(scores.shape[-1])
    return jnp.sum(jnp.where(hot, top[..., None], 0.0), axis=1), indices, \
        scores


def routed(cfg, block, u, first=0, indices=None):
    """The held experts' part of the routed sum for u [tokens, hidden]:
    every held expert applied to every token, one after another (a
    scan), weighted by the token's weight of it."""
    weights, indices, _ = route(cfg, block, u, indices)
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


def layer(cfg, block, x, first=0, indices=None):
    """(y, indices) of one decoder layer; `indices` [tokens, top_k] are
    taken in place of the reference's own choice where given."""
    eps = cfg["rms_norm_eps"]
    a = x + rms_norm(attention(cfg, block,
                               rms_norm(x, block["input_norm"], eps)),
                     block["post_attn_norm"], eps)
    u = rms_norm(a, block["pre_mlp_norm"], eps)
    f, indices = feed_forward(cfg, block, u.reshape(-1, u.shape[-1]), first,
                              indices=indices)
    return a + rms_norm(f.reshape(a.shape), block["post_mlp_norm"],
                        eps), indices


def forward(cfg, params, tokens, held=None):
    """{"logits" [batch, seq, vocab], "hidden": [L] each layer's output,
    "indices": [L] the experts chosen [tokens, top_k] (None for a dense
    layer)} for local token ids `tokens` [batch, seq]; `held` = (first,
    count) says which of the scored experts `params` holds (default: the
    first `w_gate.shape[0]`)."""
    params = jax.tree_util.tree_map(
        lambda a: jnp.asarray(a, jnp.float32), params)
    first = 0
    if held is not None:
        first = held[0]
        for block in params["blocks"]:
            if "w_gate" in block and block["w_gate"].shape[0] != held[1]:
                raise ValueError("params hold %d experts, `held` says %d"
                                 % (block["w_gate"].shape[0], held[1]))
    out = {"hidden": [], "indices": []}
    with jax.default_matmul_precision("highest"):
        x = params["embed"][tokens]
        for block in params["blocks"]:
            x, indices = layer(cfg, block, x, first)
            out["hidden"].append(x)
            out["indices"].append(indices)
        out["logits"] = rms_norm(x, params["norm_f"],
                                 cfg["rms_norm_eps"]) @ params["head"]
    return out
