"""Plain float32 reference of one chip's share of DeepSeek-V3.2
(huggingface.co/deepseek-ai/DeepSeek-V3.2 `config.json`; the layer
equations from the family's released `inference/model.py`): the
full-sequence forward pass in straightforward `jax.numpy`, with per layer
the dense [T, T] index scores, the causal top-`index_topk` a query as a
boolean mask, masked full attention with every head's keys and values
made from the latents (nothing absorbed, no cache, no gather), every held
expert applied densely to every token and masked by the routing weights,
highest matmul precision, nothing imported from the program.

The model.  x [batch, seq, hidden]; RMSNorms N with a scale each; the
block is pre-norm:

    a = x + MLA(N_1(x))
    y = a + F(N_2(a))

MLA, for h = N_1(x), per head i of `num_attention_heads`:

    c_q = N_q(h W_dq)                              [q_lora_rank]
    q_i = [c_q W_uq_nope,i | rope(c_q W_uq_rope,i)]
    [c | r] = h W_dkv;  c = N_kv(c);  r = rope(r)  [kv_lora_rank | rope]
    k_i = [c W_uk,i | r],  v_i = c W_uv,i
    o_i = softmax over s in S_t of (q_i k_i^T * sm) v_i
    MLA = [o_1 .. o_H] W_o

with sm = (nope + rope)^-0.5 * mscale^2, mscale = 0.1 ln(factor) + 1
(YaRN: the deployment's context exceeds the original one, so it always
applies).  rope is the rotate-half form with YaRN's blended frequencies
(`yarn_inv_freq`):

    f_i = theta^(-2i / dim);  corr(n) = dim ln(orig / (2 pi n)) / (2 ln theta)
    lo = max(floor(corr(beta_fast)), 0);  hi = min(ceil(corr(beta_slow)), dim - 1)
    ramp_i = clip((i - lo) / (hi - lo), 0, 1)
    f'_i = f_i / factor * ramp_i + f_i * (1 - ramp_i)

S_t, the slots query t attends, is chosen by the lightning indexer of the
layer (`index_n_heads` heads of `index_head_dim`):

    q^I_j = rope_64(c_q W_iq)_j        k^I = rope_64(LayerNorm(h W_ik))
    w = (h W_iw) * heads^-0.5 * dim^-0.5
    I_t,s = sum_j w_t,j relu(q^I_t,j . k^I_s)          for s <= t
    S_t = the min(index_topk, t + 1) slots with the largest I_t,s

(rope_64 turns the first `qk_rope_head_dim` values and hands on the
rest).  F is the gated-SiLU feed-forward (silu(u W_g) * (u W_u)) W_d of
width `intermediate_size` in the first `first_k_dense_replace` layers,
and in the others the expert layer

    s = sigmoid(u W_r) over the scored experts;   s' = s + b
    a group (consecutive experts, `n_group` of them) scores the sum of
    its two largest s'; experts outside the `topk_group` best groups are
    out; (e_j), j < top_k: the largest s' among the rest
    w_j = routed_scaling_factor * s_{e_j} / (sum_j s_{e_j} + 1e-20)
    F(u) = E_shared(u) + sum_j w_j E_{e_j}(u)

(the weight reads s, the choice s'), of which a share holds the experts
`held = (first, count)` as models/reference/pangu_moe.py's does.  After
the last layer z = N_f(x) W_head over the held rows of the vocabulary.

Departures from the release, each a fixed reparametrisation of seeded
weights or a statement of precision: the release turns q^I and k^I by a
Hadamard matrix and keeps them in float8 with a scale (the rotation is
orthogonal and changes no score; here they stay as they are, float32);
rotary pairs are (x_i, x_{i + dim/2}) and not (x_2i, x_2i+1); W_uq is
two matrices (nope columns, rope columns); gate and up of the dense
feed-forward and of the shared expert are one [hidden, 2 * width]
matrix; the multi-token-prediction module is not here.

`params`: {"embed", "blocks": [{"input_norm", "w_dq", "q_norm",
"w_uq_nope", "w_uq_rope", "w_dkv", "kv_norm", "w_uk", "w_uv", "wo",
"pre_mlp_norm", "w_iq" [q_rank, heads * dim], "w_ik" [hidden, dim],
"ik_norm", "ik_norm_b" [dim], "w_iw" [hidden, heads], then "ffn_in",
"ffn_out" or "shared_in", "shared_out", "router" [hidden, scored],
"router_bias" [scored], "w_gate", "w_up", "w_down"}], "norm_f", "head"},
matrices as [in, out].  `cfg` has the source's keys.
"""

import math

import jax
import jax.numpy as jnp


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * scale


def layer_norm(x, scale, bias, eps):
    centred = x - jnp.mean(x, axis=-1, keepdims=True)
    return centred * jax.lax.rsqrt(
        jnp.mean(jnp.square(centred), axis=-1, keepdims=True) + eps) \
        * scale + bias


def yarn_inv_freq(cfg):
    """[qk_rope_head_dim / 2] float32: the blended frequencies."""
    dim, theta = cfg["qk_rope_head_dim"], float(cfg["rope_theta"])
    scaling = cfg["rope_scaling"]
    original = scaling["original_max_position_embeddings"]

    def corr(turns):
        return dim * math.log(original / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))

    lo = max(math.floor(corr(scaling["beta_fast"])), 0)
    hi = min(math.ceil(corr(scaling["beta_slow"])), dim - 1)
    i = jnp.arange(dim // 2, dtype=jnp.float32)
    freq = theta ** (-2.0 * i / dim)
    ramp = jnp.clip((i - lo) / max(hi - lo, 0.001), 0.0, 1.0)
    return freq / scaling["factor"] * ramp + freq * (1.0 - ramp)


def softmax_scale(cfg):
    scaling = cfg["rope_scaling"]
    mscale = 0.1 * scaling["mscale_all_dim"] * math.log(scaling["factor"]) \
        + 1.0
    return (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5 \
        * mscale * mscale


def rope(x, positions, inv_freq):
    """x [batch, seq, heads, dim] with the first 2 * len(inv_freq)
    values of every head turned at `positions` [seq] (x cos +
    rotate_half(x) sin, the two halves of the turned part paired), the
    rest as they are."""
    turned = 2 * inv_freq.shape[0]
    angles = positions[:, None, None].astype(jnp.float32) * inv_freq
    angles = jnp.concatenate([angles, angles], axis=-1)
    part = x[..., :turned]
    x1, x2 = part[..., :turned // 2], part[..., turned // 2:]
    rotated = jnp.concatenate([-x2, x1], axis=-1)
    return jnp.concatenate(
        [part * jnp.cos(angles) + rotated * jnp.sin(angles),
         x[..., turned:]], axis=-1)


def gated(u, w_in, w_out):
    gate, up = jnp.split(u @ w_in, 2, axis=-1)
    return (jax.nn.silu(gate) * up) @ w_out


def latents(cfg, block, h, positions):
    """(c_q [batch, seq, q_rank], [c | r] [batch, seq, latent + rope]):
    the normed query latent, and what a cache of latents holds."""
    eps, latent = cfg["rms_norm_eps"], cfg["kv_lora_rank"]
    c_q = rms_norm(h @ block["w_dq"], block["q_norm"], eps)
    ckv = h @ block["w_dkv"]
    c = rms_norm(ckv[..., :latent], block["kv_norm"], eps)
    r = rope(ckv[..., latent:][:, :, None, :], positions,
             yarn_inv_freq(cfg))[:, :, 0]
    return c_q, jnp.concatenate([c, r], axis=-1)


def index_parts(cfg, block, h, c_q, positions):
    """(q^I [batch, seq, heads, dim], k^I [batch, seq, dim], w [batch,
    seq, heads]) of the layer's indexer; k^I is what its cache holds."""
    heads, dim = cfg["index_n_heads"], cfg["index_head_dim"]
    inv_freq = yarn_inv_freq(cfg)
    q = rope((c_q @ block["w_iq"]).reshape(h.shape[:2] + (heads, dim)),
             positions, inv_freq)
    k = layer_norm(h @ block["w_ik"], block["ik_norm"], block["ik_norm_b"],
                   cfg["rms_norm_eps"])
    k = rope(k[:, :, None, :], positions, inv_freq)[:, :, 0]
    w = (h @ block["w_iw"]) * heads ** -0.5 * dim ** -0.5
    return q, k, w


def index_scores(q, k, w):
    """I [batch, queries, keys] = sum_j w_j relu(q_j . k)."""
    return jnp.einsum("bqh,bqhs->bqs", w,
                      jax.nn.relu(jnp.einsum("bqhd,bsd->bqhs", q, k)))


def choose(scores, top_k, q_positions):
    """The boolean mask [batch, queries, keys] of the slots each query
    attends: of the keys s <= its position, the min(top_k, position + 1)
    with the largest score."""
    keys = scores.shape[-1]
    causal = jnp.arange(keys)[None, :] <= q_positions[:, None]
    live = jnp.where(causal, scores, -jnp.inf)
    kth = jax.lax.top_k(live, min(top_k, keys))[0][..., -1:]
    return causal & (live >= kth)


def attention(cfg, block, h, selection=None):
    """(MLA of h [batch, seq, hidden] over the chosen slots, {"latents",
    "index_keys", "selection", "index_scores"}); `selection` [batch, seq,
    seq] bool takes the place of the indexer's own choice where given."""
    batch, seq, _ = h.shape
    heads = cfg["num_attention_heads"]
    positions = jnp.arange(seq)
    inv_freq = yarn_inv_freq(cfg)
    c_q, cr = latents(cfg, block, h, positions)
    q_i, k_i, w_i = index_parts(cfg, block, h, c_q, positions)
    scores_i = index_scores(q_i, k_i, w_i)
    own = choose(scores_i, cfg["index_topk"], positions)
    if selection is None:
        selection = own
    latent = cfg["kv_lora_rank"]
    c, r = cr[..., :latent], cr[..., latent:]
    q_nope = (c_q @ block["w_uq_nope"]).reshape(batch, seq, heads, -1)
    q_rope = rope((c_q @ block["w_uq_rope"]).reshape(batch, seq, heads, -1),
                  positions, inv_freq)
    k_nope = (c @ block["w_uk"]).reshape(batch, seq, heads, -1)
    v = (c @ block["w_uv"]).reshape(batch, seq, heads, -1)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(r[:, :, None, :],
                                  k_nope.shape[:3] + r.shape[-1:])], axis=-1)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * softmax_scale(cfg)
    scores = jnp.where(selection[:, None], scores, -jnp.inf)
    out = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1), v)
    return out.reshape(batch, seq, -1) @ block["wo"], {
        "latents": cr, "index_keys": k_i, "selection": own,
        "index_scores": scores_i}


def group_limited(cfg, choice):
    """`choice` [tokens, experts] with -inf on the experts outside each
    token's `topk_group` best of `n_group` groups of consecutive
    experts; a group scores the sum of its two largest entries."""
    groups, kept = cfg.get("n_group", 1), cfg.get("topk_group", 1)
    if groups <= 1:
        return choice
    n, experts = choice.shape
    grouped = choice.reshape(n, groups, experts // groups)
    group_score = jnp.sum(jax.lax.top_k(grouped, 2)[0], axis=-1)
    best = jax.lax.top_k(group_score, kept)[1]
    keep = jnp.any(best[:, :, None] == jnp.arange(groups), axis=1)
    return jnp.where(keep[:, :, None], grouped, -jnp.inf).reshape(n, experts)


def route(cfg, block, u, indices=None):
    """(weights [tokens, scored], indices [tokens, top_k], scores) of u
    [tokens, hidden]: a token's weight of each scored expert, 0 where it
    is not among its chosen (the reference's own choice, by s + b inside
    the kept groups, or `indices` where given); the weights read s."""
    scores = jax.nn.sigmoid(u @ block["router"])
    if indices is None:
        choice = group_limited(cfg, scores + block["router_bias"])
        indices = jax.lax.top_k(choice, cfg["num_experts_per_tok"])[1]
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


def layer(cfg, block, x, first=0, indices=None, selection=None):
    """(y, indices, what `attention` kept, the attention sub-layer's
    output) of one decoder layer; `indices` [tokens, top_k] and
    `selection` [batch, seq, seq] are taken in place of the reference's
    own choices where given."""
    eps = cfg["rms_norm_eps"]
    o, kept = attention(cfg, block, rms_norm(x, block["input_norm"], eps),
                        selection)
    a = x + o
    u = rms_norm(a, block["pre_mlp_norm"], eps)
    f, indices = feed_forward(cfg, block, u.reshape(-1, u.shape[-1]), first,
                              indices=indices)
    return a + f.reshape(a.shape), indices, kept, o


def forward(cfg, params, tokens, held=None, indices=None, selections=None):
    """{"logits" [batch, seq, vocab], "hidden": [L] each layer's output,
    "attn_out": [L], "indices": [L] the experts chosen [tokens, top_k]
    (None for a dense layer), "selection": [L] the indexer's own choice
    [batch, seq, seq] bool, "latents": [L] [batch, seq, latent + rope]
    and "index_keys": [L] [batch, seq, dim] (what the two caches of a
    layer hold after the sequence)} for local token ids `tokens` [batch,
    seq]; `held` = (first, count) says which of the scored experts
    `params` holds; `indices` and `selections` ([L] lists, None entries
    allowed) are handed to the layers in place of their own choices."""
    params = jax.tree_util.tree_map(
        lambda a: jnp.asarray(a, jnp.float32), params)
    first = 0
    if held is not None:
        first = held[0]
        for block in params["blocks"]:
            if "w_gate" in block and block["w_gate"].shape[0] != held[1]:
                raise ValueError("params hold %d experts, `held` says %d"
                                 % (block["w_gate"].shape[0], held[1]))
    out = {"hidden": [], "attn_out": [], "indices": [], "selection": [],
           "latents": [], "index_keys": [], "index_scores": []}
    with jax.default_matmul_precision("highest"):
        x = params["embed"][tokens]
        for i, block in enumerate(params["blocks"]):
            x, chosen, kept, o = layer(
                cfg, block, x, first,
                None if indices is None else indices[i],
                None if selections is None else selections[i])
            out["hidden"].append(x)
            out["attn_out"].append(o)
            out["indices"].append(chosen)
            for key in ("selection", "latents", "index_keys",
                        "index_scores"):
                out[key].append(kept[key])
        out["logits"] = rms_norm(x, params["norm_f"],
                                 cfg["rms_norm_eps"]) @ params["head"]
    return out
