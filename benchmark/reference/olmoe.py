"""Plain float32 reference of OLMoE (Muennighoff et al., "OLMoE: Open
Mixture-of-Experts Language Models", arXiv:2409.02060;
huggingface.co/allenai/OLMoE-1B-7B-0125-Instruct): forward pass, router
and training loss in straightforward `jax.numpy`, dense attention with
the whole score matrix, every expert applied densely to every token and
masked by the routing weights (`num_experts` times three plain
products, one expert after another: no ordering, no grouped product, no
kernel), no mixed precision, nothing
imported from the program.

The model.  L identical pre-norm decoder layers, no bias anywhere.  With
x [batch, seq, hidden]:

    h = norm_1(x);  q = q_norm(h W_q), k = k_norm(h W_k), v = h W_v
    x <- x + attn(rope(q), rope(k), v) W_o
    u = norm_2(x);  l = u W_r;  p = softmax(l) over the experts
    (w, idx) = top_k(p), not renormalised (norm_topk_prob false)
    x <- x + sum_j w_j W_down[idx_j](silu(u W_gate[idx_j]) * (u W_up[idx_j]))

`q_norm` and `k_norm` are RMSNorms over the whole projection, before it
is split into heads; attention is causal over `num_attention_heads`
heads with rotate-half RoPE at base `rope_theta`.  After the last layer
z = norm_f(x) W_head.  The loss is

    mean_n CE(z_n, target_n) + aux_coef * L_lb + z_coef * L_z

with, per layer and summed over layers, L_lb = E sum_e f_e P_e (f_e the
share of the `top_k * tokens` assignments that fell on expert e, a
constant to the gradient; P_e the mean of p over the tokens) and L_z =
mean_n logsumexp_e(l_ne)^2.

Departures from the source and sizes it does not fix are the
configuration's (`assumed` and `departures` in
benchmark/configs/olmoe-1b-7b.json): `aux_coef` and `z_coef` are the
paper's; `intermediate_size` is read as one expert's width.  `params`
holds the weights by layer: {"embed", "blocks": [{"norm_1", "wq", "wk",
"wv", "q_norm", "k_norm", "wo", "norm_2", "router", "w_gate", "w_up",
"w_down"}], "norm_f", "head"}, matrices as [in, out], an expert stack
as [experts, in, out]
(benchmark/models/olmoe.py says which program variable is which).

The benchmark's own copy of paddle_tpu/models/reference/olmoe.py, as
benchmark/flops/program.py copies fluid/analysis.py: a change to the
program cannot move the yardstick.
"""

import math

import jax
import jax.numpy as jnp


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


def _attention(cfg, block, h, positions):
    batch, seq, _ = h.shape
    heads, theta = cfg["num_attention_heads"], cfg["rope_theta"]
    eps = cfg["rms_norm_eps"]
    q = rms_norm(h @ block["wq"], block["q_norm"], eps)
    k = rms_norm(h @ block["wk"], block["k_norm"], eps)
    q, k, v = (a.reshape(batch, seq, heads, -1)
               for a in (q, k, h @ block["wv"]))
    q, k = rope(q, positions, theta), rope(k, positions, theta)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(q.shape[-1])
    causal = jnp.tril(jnp.ones((seq, seq), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    out = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1), v)
    return out.reshape(batch, seq, -1) @ block["wo"]


def experts(cfg, block, u, indices=None):
    """(m, router logits, indices) for u [tokens, hidden]: every expert
    applied to every token, weighted by the token's probability of it
    where the expert is among its `top_k` (the reference's own, or
    `indices` [tokens, top_k] where given) and by zero elsewhere."""
    logits = u @ block["router"]
    probs = jax.nn.softmax(logits, axis=-1)
    if indices is None:
        indices = jax.lax.top_k(probs, cfg["num_experts_per_tok"])[1]
    n_experts = cfg["num_experts"]
    chosen = jnp.any(indices[..., None] == jnp.arange(n_experts), axis=1)
    weights = jnp.where(chosen, probs, 0.0)

    def add_expert(m, expert):
        w_gate, w_up, w_down, weight = expert
        hidden = jax.nn.silu(u @ w_gate) * (u @ w_up)
        return m + weight[:, None] * (hidden @ w_down), None

    # one expert after another (a scan, so that 64 experts compile as one)
    m, _ = jax.lax.scan(add_expert, jnp.zeros_like(u), (
        block["w_gate"], block["w_up"], block["w_down"], weights.T))
    return m, logits, indices


def aux_losses(cfg, logits, indices):
    """(L_lb, L_z) of one layer from its router logits [tokens, experts]
    and the experts chosen [tokens, top_k]."""
    n_experts = cfg["num_experts"]
    share = jnp.mean(
        (indices[..., None] == jnp.arange(n_experts)).astype(jnp.float32),
        axis=(0, 1))
    mean_p = jnp.mean(jax.nn.softmax(logits, axis=-1), axis=0)
    lb = n_experts * jnp.sum(jax.lax.stop_gradient(share) * mean_p)
    z = jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1)))
    return lb, z


def forward(cfg, params, tokens, indices=None, positions=None,
            dtype=jnp.float32):
    """{"logits" [batch, seq, vocab], "router_logits": [L] [tokens,
    experts], "indices": [L] [tokens, top_k], "moe_out": [L] [tokens,
    hidden], the expert layers' outputs}.  `dtype` is float32 for the
    reference; a narrower one (weights, activations, sums and the loss
    all in it) is how a comparison's tolerance is shown to tell
    precisions apart."""
    params = jax.tree_util.tree_map(lambda a: jnp.asarray(a, dtype), params)
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(tokens.shape[1]),
                                     tokens.shape)
    eps = cfg["rms_norm_eps"]
    x = params["embed"][tokens]
    out = {"router_logits": [], "indices": [], "moe_out": []}
    for i, block in enumerate(params["blocks"]):
        h = rms_norm(x, block["norm_1"], eps)
        x = x + _attention(cfg, block, h, positions)
        u = rms_norm(x, block["norm_2"], eps)
        m, logits, idx = experts(
            cfg, block, u.reshape(-1, u.shape[-1]),
            None if indices is None else indices[i])
        x = x + m.reshape(x.shape)
        out["router_logits"].append(logits)
        out["indices"].append(idx)
        out["moe_out"].append(m)
    out["logits"] = rms_norm(x, params["norm_f"], eps) @ params["head"]
    return out


def logits_and_router(cfg, params, tokens, indices=None):
    """(logits [batch, seq, vocab], [L] router logits [tokens, experts],
    [L] indices [tokens, top_k]).  `indices`, a list of one [tokens,
    top_k] array a layer, takes the place of the reference's own top-k:
    where the program's inputs are rounded to bfloat16 a token's 8th
    and 9th expert can change places, and a comparison has to tell such
    a tie from wrong arithmetic."""
    with jax.default_matmul_precision("highest"):
        out = forward(cfg, params, tokens, indices)
    return out["logits"], out["router_logits"], out["indices"]


def loss_terms(cfg, params, feeds, indices=None, dtype=jnp.float32):
    """{"loss", "ce", "lb", "z"} (the last two summed over layers) and
    what `forward` gives."""
    with jax.default_matmul_precision("highest"):
        out = forward(cfg, params, feeds["tokens"], indices,
                      feeds.get("positions"), dtype)
        targets = feeds["targets"].astype(jnp.int32)
        ce = jnp.mean(-jnp.take_along_axis(
            jax.nn.log_softmax(out["logits"], axis=-1), targets, axis=-1))
        aux = [aux_losses(cfg, l, i)
               for l, i in zip(out["router_logits"], out["indices"])]
        lb, z = sum(a[0] for a in aux), sum(a[1] for a in aux)
        out.update(ce=ce, lb=lb, z=z,
                   loss=ce + cfg["aux_coef"] * lb + cfg["z_coef"] * z)
        return out


def loss(cfg, params, feeds):
    """Mean cross-entropy plus the weighted load-balance and router
    z-losses of every layer."""
    return loss_terms(cfg, params, feeds)["loss"]
