"""What the share cell's served tokens are held to: the plain float32
reference of one chip's share of openPangu-Ultra-MoE-718B, a copy of
paddle_tpu/models/reference/pangu_moe.py's equations (see there: the
unabsorbed full-sequence forward, every head's keys and values made from
the latents, the whole score matrix, no cache, every held expert applied
densely to every token, highest matmul precision, nothing from the
program), applied layer by layer over blocks of rows: one expert layer
is 4.0 GB in float32 and the whole share would be 19.7, so `gaps` asks
its caller for one layer's parameters at a time and lets go of them
before the next.

A greedy token is right when no other token's logit is above its own,
and with seeded weights the top two lie close often enough that rounding
picks the other one now and then.  So the number compared is the *gap*:
by how much the reference's logit of the served token lies below the
reference's best at that position, 0 where they agree.
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
    is not among its `top_k` (the reference's own, or `indices` where a
    caller hands it a routing)."""
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


def feed_forward(cfg, block, u, first=0, shared=True):
    """(F(u), indices or None) for u [tokens, hidden]."""
    if "ffn_in" in block:
        return gated(u, block["ffn_in"], block["ffn_out"]), None
    m, indices = routed(cfg, block, u, first)
    if shared:
        m = m + gated(u, block["shared_in"], block["shared_out"])
    return m, indices


def layer(cfg, block, x, first=0):
    """(y, indices) of one decoder layer."""
    eps = cfg["rms_norm_eps"]
    a = x + rms_norm(attention(cfg, block,
                               rms_norm(x, block["input_norm"], eps)),
                     block["post_attn_norm"], eps)
    u = rms_norm(a, block["pre_mlp_norm"], eps)
    f, indices = feed_forward(cfg, block, u.reshape(-1, u.shape[-1]), first)
    return a + rms_norm(f.reshape(a.shape), block["post_mlp_norm"],
                        eps), indices


def held_part_off(cfg, block, probe):
    """How far the held experts' part a step served lies from the
    reference's: `probe` is {"in": the routed layer's input [rows, 1,
    hidden], "idx": the experts the step's router chose [rows, top_k],
    "out": what its held experts gave for them [rows, 1, hidden]} as the
    step computed them; the reference's routed sum of the same input
    under the same choice (its own float32 scores of it, its own
    weights) is what "out" is held to, as the root mean square of the
    difference over the reference's.  `block`: the layer's parameters in
    float32.  A choice of experts is not judged here (a near-tie falls
    either way between bfloat16 and float32): what the held experts'
    weights and products did to the rows they were given is."""
    u, idx, out = (jnp.asarray(probe[k]) for k in ("in", "idx", "out"))
    u = u.reshape(-1, u.shape[-1]).astype(jnp.float32)

    @jax.jit
    def want_of(block, u, idx):
        with jax.default_matmul_precision("highest"):
            return routed(cfg, block, u, cfg.get("first_expert", 0), idx)[0]

    want = want_of(block, u, idx)
    diff = out.reshape(want.shape).astype(jnp.float32) - want
    return float(jnp.sqrt(jnp.mean(jnp.square(diff))
                          / jnp.mean(jnp.square(want))))


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32),
                                  tree)


def gaps(cfg, ends, block_of, prompt, served, rows, with_block=None):
    """`[sequences, served length]` float32: at every served position,
    how far the reference's logit of the served token lies below the
    reference's best.

    `ends` is {"embed", "norm_f", "head"}; `block_of(i)` gives block i's
    parameters, asked for once a layer and dropped before the next is
    asked for; the sequences go through a layer `rows` at a time.  The
    served token i was chosen from the logits at position prompt_len - 1
    + i, whose input is the prompt and the served tokens before it.
    `with_block(i, block)` is called with block i in float32 while it is
    held (4 GB an expert layer: whatever else reads a layer's float32
    parameters reads them then)."""
    first_expert = cfg.get("first_expert", 0)
    tokens = jnp.concatenate([prompt, served], axis=1)
    start, count = prompt.shape[1] - 1, served.shape[1]
    ends = _f32(ends)

    @jax.jit
    def one_layer(block, x):
        with jax.default_matmul_precision("highest"):
            return layer(cfg, block, x, first_expert)[0]

    @jax.jit
    def head_gaps(ends, x, served):
        with jax.default_matmul_precision("highest"):
            z = rms_norm(x[:, start:start + count], ends["norm_f"],
                         cfg["rms_norm_eps"]) @ ends["head"]
        picked = jnp.take_along_axis(z, served[..., None], axis=-1)
        return jnp.max(z, axis=-1) - picked[..., 0]

    cuts = range(0, tokens.shape[0], rows)
    xs = [ends["embed"][tokens[at:at + rows]] for at in cuts]
    for i in range(cfg["num_hidden_layers"]):
        block = _f32(block_of(i))
        xs = [one_layer(block, x) for x in xs]
        if with_block is not None:
            with_block(i, block)
        del block
    return jnp.concatenate([head_gaps(ends, x, served[at:at + rows])
                            for x, at in zip(xs, cuts)])
