"""Plain float32 reference of Ouro, the looped language model (Zhu et
al., "Scaling Latent Reasoning via Looped Language Models",
arXiv:2510.25741; huggingface.co/ByteDance/Ouro-2.6B): forward pass,
exit gate and the stage-I training loss in straightforward `jax.numpy`,
dense attention with the whole score matrix, no kernels, no mixed
precision, nothing imported from the program.

The model.  A stack of L decoder blocks is applied R = `total_ut_steps`
times over the same weights.  A block, with no bias anywhere, is
sandwich-normalised (four RMSNorms):

    x <- x + norm_2(attn(rope(q), rope(k), v)),  q, k, v = norm_1(x) W_q,k,v
    x <- x + norm_4(W_down(silu(W_gate h) * (W_up h))),   h = norm_3(x)

with causal attention over `num_attention_heads` heads of `head_dim` and
rotate-half RoPE at base `rope_theta`.  After each pass t the last norm
gives h_t, the untied head its logits z_t = h_t W_head, and the exit gate
lambda_t = sigmoid(h_t w_gate + b_gate) one scalar a token.  The exit
distribution is p_t = lambda_t prod_{j<t}(1 - lambda_j) for t < R and
p_R = prod_{j<R}(1 - lambda_j); the loss is the mean over tokens of
sum_t p_t CE(z_t, target) - beta H(p).

Departures from the source and sizes it does not fix are the
configuration's (`assumed` and `departures` in
benchmark/configs/ouro-2.6b.json): the last norm sits inside the loop, as
the released modeling code applies it; beta is `exit_entropy_beta`; no
projection has a bias, the gate has one.  `params` holds the weights by
layer: {"embed", "blocks": [{"norm_1", "wq", "wk", "wv", "norm_2", "wo",
"norm_3", "w_gate", "w_up", "norm_4", "w_down"}], "norm_f", "head",
"gate": (w, b)}, matrices as [in, out].
"""

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
    return x * jnp.cos(angles) + rotated * jnp.sin(angles)


def _attention(cfg, block, h, positions):
    batch, seq, _ = h.shape
    heads, theta = cfg["num_attention_heads"], cfg["rope_theta"]
    q, k, v = ((h @ block[w]).reshape(batch, seq, heads, -1)
               for w in ("wq", "wk", "wv"))
    q, k = rope(q, positions, theta), rope(k, positions, theta)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(
        jnp.float32(q.shape[-1]))
    causal = jnp.tril(jnp.ones((seq, seq), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    out = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1), v)
    return out.reshape(batch, seq, -1) @ block["wo"]


def _block(cfg, block, x, positions):
    eps = cfg["rms_norm_eps"]
    h = rms_norm(x, block["norm_1"], eps)
    x = x + rms_norm(_attention(cfg, block, h, positions),
                     block["norm_2"], eps)
    h = rms_norm(x, block["norm_3"], eps)
    m = (jax.nn.silu(h @ block["w_gate"]) * (h @ block["w_up"])) \
        @ block["w_down"]
    return x + rms_norm(m, block["norm_4"], eps)


def logits_and_gates(cfg, params, tokens, positions=None):
    """([R] logits [batch, seq, vocab], [R] lambdas [batch, seq]), one
    of each per pass through the stack; positions default to 0..seq-1."""
    params = jax.tree_util.tree_map(
        lambda a: jnp.asarray(a, jnp.float32), params)
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(tokens.shape[1]),
                                     tokens.shape)
    gate_w, gate_b = params["gate"]
    x = params["embed"][tokens]
    logits, lambdas = [], []
    for _ in range(cfg["total_ut_steps"]):
        for block in params["blocks"]:
            x = _block(cfg, block, x, positions)
        x = rms_norm(x, params["norm_f"], cfg["rms_norm_eps"])
        logits.append(x @ params["head"])
        lambdas.append(jax.nn.sigmoid((x @ gate_w)[..., 0] + gate_b[0]))
    return logits, lambdas


def exit_distribution(lambdas):
    """[R] p_t from [R] lambda_t: exit at pass t having stayed before it;
    the last pass takes what is left, whatever its gate says."""
    stay = jnp.ones_like(lambdas[0])
    p = []
    for lam in lambdas[:-1]:
        p.append(lam * stay)
        stay = stay * (1.0 - lam)
    return p + [stay]


def loss_terms(cfg, params, feeds):
    """{"loss", "pass_ce": [R] mean cross-entropy of each pass,
    "lambdas": [R] [batch, seq], "p": [R] [batch, seq]}."""
    with jax.default_matmul_precision("highest"):
        logits, lambdas = logits_and_gates(
            cfg, params, feeds["tokens"], feeds.get("positions"))
        targets = feeds["targets"].astype(jnp.int32)
        ce = [-jnp.take_along_axis(jax.nn.log_softmax(z, axis=-1),
                                   targets, axis=-1)[..., 0]
              for z in logits]
        p = exit_distribution(lambdas)
        expected = sum(p_t * ce_t for p_t, ce_t in zip(p, ce))
        entropy = -sum(jax.scipy.special.xlogy(p_t, p_t) for p_t in p)
        per_token = expected - cfg["exit_entropy_beta"] * entropy
        return {"loss": jnp.mean(per_token),
                "pass_ce": [jnp.mean(c) for c in ce],
                "lambdas": lambdas, "p": p}


def loss(cfg, params, feeds):
    """The stage-I objective: mean over tokens of the expected
    cross-entropy under the exit distribution less beta times the
    distribution's entropy."""
    return loss_terms(cfg, params, feeds)["loss"]
