"""Plain float32 reference of Olmo-Hybrid-7B
(huggingface.co/allenai/Olmo-Hybrid-7B, `model_type` `olmo_hybrid`): the
full-sequence forward pass in straightforward `jax.numpy`, the gated
delta rule **position by position** (`lax.scan` over the sequence: no
chunk, no state handed in, no kernel), full causal attention over whole
rows of scores (no cache), highest matmul precision, nothing imported
from the program.

The model.  x [batch, seq, hidden]; N(x; w) = x / sqrt(mean(x^2) + eps)
* w; no bias anywhere; layer l is `cfg["layer_types"][l]`, three
`linear_attention` to one `full_attention`.  A sub-layer's **output** is
normed before it joins the residual, its input is not (the Olmo 2/3
family's order; the config file's `assumed.norm_order`):

    a = x + N(mixer(x); w1)
    y = a + N(W_down(silu(W_gate a) * W_up a); w2)

Linear layer (Gated DeltaNet, arXiv:2412.06464, with negative
eigenvalues allowed), H = `linear_num_value_heads` =
`linear_num_key_heads` heads of `linear_key_head_dim` /
`linear_value_head_dim` values:

    [q | k | v | z] = x W_qkvz;  [b | a] = x W_ba
    [q | k | v] = silu(conv([q | k | v]; F))    depthwise, causal, width
                                                `linear_conv_kernel_dim`
    q_h = q_h / sqrt(sum q_h^2 + 1e-6) / sqrt(key dim);  k_h likewise,
                                                          unscaled
    beta = 2 sigmoid(b)   (`linear_allow_neg_eigval`: in (0, 2), so that
                           I - beta k k^T has an eigenvalue in (-1, 1));
    g = -exp(A_log) softplus(a + dt_bias)
    per head, S [key dim, value dim] from zeros, position by position:
               S = exp(g_t) S;  r = S^T k_t;
               S = S + k_t (beta_t (v_t - r))^T;  o_t = S^T q_t
    y_h = N(o_h; w_n) * silu(z_h);   mixer = concat_h(y_h) W_o

Full layer, `num_attention_heads` heads of `head_dim`, each its own
key/value head, **no rotation** (`rope_parameters.rope_theta` null;
`assumed.positions`), no gate:

    q = N(x W_q; w_q);  k = N(x W_k; w_k)     over the whole projection
    v = x W_v
    o_h = softmax_causal(q_h k_h^T / sqrt(head_dim)) v_h
    mixer = concat_h(o_h) W_o

After the last layer z = N(x; w_f) W_head.

`params`: {"embed" [vocab, hidden], "blocks": [{"post_attn_norm",
"post_ffn_norm" [hidden], "ffn_in" [hidden, 2 * width] (gate columns
first), "ffn_out" [width, hidden], and for a linear layer "w_qkvz",
"w_ba", "conv" [channels, width], "a_log", "dt_bias" [H], "out_norm"
[value dim], "wo", for a full one "wq", "wk", "wv", "q_norm" [heads *
head_dim], "k_norm" [kv heads * head_dim], "wo"}], "norm_f", "head"
[hidden, vocab]}, matrices as [in, out].  `cfg` has the source's keys
and `head_dim`.

`cfg["control"]`, where present, makes the reference **wrong** in one
named way (a check that `correct`'s limits refuse a program that
computes something else: benchmark/tests/dense_state_control.py,
scripts/olmohybrid_check.py): {"state": "bfloat16" | "zero"} rounds or
zeroes the state after every position, "beta_scale": 1 leaves beta
undoubled, "decay": False leaves exp(g) out, "tail_cut": p starts the
convolution from zeros again at position p, "norm_order": "pre" norms a
sub-layer's input and not its output, "rotary": theta rotates q and k
(rotate-half over a whole head), "qk_norm": "head" norms q and k head by
head (each head by the scale's first `head_dim` values) and "qk_norm":
"none" not at all, "z_gate": "sigmoid" gates the rule's output by
sigmoid(z).
"""

import math

import jax
import jax.numpy as jnp

LINEAR, FULL = "linear_attention", "full_attention"


def layer_type(cfg, index):
    return cfg["layer_types"][index]


def _control(cfg, key, default):
    return (cfg.get("control") or {}).get(key, default)


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * scale


def rope(x, positions, theta):
    """x [batch, seq, heads, dim] turned at `positions` [seq] (x cos +
    rotate_half(x) sin, the two halves of a head paired): the control's
    alone, the model rotates nothing."""
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


def causal_conv(x, filt, cut=None):
    """out_t = sum_j filt[:, j] x_{t - (K - 1) + j}, zeros before
    position 0 (and, with `cut`, before position `cut` again for the
    positions from it on: a tail that is not carried)."""
    width, seq = filt.shape[1], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (width - 1, 0), (0, 0)))
    at = jnp.arange(seq)[:, None]
    out = 0.0
    for j in range(width):
        taken = padded[:, j:j + seq]
        if cut is not None:
            source = at - (width - 1) + j
            taken = jnp.where((at >= cut) & (source < cut), 0.0, taken)
        out = out + taken * filt[:, j]
    return out


def delta_rule(cfg, q, k, v, g, beta):
    """(o [batch, seq, H, value dim], the state after the last position
    [batch, H, key dim, value dim]) of the gated delta rule position by
    position from a zero state: q, k [batch, seq, H, key dim] (normed),
    v [batch, seq, H, value dim], g and beta [batch, seq, H]."""
    kept = _control(cfg, "state", None)

    def step(s, at):
        q_t, k_t, v_t, g_t, b_t = at
        if _control(cfg, "decay", True):
            s = s * jnp.exp(g_t)[..., None, None]
        held = jnp.einsum("bhkv,bhk->bhv", s, k_t)
        s = s + k_t[..., :, None] * (b_t[..., None]
                                     * (v_t - held))[..., None, :]
        out = jnp.einsum("bhkv,bhk->bhv", s, q_t)
        if kept == "bfloat16":
            # (an explicit rounding: XLA drops a cast down and up)
            s = jax.lax.reduce_precision(s, exponent_bits=8,
                                         mantissa_bits=7)
        elif kept == "zero":
            s = jnp.zeros_like(s)
        return s, out

    batch, _, heads, key_dim = q.shape
    state = jnp.zeros((batch, heads, key_dim, v.shape[-1]), jnp.float32)
    state, out = jax.lax.scan(
        step, state, tuple(jnp.moveaxis(t, 1, 0)
                           for t in (q, k, v, g, beta)))
    return jnp.moveaxis(out, 0, 1), state


def linear_mixer(cfg, block, h):
    """(the Gated DeltaNet mixer of h [batch, seq, hidden], the state
    after the last position)."""
    batch, seq, _ = h.shape
    heads = cfg["linear_num_value_heads"]
    key_dim, value_dim = (cfg["linear_key_head_dim"],
                          cfg["linear_value_head_dim"])
    key_width = cfg["linear_num_key_heads"] * key_dim
    value_width = heads * value_dim
    mixed = h @ block["w_qkvz"]
    qkv, z = mixed[..., :2 * key_width + value_width], \
        mixed[..., 2 * key_width + value_width:]
    b, a = jnp.split(h @ block["w_ba"], 2, axis=-1)
    qkv = jax.nn.silu(causal_conv(qkv, block["conv"],
                                  _control(cfg, "tail_cut", None)))
    q, k, v = (t.reshape(batch, seq, heads, -1) for t in
               jnp.split(qkv, [key_width, 2 * key_width], axis=-1))

    def l2norm(t):
        return t * jax.lax.rsqrt(
            jnp.sum(jnp.square(t), axis=-1, keepdims=True) + 1e-6)

    q, k = l2norm(q) / math.sqrt(key_dim), l2norm(k)
    # negative eigenvalues allowed: beta in (0, 2)
    doubled = 2.0 if cfg["linear_allow_neg_eigval"] else 1.0
    beta = _control(cfg, "beta_scale", doubled) * jax.nn.sigmoid(b)
    g = -jnp.exp(block["a_log"]) * jax.nn.softplus(a + block["dt_bias"])
    o, state = delta_rule(cfg, q, k, v, g, beta)
    z = z.reshape(batch, seq, heads, value_dim)
    y = rms_norm(o, block["out_norm"], cfg["rms_norm_eps"]) \
        * (jax.nn.sigmoid(z) if _control(cfg, "z_gate", "silu") == "sigmoid"
           else jax.nn.silu(z))
    return y.reshape(batch, seq, -1) @ block["wo"], state


def full_mixer(cfg, block, h):
    """Attention of h [batch, seq, hidden] over the whole sequence, a
    key/value head a query head's group, whole rows of scores."""
    batch, seq, _ = h.shape
    heads, kv_heads = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dim, eps = cfg["head_dim"], cfg["rms_norm_eps"]
    q, k, v = (h @ block[w] for w in ("wq", "wk", "wv"))
    how = _control(cfg, "qk_norm", "whole")
    if how == "whole":
        q = rms_norm(q, block["q_norm"], eps)
        k = rms_norm(k, block["k_norm"], eps)
    q = q.reshape(batch, seq, heads, dim)
    k = k.reshape(batch, seq, kv_heads, dim)
    v = v.reshape(batch, seq, kv_heads, dim)
    if how == "head":
        q = rms_norm(q, block["q_norm"][:dim], eps)
        k = rms_norm(k, block["k_norm"][:dim], eps)
    theta = _control(cfg, "rotary", cfg["rope_parameters"]["rope_theta"])
    if theta is not None:
        q, k = (rope(t, jnp.arange(seq), float(theta)) for t in (q, k))
    q = q.reshape(batch, seq, kv_heads, heads // kv_heads, dim)
    scores = jnp.einsum("bqhgd,bkhd->bhgqk", q, k) / math.sqrt(dim)
    causal = jnp.tril(jnp.ones((seq, seq), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", probs, v)
    return out.reshape(batch, seq, -1) @ block["wo"]


def layer(cfg, index, block, x):
    """(the layer's output, {"mixer": the mixer's output before its
    norm, "state": a linear layer's state after the last position or
    None}) for x [batch, seq, hidden]."""
    eps = cfg["rms_norm_eps"]
    post = _control(cfg, "norm_order", "post") == "post"
    w1, w2 = block["post_attn_norm"], block["post_ffn_norm"]
    h = x if post else rms_norm(x, w1, eps)
    if layer_type(cfg, index) == LINEAR:
        mixer, state = linear_mixer(cfg, block, h)
    else:
        mixer, state = full_mixer(cfg, block, h), None
    a = x + (rms_norm(mixer, w1, eps) if post else mixer)
    f = gated(a if post else rms_norm(a, w2, eps), block["ffn_in"],
              block["ffn_out"])
    return a + (rms_norm(f, w2, eps) if post else f), \
        {"mixer": mixer, "state": state}


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32),
                                  tree)


def forward(cfg, params, tokens):
    """{"logits" [batch, seq, vocab], "hidden": [L] each layer's output,
    "mixer": [L] each mixer's output, "states": [L] a linear layer's
    state after the last position (None for a full layer)} for token ids
    `tokens` [batch, seq]."""
    params = _f32(params)
    out = {"hidden": [], "mixer": [], "states": []}
    with jax.default_matmul_precision("highest"):
        x = params["embed"][tokens]
        for i, block in enumerate(params["blocks"]):
            x, found = layer(cfg, i, block, x)
            out["hidden"].append(x)
            out["mixer"].append(found["mixer"])
            out["states"].append(found["state"])
        out["logits"] = rms_norm(x, params["norm_f"],
                                 cfg["rms_norm_eps"]) @ params["head"]
    return out


def state_off(got, want):
    """The root mean square of a served state's difference from the
    reference's, over the reference's (both [rows, H, key dim, value
    dim], as the recurrence has them)."""
    got, want = (jnp.asarray(t, jnp.float32) for t in (got, want))
    return float(jnp.sqrt(jnp.mean(jnp.square(got - want))
                          / jnp.mean(jnp.square(want))))


def gaps(cfg, ends, block_of, prompt, served, rows, with_state=None):
    """`[sequences, served length]` float32: at every served position,
    how far the reference's logit of the served token lies below the
    reference's best.

    `ends` is {"embed", "norm_f", "head"}; `block_of(i)` gives block i's
    parameters, asked for once a layer and dropped before the next is
    asked for; the sequences go through a layer `rows` at a time.  The
    served token i was chosen from the logits at position prompt_len - 1
    + i, whose input is the prompt and the served tokens before it.
    `with_state(i, state)` is called for every linear layer with the
    reference's state [sequences, H, key dim, value dim] after the input
    of the **last served step**: the prompt and all served tokens but
    the last (the step that chose the last token read the one before
    it)."""
    tokens = jnp.concatenate([prompt, served], axis=1)[:, :-1]
    start, count = prompt.shape[1] - 1, served.shape[1]
    ends = _f32(ends)

    def one_layer(i):
        @jax.jit
        def apply(block, x):
            with jax.default_matmul_precision("highest"):
                out, found = layer(cfg, i, block, x)
            return out, found["state"]
        return apply

    @jax.jit
    def head_gaps(ends, x, served):
        with jax.default_matmul_precision("highest"):
            z = rms_norm(x[:, start:start + count], ends["norm_f"],
                         cfg["rms_norm_eps"]) @ ends["head"]
        picked = jnp.take_along_axis(z, served[..., None], axis=-1)
        return jnp.max(z, axis=-1) - picked[..., 0]

    cuts = range(0, tokens.shape[0], rows)
    xs = [ends["embed"][tokens[at:at + rows]] for at in cuts]
    applies = {}
    for i in range(cfg["num_hidden_layers"]):
        block = _f32(block_of(i))
        if layer_type(cfg, i) not in applies:   # one compile a kind
            applies[layer_type(cfg, i)] = one_layer(i)
        apply = applies[layer_type(cfg, i)]
        found = [apply(block, x) for x in xs]
        xs = [x for x, _ in found]
        if with_state is not None and found[0][1] is not None:
            with_state(i, jnp.concatenate([s for _, s in found]))
        del block, found
    return jnp.concatenate([head_gaps(ends, x, served[at:at + rows])
                            for x, at in zip(xs, cuts)])
