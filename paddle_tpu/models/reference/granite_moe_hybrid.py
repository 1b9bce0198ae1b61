"""Plain float32 reference of one chip's share of granite-4.0-h-small
(huggingface.co/ibm-granite/granite-4.0-h-small, `model_type`
`granitemoehybrid`; the state-space mixer is Mamba-2, Dao & Gu,
"Transformers are SSMs", arXiv:2405.21060 section 6): the full-sequence
forward pass in straightforward `jax.numpy`, the recurrence **position
by position** (`lax.scan` over the sequence with the [heads, head_dim,
d_state] state: no chunk, no decay mask, no state handed in, no kernel),
the convolution four shifted adds, causal attention over the whole
sequence (no cache) with the key/value heads indexed, every held expert
applied densely to every token and masked by the routing weights,
highest matmul precision, nothing imported from the program.

The model.  x [batch, seq, hidden]; N(x; w) = x / sqrt(mean(x^2) + eps)
* w; no bias but the convolution's; no positions ("nope"); E the
[vocab, hidden] table, also the head:

    x_0 = embedding_multiplier * E[tokens]
    every layer:  h = x + residual_multiplier * mixer(N(x; w1))
                  y = h + residual_multiplier * ffn(N(h; w2))
    z = N(y; w_f) E^T / logits_scaling

`layer_types` says which mixer a layer has.  "mamba", on u = N(x; w1),
`mamba_n_heads` H heads of `mamba_d_head` P, state `mamba_d_state` N,
one group (every head reads the same B and C):

    [z | xBC | dt] = u W_in          widths H P, H P + 2 N, H
    xBC_t <- silu(b_c + sum_{j<K} w_c[:, j] xBC_{t-(K-1)+j})  zeros before 0
    [x | B | C] = xBC                widths H P, N, N
    dt <- softplus(dt + dt_bias);  A = -exp(A_log)
    per head, S [P, N] from zeros, position by position:
        S = exp(dt_t A) S + dt_t x_t B_t^T;   y_t = S C_t + D x_t
    mixer = N(y * silu(z); w_g) W_out      the norm over the whole H P

"attention": q = u W_q (`num_attention_heads` heads of `head_dim`), k =
u W_k, v = u W_v (`num_key_value_heads` heads), no rotation; query head
i reads key/value head i // (heads / kv heads); scores times
`attention_multiplier` (not head_dim ** -0.5), causal softmax; mixer =
attn W_o.

Feed-forward, every layer, s = N(h; w2):

    r = s W_r over the `scored_experts`;  (r_j, e_j), j <
    `num_experts_per_tok`: the largest;  w_j = softmax_j(r_j)
    ffn = E_shared(s) + sum_j w_j E_{e_j}(s)
    E(s) = (silu(s W_gate) * s W_up) W_down

the shared expert `shared_intermediate_size` wide (gate and up in one
[hidden, 2 * width] matrix, the gate's columns first), a routed one
`intermediate_size`.  A share holds the experts first .. first + count
- 1: the sum then runs over the j whose e_j lies there (the shared
expert is replicated, whole in every share: `shared=False` leaves it
out, for adding shares up), and the rows of E it holds; token ids are
local to them.

`params`: {"embed" [vocab, hidden], "blocks": [{"norm_1", "norm_2",
"shared_in" [hidden, 2 * shared width], "shared_out", "router" [hidden,
scored], "w_gate", "w_up" [count, hidden, width], "w_down" [count,
width, hidden], and for a mamba layer "in_proj", "conv_w" [channels,
K], "conv_b", "dt_bias", "a_log", "d" [H], "norm_g" [H P], "out_proj",
for an attention layer "wq", "wk", "wv", "wo"}], "norm_f"}, matrices as
[in, out].  `cfg` has the source's keys, and `scored_experts` and
`first_expert` of a share (default: `num_local_experts` and 0).

`cfg["control"]`, where present, makes the reference **wrong** in one
named way (a check that `correct`'s limits refuse a program that
computes something else: benchmark/tests/ssd_state_control.py,
scripts/granite_small_check.py): {"state": "bfloat16"} rounds the state
after every position, "decay": False leaves exp(dt A) out, "skip": False
leaves D x out, "state_cut": p starts the state from zeros again at
position p and "tail_cut": p the convolution (a state or a tail that is
not carried across the prefill/decode border), "attention_multiplier"
and "residual_multiplier": another value, "shared_width": w the shared
expert's first w gate and up columns alone, "drop": True drops every
token's last chosen expert among those held.
"""

import jax
import jax.numpy as jnp

MAMBA, ATTENTION = "mamba", "attention"
_ROUTED = ("w_gate", "w_up", "w_down")


def _control(cfg, key, default):
    return (cfg.get("control") or {}).get(key, default)


def layer_type(cfg, index):
    return cfg["layer_types"][index]


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * scale


def gated(u, w_in, w_out):
    gate, up = jnp.split(u @ w_in, 2, axis=-1)
    return (jax.nn.silu(gate) * up) @ w_out


def causal_conv(x, w, b, cut=None):
    """silu(b + sum_j w[:, j] x_{t-(K-1)+j}) over x [batch, seq,
    channels], zeros before position 0 (and, with `cut`, before position
    `cut` again for the positions from it on: a tail that is not
    carried)."""
    width, seq = w.shape[1], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (width - 1, 0), (0, 0)))
    at = jnp.arange(seq)[:, None]
    out = b
    for j in range(width):
        taken = padded[:, j:j + seq]
        if cut is not None:
            source = at - (width - 1) + j
            taken = jnp.where((at >= cut) & (source < cut), 0.0, taken)
        out = out + taken * w[:, j]
    return jax.nn.silu(out)


def recurrence(cfg, x, dt, a, b, c, d_skip, start=None):
    """(y [batch, seq, heads, head_dim], the state after the last
    position [batch, heads, head_dim, d_state]) of the selective scan,
    one position after another from a zero state (from `start`, where
    one step of a served state is held to it: `state_step_off`): x
    [batch, seq, heads, head_dim], dt [batch, seq, heads] (after the
    softplus), a [heads] (negative), b and c [batch, seq, d_state],
    d_skip [heads]."""
    kept = _control(cfg, "state", None)
    cut = _control(cfg, "state_cut", None)

    def step(state, inputs):
        at, x_t, dt_t, b_t, c_t = inputs
        if cut is not None:
            state = jnp.where(at == cut, 0.0, state)
        if _control(cfg, "decay", True):
            state = jnp.exp(dt_t * a)[..., None, None] * state
        state = state \
            + (dt_t[..., None] * x_t)[..., None] * b_t[:, None, None, :]
        out = jnp.einsum("bhpn,bn->bhp", state, c_t)
        if kept == "bfloat16":
            # (an explicit rounding: XLA drops a cast down and up)
            state = jax.lax.reduce_precision(state, exponent_bits=8,
                                             mantissa_bits=7)
        return state, out

    batch, seq, heads, dim = x.shape
    if start is None:
        start = jnp.zeros((batch, heads, dim, b.shape[-1]), x.dtype)
    state, y = jax.lax.scan(
        step, start, (jnp.arange(seq),) + tuple(
            jnp.moveaxis(t, 1, 0) for t in (x, dt, b, c)))
    y = jnp.moveaxis(y, 0, 1)
    if _control(cfg, "skip", True):
        y = y + d_skip[:, None] * x
    return y, state


def mamba_mixer(cfg, block, h):
    """(the Mamba-2 mixer of h [batch, seq, hidden], the state after the
    last position)."""
    batch, seq, _ = h.shape
    heads, dim = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    entries = cfg["mamba_d_state"]
    inner = heads * dim
    proj = h @ block["in_proj"]
    z, xbc, dt = (proj[..., :inner],
                  proj[..., inner:2 * inner + 2 * entries],
                  proj[..., 2 * inner + 2 * entries:])
    xbc = causal_conv(xbc, block["conv_w"], block["conv_b"],
                      _control(cfg, "tail_cut", None))
    x, b, c = (xbc[..., :inner], xbc[..., inner:inner + entries],
               xbc[..., inner + entries:])
    y, state = recurrence(
        cfg, x.reshape(batch, seq, heads, dim),
        jax.nn.softplus(dt + block["dt_bias"]), -jnp.exp(block["a_log"]),
        b, c, block["d"])
    y = rms_norm(y.reshape(batch, seq, inner) * jax.nn.silu(z),
                 block["norm_g"], cfg["rms_norm_eps"])
    return y @ block["out_proj"], state


def attention_mixer(cfg, block, h):
    batch, seq, _ = h.shape
    heads, kv_heads = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    q = (h @ block["wq"]).reshape(batch, seq, kv_heads, heads // kv_heads,
                                  -1)
    k = (h @ block["wk"]).reshape(batch, seq, kv_heads, -1)
    v = (h @ block["wv"]).reshape(batch, seq, kv_heads, -1)
    # query head g * (heads / kv_heads) + r reads key/value head g
    scores = jnp.einsum("bqgrd,bkgd->bgrqk", q, k) * _control(
        cfg, "attention_multiplier", cfg["attention_multiplier"])
    causal = jnp.tril(jnp.ones((seq, seq), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    out = jnp.einsum("bgrqk,bkgd->bqgrd", probs, v)
    return out.reshape(batch, seq, -1) @ block["wo"]


def route(cfg, block, u, first, indices=None):
    """(weights [tokens, scored], indices [tokens, top_k]) of u [tokens,
    hidden]: a token's weight of each scored expert, 0 where it is not
    among its `top_k` (the reference's own, or `indices` where given,
    weighted by the softmax over the reference's logits of them)."""
    logits = u @ block["router"]
    if indices is None:
        _, indices = jax.lax.top_k(logits, cfg["num_experts_per_tok"])
    chosen = jax.nn.softmax(jnp.take_along_axis(logits, indices, axis=-1),
                            axis=-1)
    if _control(cfg, "drop", False):
        # a token's last chosen expert among those held
        held = (indices >= first) \
            & (indices < first + block["w_gate"].shape[0])
        order = jnp.where(held, jnp.arange(indices.shape[1]), -1)
        last = jnp.max(order, axis=-1, keepdims=True)
        chosen = jnp.where(held & (order == last), 0.0, chosen)
    rows = jnp.arange(u.shape[0])[:, None]
    return jnp.zeros_like(logits).at[rows, indices].add(chosen), indices


def routed(cfg, block, u, first, indices=None):
    """(the held experts' part of the routed sum for u [tokens, hidden],
    the experts chosen): every held expert applied to every token,
    weighted by the routing weights (0 for a token that did not choose
    it)."""
    weights, indices = route(cfg, block, u, first, indices)
    count = block["w_gate"].shape[0]
    held = weights[:, first:first + count]
    act = jax.nn.silu(jnp.einsum("td,edf->etf", u, block["w_gate"])) \
        * jnp.einsum("td,edf->etf", u, block["w_up"])
    each = jnp.einsum("etf,efd->etd", act, block["w_down"])
    return jnp.einsum("te,etd->td", held, each), indices


def shared_expert(cfg, block, u):
    w_in, w_out = block["shared_in"], block["shared_out"]
    width = _control(cfg, "shared_width", None)
    if width is not None:
        whole = w_out.shape[0]
        w_in = jnp.concatenate([w_in[:, :width],
                                w_in[:, whole:whole + width]], axis=1)
        w_out = w_out[:width]
    return gated(u, w_in, w_out)


def feed_forward(cfg, block, u, first, indices=None, shared=True):
    """(ffn(u) for u [tokens, hidden], the experts chosen, the held
    experts' part of it)."""
    part, indices = routed(cfg, block, u, first, indices)
    out = part + shared_expert(cfg, block, u) if shared else part
    return out, indices, part


def layer(cfg, index, block, x, first=0, indices=None, shared=True):
    """(the layer's output, {"mixer": the mixer's output, "state": a
    mamba layer's state after the last position or None, "indices",
    "routed": the held experts' part [tokens, hidden]}) for x [batch,
    seq, hidden]."""
    eps = cfg["rms_norm_eps"]
    res = _control(cfg, "residual_multiplier", cfg["residual_multiplier"])
    h = rms_norm(x, block["norm_1"], eps)
    if layer_type(cfg, index) == MAMBA:
        mixer, state = mamba_mixer(cfg, block, h)
    else:
        mixer, state = attention_mixer(cfg, block, h), None
    a = x + res * mixer
    u = rms_norm(a, block["norm_2"], eps)
    f, indices, part = feed_forward(
        cfg, block, u.reshape(-1, u.shape[-1]), first, indices, shared)
    return a + res * f.reshape(a.shape), {
        "mixer": mixer, "state": state, "indices": indices, "routed": part}


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32),
                                  tree)


def head(cfg, ends, x):
    """z = N(x; w_f) E^T / logits_scaling over the rows of E held."""
    return rms_norm(x, ends["norm_f"], cfg["rms_norm_eps"]) \
        @ ends["embed"].T / cfg["logits_scaling"]


def forward(cfg, params, tokens, held=None, vocab=None, shared=True):
    """{"logits" [batch, seq, vocab], "hidden": [L] each layer's output,
    "mixer": [L] each mixer's output, "states": [L] a mamba layer's state
    after the last position [batch, heads, head_dim, d_state] (None for
    an attention layer), "indices": [L] the experts chosen [tokens,
    top_k], "routed": [L] the held experts' part [tokens, hidden]} for
    local token ids `tokens` [batch, seq].  `held` = (first, count) cuts
    the share's experts out of `params`' (which then hold every scored
    one); without it `params` hold the experts from
    `cfg["first_expert"]` (default 0) on.  `vocab` = (first, count) cuts
    the table's rows likewise."""
    params = _f32(params)
    first = cfg.get("first_expert", 0)
    blocks = params["blocks"]
    if held is not None:
        first, count = held
        blocks = [dict(block, **{w: block[w][first:first + count]
                                 for w in _ROUTED}) for block in blocks]
    embed = params["embed"]
    if vocab is not None:
        embed = embed[vocab[0]:vocab[0] + vocab[1]]
    out = {"hidden": [], "mixer": [], "states": [], "indices": [],
           "routed": []}
    with jax.default_matmul_precision("highest"):
        x = cfg["embedding_multiplier"] * embed[tokens]
        for i, block in enumerate(blocks):
            x, found = layer(cfg, i, block, x, first, shared=shared)
            out["hidden"].append(x)
            for key, name in (("mixer", "mixer"), ("states", "state"),
                              ("indices", "indices"), ("routed", "routed")):
                out[key].append(found[name])
        out["logits"] = head(cfg, {"embed": embed,
                                   "norm_f": params["norm_f"]}, x)
    return out


def held_part_off(cfg, block, probe):
    """How far the held experts' part a step served lies from the
    reference's: `probe` is {"in": the routed layer's input [rows, 1,
    hidden], "idx": the experts the step's router chose [rows, top_k],
    "out": what its held experts gave for them [rows, 1, hidden]} as the
    step computed them; the reference's routed sum of the same input
    under the same choice (its own float32 weights of it) is what "out"
    is held to, as the root mean square of the difference over the
    reference's.  `block`: the layer's parameters in float32.  A choice
    of experts is not judged here (a near-tie falls either way between
    bfloat16 and float32): what the held experts' weights and products
    did to the rows they were given is."""
    u, idx, out = (jnp.asarray(probe[k]) for k in ("in", "idx", "out"))
    u = u.reshape(-1, u.shape[-1]).astype(jnp.float32)

    @jax.jit
    def want_of(block, u, idx):
        with jax.default_matmul_precision("highest"):
            return routed(cfg, block, u, cfg.get("first_expert", 0), idx)[0]

    want = want_of(block, u, idx)
    return rms_off(out.reshape(want.shape), want)


def rms_off(got, want):
    """The root mean square of `got`'s difference from the reference's
    `want`, over the reference's."""
    got, want = (jnp.asarray(t, jnp.float32) for t in (got, want))
    return float(jnp.sqrt(jnp.mean(jnp.square(got - want))
                          / jnp.mean(jnp.square(want))))


def state_off(got, want):
    """How far a served state [rows, heads, head_dim, d_state] lies from
    the reference's, **a head at a time**: each head's root mean square
    difference over the reference's head, and of those the root mean
    square over the quarter of a row's heads that lie furthest off.

    A head at a time, because the heads' states differ in size by what
    they remember: a head whose step is small (dt near 1e-3) decays by
    0.999 a position, holds hundreds of positions and a small state; one
    whose step is large forgets within a few and holds a large one.  A
    state kept in a narrower type is rounded after every position, which
    adds up over the positions a head remembers: the slow heads are off
    by several times what the fast ones are, where a sound step's
    rounding (its bfloat16 x, B, C and dt) is much the same in every
    head.  A mean over all entries reads mostly the large states of the
    heads that forget at once (sound 0.0057, a bfloat16 state 0.0070 at
    the cell's size); the furthest quarter reads 0.0074 and 0.0141."""
    got, want = (jnp.asarray(t, jnp.float32) for t in (got, want))
    each = jnp.sum(jnp.square(got - want), axis=(-2, -1)) \
        / jnp.sum(jnp.square(want), axis=(-2, -1))
    furthest = jnp.sort(each, axis=-1)[..., -max(each.shape[-1] // 4, 1):]
    return float(jnp.sqrt(jnp.mean(furthest)))


def state_step_off(cfg, block, probe):
    """How far the state a mamba layer's step handed on lies from **one
    float32 update of the state it was handed**: `probe` is {"state_in":
    the state the step entered with [rows, k, head_dim, d_state], the
    first k heads of it, "step_in": what its scan read [rows, 1, heads *
    head_dim + 2 * d_state + heads], [x | B | C] after the convolution
    and dt before the softplus, "state": the state it handed on [rows,
    heads, head_dim, d_state]} as the step had them; `state_off` of
    "state"'s first k heads against `recurrence` over that one position
    from "state_in".  `block`: the layer's parameters in float32.

    This reads a layer's own step, whatever the layers before it did to
    its input: the state a call leaves (`state_off`) holds every
    rounding of the stream above the layer, by which a sound program's
    ninth mamba layer is off by seven times its first, so that a state
    kept in a narrower type is seen there in the first layer alone.
    Here the step's inputs are the served ones on both sides, a sound
    step differs by the last bits of its exponentials at most (by
    nothing where both run on one device), and one rounding of the
    state to bfloat16 reads 1.7e-3 in every layer.  What is wrong outside the one update (the border, the
    tail, the other layers) is not seen here."""
    heads, dim = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    entries, inner = cfg["mamba_d_state"], heads * dim

    @jax.jit
    def want_of(block, start, read):
        k = start.shape[1]
        x, b, c, dt = (read[..., :inner], read[..., inner:inner + entries],
                       read[..., inner + entries:inner + 2 * entries],
                       read[..., inner + 2 * entries:])
        return recurrence(
            cfg, x.reshape(*x.shape[:2], heads, dim)[:, :, :k],
            jax.nn.softplus(dt + block["dt_bias"])[..., :k],
            -jnp.exp(block["a_log"])[:k], b, c, block["d"][:k], start)[1]

    want = want_of(block, jnp.asarray(probe["state_in"], jnp.float32),
                   jnp.asarray(probe["step_in"], jnp.float32))
    return state_off(jnp.asarray(probe["state"])[:, :want.shape[1]], want)


def gaps(cfg, ends, block_of, prompt, served, rows, with_block=None,
         with_state=None):
    """`[sequences, served length]` float32: at every served position,
    how far the reference's logit of the served token lies below the
    reference's best.

    `ends` is {"embed", "norm_f"}; `block_of(i)` gives block i's
    parameters, asked for once a layer and dropped before the next is
    asked for; the sequences go through a layer `rows` at a time.  The
    served token i was chosen from the logits at position prompt_len - 1
    + i, whose input is the prompt and the served tokens before it.
    `with_block(i, block)` is called with block i in float32 while it is
    held.  `with_state(i, state)` is called for every mamba layer with
    the reference's state [sequences, heads, head_dim, d_state] after
    the input of the **last served step**: the prompt and all served
    tokens but the last (the step that chose the last token read the one
    before it)."""
    first_expert = cfg.get("first_expert", 0)
    tokens = jnp.concatenate([prompt, served], axis=1)[:, :-1]
    start, count = prompt.shape[1] - 1, served.shape[1]
    ends = _f32(ends)

    def one_layer(i):
        @jax.jit
        def apply(block, x):
            with jax.default_matmul_precision("highest"):
                out, found = layer(cfg, i, block, x, first_expert)
            return out, found["state"]
        return apply

    @jax.jit
    def head_gaps(ends, x, served):
        with jax.default_matmul_precision("highest"):
            z = head(cfg, ends, x[:, start:start + count])
        picked = jnp.take_along_axis(z, served[..., None], axis=-1)
        return jnp.max(z, axis=-1) - picked[..., 0]

    cuts = range(0, tokens.shape[0], rows)
    xs = [cfg["embedding_multiplier"] * ends["embed"][tokens[at:at + rows]]
          for at in cuts]
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
        if with_block is not None:
            with_block(i, block)
        del block, found
    return jnp.concatenate([head_gaps(ends, x, served[at:at + rows])
                            for x, at in zip(xs, cuts)])
