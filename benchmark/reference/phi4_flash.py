"""Plain float32 reference of Phi-4-mini-flash-reasoning
(huggingface.co/microsoft/Phi-4-mini-flash-reasoning, `model_type`
`phi4flash`): the decoder-hybrid-decoder of arXiv:2507.06607 (SambaY)
with the differential attention of arXiv:2410.05258, in straightforward
`jax.numpy`: the whole score matrix under a mask (a window is a mask,
there is no cache), the scan a `lax.scan` a position, every layer run at
every position (no skip), highest matmul precision, nothing imported
from the program.

The model.  Layer i of `num_hidden_layers` = 32, x the residual stream
[T, hidden], LN a LayerNorm with scale and bias, eps `layer_norm_eps`:

    u  = x + mixer_i(LN1_i(x))
    x' = u + W_down (silu(g) * v),   [g, v] = LN2_i(u) W_gate_up

(`ffn_in` [hidden, 2 * intermediate], the gate half first; no bias).  The
mixer by i (`layer_kinds`: `mb_per_layer` 2 makes the even layers the
state-space kind, `sliding_window` applies to the odd i below half = 16,
layer half + 1 = 17 is the one full layer, i >= 18 is the cross-decoder):

    i = 0, 2 .. 14      Mamba-1
    i = 1, 3 .. 15      differential attention, window `sliding_window`
    i = 16              Mamba-1 that also gives the memory m
    i = 17              differential attention over every position before
    i = 18, 20 .. 30    a gated memory unit over m
    i = 19, 21 .. 31    differential cross attention: queries of its
                        own, keys and values those of layer 17

After layer 31 a last LayerNorm and the head tied to the embedding.  No
rotary or learned position anywhere (*assumed*: the released modeling
file applies none; the configuration file's `assumed.positions`).

Mamba-1 (d_inner = `mamba_expand` x hidden = 5120, `mamba_d_state` 16,
`mamba_d_conv` 4, `mamba_dt_rank` 160 = ceil(hidden / 16): *assumed*, the
model class's defaults, config.json carries none of them; their
parameter count meets the published 3.8 B), for h = LN1(x):

    [xs, z] = h W_in                           [T, 2 x d_inner]
    xc = silu(conv4(xs) + b_conv)              causal, a channel at a time
    [dt_r, B, C] = xc W_x                      160 + 16 + 16
    dt = softplus(dt_r W_dt + b_dt)            [T, d_inner]
    A = -exp(A_log)                            [d_inner, 16]
    S_t = exp(dt_t (x) A) * S_{t-1} + (dt_t * xc_t) (x) B_t     float32
    y_t = S_t C_t + D * xc_t
    mixer = (y * silu(z)) W_out;   layer 16 hands on m = y  (before the gate)

Gated memory unit: (silu(h W_in_i) * m) W_out_i, m layer 16's of the same
position.

Differential attention (`num_attention_heads` 40 and
`num_key_value_heads` 20 of 64 are 20 query pairs over 10 key/value
pairs; pair p is heads 2p, 2p + 1 as the fused projection leaves them,
g = p // 2): q = h W_q, [k | v] = h W_kv (a cross layer has W_q and W_o
alone and reads layer 17's k, v), with the causal mask and, on a window
layer, i - j < `sliding_window` (a query sees itself and the window - 1
positions before it: *assumed*, the inclusive edge of the released mask),

    P1 = softmax(q_{2p} k_{2g}^T / 8),  P2 = softmax(q_{2p+1} k_{2g+1}^T / 8)
    a_p = (1 - l0_i) * RMSNorm_128([P1 v_{2g} | P1 v_{2g+1}]
                                   - l_i * [P2 v_{2g} | P2 v_{2g+1}])
    l_i = exp(lq1 . lk1) - exp(lq2 . lk2) + l0_i
    l0_i = 0.8 - 0.6 exp(-0.3 i)

(four products a pair; lq1, lk1, lq2, lk2 four learned 64-vectors a
layer, the RMSNorm's scale learned, eps 1e-5; the schedule l0_i is the
differential transformer's, *assumed*; differential attention on every
attention layer, *assumed*), and the layer gives [a_0 | .. | a_19] W_o.

`params`: {"embed" [vocab, hidden], "blocks": [{"ln1.w", "ln1.b",
"ln2.w", "ln2.b", "ffn_in", "ffn_out", and by kind "in_proj" [hidden,
2 x d_inner], "conv_w" [d_inner, 4], "conv_b", "x_proj" [d_inner, 192],
"dt_proj" [160, d_inner], "dt_bias", "a_log" [d_inner, 16], "d",
"out_proj"; or "wq", "wkv" [hidden, 2 x kv heads x 64] (keys first),
"wo", "lq1", "lk1", "lq2", "lk2" [64], "subln" [128] (a cross layer has
no "wkv"); or "gmu_in" [hidden, d_inner], "gmu_out"}], "norm_f": {"w",
"b"}}, matrices as [in, out].

Two ways through the same layer functions.  `forward` is the whole
sequence at once (the tests' sizes).  `Layers`, `session` and `gaps` run
a layer at a time, a sequence at a time, a *turn* of positions at a
time, against the float32 keys, values, scan state and convolution tail
of every position before (a position's output reads nothing after it,
so the turns of a sequence one after another are the full forward):
`session` makes the states a decode-pool chip is handed for a document
(the self-decoder alone: the cross-decoder holds no state, so a state's
maker does not run it; no logit is asked of a document), and `gaps`
continues from the session's own float32 states over a question and the
served tokens, the cross-decoder at every position, for by how much the
reference's logit of each served token lies below its best, and, for
the call's last step, what each of the program's sub-layers is held to.
"""

import math

import jax
import jax.numpy as jnp

MAMBA, WINDOW, FULL, GMU, CROSS = "mamba", "window", "full", "gmu", "cross"
_RMS_EPS = 1e-5     # of the pair's norm: *assumed*, `assumed.subln_eps`


def layer_kinds(cfg):
    """The mixer of every layer, from `num_hidden_layers` alone (with
    the `mb_per_layer` 2 this reference is written for)."""
    n = cfg["num_hidden_layers"]
    half = n // 2
    if cfg.get("mb_per_layer", 2) != 2 or n % 4:
        raise ValueError("phi4_flash reference: mb_per_layer %r over %d "
                         "layers" % (cfg.get("mb_per_layer"), n))
    return tuple(
        (MAMBA if i <= half else GMU) if i % 2 == 0
        else WINDOW if i < half else FULL if i == half + 1 else CROSS
        for i in range(n))


def lambda_init(layer):
    return 0.8 - 0.6 * math.exp(-0.3 * layer)


def sizes(cfg):
    """(d_inner, d_state, d_conv, dt_rank), the *assumed* class defaults
    where the configuration's file does not carry them."""
    hidden = cfg["hidden_size"]
    return (cfg.get("mamba_expand", 2) * hidden, cfg.get("mamba_d_state", 16),
            cfg.get("mamba_d_conv", 4),
            cfg.get("mamba_dt_rank", -(-hidden // 16)))


def head_dim(cfg):
    return cfg.get("head_dim") or \
        cfg["hidden_size"] // cfg["num_attention_heads"]


def layer_norm(x, w, b, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * w + b


def feed_forward(block, u):
    gate, up = jnp.split(u @ block["ffn_in"], 2, axis=-1)
    return (jax.nn.silu(gate) * up) @ block["ffn_out"]


# -- Mamba-1 ---------------------------------------------------------------------

def steps(block, xc):
    """(dt [n, d_inner], B, C [n, N]) of xc [n, d_inner]."""
    n = block["a_log"].shape[1]
    low = xc @ block["x_proj"]
    rank = low.shape[-1] - 2 * n
    dt = jax.nn.softplus(low[:, :rank] @ block["dt_proj"]
                         + block["dt_bias"])
    return dt, low[:, rank:rank + n], low[:, rank + n:]


def scan_update(block, state, xc, dt, b, c):
    """One position: `state` [d_inner, N], `xc` and `dt` [d_inner], `b`
    and `c` [N] -> (the state after it, y [d_inner])."""
    state = jnp.exp(dt[:, None] * -jnp.exp(block["a_log"])) * state \
        + (dt * xc)[:, None] * b[None, :]
    return state, state @ c + block["d"] * xc


def convolved(block, tail, xs):
    """silu(conv(xs) + b) for xs [n, d_inner] with `tail` [d_conv - 1,
    d_inner] the positions before it (zeros at a sequence's start); and
    the tail after it."""
    width = block["conv_w"].shape[1]
    joined = jnp.concatenate([tail, xs])
    pre = sum(joined[j:j + xs.shape[0]] * block["conv_w"][:, j]
              for j in range(width)) + block["conv_b"]
    return jax.nn.silu(pre), joined[xs.shape[0]:]


def mamba(block, h, state, tail, keep_before=None):
    """(the mixer's output [n, hidden], y [n, d_inner] the scan's output
    before the gate, the state and the tail after the n positions, and
    the state and tail *before* position `keep_before` of them) for h
    [n, hidden] from `state` [d_inner, N] and `tail`."""
    xs, z = jnp.split(h @ block["in_proj"], 2, axis=-1)
    xc, tail_after = convolved(block, tail, xs)
    at = -1 if keep_before is None else keep_before

    def one(carry, inputs):
        s, kept = carry
        t, *position = inputs
        kept = jnp.where(t == at, s, kept)
        s, y = scan_update(block, s, *position)
        return (s, kept), y

    (state_after, state_before), y = jax.lax.scan(
        one, (state, state),
        (jnp.arange(h.shape[0]), xc) + steps(block, xc))
    width = tail.shape[0]
    tail_before = jax.lax.dynamic_slice_in_dim(
        jnp.concatenate([tail, xs]), jnp.maximum(at, 0), width, axis=0)
    return (y * jax.nn.silu(z)) @ block["out_proj"], y, state_after, \
        tail_after, (state_before, tail_before)


def mamba_step(block, h_1, state, tail):
    """(y [d_inner], the state after) of one position whose normed input
    is h_1 [hidden], from the state and tail before it."""
    xs, _ = jnp.split(h_1 @ block["in_proj"], 2, axis=-1)
    xc, _ = convolved(block, tail, xs[None])
    state, y = scan_update(block, state, xc[0],
                           *(t[0] for t in steps(block, xc)))
    return y, state


# -- differential attention ------------------------------------------------------

def keys_values(cfg, block, h):
    """(k, v) [n, kv heads, dim] of the normed input h [n, hidden]: what
    a cache of the layer holds."""
    kv_heads, dim = cfg["num_key_value_heads"], head_dim(cfg)
    k, v = jnp.split(h @ block["wkv"], 2, axis=-1)
    return k.reshape(-1, kv_heads, dim), v.reshape(-1, kv_heads, dim)


def attend(cfg, block, l0, h, q_positions, k, v, k_positions, window):
    """The differential attention layer's output [n, hidden] for the
    normed input h [n, hidden] at `q_positions` over keys and values [m,
    kv heads, dim] at `k_positions` (a negative one holds nothing);
    `l0` the layer's `lambda_init`, `window` 0 for none."""
    heads, kv_heads = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dim = head_dim(cfg)
    n = h.shape[0]
    q = (h @ block["wq"]).reshape(n, heads // 2, 2, dim)
    group = heads // kv_heads
    keep = (k_positions[None, :] <= q_positions[:, None]) \
        & (k_positions[None, :] >= 0)
    if window:
        keep &= q_positions[:, None] - k_positions[None, :] < window
    kp = k.reshape(-1, kv_heads // 2, 2, dim)
    vp = v.reshape(-1, kv_heads // 2, 2 * dim)     # [v_{2g} | v_{2g+1}]
    maps = []
    for side in (0, 1):
        # pair p = g * group + r reads key/value pair g
        qs = q[:, :, side].reshape(n, kv_heads // 2, group, dim)
        scores = jnp.einsum("qgrd,kgd->grqk", qs, kp[:, :, side]) \
            / math.sqrt(dim)
        scores = jnp.where(keep, scores, -jnp.inf)
        maps.append(jnp.einsum("grqk,kgd->qgrd",
                               jax.nn.softmax(scores, axis=-1), vp))
    lam = jnp.exp(jnp.sum(block["lq1"] * block["lk1"])) \
        - jnp.exp(jnp.sum(block["lq2"] * block["lk2"])) + l0
    diff = maps[0] - lam * maps[1]
    diff = diff * jax.lax.rsqrt(
        jnp.mean(jnp.square(diff), axis=-1, keepdims=True) + _RMS_EPS) \
        * block["subln"]
    return ((1.0 - l0) * diff).reshape(n, -1) @ block["wo"]


def memory_unit(block, h, m):
    return (jax.nn.silu(h @ block["gmu_in"]) * m) @ block["gmu_out"]


# -- the whole sequence at once ----------------------------------------------------

def forward(cfg, params, tokens, with_states=False):
    """Logits [batch, seq, vocab] of `tokens` [batch, seq], every layer
    at every position; with `with_states` also, a layer, what a decoder
    would carry after the sequence: {"state" [batch, d_inner, N], "tail"
    [batch, d_conv - 1, d_inner]} or {"k", "v" [batch, seq, kv heads,
    dim]} (none for the cross-decoder's layers), and "mixer_out" [batch,
    seq, hidden] for every layer."""
    kinds = layer_kinds(cfg)
    d_inner, d_state, d_conv, _ = sizes(cfg)
    eps = cfg["layer_norm_eps"]

    def one(row):
        n = row.shape[0]
        at = jnp.arange(n)
        x = params["embed"][row]
        memory = shared = None
        states = []
        for i, (kind, block) in enumerate(zip(kinds, params["blocks"])):
            h = layer_norm(x, block["ln1.w"], block["ln1.b"], eps)
            found = {}
            if kind == MAMBA:
                o, y, state, tail, _ = mamba(
                    block, h, jnp.zeros((d_inner, d_state), jnp.float32),
                    jnp.zeros((d_conv - 1, d_inner), jnp.float32))
                found = {"state": state, "tail": tail}
                if i == len(kinds) // 2:
                    memory = y
            elif kind == GMU:
                o = memory_unit(block, h, memory)
            else:
                if kind != CROSS:
                    k, v = keys_values(cfg, block, h)
                    found = {"k": k, "v": v}
                    if kind == FULL:
                        shared = k, v
                k, v = shared if kind == CROSS else (k, v)
                o = attend(cfg, block, lambda_init(i), h, at, k, v, at,
                           cfg["sliding_window"] if kind == WINDOW else 0)
            found["mixer_out"] = o
            states.append(found)
            u = x + o
            x = u + feed_forward(
                block, layer_norm(u, block["ln2.w"], block["ln2.b"], eps))
        z = layer_norm(x, params["norm_f"]["w"], params["norm_f"]["b"], eps)
        return z @ params["embed"].T, states

    with jax.default_matmul_precision("highest"):
        params = _f32(params)
        logits, states = jax.vmap(one)(jnp.asarray(tokens))
    return (logits, states) if with_states else logits


# -- a layer, a sequence, a turn of positions at a time ----------------------------

def _f32(tree):
    return jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32),
                                  tree)


def _blocks(x, size):
    """x [n, ...] as [n / size, size, ...]."""
    return x.reshape((x.shape[0] // size, size) + x.shape[1:])


def turn(cfg, block, kind, gives_memory, l0, x, start, before, shared,
         memory, query_block, last, states_alone=False):
    """(y, after, handed, probe) of one decoder layer of `kind` over the
    positions start .. start + n - 1 of one sequence, x [n, hidden] its
    input there; `gives_memory`: layer half, whose scan output the
    memory units read; `l0`: an attention layer's `lambda_init`.

    `before` is what the layer holds of the positions before `start`,
    float32: a Mamba layer's (state [d_inner, N], tail [d_conv - 1,
    d_inner]); a window layer's (k, v) [window, kv heads, dim], the
    positions start - window .. start - 1 in order (those below 0 hold
    nothing); the full layer's (k, v) [extent, kv heads, dim], position
    p in row p, rows from `start` on unread; None for the
    cross-decoder's layers.  `after` is the same after the turn.
    `shared` is the full layer's (k, v) with the turn written (a cross
    layer's), `memory` layer half's y over the turn (a memory unit's).
    `handed` is what the layer hands the layers above: the memory (layer
    half), the full layer's `after`, else None.

    `last` = (j, h [hidden]): the normed input the program's mixer had at
    position start + j; `probe` is what the reference makes of that same
    input there with its own float32 weights and what the positions
    before left in its own states: the scan's output before the gate
    and the state after the position (Mamba), the mixer's output (every
    other kind: the attention's own entry of position start + j made
    from h on a layer that owns a cache, the reference's own on a cross
    layer, which also gives that output with the slot of position start
    + j left out; the memory unit over the reference's own m).

    `states_alone`: y is x as it came and nothing but `after` is made
    (the full layer when a session is made: its keys and values need its
    input alone)."""
    eps = cfg["layer_norm_eps"]
    n = x.shape[0]
    positions = start + jnp.arange(n)
    j, h_1 = last
    h = layer_norm(x, block["ln1.w"], block["ln1.b"], eps)
    put = jax.lax.dynamic_update_slice_in_dim
    after = handed = None
    if kind == MAMBA:
        o, y, state, tail, (s_0, t_0) = mamba(block, h, *before,
                                              keep_before=j)
        after = (state, tail)
        if gives_memory:
            handed = y
        probe = mamba_step(block, h_1, s_0, t_0)
    elif kind == GMU:
        o = memory_unit(block, h, memory)
        probe = memory_unit(block, h_1, memory[j])
    else:
        window = cfg["sliding_window"] if kind == WINDOW else 0
        if kind == CROSS:
            keys, values = shared
            at, offset = jnp.arange(keys.shape[0]), None
        else:
            k, v = keys_values(cfg, block, h)
            if kind == WINDOW:
                keys = jnp.concatenate([before[0], k])
                values = jnp.concatenate([before[1], v])
                at = jnp.concatenate([start - window + jnp.arange(window),
                                      positions])
                after, offset = (keys[-window:], values[-window:]), window
            else:
                keys = put(before[0], k, start, 0)
                values = put(before[1], v, start, 0)
                at, offset = jnp.arange(keys.shape[0]), start
                after = handed = (keys, values)
        if states_alone:
            return x, after, handed, jnp.zeros_like(x[0])

        def some_queries(part):
            return attend(cfg, block, l0, part[0], part[1], keys, values,
                          at, window)

        o = jax.lax.map(some_queries, (_blocks(h, query_block),
                                       _blocks(positions, query_block)))
        o = o.reshape(n, -1)
        one = jnp.reshape(start + j, (1,))
        if offset is not None:
            k_1, v_1 = keys_values(cfg, block, h_1[None])
            keys = put(keys, k_1, offset + j, 0)
            values = put(values, v_1, offset + j, 0)
        probe = attend(cfg, block, l0, h_1[None], one, keys, values, at,
                       window)[0]
        if kind == CROSS:
            # and what a layer that missed the slot of its own position
            # would give: the same, that one slot holding nothing
            probe = (probe, attend(cfg, block, l0, h_1[None], one, keys,
                                   values, at.at[start + j].set(-1),
                                   window)[0])
    u = x + o
    f = jax.lax.map(
        lambda part: feed_forward(block, layer_norm(
            part, block["ln2.w"], block["ln2.b"], eps)),
        _blocks(u, math.gcd(n, 2048)))
    return u + f.reshape(n, -1), after, handed, probe


class Layers:
    """The compiled `turn` of every kind of layer, one a (kind, states
    alone) that is asked for: `session` and `gaps` of one run share
    them."""

    def __init__(self, cfg, query_block):
        self.cfg, self.query_block = cfg, query_block
        self.kinds = layer_kinds(cfg)
        self._made = {}

    def __call__(self, i, block, x, start, before, shared=None,
                 memory=None, last=None, states_alone=False):
        cfg, kind = self.cfg, self.kinds[i]
        gives_memory = i == len(self.kinds) // 2
        key = (kind, gives_memory, states_alone)
        if key not in self._made:
            def one(block, l0, x, start, before, shared, memory, last):
                with jax.default_matmul_precision("highest"):
                    return turn(cfg, block, kind, gives_memory, l0, x,
                                start, before, shared, memory,
                                math.gcd(x.shape[0], self.query_block),
                                last, states_alone)
            self._made[key] = jax.jit(one)
        if last is None:    # nobody reads `probe`: position 0, zeros
            last = (jnp.int32(0), jnp.zeros((x.shape[1],), jnp.float32))
        return self._made[key](block, jnp.float32(lambda_init(i)), x,
                               jnp.int32(start), before, shared, memory,
                               last)

    def nothing_before(self, i, extent):
        """What layer i holds of no position at all."""
        cfg, kind = self.cfg, self.kinds[i]
        d_inner, d_state, d_conv, _ = sizes(cfg)
        if kind == MAMBA:
            return (jnp.zeros((d_inner, d_state), jnp.float32),
                    jnp.zeros((d_conv - 1, d_inner), jnp.float32))
        if kind in (WINDOW, FULL):
            slots = cfg["sliding_window"] if kind == WINDOW else extent
            empty = jnp.zeros((slots, cfg["num_key_value_heads"],
                               head_dim(cfg)), jnp.float32)
            return (empty, empty)
        return None


def session(cfg, layers, ends, block_of, documents, size, extent, keep=()):
    """({layer: (first, second)} float32 on the host, [documents, ...]
    as `turn` holds them: a Mamba layer's (state [d_inner, N], tail), a
    window layer's (k, v) of the last `sliding_window` positions in
    order, the full layer's (k, v) over `extent` rows, position p in row
    p; and {document: {layer: the same on the device side's layout}}
    for the documents `keep` names, from which `gaps` continues).  The
    states a prefill pool would hand over for the seeded `documents`
    [documents, seq], seq a multiple of `size`, for their caller to lay
    out and round once to the states' types.  The self-decoder alone:
    the full layer's keys and values need its input alone, and no layer
    past it holds a state."""
    import numpy as np

    count, seq = documents.shape
    kinds = layers.kinds
    full = kinds.index(FULL)
    if seq % size or (seq and seq < cfg["sliding_window"]):
        raise ValueError("a session of %d positions is not whole turns of "
                         "%d, or shorter than the window" % (seq, size))
    embed = _f32(ends["embed"])
    xs = [[embed[jnp.asarray(documents[d, at:at + size])]
           for at in range(0, seq, size)] for d in range(count)]
    del embed
    made, kept = {}, {int(d): {} for d in keep}
    for i in range(full + 1):
        block = _f32(block_of(i))
        held = ([], [])
        for d in range(count):
            state = layers.nothing_before(i, extent)
            for t in range(seq // size):
                xs[d][t], state, _, _ = layers(
                    i, block, xs[d][t], t * size, state,
                    states_alone=i == full)
            if d in kept:
                kept[d][i] = tuple(np.asarray(s) for s in state)
            for out, value in zip(held, state):
                out.append(np.asarray(value))
        made[i] = (np.stack(held[0]), np.stack(held[1]))
        del block
    return made, kept


def gaps(cfg, layers, ends, block_of, tokens, start, first_logit, served,
         last, before, with_block=None):
    """(`[sequences, served length]` float32: at every served position,
    how far the reference's logit of the served token lies below the
    reference's best; and {layer: [sequences] x `turn`'s probe}).

    `tokens` [sequences, n] are the question and the served tokens of
    each checked row, at positions start .. start + n - 1; served token
    i was chosen from the logits at position start + first_logit + i.
    `before` = [sequences] x {layer: states} as `session` kept them.
    `ends` is {"embed", "norm_f"}; `block_of(i)` gives block i's
    parameters, asked for once a layer and dropped before the next.
    `last` = {"at": the position of the call's last step, "mixer_in":
    [layers] x [sequences, hidden]}."""
    count = served.shape[1]
    depth = cfg["num_hidden_layers"]
    embed = _f32(ends["embed"])
    xs = [embed[jnp.asarray(row)] for row in tokens]
    rows = range(len(xs))
    shared, memory = [None] * len(xs), [None] * len(xs)
    probes = {}
    for i in range(depth):
        block = _f32(block_of(i))
        kind = layers.kinds[i]
        probes[i] = []
        for row in rows:
            handed = (jnp.int32(last["at"] - start),
                      jnp.asarray(last["mixer_in"][i][row], jnp.float32))
            state = before[row].get(i)
            if state is not None:
                state = tuple(jnp.asarray(s) for s in state)
            xs[row], _, given, probe = layers(
                i, block, xs[row], start, state, shared[row], memory[row],
                handed)
            if kind == FULL:
                shared[row] = given
            elif given is not None:
                memory[row] = given
            probes[i].append(jax.device_get(probe))
        if with_block is not None:
            with_block(i, block)
        del block
    norm_f = _f32(ends["norm_f"])

    @jax.jit
    def head_gaps(embed, norm_f, x, served):
        with jax.default_matmul_precision("highest"):
            z = layer_norm(x[first_logit:first_logit + count], norm_f["w"],
                           norm_f["b"], cfg["layer_norm_eps"]) @ embed.T
        picked = jnp.take_along_axis(z, served[:, None], axis=-1)
        return jnp.max(z, axis=-1) - picked[:, 0]

    return jnp.stack([head_gaps(embed, norm_f, x, jnp.asarray(row))
                      for x, row in zip(xs, served)]), probes
