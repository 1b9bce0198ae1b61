"""Linear attention with a recurrent state a cached step takes in and
hands on: the gated delta rule (Gated DeltaNet, arXiv:2412.06464, as
Qwen3-Next's `linear_attention` layers run it).

One state `S` [key_dim, value_dim] float32 a row and value head, walked
position by position:

    S   = exp(g_t) S                      (the gate: a decay in (0, 1])
    r   = S^T k_t                         (what the state holds for k_t)
    S   = S + k_t (beta_t (v_t - r))^T    (the delta rule)
    o_t = S^T q_t

with q and k l2-normed over a head's values and q scaled by
key_dim ** -0.5 where `qk_l2norm` is set (inside the op: the norms are
float32 whatever type the projections come in).  Value head j reads
key/query head j // (value heads / key heads), by index: no key is
repeated in memory.

`gated_delta_rule` is the op: Q, K [rows, T, key heads * key_dim], V
[rows, T, value heads * value_dim], G and Beta [rows, T, value heads]
float32 (g <= 0; beta's range is the caller's: sigmoid(b) in (0, 1) as
Qwen3-Next has it, or 2 sigmoid(b) in (0, 2) under the family's
`allow_neg_eigval`, Olmo-Hybrid's, where a step's transition `I - beta
k k^T` has an eigenvalue in (-1, 1); the op clamps nothing), State
[rows, value heads, key_dim, value_dim] float32 -> Out [rows, T, value
heads * value_dim] in V's type and StateOut, State's shape and type: a
`fluid.ProgramDecoder` state pair that a step rewrites whole.

**Heads side by side** (`state_pack` = p > 1): State is [rows, value
heads / p, key_dim, p * value_dim], heads p u .. p u + p - 1 beside one
another along the last axis of unit u (kernels/gdn_step.py
`pack_state`).  A device stores an array's last axis in whole blocks of
128 lanes, so a state of 192 values a head would lie in HBM as 256; two
such heads side by side are three whole blocks, and a step moves the
state's own bytes.  The recurrence is the same; the step kernel works
the state as it lies, and the plain step and the block form take it
apart and put it together again around the same lines.

**A gate a key channel** (Kimi Delta Attention, arXiv:2510.26692, as
Ling-3.0-flash's KDA layers run it): G [rows, T, value heads * key_dim],
and the first line of the recurrence is `S = diag(exp(g_t)) S`, row d of
a head's state decayed by its own exp(g_t[d]).  The same op and the same
recurrence; which gate an op instance has is read off G's last axis as
the program is traced, and the scalar gate lowers as it always did.

Two forms, chosen by T as the program is traced:

T = 1, a decode step (`gdn_state`): every head's state is read once,
decayed, read for `S^T k`, written with the rank-one update and read for
`S^T q`.  On the TPU that is one Pallas kernel that stores the state in
place (kernels/gdn_step.py: `gdn_step_*` under a gate a head,
`kda_step_*` under a gate a key channel, `kda_state` the scope); off it,
and for shapes the kernel does not take, the same four lines in
`jax.numpy`.  Which shapes it takes is `gdn_step.choose_block`'s to
say: a float32 state of 128 x 128 a head under either gate, and under a
gate a head any state of a key head a value head whose key_dim is whole
sublane tiles and whose (packed) last axis is whole lane blocks
(Olmo-Hybrid's 96 x 192, two heads side by side).

T > 1, a block (`gdn_chunks`): the same recurrence rearranged over
chunks of `chunk` positions (the family's `chunk_gated_delta_rule`).
With `G_i` the running sum of g inside the chunk and `D_ij = exp(G_i -
G_j)` for i >= j,

    A     = strict_lower(diag(beta) (K K^T) * D)
    T     = (I + A)^-1                    (a unit lower triangular solve)
    U     = T (beta V);  W = T (beta K exp(G))
    V_new = U - W S                       S: the state entering the chunk
    O     = (Q exp(G)) S + lower((Q K^T) * D) V_new
    S'    = exp(G_C) S + (K exp(G_C - G))^T V_new

walked chunk by chunk; every product float32 at the highest precision
(no exponent is ever positive: D, exp(G) and exp(G_C - G) are decays).
With beta up to 2 the entries of A are up to twice as large and those of
(I + A)^-1 grow with them (l2-normed keys keep |k_i . k_j| <= 1, so |A|
<= 2 an entry): the solve is exact in exact arithmetic whatever beta,
and the tests hold it to the recurrence at beta drawn over (0, 2).
A block that is no multiple of the chunk is padded with beta 0 and g 0,
which leave the state as it is.  Plain `jax.numpy` on every platform.

Under a gate a key channel (`kda_chunks`) G_i is a vector over the key's
channels and `(K K^T) * D` no longer factors through one decay a pair of
positions: entry ij is sum_d k_i[d] k_j[d] exp(G_i[d] - G_j[d]), which
is a matrix product only as (K exp(G - G_ref)) (K exp(G_ref - G))^T, and
the second factor **grows**.  So a chunk is cut into sub-blocks of `sub`
positions, and the rows of sub-block b take G_ref = G at its first
position: the left factor exp(G_i - G_ref) is a decay; the right factor
exp(G_ref - G_j) is a decay for every j before the sub-block (products
between sub-blocks go through decays only), at most exp((sub - 1) |g|min)
for a j inside it, and is left out (the exponent masked, not the
exponential) for a j after it, which the causal mask drops anyway.  The
caller states the least g a position can have (`gate_floor`: the
config's `kda_lower_bound`, -5) and `sub_chunk` is chosen from it so
that (sub - 1) * |gate_floor| <= 80 < 88.7 = log(float32's largest): 16
positions at -5, where the largest exponent is 75 and a product of two
l2-normed channels summed over the key's 128 stays under e^80.
Everything after the two decayed products (the solve, V_new, O, S') is
the form above with exp(G) a vector a position: `(Q exp(G)) S`, `(K
exp(G_C - G))^T V_new` and `diag(exp(G_C)) S` hold decays only.

Forward only: generation needs no gradient, and a gradient of this op
asked for raises (training the layer wants the block form's backward,
which nothing here has).
"""

import jax
import jax.numpy as jnp

from ..obs import telemetry
from .registry import (register_grad_kernel, register_op,
                       same_meta_infer_shape)

F32 = jnp.float32
_HIGHEST = jax.lax.Precision.HIGHEST
_EPS = 1e-6


def _infer_shape(block, op_desc):
    """`Out` is `V`'s and the state comes out as it went in: stated, not
    traced, so that a block axis the Program leaves open stays open."""
    for src, dst in (("V", "Out"), ("State", "StateOut")):
        same_meta_infer_shape(src, dst)(block, op_desc)


def _heads(ins, pack=1):
    """q, k [rows, T, key heads, key_dim], v [rows, T, value heads,
    value_dim] as they come, beta [rows, T, value heads] and g [rows, T,
    value heads] (a gate a head) or [rows, T, value heads, key_dim] (a
    gate a key channel) float32, the state as it is handed in (`pack`
    heads side by side); checked against one another."""
    q, k, v = ins["Q"][0], ins["K"][0], ins["V"][0]
    g, beta, state = ins["G"][0], ins["Beta"][0], ins["State"][0]
    rows, units, key_dim, wide = state.shape
    heads, value_dim = units * pack, wide // pack
    if q.shape != k.shape or q.shape[-1] % key_dim or wide % pack \
            or v.shape[-1] != heads * value_dim \
            or heads % (q.shape[-1] // key_dim) \
            or beta.shape != v.shape[:2] + (heads,) \
            or g.shape not in (beta.shape,
                               beta.shape[:2] + (heads * key_dim,)):
        raise ValueError(
            "gated_delta_rule: Q %s, K %s, V %s, G %s and Beta %s do not "
            "fit a state of %s ([rows, value heads / %d, key_dim, %d * "
            "value_dim]: state_pack %d): G is [rows, T, value heads] (a "
            "gate a head) or [rows, T, value heads * key_dim] (a gate a "
            "key channel), Beta [rows, T, value heads], in (0, 1) or, "
            "with negative eigenvalues allowed, (0, 2) as the caller "
            "made it"
            % (q.shape, k.shape, v.shape, g.shape, beta.shape, state.shape,
               pack, pack, pack))
    split = lambda t, d: t.reshape(*t.shape[:2], -1, d)
    if g.shape != beta.shape:
        g = split(g, key_dim)
    return (split(q, key_dim), split(k, key_dim), split(v, value_dim),
            g.astype(F32), beta.astype(F32), state)


def l2norm(t):
    """t / sqrt(sum(t^2) + 1e-6) over the last axis, float32."""
    t = t.astype(F32)
    return t * jax.lax.rsqrt(jnp.sum(t * t, axis=-1, keepdims=True) + _EPS)


def recurrent(q, k, v, g, beta, state):
    """The recurrence position by position (`lax.scan` over T): q, k
    [rows, T, key heads, key_dim] (normed and scaled already), v [rows,
    T, heads, value_dim], beta [rows, T, heads], g [rows, T, heads] or
    [rows, T, heads, key_dim] (a gate a key channel), state [rows,
    heads, key_dim, value_dim], all float32 -> (out [rows, T, heads,
    value_dim], the state after the block)."""
    rows, _, key_heads, key_dim = q.shape
    heads = v.shape[2]
    group = heads // key_heads

    def step(s, at):
        q_t, k_t, v_t, g_t, b_t = at
        # [rows, key heads, group, ...]: a value head beside its key's
        # (a gate a head is one value for the state's rows, a gate a
        # key channel one a row)
        s = s.reshape(rows, key_heads, group, key_dim, -1) \
            * jnp.exp(g_t).reshape(rows, key_heads, group, -1, 1)
        v_t = v_t.reshape(rows, key_heads, group, -1)
        b_t = b_t.reshape(rows, key_heads, group, 1)
        held = jnp.einsum("bhgkv,bhk->bhgv", s, k_t, precision=_HIGHEST)
        s = s + k_t[:, :, None, :, None] * (b_t * (v_t - held))[..., None, :]
        out = jnp.einsum("bhgkv,bhk->bhgv", s, q_t, precision=_HIGHEST)
        return s.reshape(rows, heads, key_dim, -1), \
            out.reshape(rows, heads, -1)

    state, out = jax.lax.scan(
        step, state, tuple(jnp.moveaxis(t, 1, 0)
                           for t in (q, k, v, g, beta)))
    return jnp.moveaxis(out, 0, 1), state


def chunked(q, k, v, g, beta, state, chunk):
    """The block form (the module's docstring); `recurrent`'s operands
    and results, T any length >= 1."""
    rows, length, key_heads, key_dim = q.shape
    heads, value_dim = v.shape[2:]
    group = heads // key_heads
    pad = -length % chunk
    if pad:
        # beta 0 writes nothing and g 0 decays nothing: the state after
        # the padding is the state before it
        q, k, v, g, beta = (
            jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
            for t in (q, k, v, g, beta))
    count = (length + pad) // chunk

    # the scan's axis first, a head's positions beside their values
    def keyed(t):       # [rows, T, Hk, Dk] -> [N, rows, Hk, C, Dk]
        return t.reshape(rows, count, chunk, key_heads, key_dim) \
            .transpose(1, 0, 3, 2, 4)

    def gated(t):       # [rows, T, H] -> [N, rows, Hk, R, C]
        return t.reshape(rows, count, chunk, key_heads, group) \
            .transpose(1, 0, 3, 4, 2)

    qc, kc = keyed(q), keyed(k)
    vc = v.reshape(rows, count, chunk, key_heads, group, value_dim) \
        .transpose(1, 0, 3, 4, 2, 5)             # [N, rows, Hk, R, C, Dv]
    cum = jnp.cumsum(gated(g), axis=-1)
    bc = gated(beta)
    lower = jnp.tril(jnp.ones((chunk, chunk), bool))
    # D_ij = exp(G_i - G_j) for i >= j: the exponent is masked, not the
    # exponential (a masked-out entry's would overflow)
    decay = jnp.exp(jnp.where(lower, cum[..., :, None] - cum[..., None, :],
                              -jnp.inf))
    kk = jnp.einsum("nbhid,nbhjd->nbhij", kc, kc, precision=_HIGHEST)
    qk = jnp.einsum("nbhid,nbhjd->nbhij", qc, kc, precision=_HIGHEST)
    a = jnp.where(jnp.tril(lower, -1),
                  bc[..., :, None] * kk[:, :, :, None] * decay, 0.0)
    from_start = jnp.exp(cum)                    # exp(G_i)
    rhs = jnp.concatenate(
        [bc[..., None] * vc,
         (bc * from_start)[..., None] * kc[:, :, :, None]], axis=-1)
    solved = jax.scipy.linalg.solve_triangular(
        a + jnp.eye(chunk, dtype=F32), rhs, lower=True, unit_diagonal=True)
    u, w = solved[..., :value_dim], solved[..., value_dim:]
    attend = qk[:, :, :, None] * decay           # lower((Q K^T) * D)
    q_dec = from_start[..., None] * qc[:, :, :, None]
    to_end = jnp.exp(cum[..., -1:] - cum)        # exp(G_C - G_i)
    k_end = to_end[..., None] * kc[:, :, :, None]
    whole = jnp.exp(cum[..., -1])                # exp(G_C)

    return _walk(state, u, w, attend, q_dec, k_end, whole, length)


def _walk(state, u, w, attend, q_dec, k_end, whole, length):
    """The chunks' states walked one after the other: `chunked`'s last
    three lines for u, w [N, rows, Hk, R, C, .], attend [N, rows, Hk, R,
    C, C], q_dec = Q exp(G) and k_end = K exp(G_C - G) [N, rows, Hk, R,
    C, Dk] and whole = exp(G_C) [N, rows, Hk, R] (a gate a head) or [N,
    rows, Hk, R, Dk] (a gate a key channel) -> (out [rows, length, H,
    Dv], the state after the block)."""
    count, rows, key_heads, group, chunk, value_dim = u.shape
    key_dim = q_dec.shape[-1]
    # one decay for a head's state, or one a row of it
    lift = (Ellipsis, None, None) if whole.ndim == 4 else (Ellipsis, None)

    def step(s, at):
        u_c, w_c, attend_c, q_c, k_c, whole_c = at
        v_new = u_c - jnp.einsum("bhrik,bhrkv->bhriv", w_c, s,
                                 precision=_HIGHEST)
        out = jnp.einsum("bhrik,bhrkv->bhriv", q_c, s, precision=_HIGHEST) \
            + jnp.einsum("bhrij,bhrjv->bhriv", attend_c, v_new,
                         precision=_HIGHEST)
        s = whole_c[lift] * s + jnp.einsum(
            "bhrik,bhriv->bhrkv", k_c, v_new, precision=_HIGHEST)
        return s, out

    s, out = jax.lax.scan(
        step, state.reshape(rows, key_heads, group, key_dim, value_dim),
        (u, w, attend, q_dec, k_end, whole))
    # [N, B, Hk, R, C, Dv] -> [B, N * C, H, Dv]
    out = out.transpose(1, 0, 4, 2, 3, 5).reshape(
        rows, count * chunk, key_heads * group, value_dim)
    return out[:, :length], s.reshape(state.shape)


def sub_chunk(chunk, gate_floor):
    """The positions of a sub-block of `chunked_channel` under a gate no
    position of which lies below `gate_floor` (< 0): the largest power
    of two, `chunk` at most, with (sub - 1) * |gate_floor| <= 80, so
    that the one growing factor, exp(G_ref - G_j) for a j inside its own
    sub-block, stays under e^80 where float32 ends at e^88.7 (a product
    with an l2-normed key's channel summed over a few hundred channels
    adds e^6 at most).  16 at Ling-3.0-flash's -5."""
    sub = 1
    while 2 * sub <= chunk and (2 * sub - 1) * abs(gate_floor) <= 80.0:
        sub *= 2
    return sub


def chunked_channel(q, k, v, g, beta, state, chunk, sub):
    """The block form under a gate a key channel (the module's
    docstring): `recurrent`'s operands with g [rows, T, heads, key_dim],
    T any length >= 1; `sub` positions a sub-block, a divisor of
    `chunk`."""
    rows, length, key_heads, key_dim = q.shape
    heads, value_dim = v.shape[2:]
    group = heads // key_heads
    if chunk % sub:
        raise ValueError("gated_delta_rule: a sub-block of %d positions "
                         "does not divide a chunk of %d" % (sub, chunk))
    pad = -length % chunk
    if pad:
        q, k, v, g, beta = (
            jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
            for t in (q, k, v, g, beta))
    count, blocks = (length + pad) // chunk, chunk // sub

    def keyed(t):       # [rows, T, Hk, Dk] -> [N, rows, Hk, 1, C, Dk]
        return t.reshape(rows, count, chunk, key_heads, 1, key_dim) \
            .transpose(1, 0, 3, 4, 2, 5)

    def headed(t, *last):   # [rows, T, H, .] -> [N, rows, Hk, R, C, .]
        return t.reshape(rows, count, chunk, key_heads, group, *last) \
            .transpose(1, 0, 3, 4, 2, *range(5, 5 + len(last)))

    qc, kc = keyed(q), keyed(k)
    vc, bc = headed(v, value_dim), headed(beta)
    cum = jnp.cumsum(headed(g, key_dim), axis=-2)   # [N, B, Hk, R, C, Dk]
    lead = cum.shape[:4]
    # G at each sub-block's first position, beside that sub-block's rows
    # and beside every position of the chunk
    ref = cum.reshape(lead + (blocks, sub, key_dim))[..., :1, :]
    left = jnp.exp(cum.reshape(lead + (blocks, sub, key_dim)) - ref)
    # the right factor of sub-block b at position j: exp(G_ref,b - G_j),
    # left out (0) for a j past the sub-block, which the mask drops
    upto = (jnp.arange(chunk)[None, :]
            < (jnp.arange(blocks)[:, None] + 1) * sub)[..., None]
    right = jnp.exp(jnp.where(upto, ref - cum[..., None, :, :], -jnp.inf)) \
        * kc[..., None, :, :]                       # [.., blocks, C, Dk]

    def decayed(t):
        """entry ij: sum_d t_i[d] k_j[d] exp(G_i[d] - G_j[d]), any i, j
        in sub-blocks b >= b' (others: whatever the factors give, finite;
        masked by the caller)."""
        rows_of = jnp.broadcast_to(t, cum.shape).reshape(
            lead + (blocks, sub, key_dim)) * left
        return jnp.einsum("nbhrsid,nbhrsjd->nbhrsij", rows_of, right,
                          precision=_HIGHEST).reshape(
                              lead + (chunk, chunk))

    lower = jnp.tril(jnp.ones((chunk, chunk), bool))
    a = jnp.where(jnp.tril(lower, -1), bc[..., None] * decayed(kc), 0.0)
    attend = jnp.where(lower, decayed(qc), 0.0)
    from_start = jnp.exp(cum)                       # exp(G_i), a channel
    rhs = jnp.concatenate(
        [bc[..., None] * vc, bc[..., None] * from_start * kc], axis=-1)
    solved = jax.scipy.linalg.solve_triangular(
        a + jnp.eye(chunk, dtype=F32), rhs, lower=True, unit_diagonal=True)
    u, w = solved[..., :value_dim], solved[..., value_dim:]
    q_dec = from_start * qc
    k_end = jnp.exp(cum[..., -1:, :] - cum) * kc    # K exp(G_C - G_i)
    whole = jnp.exp(cum[..., -1, :])                # exp(G_C)
    return _walk(state, u, w, attend, q_dec, k_end, whole, length)


@register_op("gated_delta_rule", stop_gradient_op=True,
             infer_shape=_infer_shape)
def gated_delta_rule(ctx, ins, attrs):
    """The module's docstring.  attrs: `qk_l2norm` (default true: l2
    norm q and k a head and scale q by key_dim ** -0.5), `chunk` (the
    block form's, default 64), `state_pack` (the value heads side by
    side in a unit of State, default 1) and, for a gate a key channel,
    `sub_chunk` (the positions of a sub-block of the block form: what
    `sub_chunk(chunk, gate_floor)` gives for the least g a caller
    promises, default 16, -5's)."""
    from ..kernels import gdn_step

    pack = int(attrs.get("state_pack", 1))
    q, k, v, g, beta, state = _heads(ins, pack)
    chunk = int(attrs.get("chunk", 64))
    rows, length, key_heads, key_dim = q.shape
    heads, value_dim = v.shape[2:]
    step, channel = length == 1, g.ndim == 4
    # the scopes a trace's readers know the two gates by
    scope = "kda_" if channel else "gdn_"
    kernel = step and gdn_step.choose_block(
        rows, heads, key_heads, key_dim, value_dim, state.dtype, pack,
        channel)
    telemetry.on_gated_delta_rule_lowering(
        "step" if step else "block", "kernel" if kernel else "plain",
        0 if step else chunk, heads, state.dtype,
        state[0].size * state.dtype.itemsize,
        "channel" if channel else "head", key_dim, value_dim)
    with jax.named_scope("gdn_gates"):
        if attrs.get("qk_l2norm", True):
            q, k = l2norm(q) * key_dim ** -0.5, l2norm(k)
        else:
            q, k = q.astype(F32), k.astype(F32)

    def apart(form):
        """`form` over the state as the recurrence has it, the result's
        heads side by side again as the state came."""
        def run(*operands):
            out, new = form(
                *operands[:-1],
                gdn_step.unpack_state(operands[-1], pack).astype(F32))
            return out, gdn_step.pack_state(new, pack)
        return run

    if step:
        @apart
        def one(q, k, v, g, beta, state):
            """`recurrent` on the operands of one position."""
            out, new = recurrent(q[:, None], k[:, None],
                                 v[:, None].astype(F32), g[:, None],
                                 beta[:, None], state)
            return out[:, 0], new

        with jax.named_scope(scope + "state"):
            operands = (q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0],
                        state)
            out, new = gdn_step.step(*operands, plain=one, block=kernel,
                                     pack=pack) \
                if kernel else one(*operands)
            out = out[:, None]
    else:
        with jax.named_scope(scope + "chunks"):
            out, new = apart(
                lambda *a: chunked_channel(
                    *a, chunk, min(int(attrs.get("sub_chunk", 16)), chunk))
                if channel else chunked(*a, chunk))(
                    q, k, v.astype(F32), g, beta, state)
    return {"Out": [out.reshape(rows, length, -1).astype(v.dtype)],
            "StateOut": [new.astype(state.dtype)]}


@register_grad_kernel("gated_delta_rule")
def gated_delta_rule_grad(ctx, ins, attrs):
    raise NotImplementedError(
        "gated_delta_rule is forward only: generation needs no gradient, "
        "and training the layer wants the block form's backward, which "
        "this op does not have")
