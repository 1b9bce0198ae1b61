"""A routed expert layer (mixture of experts) as two framework ops.

`moe_router` gives every token its probabilities over the experts, the
`top_k` it is sent to with their (not renormalised) probabilities, and
the two auxiliary losses of the layer (load balance, Fedus et al. 2021,
and the router z-loss, Zoph et al. 2022, as OLMoE, arXiv:2409.02060,
trains with).  All of it is float32 whatever the compute type: a
rounded logit sends a token to another expert.  Its gradient is the
generic one: jax.vjp reaches the weights through the chosen
probabilities and the two losses and never through the indices, which
are integers, and what it computes again is one [tokens, hidden] x
[hidden, experts] product.

`moe_experts` applies to every token all of its experts, with no
capacity and nothing dropped: the `tokens * top_k` assignments are
ordered by expert (a stable sort: static shapes, the group sizes an
[experts] array on the device), the tokens gathered into that order,
three grouped products (kernels/grouped_matmul.py) make
down(silu(gate(x)) * up(x)) for each row with its expert's weights, and
each token adds up its rows weighted by the router's probabilities.
Products take the compute type's operands (bfloat16 under AMP) and add
up in float32.

`moe_router` can also score as the DeepSeek-V3 family serves
(`scoring="sigmoid"`: each expert's sigmoid on its own; `norm_topk`
divides the chosen weights by their sum, `scale` multiplies them), with
no auxiliary loss: those two are the softmax router's.  And
`moe_experts` can hold a *range* of the experts the router scores, as
one chip of an expert-parallel deployment does: `WGate.shape[0]` experts
from `first_expert` on.  An assignment to an expert outside the range is
left out before the ordering (it sorts behind every held one and
belongs to no group, so no product visits it) and adds nothing: the
output is the held experts' part of the layer's, which the other chips'
parts would be added to.  A held expert that no token chose is not
visited either (the grouped row products walk the groups that have a
row), so a lightly loaded share reads the weights of the experts that
were chosen and no others.  Its gradient runs the six grouped products
over the held groups alone: an absent assignment's row gives the input
no gradient and its routing weight a gradient of 0 (its part of the
layer's output, and of every gradient, is another chip's), and the
weights' gradients are the held experts'.  The shares' outputs and
input gradients add up to the uncut layer's.

The stable order puts the held groups' rows first, so the first
sum(Counts) rows of every ordered array are the held ones, and an op
that holds an eighth of the experts scored needs an eighth of its rows.
Where a ranged op orders 32768 rows or more it therefore has a *compact
row path*: a bound B from the shapes alone (`_compact_rows`: twice the
rows an even router sends the range, on the grouped kernels' row tile,
and at most half of the rows: 24576 of 16384 x 6 for 8 of 64), and its
row work between the grouped products is a choice, `lax.cond(sum(Counts)
<= B, compact, all_rows)` (`_either_row_path`).  `all_rows` is the op as
it stands for every other shape; a batch whose routers send the range
more than B rows runs it, so nothing is ever dropped or approximated: B
decides which of two exact bodies runs.  The compact body works on the
first B rows: the activation and its derivative, dOut gathered for B
rows, and a token's sum of its held rows (`_held_token_sums`: Out
forward, X@GRAD backward) from B gathered rows in place of n * k.  Rows
from sum(Counts) to B hold whatever was there and are masked as the rows
past the held groups always were.  The forward is one choice (the
activation, the down product on `[B, width]`, the sums); the ordering,
the gather of Xs and the gate and up products stay outside it (the kept
outputs keep their n * k rows).  The backward is three (dOut's rows;
around the activation; the gate's and the up's input gradients and the
sums) with the down product's `gmm_dx` and the three `gmm_dw` between
them, once, over whole arrays: a grouped product reads and writes no row
past the held groups' whatever its operands' length (98304 rows for
24576 cost a row product 0.1 ms and a `gmm_dw` 0.5 on the v5e), the
compact body pads its operands with zeros (`_whole`), and a product
inside a choice is lowered twice, which costs set-up time (a fifth of a
second each in a four-layer program).  The bodies open the scopes
`moe_compact` and `moe_all_rows` inside the op's three.

The experts' gate is SiLU, or with `activation="relu"` ReLU (ReGLU
experts), forward and backward.

The gradient of `moe_experts` is explicit, for the reason
`flash_attention`'s is: jax.vjp of the op would run the forward's
three grouped products again.  The forward op keeps, as outputs of its
own that the gradient op reads:

    RowSlot, TokenRow [tokens * top_k] int32    the order and its inverse
    Counts             [experts] int32          rows an expert
    Xs   [tokens * top_k, hidden]               the ordered input
    Gate, Up [tokens * top_k, expert width]     the two pre-activations

in the compute type: at 4096 tokens, top-8, 2048 -> 1024 in bfloat16
128 + 64 + 64 MiB a layer.  (Recomputing Gate and Up from Xs instead
would save the 128 MiB and cost two of the nine products of a step;
gathering Xs again would save 128 MiB for one more pass over it.)  The
down product's result is not kept: a routing weight's gradient is
<dOut_token, y_row> = <dOut_token @ w_down^T, h_row>, which the backward
has on its way to dh.  Six grouped products, none of them a forward one.
"""

import contextlib
import math

import jax
import jax.numpy as jnp

from ..obs import telemetry
from .amp_util import amp_result, mxu_operands
from .registry import register_grad_kernel, register_op


def _set_meta(block, name, shape, dtype):
    desc = block.var_recursive(name).desc
    desc.shape, desc.dtype, desc.lod_level = tuple(shape), dtype, 0


def _tokens(shape):
    """The rows of X [..., hidden]; -1 where the Program leaves an axis
    open."""
    return -1 if min(shape[:-1]) < 0 else math.prod(shape[:-1])


def _rows_an_expert(top_idx, experts):
    """[experts] int32: how many of the assignments fell on each expert.
    By comparison: a scatter-add of 32768 single elements costs ten
    times as much on the TPU."""
    return jnp.sum(
        top_idx.reshape(-1, 1) == jnp.arange(experts, dtype=top_idx.dtype),
        axis=0, dtype=jnp.int32)


def _router_infer_shape(block, op_desc):
    x = block.var_recursive(op_desc.input("X")[0]).desc
    experts = block.var_recursive(op_desc.input("W")[0]).desc.shape[1]
    n, k = _tokens(x.shape), int(op_desc.attrs["top_k"])
    for slot, shape, dtype in (
            ("Logits", (n, experts), "float32"),
            ("TopW", (n, k), "float32"), ("TopIdx", (n, k), "int32"),
            ("LbLoss", (1,), "float32"), ("ZLoss", (1,), "float32")):
        _set_meta(block, op_desc.output(slot)[0], shape, dtype)


def _chosen(top_w, attrs):
    """The chosen experts' weights as the op's attrs ask for them; as
    they are where neither is set."""
    if attrs.get("norm_topk", False):
        top_w = top_w / (jnp.sum(top_w, axis=-1, keepdims=True) + 1e-20)
    scale = float(attrs.get("scale", 1.0))
    return top_w if scale == 1.0 else top_w * scale


def _kept_groups(choice, groups, kept, top_k):
    """`choice` [tokens, experts] with -inf on every expert outside the
    token's `kept` best of `groups` groups of consecutive experts; a
    group scores the sum of its two largest entries."""
    n, experts = choice.shape
    if experts % groups or not 0 < kept <= groups \
            or experts // groups < 2 or kept * (experts // groups) < top_k:
        raise ValueError(
            "moe_router: %d experts in %d groups of which %d are kept "
            "cannot give %d experts a token" % (experts, groups, kept,
                                                top_k))
    telemetry.on_moe_grouped_router_lowering(experts, groups, kept, top_k)
    with jax.named_scope("moe_groups"):
        grouped = choice.reshape(n, groups, experts // groups)
        group_score = jnp.sum(jax.lax.top_k(grouped, 2)[0], axis=-1)
        _, best = jax.lax.top_k(group_score, kept)
        keep = jnp.any(best[:, :, None] == jnp.arange(groups), axis=1)
        return jnp.where(keep[:, :, None], grouped,
                         -jnp.inf).reshape(n, experts)


@register_op("moe_router", infer_shape=_router_infer_shape)
def moe_router(ctx, ins, attrs):
    """X [..., hidden], W [hidden, experts] -> Logits [tokens, experts],
    TopW and TopIdx [tokens, top_k] (the largest probabilities, largest
    first, as they are: no renormalising), LbLoss = experts * sum_e f_e
    P_e (f_e the share of the assignments that fell on expert e, a
    constant to the gradient; P_e the mean probability of e) and ZLoss
    = mean(logsumexp(logits)^2), each [1].  float32 throughout, the
    product at the highest precision: the TPU's default would round its
    operands to bfloat16.

    With `scoring` "sigmoid" the scores are sigmoid(logits), TopW the
    largest of them, and both losses 0.  `norm_topk` divides TopW by its
    sum over the chosen (+ 1e-20) and `scale` multiplies it, under
    either scoring.

    Under sigmoid scoring the *choice* can be steered apart from the
    weights, as DeepSeek-V3 routes (`noaux_tc`): Bias [experts] is added
    to the scores for the choice alone (s' = s + b; TopW still reads s),
    and with `n_group` > 1 the experts are `n_group` groups of
    consecutive ones, a group scores the sum of its two largest s', and
    an expert outside the `topk_group` best groups cannot be chosen
    whatever its score (`moe_groups`)."""
    x, w = ins["X"][0], ins["W"][0]
    k = int(attrs["top_k"])
    experts = w.shape[1]
    scoring = attrs.get("scoring", "softmax")
    bias = (ins.get("Bias") or [None])[0]
    groups = int(attrs.get("n_group", 0))
    logits = jnp.dot(x.reshape(-1, x.shape[-1]).astype(jnp.float32),
                     w.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    if (bias is not None or groups > 1) and scoring != "sigmoid":
        raise ValueError("moe_router: a selection bias and groups steer "
                         "the sigmoid router's choice, scoring is %r"
                         % scoring)
    if scoring == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        if bias is None and groups <= 1:
            top_w, top_idx = jax.lax.top_k(scores, k)
        else:
            choice = scores if bias is None \
                else scores + bias.astype(jnp.float32).reshape(1, experts)
            if groups > 1:
                choice = _kept_groups(choice, groups,
                                      int(attrs["topk_group"]), k)
            _, top_idx = jax.lax.top_k(choice, k)
            top_w = jnp.take_along_axis(scores, top_idx, axis=1)
        zero = jnp.zeros((1,), jnp.float32)
        return {"Logits": [logits], "TopW": [_chosen(top_w, attrs)],
                "TopIdx": [top_idx.astype(jnp.int32)],
                "LbLoss": [zero], "ZLoss": [zero]}
    if scoring != "softmax":
        raise ValueError("moe_router: scoring is softmax or sigmoid, got "
                         "%r" % scoring)
    lse = jax.nn.logsumexp(logits, axis=-1, keepdims=True)
    probs = jnp.exp(logits - lse)
    top_w, top_idx = jax.lax.top_k(probs, k)
    share = _rows_an_expert(top_idx, experts).astype(jnp.float32) \
        / top_idx.size
    lb = experts * jnp.sum(share * jnp.mean(probs, axis=0))
    z = jnp.mean(jnp.square(lse))
    return {"Logits": [logits], "TopW": [_chosen(top_w, attrs)],
            "TopIdx": [top_idx.astype(jnp.int32)],
            "LbLoss": [lb.reshape(1)], "ZLoss": [z.reshape(1)]}


def _experts_infer_shape(block, op_desc):
    x = block.var_recursive(op_desc.input("X")[0]).desc
    experts, hidden, width = block.var_recursive(
        op_desc.input("WGate")[0]).desc.shape
    rows = _tokens(x.shape) * block.var_recursive(
        op_desc.input("TopIdx")[0]).desc.shape[1]
    for slot, shape, dtype in (
            ("Out", x.shape, x.dtype), ("Xs", (rows, hidden), x.dtype),
            ("Gate", (rows, width), x.dtype), ("Up", (rows, width), x.dtype),
            ("RowSlot", (rows,), "int32"), ("TokenRow", (rows,), "int32"),
            ("Counts", (experts,), "int32")):
        _set_meta(block, op_desc.output(slot)[0], shape, dtype)


def _held_range(attrs, experts):
    """(first_expert, scored) of an expert op that holds `experts`."""
    return (int(attrs.get("first_expert", 0)),
            int(attrs.get("scored", 0)) or experts)


def _token_rows(rows, token_row, n, k):
    """[n, k, width]: each token's k rows of `rows`, in its own order."""
    return rows[token_row].reshape(n, k, rows.shape[-1])


def _by_key(keys, values):
    """`values` in the order that sorts `keys`, a permutation: with
    `keys` the inverse of a permutation p this is values[p], by a sort
    (19 us for 32768 on the v5e) in place of a gather of single
    elements (230-280 us)."""
    return jax.lax.sort((keys, values), num_keys=1)[1]


# The compact row path of the range form (the module's docstring): only
# where an op orders at least so many rows, and over a bound of so many
# times the rows an even router sends the held range, on the grouped
# kernels' row tile.
_COMPACT_MIN_ROWS = 32768
_COMPACT_FACTOR = 2
_ROW_TILE = 256


def _compact_rows(n, k, held, scored):
    """The rows the compact path of an op works on that orders `n * k`
    assignments and holds `held` of the `scored` experts, from the
    shapes alone; 0 where the op has no compact path: it holds every
    expert scored, orders too few rows for a second body to pay, or the
    bound would pass half of them."""
    rows = n * k
    if held >= scored or rows < _COMPACT_MIN_ROWS:
        return 0
    bound = -(-_COMPACT_FACTOR * rows * held // scored)
    bound = -(-bound // _ROW_TILE) * _ROW_TILE
    return bound if bound <= rows // 2 else 0


@contextlib.contextmanager
def _scopes(phase, branch):
    """The op's scope `phase` and, inside it, that of the row path
    (`branch`) where the op has two."""
    with jax.named_scope(phase):
        if branch is None:
            yield
        else:
            with jax.named_scope(branch):
                yield


def _first(ordered, some):
    """The first `some` rows of an array of the order."""
    return ordered if some == ordered.shape[0] else ordered[:some]


def _whole(first, rows):
    """`first` with rows of 0 behind it, `rows` in all: what a grouped
    product takes, which reads no row past the held groups'."""
    short = rows - first.shape[0]
    if not short:
        return first
    return jnp.pad(first, ((0, short),) + ((0, 0),) * (first.ndim - 1))


def _either_row_path(work, counts, rows, bound):
    """`work(rows, None)` over all `rows` of the order; or, where the op
    has a compact path (`bound` > 0), whichever the held rows' count
    picks of `work(bound, "moe_compact")` and `work(rows,
    "moe_all_rows")`, which return the same shapes."""
    if not bound:
        return work(rows, None)
    return jax.lax.cond(jnp.sum(counts) <= bound,
                        lambda: work(bound, "moe_compact"),
                        lambda: work(rows, "moe_all_rows"))


def _held_token_sums(rows, token_row, counts, n, k, weights=None):
    """[n, width] float32: each token's sum of its held assignments'
    rows, from the first `bound` rows of the order alone (`rows`
    [bound, width]; sum(counts) <= bound of them are held), each times
    its entry of `weights` [n * k] (by slot) where given.  The held
    slots in slot order are in token order, so one sort of the n * k
    slots (held first) puts a token's rows side by side: `bound` rows
    are gathered, each adds the up to k - 1 after it that share its
    token, and a token reads the sum at its first row, which lies as
    far in as the tokens before it have held slots.  (A gather costs by
    the rows it fetches, 42 us a thousand of 2560 on the v5e whatever
    their order: each token fetching its k rows would fetch n * k.)"""
    f32 = jnp.float32
    bound = rows.shape[0]
    slots = jnp.arange(n * k, dtype=jnp.int32)
    held = token_row < jnp.sum(counts)
    ordered = jax.lax.sort(
        (jnp.where(held, slots, n * k), token_row)
        + (() if weights is None else (weights,)), num_keys=1)
    # k - 1 rows past the bound, the last rows' neighbours: the shifted
    # reads are then slices of one array, in the rows' own type, which
    # one pass reads
    slot, row = (a[:bound + k - 1] for a in ordered[:2])
    live = slot < n * k
    picked = rows[jnp.where(live, row, 0)]
    token = jnp.where(live, slot // k, -1)
    summed = 0.0
    for j in range(k):
        mine = picked[j:bound + j].astype(f32)
        if weights is not None:
            mine = mine * ordered[2][j:bound + j, None]
        same = live[j:bound + j] & (token[j:bound + j] == token[:bound])
        summed = summed + jnp.where(same[:, None], mine, 0.0)
    mine_per_token = jnp.sum(held.reshape(n, k), axis=1, dtype=jnp.int32)
    first = jnp.cumsum(mine_per_token) - mine_per_token
    return jnp.where((mine_per_token > 0)[:, None],
                     summed[jnp.minimum(first, bound - 1)], 0.0)


def _gate(g, attrs):
    """(act(g), act'(g)) of the float32 pre-activation `g` under the
    op's `activation`: "silu" (the default, which an op need not
    carry) or "relu"."""
    activation = attrs.get("activation", "silu")
    if activation == "silu":
        sig = jax.nn.sigmoid(g)
        act = g * sig
        return act, sig + act * (1.0 - sig)
    if activation == "relu":
        on = g > 0
        return jnp.where(on, g, 0.0), on.astype(g.dtype)
    raise ValueError("moe_experts: activation is silu or relu, got %r"
                     % activation)


@register_op("moe_experts", nondiff_inputs=("TopIdx",),
             infer_shape=_experts_infer_shape)
def moe_experts(ctx, ins, attrs):
    """X [..., hidden], TopW / TopIdx [tokens, top_k], WGate and WUp
    [experts, hidden, width], WDown [experts, width, hidden] -> Out, X's
    shape: sum_j TopW[n, j] * down_e(act(gate_e(x_n)) * up_e(x_n)) with
    e = TopIdx[n, j] and act the op's `activation` (SiLU; "relu": ReLU);
    and what the gradient reads (the module's docstring).  Where the router scores `scored` experts and the op
    holds fewer (`experts` of them from `first_expert` on), the sum is
    over the held e alone."""
    from ..kernels.grouped_matmul import gmm

    x, top_w, top_idx = ins["X"][0], ins["TopW"][0], ins["TopIdx"][0]
    w_gate, w_up, w_down = (ins[s][0] for s in ("WGate", "WUp", "WDown"))
    n, k = top_idx.shape
    experts = w_gate.shape[0]
    telemetry.on_moe_lowering(experts, k)
    first, scored = _held_range(attrs, experts)
    ranged = (first, scored) != (0, experts)

    with jax.named_scope("moe_route"):
        flat = top_idx.reshape(-1).astype(jnp.int32)
        if ranged:
            telemetry.on_moe_share_lowering(scored, experts, k)
            with jax.named_scope("moe_hold"):
                # an absent expert's assignments sort behind every held
                # one's and are counted for no group
                flat = flat - first
                held = (flat >= 0) & (flat < experts)
                flat = jnp.where(held, flat, experts)
        slots = jnp.arange(n * k, dtype=jnp.int32)
        # row r of the order holds slot row_slot[r] = token * k + j, and
        # slot s lies in row token_row[s]
        _, row_slot = jax.lax.sort((flat, slots), num_keys=1, is_stable=True)
        token_row = _by_key(row_slot, slots)
        counts = _rows_an_expert(flat, experts)
        x2 = x.reshape(n, x.shape[-1])
        (xs,) = mxu_operands(x2[row_slot // k])
    with jax.named_scope("moe_experts"):
        wg, wu, wd = mxu_operands(w_gate, w_up, w_down)
        gate = gmm(xs, wg, counts)
        up = gmm(xs, wu, counts)

    rows, bound = n * k, _compact_rows(n, k, experts, scored)
    if bound:
        telemetry.on_moe_share_compact_lowering(rows, bound)

    def token_sums(some, branch):
        """Out in float32 from the first `some` rows of the order."""
        with _scopes("moe_experts", branch):
            h = (_gate(_first(gate, some).astype(jnp.float32), attrs)[0]
                 * _first(up, some).astype(jnp.float32)).astype(xs.dtype)
            y = gmm(h, wd, counts)
        with _scopes("moe_combine", branch):
            if some < rows:
                return _held_token_sums(
                    y, token_row, counts, n, k,
                    top_w.astype(jnp.float32).reshape(-1))
            mine = _token_rows(y, token_row, n, k).astype(jnp.float32)
            if ranged:
                # no product wrote an absent assignment's row
                mine = jnp.where(held.reshape(n, k, 1), mine, 0.0)
            return jnp.sum(mine * top_w.astype(jnp.float32)[..., None],
                           axis=1)

    out = _either_row_path(token_sums, counts, rows, bound)
    out = amp_result(out, x.dtype).reshape(x.shape)
    return {"Out": [out], "Xs": [xs], "Gate": [gate], "Up": [up],
            "RowSlot": [row_slot], "TokenRow": [token_row],
            "Counts": [counts]}


@register_grad_kernel("moe_experts")
def moe_experts_grad(ctx, ins, attrs):
    """X@GRAD, TopW@GRAD and the three weights' gradients from what the
    forward op kept: six grouped products (two `gmm_dx` over the
    gate/up pair, one over down, three `gmm_dw`), the weights'
    gradients added up and returned in float32.  Where the op holds a
    range of the experts scored, the rows past the held groups' belong
    to absent experts: no product wrote them, forward or here, so what
    they hold is set to 0 wherever it would reach a sum."""
    from ..kernels.grouped_matmul import gmm_dw, gmm_dx

    x, top_w = ins["X"][0], ins["TopW"][0]
    w_gate, w_up, w_down = (ins[s][0] for s in ("WGate", "WUp", "WDown"))
    experts = w_gate.shape[0]
    first, scored = _held_range(attrs, experts)
    ranged = (first, scored) != (0, experts)
    xs, gate, up, row_slot, token_row, counts = (
        ins["O@" + s][0] for s in ("Xs", "Gate", "Up", "RowSlot",
                                   "TokenRow", "Counts"))
    n, k = top_w.shape
    d_out = ins["OG@Out"][0].reshape(n, x.shape[-1])
    f32 = jnp.float32

    rows, bound = n * k, _compact_rows(n, k, experts, scored)
    present = None
    if ranged:
        telemetry.on_moe_share_bwd_lowering(scored, experts, k)
        with jax.named_scope("moe_route"), jax.named_scope("moe_hold"):
            # the order puts the held groups' rows first
            present = (jnp.arange(rows, dtype=jnp.int32)
                       < jnp.sum(counts))[:, None]

    def held(first):
        """The first rows of an ordered array with 0 in those of absent
        experts."""
        if present is None:
            return first
        return jnp.where(_first(present, first.shape[0]), first,
                         jnp.zeros((), first.dtype))

    def upstream(some, branch):
        """[n * k, hidden]: dOut of the first `some` rows' tokens."""
        with _scopes("moe_combine", branch):
            return _whole(
                d_out.astype(xs.dtype)[_first(row_slot, some) // k], rows)

    def around_activation(some, branch):
        """From the first `some` rows of Gate, Up and `dx_down()`: the
        rows' part of the routing weights' gradient [n * k], and the
        gate's, the up's and the down product's row operands [n * k,
        width] of the five products that follow."""
        with _scopes("moe_experts", branch):
            g, u = (held(_first(a, some)).astype(f32) for a in (gate, up))
            act, d_act = _gate(g, attrs)
            h = act * u
            # d<y_row, dOut> / dh, before the routing weight
            dh_raw = held(_first(dx_down(), some)).astype(f32)
            w_some = _first(w_rows, some)
            d_w_rows = jnp.sum(dh_raw * h, axis=-1)
            dh = dh_raw * w_some[:, None]
            d_gate = (dh * u * d_act).astype(xs.dtype)
            d_up = (dh * act).astype(xs.dtype)
            hw = (h * w_some[:, None]).astype(xs.dtype)
            return tuple(_whole(a, rows) for a in (d_w_rows, d_gate, d_up, hw))

    def token_grads(some, branch):
        """X@GRAD in float32 from the first `some` rows of the gate's
        and the up's gradients."""
        with _scopes("moe_experts", branch):
            d_xs = held(
                (gmm_dx(_first(d_gate, some), wg, counts).astype(f32)
                 + gmm_dx(_first(d_up, some), wu, counts).astype(f32))
                .astype(xs.dtype))
        with _scopes("moe_route", branch):
            if some < rows:
                return _held_token_sums(d_xs, token_row, counts, n, k)
            return jnp.sum(_token_rows(d_xs, token_row, n, k).astype(f32),
                           axis=1)

    d_rows = _either_row_path(upstream, counts, rows, bound)
    with jax.named_scope("moe_combine"):
        # every row's routing weight
        w_rows = _by_key(token_row, top_w.astype(f32).reshape(-1))
    with jax.named_scope("moe_experts"):
        wg, wu, wd = mxu_operands(w_gate, w_up, w_down)
        # between two choices of row path the product stands alone
        dh_whole = gmm_dx(d_rows, wd, counts) if bound else None

    def dx_down():
        """[n * k, width]: the down product's gradient to its rows;
        with one row path where it always stood, behind the
        activation."""
        if dh_whole is None:
            return gmm_dx(d_rows, wd, counts)
        return dh_whole

    d_w_rows, d_gate, d_up, hw = _either_row_path(
        around_activation, counts, rows, bound)
    with jax.named_scope("moe_experts"):
        d_w_down = gmm_dw(hw, d_rows, counts)
        d_w_gate = gmm_dw(xs, d_gate, counts)
        d_w_up = gmm_dw(xs, d_up, counts)
    d_x = _either_row_path(token_grads, counts, rows, bound)
    with jax.named_scope("moe_route"):
        d_top_w = _by_key(row_slot, d_w_rows).reshape(n, k)
    return {"X@GRAD": [d_x.astype(d_out.dtype).reshape(x.shape)],
            "TopW@GRAD": [d_top_w.astype(top_w.dtype)],
            "WGate@GRAD": [d_w_gate.astype(w_gate.dtype)],
            "WUp@GRAD": [d_w_up.astype(w_up.dtype)],
            "WDown@GRAD": [d_w_down.astype(w_down.dtype)]}
