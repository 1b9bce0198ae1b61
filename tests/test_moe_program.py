"""The routed expert layer (ops/moe.py, kernels/grouped_matmul.py,
`fluid.layers.moe`) and the OLMoE decoder built on it
(models/moe_program.py) against the plain float32 reference
(models/reference/olmoe.py): logits, router logits, the experts chosen,
both auxiliary losses, the loss and every parameter's gradient; a routing
so skewed that experts get everything or nothing; the grouped kernels
under the Pallas interpreter against `jax.lax.ragged_dot`; the
initializer's fans for a stack of experts; what stays float32 under
bfloat16 compute; and the counters.

Tiny sizes on the CPU: 2 layers, hidden 64, 4 heads of 16, 8 experts of
32, 2 a token, vocabulary 97, 32 tokens, seeded random weights (norm
scales moved off their initial 1, so that a scale left out shows).
"""

import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid.param_attr import ParamAttr
from paddle_tpu.kernels import grouped_matmul
from paddle_tpu.models.moe_program import (build_olmoe_program,
                                           olmoe_param_names)
from paddle_tpu.models.reference import olmoe as reference
from paddle_tpu.models.transformer_program import transformer_program_feeds
from paddle_tpu.obs import telemetry
from paddle_tpu.ops import registry

B, T, V, L, H, D, F, E, K = 1, 32, 97, 2, 4, 64, 32, 8, 2
CFG = {"num_attention_heads": H, "rope_theta": 10000, "rms_norm_eps": 1e-5,
       "num_experts": E, "num_experts_per_tok": K, "aux_coef": 0.01,
       "z_coef": 0.001}
NAMES = olmoe_param_names(L)
PARAMS = jax.tree_util.tree_leaves(NAMES)

# float32 on the CPU.  The program's attention is the flash kernel under
# the interpreter (online softmax, another summation order than the dense
# reference's), its experts are grouped products over gathered rows where
# the reference's are dense products masked afterwards: logits of size ~1
# were seen to differ by 2e-6, router logits by 2e-6.  1e-5 is five times
# that and two hundred times under one bfloat16 rounding (2^-9) of a logit.
FORWARD_ATOL = 1e-5
# the losses are means over 32 tokens of such values: seen 1e-7 of them
LOSS_RTOL = 2e-6
# gradients, as a share of each parameter's largest entry: seen 2e-6; an
# assignment dropped, or the router's gradient through one of its three
# paths (weights, load balance, z) left out, is off by a tenth or more
GRAD_RTOL = 2e-5


def _build():
    return build_olmoe_program(B, T, V, n_layer=L, n_head=H, d_model=D,
                               d_expert=F, n_experts=E, top_k=K)


def _start(startup, seed=3):
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    startup.random_seed = seed
    exe.run(startup, scope=scope)
    rs = np.random.RandomState(seed)
    for name in PARAMS:
        value = np.asarray(scope.get(name))
        if value.ndim == 1:
            scope.set(name, jnp.asarray(
                value + 0.1 * rs.randn(*value.shape).astype("float32")))
    return exe, scope


@pytest.fixture(scope="module")
def trained_once():
    """The program run once in float32 beside the reference on the same
    weights and batch."""
    before = telemetry.snapshot()
    main, startup, loss, parts = _build()
    built = telemetry.snapshot_delta(before)
    with fluid.program_guard(main, startup):
        grads = dict((p.name, g) for p, g in
                     fluid.backward.append_backward(loss))
    exe, scope = _start(startup)
    feeds = transformer_program_feeds(B, T, V, seed=1)
    scalars = [loss, parts["ce"], parts["lb"], parts["z"]]
    fetch = scalars + [parts["logits"]] + parts["router_logits"] \
        + parts["top_idx"] + parts["top_w"] + parts["counts"] \
        + [grads[n] for n in PARAMS]
    out = exe.run(main, feed=feeds, fetch_list=fetch, scope=scope)
    lowered = telemetry.snapshot_delta(before)
    params = jax.tree_util.tree_map(scope.get, NAMES)
    jfeeds = {k: jnp.asarray(v) for k, v in feeds.items()}
    want = reference.loss_terms(CFG, params, jfeeds)
    want_grads = jax.grad(lambda p: reference.loss(CFG, p, jfeeds))(params)
    per_layer = [out[5 + i * L:5 + (i + 1) * L] for i in range(4)]
    return {
        "main": main, "built": built, "lowered": lowered,
        "scalars": dict(zip(("loss", "ce", "lb", "z"),
                            (float(v.reshape(-1)[0]) for v in out[:4]))),
        "logits": out[4], "router_logits": per_layer[0],
        "top_idx": per_layer[1], "top_w": per_layer[2],
        "counts": per_layer[3],
        "grads": dict(zip(PARAMS, out[5 + 4 * L:])),
        "want": want,
        "want_grads": dict(zip(PARAMS,
                               jax.tree_util.tree_leaves(want_grads))),
    }


# -- the program against the reference ---------------------------------------

@pytest.mark.parametrize("term", ["loss", "ce", "lb", "z"])
def test_losses_agree_with_the_reference(trained_once, term):
    want = float(trained_once["want"][term])
    assert trained_once["scalars"][term] == pytest.approx(want,
                                                          rel=LOSS_RTOL)


def test_the_loss_is_the_sum_of_its_three_terms(trained_once):
    s = trained_once["scalars"]
    assert s["loss"] == pytest.approx(
        s["ce"] + CFG["aux_coef"] * s["lb"] + CFG["z_coef"] * s["z"],
        rel=1e-6)
    # both auxiliary losses are there to be seen: L_lb is 1 when balanced
    # and above it otherwise, L_z the squared log-partition
    assert s["lb"] > L and s["z"] > 1.0


def test_logits_agree_with_the_reference(trained_once):
    np.testing.assert_allclose(
        trained_once["logits"], np.asarray(trained_once["want"]["logits"]),
        atol=FORWARD_ATOL, rtol=0)


@pytest.mark.parametrize("layer", range(L))
def test_router_agrees_with_the_reference(trained_once, layer):
    want = trained_once["want"]
    np.testing.assert_allclose(
        trained_once["router_logits"][layer],
        np.asarray(want["router_logits"][layer]), atol=FORWARD_ATOL, rtol=0)
    idx = trained_once["top_idx"][layer]
    assert idx.dtype == np.int32 and idx.shape == (B * T, K)
    # the same experts in the same order, largest probability first
    np.testing.assert_array_equal(idx, np.asarray(want["indices"][layer]))
    probs = np.asarray(jax.nn.softmax(want["router_logits"][layer], axis=-1))
    top_w = trained_once["top_w"][layer]
    np.testing.assert_allclose(
        top_w, np.take_along_axis(probs, idx, axis=1), atol=1e-6)
    # not renormalised: a token's weights are its probabilities as they are
    assert (top_w.sum(axis=1) < 0.999).all()


@pytest.mark.parametrize("layer", range(L))
def test_every_assignment_is_computed(trained_once, layer):
    counts = trained_once["counts"][layer]
    assert counts.sum() == B * T * K
    np.testing.assert_array_equal(
        counts, np.bincount(trained_once["top_idx"][layer].reshape(-1),
                            minlength=E))


@pytest.mark.parametrize("name", PARAMS)
def test_gradients_agree_with_the_reference(trained_once, name):
    got, want = trained_once["grads"][name], trained_once["want_grads"][name]
    want = np.asarray(want)
    assert got.shape == want.shape
    assert np.abs(want).max() > 0
    assert np.abs(got - want).max() <= GRAD_RTOL * np.abs(want).max()


def test_the_reference_takes_indices_that_are_handed_to_it(trained_once):
    """`logits_and_router(..., indices)` routes as it is told: with its
    own choice handed back nothing changes; with every token's second
    expert replaced the logits do."""
    main, startup, _, _ = _build()
    _, scope = _start(startup)
    params = jax.tree_util.tree_map(scope.get, NAMES)
    tokens = jnp.asarray(transformer_program_feeds(B, T, V, seed=1)["tokens"])
    logits, router, own = reference.logits_and_router(CFG, params, tokens)
    again, _, used = reference.logits_and_router(CFG, params, tokens, own)
    np.testing.assert_allclose(again, logits, atol=1e-6)
    other = [jnp.stack([i[:, 0], (i[:, 0] + 1) % E], axis=1) for i in own]
    moved, _, used = reference.logits_and_router(CFG, params, tokens, other)
    np.testing.assert_array_equal(used[0], other[0])
    assert np.abs(np.asarray(moved - logits)).max() > 1e-3
    assert len(router) == L and router[0].shape == (B * T, E)


# -- the expert op alone, on routings made by hand ----------------------------

def _dense_experts(x, top_w, top_idx, w_gate, w_up, w_down):
    out = jnp.zeros_like(x)
    for e in range(w_gate.shape[0]):
        weight = jnp.sum(jnp.where(top_idx == e, top_w, 0.0), axis=1)
        hidden = jax.nn.silu(x @ w_gate[e]) * (x @ w_up[e])
        out = out + weight[:, None] * (hidden @ w_down[e])
    return out


def _routing(kind, n, rs):
    if kind == "one expert gets every token, three get none":
        # expert 2 is every token's first; the second is one of 4 others
        second = rs.choice([0, 1, 5, 7], size=n)
        return np.stack([np.full(n, 2), second], axis=1)
    if kind == "all on one expert":
        return np.full((n, 1), 6)
    if kind == "one token an expert":
        return np.arange(n).reshape(n, 1) % E
    return np.stack([rs.permutation(E)[:K] for _ in range(n)])


@pytest.mark.parametrize("kind", [
    "one expert gets every token, three get none", "all on one expert",
    "one token an expert", "uniform"])
def test_expert_op_and_its_gradient_on_a_routing_made_by_hand(kind):
    rs = np.random.RandomState(len(kind))
    n = 8 if kind == "one token an expert" else 48
    top_idx = jnp.asarray(_routing(kind, n, rs), jnp.int32)
    k = top_idx.shape[1]
    x = jnp.asarray(rs.randn(n, D), jnp.float32)
    top_w = jnp.asarray(rs.uniform(0.1, 0.5, (n, k)), jnp.float32)
    w_gate, w_up = (jnp.asarray(rs.randn(E, D, F) * 0.2, jnp.float32)
                    for _ in range(2))
    w_down = jnp.asarray(rs.randn(E, F, D) * 0.2, jnp.float32)
    d_out = jnp.asarray(rs.randn(n, D), jnp.float32)
    info = registry.get_op_info("moe_experts")
    ins = {"X": [x], "TopW": [top_w], "TopIdx": [top_idx],
           "WGate": [w_gate], "WUp": [w_up], "WDown": [w_down]}
    outs = info.kernel(None, ins, {})
    counts = np.asarray(outs["Counts"][0])
    assert counts.sum() == n * k
    if kind.startswith("one expert gets"):
        assert counts[2] == n and (counts[[3, 4, 6]] == 0).all()
    want = _dense_experts(x, top_w, top_idx, w_gate, w_up, w_down)
    np.testing.assert_allclose(outs["Out"][0], want, atol=2e-5)

    grad_ins = dict(ins, **{"OG@Out": [d_out]})
    grad_ins.update({"O@" + slot: v for slot, v in outs.items()})
    got = info.grad_kernel(None, grad_ins, {})
    want_grads = jax.grad(
        lambda *a: jnp.sum(_dense_experts(a[0], a[1], top_idx, *a[2:])
                           * d_out), argnums=(0, 1, 2, 3, 4))(
        x, top_w, w_gate, w_up, w_down)
    for slot, w in zip(("X", "TopW", "WGate", "WUp", "WDown"), want_grads):
        g = np.asarray(got[slot + "@GRAD"][0])
        assert np.abs(g - w).max() <= 1e-5 * max(np.abs(w).max(), 1.0), slot
    if kind.startswith("one expert gets"):
        # an expert without a row gets a gradient of zeros, not garbage
        assert not np.asarray(got["WGate@GRAD"][0])[[3, 4, 6]].any()


def test_the_explicit_gradient_runs_no_forward_product():
    """Traced as one program, forward op and gradient hold the three
    forward products once and the backward's six.  (The generic
    gradient, jax.vjp of the op, would hold the forward's again, and
    cannot be taken at all: a kernel with scalar prefetch has no JVP.)"""
    info = registry.get_op_info("moe_experts")
    n = 16
    shapes = {"X": (n, D), "TopW": (n, K), "WGate": (E, D, F),
              "WUp": (E, D, F), "WDown": (E, F, D)}
    ins = {s: [jax.ShapeDtypeStruct(shape, jnp.float32)]
           for s, shape in shapes.items()}
    ins["TopIdx"] = [jax.ShapeDtypeStruct((n, K), jnp.int32)]

    def count(gradient):
        def step(ins, d_out):
            outs = info.kernel(None, ins, {})
            grad_ins = dict(ins, **{"OG@Out": [d_out]})
            grad_ins.update({"O@" + s: v for s, v in outs.items()})
            return gradient(grad_ins)
        before = telemetry.snapshot()
        jax.eval_shape(step, ins, ins["X"][0])
        delta = telemetry.snapshot_delta(before)
        return {kernel: sum(v for key, v in delta.items() if key.startswith(
            "moe_gmm_lowerings_total") and "kernel=%s" % kernel in key)
            for kernel in ("fwd", "dx", "dw")}

    assert count(lambda g: info.grad_kernel(None, g, {})) == \
        {"fwd": 3, "dx": 3, "dw": 3}
    with pytest.raises(NotImplementedError):
        count(lambda g: registry.run_generic_grad(None, "moe_experts", g,
                                                  {}))


# -- the grouped kernels under the interpreter --------------------------------

M, GK, GN, GE, TILE = 512, 256, 128, 6, 128
GROUPS = {
    "sizes 0, 1 and not a multiple of the tile": [0, 1, 130, 381, 0, 0],
    "the whole of the rows in one group": [0, 0, M, 0, 0, 0],
    "the whole in the first, the last empty": [M, 0, 0, 0, 0, 0],
    "tile-aligned groups": [128, 128, 0, 256, 0, 0],
    "every group a few rows short of the tile": [100, 100, 100, 100, 100,
                                                 12],
    # the range form of `moe_experts`: the sizes sum below the rows, and
    # the rows behind the last group's are no group's
    "rows of no group behind the groups": [0, 70, 0, 200, 3, 0],
    # dsv32-turn-16k-ep16's regime: 16 held experts, one tile of 128 rows,
    # 8 assignments on 6 of them
    "16 groups, one tile, 8 rows on 6 groups": [0, 2, 0, 0, 1, 0, 0, 1, 0,
                                                2, 0, 0, 1, 0, 0, 1],
    "every group empty": [0] * 16,
    "only the last group has rows": [0, 0, 0, 0, 0, 5],
    "only the first group has rows": [130, 0, 0, 0, 0, 0],
}
ROWS = {"16 groups, one tile, 8 rows on 6 groups": 128,
        "every group empty": 128}


def _operands(kernel, rs, m=M, e=GE):
    x = jnp.asarray(rs.randn(m, GK), jnp.float32)
    w = jnp.asarray(rs.randn(e, GK, GN), jnp.float32)
    dy = jnp.asarray(rs.randn(m, GN), jnp.float32)
    return {"fwd": (x, w), "dx": (dy, w), "dw": (x, dy)}[kernel]


@pytest.mark.parametrize("groups", list(GROUPS))
@pytest.mark.parametrize("kernel", ["fwd", "dx", "dw"])
def test_grouped_kernel_against_ragged_dot(kernel, groups):
    """The Mosaic kernels' bodies under the Pallas interpreter (which
    fills what nothing wrote with NaN and raises on a block out of
    range), at tiles smaller than the groups and larger, against XLA's
    ragged product and against a loop over the groups.  Rows of no group
    are not written by a row product and not compared."""
    from jax.experimental.pallas import tpu as pltpu

    sizes = GROUPS[groups]
    m = ROWS.get(groups, M)
    counts = jnp.asarray(sizes, jnp.int32)
    a, b = _operands(kernel, np.random.RandomState(7), m, len(sizes))
    plain = {"fwd": grouped_matmul.ragged_gmm,
             "dx": grouped_matmul.ragged_gmm_dx,
             "dw": grouped_matmul.ragged_gmm_dw}[kernel](a, b, counts)
    ends = np.cumsum(sizes)
    rows = [slice(e - c, e) for c, e in zip(sizes, ends)]
    owned = slice(None) if kernel == "dw" else slice(0, ends[-1])
    if kernel == "dw":
        loop = np.stack([np.asarray(a)[r].T @ np.asarray(b)[r]
                         for r in rows])
    else:
        loop = np.zeros(plain.shape, np.float32)
        for g, r in enumerate(rows):
            w = np.asarray(b)[g]
            loop[r] = np.asarray(a)[r] @ (w if kernel == "fwd" else w.T)
    np.testing.assert_allclose(plain[owned], loop[owned], atol=2e-4)
    blocks = {"fwd": (TILE, GN, GK), "dx": (TILE, GN, 128),
              "dw": (TILE, GN, 128)}[kernel]
    with pltpu.force_tpu_interpret_mode():
        if kernel == "dw":
            got = grouped_matmul._dw_call(blocks, a, b, counts)
        else:
            got = grouped_matmul._rows_call(kernel, blocks, a, b, counts)
    assert got.shape == plain.shape and got.dtype == plain.dtype
    np.testing.assert_allclose(got[owned], loop[owned], atol=2e-4)


def _visits(sizes, m, empty_groups):
    return [np.asarray(v) for v in grouped_matmul.visits(
        jnp.asarray(sizes), m, TILE, empty_groups)]


def _times_seen(group, tile, offsets, m):
    """[m]: how many of the visits own each row."""
    seen = np.zeros(m, int)
    for g, t in zip(group, tile):
        seen[max(offsets[g], t * TILE):min(offsets[g + 1],
                                           (t + 1) * TILE)] += 1
    return seen


@pytest.mark.parametrize("seed", range(5))    # 1, 3 and 4 draw empty groups
@pytest.mark.parametrize("product", ["rows", "dw"])
def test_visits_cover_every_row_of_every_group_once(product, seed):
    rs = np.random.RandomState(seed)
    sizes = rs.multinomial(M, rs.dirichlet(np.ones(GE) * 0.3))
    group, tile, offsets, length = _visits(sizes, M, product == "dw")
    assert group.shape == (M // TILE + GE - 1,)
    n = int(length[0])
    assert (_times_seen(group[:n], tile[:n], offsets, M) == 1).all()
    # the weight gradient visits every group (an empty one once, for its
    # zeros), the row products the groups that have a row and no other;
    # in order, and the padding names the last real visit again
    assert sorted(set(group[:n])) == [
        g for g in range(GE) if product == "dw" or sizes[g]]
    assert (np.diff(group[:n]) >= 0).all()
    assert (group[n:] == group[n - 1]).all() and \
        (tile[n:] == tile[n - 1]).all()


@pytest.mark.parametrize("groups", [
    "rows of no group behind the groups", "every group empty",
    "16 groups, one tile, 8 rows on 6 groups", "only the last group has rows"])
def test_visits_of_a_row_product_where_rows_are_no_groups(groups):
    """The range form: a list that may be short of the rows, or empty.
    Every entry, real or padding, names a block that exists."""
    sizes = np.asarray(GROUPS[groups])
    m = ROWS.get(groups, M)
    group, tile, offsets, length = _visits(sizes, m, False)
    n = int(length[0])
    assert sorted(set(group[:n])) == list(np.flatnonzero(sizes))
    assert (np.diff(group[:n]) >= 0).all()
    assert ((group >= 0) & (group < len(sizes))).all()
    assert ((tile >= 0) & (tile < m // TILE)).all()
    seen = _times_seen(group[:n], tile[:n], offsets, m)
    assert (seen[:sizes.sum()] == 1).all() and not seen[sizes.sum():].any()
    if not n:
        assert not sizes.any() and len(set(group)) == 1 == len(set(tile))


# (sizes, group, tile, length) of `visits` on the parent commit (d4f593b),
# which knew one list for all three kernels, on count vectors drawn from
# seeds 0 and 3 in units of 32 rows and from seed 100 row by row: no empty
# group, lists of 8, 7 and 9 real visits of the 9 there is room for
PARENT_VISITS = [
    ([64, 160, 128, 32, 64, 64], [0, 1, 1, 2, 2, 3, 4, 5, 5],
     [0, 0, 1, 1, 2, 2, 3, 3, 3], 8),
    ([32, 64, 32, 64, 64, 256], [0, 1, 2, 3, 4, 5, 5, 5, 5],
     [0, 0, 0, 1, 1, 2, 3, 3, 3], 7),
    ([104, 49, 71, 263, 1, 24], [0, 1, 1, 2, 3, 3, 3, 4, 5],
     [0, 0, 1, 1, 1, 2, 3, 3, 3], 9),
]


@pytest.mark.parametrize("recorded", PARENT_VISITS,
                         ids=lambda r: "length %d" % r[3])
@pytest.mark.parametrize("product", ["rows", "dw"])
def test_without_an_empty_group_the_visits_are_the_parents(product,
                                                           recorded):
    sizes, want_group, want_tile, want_length = recorded
    group, tile, offsets, length = _visits(sizes, M, product == "dw")
    assert list(group) == want_group and list(tile) == want_tile
    assert list(length) == [want_length]
    assert list(offsets) == [0] + list(np.cumsum(sizes))


@pytest.mark.parametrize("path", ["plain", "interpreter"])
def test_a_share_with_no_held_assignment_adds_exactly_zero(path,
                                                           monkeypatch):
    """`moe_experts` holding experts 4..7 of 8 when every token chose
    among 0..3: no grouped product visits anything, and the rows nothing
    wrote (NaN under the interpreter) do not reach `Out`."""
    from jax.experimental.pallas import tpu as pltpu

    rs = np.random.RandomState(11)
    n = 8
    ins = {"X": [jnp.asarray(rs.randn(n, D), jnp.float32)],
           "TopW": [jnp.asarray(rs.uniform(0.1, 0.5, (n, K)), jnp.float32)],
           "TopIdx": [jnp.asarray(rs.randint(0, 4, (n, K)), jnp.int32)],
           "WGate": [jnp.asarray(rs.randn(4, D, F) * 0.2, jnp.float32)],
           "WUp": [jnp.asarray(rs.randn(4, D, F) * 0.2, jnp.float32)],
           "WDown": [jnp.asarray(rs.randn(4, F, D) * 0.2, jnp.float32)]}
    kernel = registry.get_op_info("moe_experts").kernel
    attrs = {"first_expert": 4, "scored": E}
    if path == "plain":
        outs = kernel(None, ins, attrs)
    else:
        def through_the_kernel(x, w, counts):
            blocks = grouped_matmul.choose_blocks(
                x.shape[0], w.shape[1], w.shape[2], 4, "fwd")
            return grouped_matmul._rows_call("fwd", blocks, x, w, counts)

        # off the TPU `gmm` takes its plain path: hand it the kernel
        monkeypatch.setattr(grouped_matmul, "ragged_gmm", through_the_kernel)
        with pltpu.force_tpu_interpret_mode():
            outs = kernel(None, ins, attrs)
        assert np.isnan(np.asarray(outs["Gate"][0])).all()
    assert not np.asarray(outs["Counts"][0]).any()
    out = np.asarray(outs["Out"][0])
    assert out.shape == (n, D) and not out.any()


def test_two_products_of_one_shape_are_counted_twice():
    """The lowering is counted where the product is asked for, not where
    the kernel is traced: JAX traces a branch of `platform_dependent`
    once for a function and its shapes, and the gate and the up product
    of a layer have the same."""
    x = jax.ShapeDtypeStruct((M, GK), jnp.float32)
    dy = jax.ShapeDtypeStruct((M, GN), jnp.float32)
    counts = jax.ShapeDtypeStruct((GE,), jnp.int32)
    before = telemetry.snapshot()
    jax.eval_shape(lambda x, dy, c: (grouped_matmul.gmm_dw(x, dy, c),
                                     grouped_matmul.gmm_dw(x, dy, c)),
                   x, dy, counts)
    delta = telemetry.snapshot_delta(before)
    assert sum(v for key, v in delta.items() if key.startswith(
        "moe_gmm_lowerings_total") and "kernel=dw" in key) == 2


def test_blocks_are_chosen_from_the_shapes():
    """At the cell's shapes the contraction is whole, rows come 256 at a
    time and the weight block is one expert's whole matrix."""
    choose = grouped_matmul.choose_blocks
    assert choose(32768, 2048, 1024, 2, "fwd") == (256, 1024, 2048)
    assert choose(32768, 1024, 2048, 2, "fwd") == (256, 2048, 1024)
    assert choose(32768, 2048, 1024, 2, "dx") == (256, 1024, 2048)
    assert choose(32768, 2048, 1024, 2, "dw") == (256, 1024, 2048)
    # float32 operands: the same budget holds half as much
    bm, bn, bk = choose(32768, 2048, 1024, 4, "fwd")
    assert (bm, bk) == (256, 2048) and bn <= 1024
    with pytest.raises(ValueError, match="100 rows"):
        grouped_matmul._rows_call(
            "fwd", (64, 128, 128), jnp.zeros((100, 128)),
            jnp.zeros((2, 128, 128)), jnp.asarray([50, 50]))


# -- the initializer ----------------------------------------------------------

@pytest.mark.parametrize("init,limit", [
    (fluid.initializer.Xavier(stacked=True), math.sqrt(6.0 / (D + F))),
    # what a stack would get without the flag: a convolution's fans
    (fluid.initializer.Xavier(), math.sqrt(6.0 / (D * F + E * F))),
])
def test_a_stacked_parameter_has_one_experts_fans(init, limit):
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        fluid.layers.create_parameter([E, D, F], "float32", attr=ParamAttr(
            name="w", initializer=init))
    op = startup.global_block().desc.ops[-1]
    assert op.type == "uniform_random"
    assert op.attrs["max"] == pytest.approx(limit)
    assert op.attrs["min"] == pytest.approx(-limit)


def test_the_layer_initialises_its_experts_with_one_experts_fans():
    main, startup, _, _ = _build()
    limits = {op.outputs["Out"][0]: op.attrs["max"]
              for op in startup.global_block().desc.ops
              if op.type == "uniform_random"}
    block = NAMES["blocks"][0]
    assert limits[block["w_gate"]] == pytest.approx(math.sqrt(6.0 / (D + F)))
    assert limits[block["w_down"]] == pytest.approx(math.sqrt(6.0 / (F + D)))
    assert limits[block["router"]] == pytest.approx(math.sqrt(6.0 / (D + E)))
    with pytest.raises(ValueError, match="stacked"):
        fluid.initializer.Xavier(stacked=True)._fan_in_out(
            main.global_block().var(block["router"]), True)


# -- shapes without a trace, counters, precision ------------------------------

def test_the_build_traces_no_kernel(trained_once):
    """Both ops have an explicit shape rule: building the program lowers
    nothing, and every output has its static shape."""
    assert not [k for k in trained_once["built"] if k.startswith("moe_")]
    block = trained_once["main"].global_block()
    experts = [op for op in block.desc.ops if op.type == "moe_experts"]
    assert len(experts) == L
    shapes = {slot: tuple(block.var(experts[0].output(slot)[0]).shape)
              for slot in experts[0].outputs}
    rows = B * T * K
    assert shapes == {"Out": (B, T, D), "Xs": (rows, D), "Gate": (rows, F),
                      "Up": (rows, F), "RowSlot": (rows,),
                      "TokenRow": (rows,), "Counts": (E,)}
    router = [op for op in block.desc.ops if op.type == "moe_router"][0]
    assert tuple(block.var(router.output("TopIdx")[0]).shape) == (B * T, K)
    assert block.var(router.output("TopIdx")[0]).dtype == "int32"


def test_counters_say_what_was_lowered(trained_once):
    lowered = trained_once["lowered"]
    assert lowered["moe_lowerings_total{experts=%d,top_k=%d}" % (E, K)] == L
    by_kernel = {kernel: sum(v for key, v in lowered.items()
                             if key.startswith("moe_gmm_lowerings_total")
                             and "kernel=%s" % kernel in key)
                 for kernel in ("fwd", "dx", "dw")}
    # three products a layer forward; a dx and a dw for each backward
    assert by_kernel == {"fwd": 3 * L, "dx": 3 * L, "dw": 3 * L}
    assert all("block_m=" in k and "block_n=" in k and "block_k=" in k
               for k in lowered if k.startswith("moe_gmm_lowerings_total"))
    # the row products walk the groups that have a row, the weight
    # gradient every group
    walks = {(kernel, form): sum(
        v for key, v in lowered.items()
        if key.startswith("moe_gmm_lowerings_total")
        and "kernel=%s" % kernel in key and "empty_groups=%s" % form in key)
        for kernel in ("fwd", "dx", "dw") for form in ("skipped", "visited")}
    assert walks == {("fwd", "skipped"): 3 * L, ("fwd", "visited"): 0,
                     ("dx", "skipped"): 3 * L, ("dx", "visited"): 0,
                     ("dw", "skipped"): 0, ("dw", "visited"): 3 * L}


def test_router_stays_float32_under_bfloat16_compute():
    """Under `amp.enable_bf16` the router's logits, weights and losses
    are float32 and agree with a float32 product of the same (bfloat16)
    input; the experts' kept rows are bfloat16; a weight's gradient is
    float32."""
    with fluid.amp.bf16_guard():
        main, startup, loss, parts = build_olmoe_program(
            B, T, V, n_layer=1, n_head=H, d_model=D, d_expert=F,
            n_experts=E, top_k=K)
        names = olmoe_param_names(1)["blocks"][0]
        with fluid.program_guard(main, startup):
            grads = dict((p.name, g) for p, g in
                         fluid.backward.append_backward(loss))
        block = main.global_block()
        router = [op for op in block.desc.ops if op.type == "moe_router"][0]
        experts = [op for op in block.desc.ops if op.type == "moe_experts"][0]
        u, xs = router.input("X")[0], experts.output("Xs")[0]
        exe, scope = _start(startup)
        # float32 masters, as a trainer keeps them (under AMP the
        # start-up program leaves them in bfloat16)
        for name in jax.tree_util.tree_leaves(olmoe_param_names(1)):
            scope.set(name, scope.get(name).astype(jnp.float32))
        feeds = transformer_program_feeds(B, T, V, seed=1)
        logits, top_w, lb, u, xs, g_gate, g_router = exe.run(
            main, feed=feeds, scope=scope, return_numpy=False,
            fetch_list=[parts["router_logits"][0], parts["top_w"][0],
                        parts["lb"], u, xs, grads[names["w_gate"]],
                        grads[names["router"]]])
    assert (logits.dtype, top_w.dtype, lb.dtype) == (jnp.float32,) * 3
    assert u.dtype == jnp.bfloat16 and xs.dtype == jnp.bfloat16
    assert g_gate.dtype == jnp.float32 and g_router.dtype == jnp.float32
    want = np.asarray(u, np.float32).reshape(-1, D) @ np.asarray(
        scope.get(names["router"]), np.float32)
    # a product of bfloat16-rounded weights would be off by 2^-9 of a logit
    np.testing.assert_allclose(logits, want, atol=2e-5)
