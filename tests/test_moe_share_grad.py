"""`moe_experts` holding a range of the experts its router scores
(ops/moe.py), backward as well as forward: the op and every gradient
against dense experts; the eight shares' outputs and input gradients
add up to the uncut layer's; garbage in the rows no product wrote
reaches no sum; ReGLU experts (`activation="relu"`); a router that reads
another tensor than the experts (`fluid.layers.moe(router_input=)`); the
counters; and the compact row path of an op that orders 32768 rows or
more (a bound from the shapes, a check at run time, two exact bodies of
the row work between the grouped products).

Tiny sizes on the CPU: hidden 64, 8 experts of 32 scored, 2 a token; the
compact path at 8192 tokens x 4, 2 of 16 experts held, hidden 16.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid.param_attr import ParamAttr
from paddle_tpu.obs import telemetry
from paddle_tpu.ops import moe as moe_ops
from paddle_tpu.ops import registry

D, F, E, K, N = 64, 32, 8, 2, 40
INFO = registry.get_op_info("moe_experts")
GRADS = ("X", "TopW", "WGate", "WUp", "WDown")


def _act(g, activation):
    return jax.nn.relu(g) if activation == "relu" else g * jax.nn.sigmoid(g)


def _dense(x, top_w, top_idx, w_gate, w_up, w_down, first, activation):
    """Every held expert applied to every token, the rows of the others
    masked afterwards: weights [count, ...] are experts first .. ."""
    count = w_gate.shape[0]
    h = _act(jnp.einsum("nd,edf->nef", x, w_gate, precision="highest"),
             activation) \
        * jnp.einsum("nd,edf->nef", x, w_up, precision="highest")
    y = jnp.einsum("nef,efd->ned", h, w_down, precision="highest")
    weight = jnp.sum(
        (top_idx[:, :, None] == first + jnp.arange(count)) * top_w[:, :, None],
        axis=1)
    return jnp.einsum("ne,ned->nd", weight, y, precision="highest")


def _operands(seed, n=N, d=D, f=F, scored=E, k=K):
    rs = np.random.RandomState(seed)
    x = jnp.asarray(rs.randn(n, d), jnp.float32)
    top_idx = jnp.asarray(
        np.stack([rs.permutation(scored)[:k] for _ in range(n)]), jnp.int32)
    top_w = jnp.asarray(rs.uniform(0.1, 0.5, (n, k)), jnp.float32)
    w_gate, w_up = (jnp.asarray(rs.randn(scored, d, f) * 0.2, jnp.float32)
                    for _ in range(2))
    w_down = jnp.asarray(rs.randn(scored, f, d) * 0.2, jnp.float32)
    d_out = jnp.asarray(rs.randn(n, d), jnp.float32)
    return x, top_w, top_idx, (w_gate, w_up, w_down), d_out


def _share(x, top_w, top_idx, weights, d_out, first, count, activation,
           spoil=False, counts=None):
    """The op and its gradient op holding experts first .. first +
    count; with `spoil`, NaN where the forward wrote nothing; the
    forward's Counts appended to `counts`."""
    scored = weights[0].shape[0]
    attrs = {} if (first, count) == (0, scored) \
        else {"first_expert": first, "scored": scored}
    if activation != "silu":
        attrs["activation"] = activation
    ins = {"X": [x], "TopW": [top_w], "TopIdx": [top_idx]}
    ins.update({slot: [w[first:first + count]]
                for slot, w in zip(("WGate", "WUp", "WDown"), weights)})
    outs = INFO.kernel(None, ins, attrs)
    kept = {slot: v[0] for slot, v in outs.items()}
    if counts is not None:
        counts.append(np.asarray(kept["Counts"]))
    if spoil:
        held = int(np.asarray(kept["Counts"]).sum())
        for slot in ("Gate", "Up"):
            kept[slot] = kept[slot].at[held:].set(jnp.nan)
    grad_ins = dict(ins, **{"OG@Out": [d_out]})
    grad_ins.update({"O@" + slot: [v] for slot, v in kept.items()})
    grads = INFO.grad_kernel(None, grad_ins, attrs)
    return outs["Out"][0], {s: grads[s + "@GRAD"][0] for s in GRADS}


def _close(got, want, what):
    got, want = np.asarray(got), np.asarray(want)
    assert np.abs(got - want).max() \
        <= 2e-5 * max(np.abs(want).max(), 1.0), what


@pytest.mark.parametrize("activation", ["silu", "relu"])
@pytest.mark.parametrize("first,count", [(0, 8), (0, 4), (3, 2), (6, 2),
                                         (5, 1)])
def test_a_share_and_every_gradient_against_dense_experts(first, count,
                                                          activation):
    x, top_w, top_idx, weights, d_out = _operands(first * 8 + count)
    held = tuple(w[first:first + count] for w in weights)
    out, grads = _share(x, top_w, top_idx, weights, d_out, first, count,
                        activation)
    _close(out, _dense(x, top_w, top_idx, *held, first, activation), "Out")
    want = jax.grad(
        lambda x, top_w, *w: jnp.sum(
            _dense(x, top_w, top_idx, *w, first, activation) * d_out),
        argnums=(0, 1, 2, 3, 4))(x, top_w, *held)
    for slot, w in zip(GRADS, want):
        _close(grads[slot], w, slot)
    # an assignment to an absent expert: a routing weight's gradient of 0
    absent = (np.asarray(top_idx) < first) \
        | (np.asarray(top_idx) >= first + count)
    assert not np.asarray(grads["TopW"])[absent].any()


@pytest.mark.parametrize("activation", ["silu", "relu"])
@pytest.mark.parametrize("ranges", [[(i, 1) for i in range(E)],
                                    [(0, 4), (4, 4)],
                                    [(0, 2), (2, 5), (7, 1)],
                                    "compact"])
def test_the_shares_add_up_to_the_uncut_layer_backward_too(ranges,
                                                           activation):
    """Outputs, input gradients and routing-weight gradients of the
    shares add up to the uncut layer's; each share's weight gradients
    are the uncut layer's for its experts.  "compact": eight shares of
    two at the shape that has a compact path, under a routing that
    sends the first share more rows than its bound and the others
    fewer, so both bodies are among the parts."""
    counts = []
    if ranges == "compact":
        ranges = [(first, HELD) for first in range(0, SCORED, HELD)]
        x, top_w, _, weights, d_out = _compact_operands(4)
        top_idx = _routing(9000, 9000, first=0, others=True)
    else:
        x, top_w, top_idx, weights, d_out = _operands(len(ranges))
    whole_out, whole = _share(x, top_w, top_idx, weights, d_out, 0,
                              weights[0].shape[0], activation)
    out = 0.0
    total = {"X": 0.0, "TopW": 0.0}
    for first, count in ranges:
        part, grads = _share(x, top_w, top_idx, weights, d_out, first, count,
                             activation, counts=counts)
        out = out + part
        for slot in total:
            total[slot] = total[slot] + grads[slot]
        for slot in ("WGate", "WUp", "WDown"):
            _close(grads[slot], whole[slot][first:first + count], slot)
    _close(out, whole_out, "Out")
    for slot in total:
        _close(total[slot], whole[slot], slot)
    if len(x) == TOKENS:
        assert [int(c.sum()) <= BOUND for c in counts] == [False] + 7 * [True]


def test_rows_no_product_wrote_reach_no_sum():
    """The kept Gate and Up rows of absent assignments are whatever was
    there (the grouped kernels write no row of no group): NaN in them
    changes no gradient."""
    operands = _operands(11)
    _, clean = _share(*operands, 2, 3, "silu")
    _, spoiled = _share(*operands, 2, 3, "silu", spoil=True)
    for slot in GRADS:
        np.testing.assert_array_equal(spoiled[slot], clean[slot])


# the compact row path: 32768 assignments, the held two of sixteen
# experts get an even router's 4096 of them under a bound of 8192
TOKENS, TOP, SCORED, HELD, WIDE, NARROW, FIRST = 8192, 4, 16, 2, 16, 8, 6
BOUND = 8192


def _compact_operands(seed):
    return _operands(seed, n=TOKENS, d=WIDE, f=NARROW, scored=SCORED, k=TOP)


def _routing(in_slot_0, in_slot_1, first=FIRST, others=False):
    """TopIdx [TOKENS, TOP] that sends expert `first` the first
    `in_slot_0` tokens and `first + 1` the first `in_slot_1`, and the
    held pair nothing else: every other entry is one of the 14 absent
    experts, a token's all different (with `others` its last two)."""
    rs = np.random.RandomState(in_slot_0 + in_slot_1)
    absent = np.array([e for e in range(SCORED)
                       if e not in (first, first + 1)])
    idx = np.stack([absent[rs.permutation(len(absent))[:TOP]]
                    for _ in range(TOKENS)])
    if others:
        idx = np.roll(idx, 2, axis=1)
    idx[:in_slot_0, 0] = first
    idx[:in_slot_1, 1] = first + 1
    return jnp.asarray(idx, jnp.int32)


def test_the_bound_of_the_compact_path_from_shapes():
    """`_compact_rows` (tokens, a token, held, scored): twice an even
    router's share on the row tile, where the op is ranged, orders 32768
    rows or more and the bound is at most half of them; else 0."""
    for shape, bound in (
            ((16384, 6, 8, 64), 24576),       # smallthinker-train-16k-ep8
            ((TOKENS, TOP, HELD, SCORED), BOUND),
            ((8200, 4, 2, 16), 8448),         # 8200 on the tile of 256
            ((4096, 8, 64, 64), 0),           # olmoe-train-4k: all held
            ((256, 8, 16, 256), 0),           # pangu-decode-ep16's step
            ((16, 8, 16, 256), 0),            # dsv32's step
            ((16 * 128, 8, 16, 256), 0),      # and its question block
            ((8, 8, 8, 128), 0),              # exaone's step
            ((8 * 128, 8, 8, 128), 0),        # and its block
            ((TOKENS, TOP, 5, SCORED), 0),    # the bound past half the rows
            ((TOKENS - 1, TOP, HELD, SCORED), 0)):
        assert moe_ops._compact_rows(*shape) == bound, shape


@pytest.mark.parametrize("activation", ["silu", "relu"])
@pytest.mark.parametrize("in_slot_0,in_slot_1,compact", [
    (None, None, True),          # a near even router
    (TOKENS, 4000, False),       # the range gets 12192 rows
    (TOKENS, 0, True),           # exactly the bound
    (TOKENS, 1, False),          # one more
    (0, 0, True),                # none at all
])
def test_either_row_path_against_dense_experts(in_slot_0, in_slot_1, compact,
                                               activation):
    """At a shape with a compact path, Out and all five gradients
    against dense experts whichever body the held rows' count picks:
    nothing is dropped past the bound, the other body runs."""
    x, top_w, top_idx, weights, d_out = _compact_operands(17)
    if in_slot_0 is not None:
        top_idx = _routing(in_slot_0, in_slot_1)
    held = tuple(w[FIRST:FIRST + HELD] for w in weights)
    counts = []
    out, grads = _share(x, top_w, top_idx, weights, d_out, FIRST, HELD,
                        activation, counts=counts)
    assert moe_ops._compact_rows(TOKENS, TOP, HELD, SCORED) == BOUND
    assert (int(counts[0].sum()) <= BOUND) == compact
    if in_slot_0 is not None:
        assert int(counts[0].sum()) == in_slot_0 + in_slot_1
    _close(out, _dense(x, top_w, top_idx, *held, FIRST, activation), "Out")
    want = jax.grad(
        lambda x, top_w, *w: jnp.sum(
            _dense(x, top_w, top_idx, *w, FIRST, activation) * d_out),
        argnums=(0, 1, 2, 3, 4))(x, top_w, *held)
    for slot, w in zip(GRADS, want):
        _close(grads[slot], w, slot)


@pytest.mark.parametrize("in_slot_1", [0, 1])
def test_rows_no_product_wrote_reach_no_sum_on_either_row_path(in_slot_1):
    """NaN in the kept rows past the held ones changes no gradient, at
    the bound (the compact body masks its rows up to the bound and reads
    none past it) and one past it."""
    x, top_w, _, weights, d_out = _compact_operands(5)
    operands = (x, top_w, _routing(TOKENS, in_slot_1), weights, d_out)
    _, clean = _share(*operands, FIRST, HELD, "relu")
    _, spoiled = _share(*operands, FIRST, HELD, "relu", spoil=True)
    for slot in GRADS:
        np.testing.assert_array_equal(spoiled[slot], clean[slot])


def _traced(n, k, held, scored, hidden=16, width=8):
    """The lowered text of the op and its gradient at a shape."""
    f32 = jnp.float32
    ins = {"X": [jax.ShapeDtypeStruct((n, hidden), f32)],
           "TopW": [jax.ShapeDtypeStruct((n, k), f32)],
           "TopIdx": [jax.ShapeDtypeStruct((n, k), jnp.int32)],
           "WGate": [jax.ShapeDtypeStruct((held, hidden, width), f32)],
           "WUp": [jax.ShapeDtypeStruct((held, hidden, width), f32)],
           "WDown": [jax.ShapeDtypeStruct((held, width, hidden), f32)]}
    attrs = {"first_expert": 0, "scored": scored}

    def step(ins, d_out):
        outs = INFO.kernel(None, ins, attrs)
        grad_ins = dict(ins, **{"OG@Out": [d_out]})
        grad_ins.update({"O@" + slot: v for slot, v in outs.items()})
        return outs["Out"], INFO.grad_kernel(None, grad_ins, attrs)

    return jax.jit(step).lower(ins, ins["X"][0]).as_text(debug_info=True)


def test_a_share_under_32768_rows_has_one_body():
    """pangu-decode-ep16's step (256 rows x 8, 16 of 256 held) opens
    neither row path's scope, forward or backward: it has the one body
    it had.  The shape with a compact path has both scopes under each of
    the op's three."""
    text = _traced(256, 8, 16, 256)
    assert "moe_compact" not in text and "moe_all_rows" not in text
    text = _traced(TOKENS, TOP, HELD, SCORED)
    for phase in ("moe_route", "moe_experts", "moe_combine"):
        for branch in ("moe_compact", "moe_all_rows"):
            assert "/%s/%s/" % (phase, branch) in text, (phase, branch)


def test_an_unknown_activation_is_refused():
    operands = _operands(1)
    with pytest.raises(ValueError, match="activation"):
        _share(*operands, 0, E, "gelu")


def test_the_default_activation_is_the_op_as_it_was():
    """No `activation` and "silu" trace the same program."""
    x, top_w, top_idx, weights, _ = _operands(2)
    ins = {"X": [x], "TopW": [top_w], "TopIdx": [top_idx],
           "WGate": [weights[0]], "WUp": [weights[1]],
           "WDown": [weights[2]]}
    plain = jax.make_jaxpr(lambda i: INFO.kernel(None, i, {})["Out"][0])(ins)
    named = jax.make_jaxpr(lambda i: INFO.kernel(
        None, i, {"activation": "silu"})["Out"][0])(ins)
    assert str(plain) == str(named)


def test_counters_say_a_share_was_differentiated():
    operands = _operands(3)
    before = telemetry.snapshot()
    _share(*operands, 2, 3, "relu")
    _share(*operands, 0, E, "relu")
    delta = telemetry.snapshot_delta(before)
    assert not any(key.startswith("moe_share_compact_lowerings_total")
                   for key in delta)
    assert delta["moe_share_bwd_lowerings_total{held=3,scored=8,top_k=2}"] \
        == 1
    assert delta["moe_share_lowerings_total{held=3,scored=8,top_k=2}"] == 1
    assert sum(v for key, v in delta.items()
               if key.startswith("moe_share_bwd_lowerings_total")) == 1
    before = telemetry.snapshot()
    _share(*_compact_operands(3), FIRST, HELD, "relu")
    delta = telemetry.snapshot_delta(before)
    assert delta["moe_share_compact_lowerings_total{bound=8192,rows=32768}"] \
        == 1
    assert delta["moe_share_lowerings_total{held=2,scored=16,top_k=4}"] == 1


def _layer_program(held, activation, router_elsewhere):
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[N, D], dtype="float32",
                              append_batch_size=False, stop_gradient=False)
        r = fluid.layers.data(name="r", shape=[N, D], dtype="float32",
                              append_batch_size=False, stop_gradient=False)
        out, _, _, routing = fluid.layers.moe(
            x, E, F, K, *(ParamAttr(name=n) for n in
                          ("router", "w_gate", "w_up", "w_down")),
            norm_topk=True, held=held, activation=activation,
            router_input=r if router_elsewhere else None)
        loss = fluid.layers.mean(x=out * out)
        fluid.backward.append_backward(loss)
    return main, startup, loss, routing


@pytest.mark.parametrize("held", [None, (2, 4)])
def test_the_layer_routes_on_another_tensor_than_its_experts_read(held):
    """`router_input`: the router's X is that tensor, the experts' is
    `input`; loss and every gradient (the router's through the chosen
    weights, the other tensor's through the router alone) against dense
    ReGLU experts under the same routing."""
    main, startup, loss, routing = _layer_program(held, "relu", True)
    ops = {o.type: o for o in main.global_block().desc.ops}
    assert ops["moe_router"].input("X") == ["r"]
    assert ops["moe_experts"].input("X") == ["x"]
    assert ops["moe_experts"].attrs["activation"] == "relu"
    assert ("first_expert" in ops["moe_experts"].attrs) == (held is not None)
    rs = np.random.RandomState(7)
    x, r = (rs.randn(N, D).astype("float32") for _ in range(2))
    exe, scope = fluid.Executor(fluid.CPUPlace()), fluid.Scope()
    startup.random_seed = 5
    exe.run(startup, scope=scope)
    names = ["router", "w_gate", "w_up", "w_down"]
    fetched = exe.run(
        main, feed={"x": x, "r": r}, scope=scope,
        fetch_list=[loss, routing["top_idx"], "x@GRAD", "r@GRAD"]
        + [n + "@GRAD" for n in names])
    first, count = held or (0, E)
    params = [jnp.asarray(scope.get(n)) for n in names]
    assert params[1].shape == (count, D, F)
    top_idx = jnp.asarray(fetched[1])

    def plain(x, r, router, w_gate, w_up, w_down):
        probs = jax.nn.softmax(jnp.dot(r, router, precision="highest"))
        top_w = jnp.take_along_axis(probs, top_idx, axis=1)
        top_w = top_w / (jnp.sum(top_w, axis=-1, keepdims=True) + 1e-20)
        out = _dense(x, top_w, top_idx, w_gate, w_up, w_down, first, "relu")
        return jnp.mean(out * out)

    want, grads = jax.value_and_grad(plain, argnums=tuple(range(6)))(
        jnp.asarray(x), jnp.asarray(r), *params)
    np.testing.assert_array_equal(
        top_idx, jax.lax.top_k(jnp.dot(r, params[0], precision="highest"),
                               K)[1])
    np.testing.assert_allclose(fetched[0].reshape(()), want, rtol=2e-6)
    for got, ref, name in zip(fetched[2:], grads, ["x", "r"] + names):
        _close(got, ref, name)


def test_without_router_input_the_program_is_as_it_was():
    main, _, _, _ = _layer_program(None, "silu", False)
    ops = {o.type: o for o in main.global_block().desc.ops}
    assert ops["moe_router"].input("X") == ["x"]
    assert ops["moe_experts"].attrs == {}
