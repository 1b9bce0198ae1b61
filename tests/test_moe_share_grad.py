"""`moe_experts` holding a range of the experts its router scores
(ops/moe.py), backward as well as forward: the op and every gradient
against dense experts; the eight shares' outputs and input gradients
add up to the uncut layer's; garbage in the rows no product wrote
reaches no sum; ReGLU experts (`activation="relu"`); a router that reads
another tensor than the experts (`fluid.layers.moe(router_input=)`); the
counters; and the chunked row work of an op that orders 32768 rows or
more (the passes between the grouped products as loops over chunks of
the order, as many trips as hold a held row: one body, no choice).

Tiny sizes on the CPU: hidden 64, 8 experts of 32 scored, 2 a token; the
chunked row work at 8192 tokens x 4, 2 of 16 experts held, hidden 16.
"""

import functools
import re

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
def test_the_shares_add_up_to_the_uncut_layer_backward_too(
        ranges, activation, nan_where_nothing_wrote):
    """Outputs, input gradients and routing-weight gradients of the
    shares add up to the uncut layer's; each share's weight gradients
    are the uncut layer's for its experts.  "compact": eight shares of
    two at the shape whose row work goes by chunks, under a routing
    that sends the first share several chunks' rows and the others
    less than one, while the uncut layer has the plain body."""
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
        assert [int(c.sum()) > CHUNK for c in counts] == [True] + 7 * [False]


def test_rows_no_product_wrote_reach_no_sum():
    """The kept Gate and Up rows of absent assignments are whatever was
    there (the grouped kernels write no row of no group): NaN in them
    changes no gradient."""
    operands = _operands(11)
    _, clean = _share(*operands, 2, 3, "silu")
    _, spoiled = _share(*operands, 2, 3, "silu", spoil=True)
    for slot in GRADS:
        np.testing.assert_array_equal(spoiled[slot], clean[slot])


# the chunked row work: 32768 assignments in chunks of 8192, the held two
# of sixteen experts get an even router's 4096 of them
TOKENS, TOP, SCORED, HELD, WIDE, NARROW, FIRST = 8192, 4, 16, 2, 16, 8, 6
ROWS, CHUNK = TOKENS * TOP, 8192


def _compact_operands(seed):
    return _operands(seed, n=TOKENS, d=WIDE, f=NARROW, scored=SCORED, k=TOP)


@pytest.fixture
def nan_where_nothing_wrote(monkeypatch):
    """Off the TPU an array nothing has written is zeros
    (`grouped_matmul.unwritten`); on it, whatever the memory held.  NaN
    in its place shows a read of a row that no trip wrote."""
    from paddle_tpu.kernels import grouped_matmul

    monkeypatch.setattr(grouped_matmul, "unwritten",
                        lambda shape, dtype, after: jnp.full(shape, jnp.nan,
                                                             dtype))


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


def _held_routing(held_rows):
    """TopIdx [TOKENS, TOP] with `held_rows` of its entries, anywhere,
    on the held pair and the others on the 14 absent experts (a token
    may name an expert twice: each is an assignment of its own); with
    all ROWS of them a router that sends the range everything."""
    rs = np.random.RandomState(held_rows)
    absent = np.array([e for e in range(SCORED)
                       if e not in (FIRST, FIRST + 1)])
    flat = absent[rs.randint(0, len(absent), ROWS)]
    flat[rs.permutation(ROWS)[:held_rows]] = \
        FIRST + rs.randint(0, HELD, held_rows)
    return jnp.asarray(flat.reshape(TOKENS, TOP), jnp.int32)


def test_which_shapes_take_the_loop_and_with_what_chunk():
    """`_chunk_rows` (tokens, a token, held, scored): a ranged op that
    orders 32768 rows or more, a whole number of chunks, runs its row
    work in chunks of 8192, a whole number of the grouped kernels' row
    tiles, however few or many of the experts scored it holds; every
    other op has the plain body (0)."""
    assert moe_ops._CHUNK_ROWS == CHUNK and CHUNK % moe_ops._ROW_TILE == 0
    for shape, chunk in (
            ((16384, 6, 8, 64), CHUNK),       # smallthinker-train-16k-ep8
            ((TOKENS, TOP, HELD, SCORED), CHUNK),
            ((4096, 8, 16, 256), CHUNK),      # pangu-decode-ep16's prefill
            ((8192, 8, 8, 128), CHUNK),       # exaone-turn-32k-ep16's
            ((16384, 10, 32, 512), CHUNK),    # qwen3next-decode-ep16's
            ((TOKENS, TOP, 15, SCORED), CHUNK),  # all but one held
            ((4096, 8, 64, 64), 0),           # olmoe-train-4k: all held
            ((256, 8, 16, 256), 0),           # pangu-decode-ep16's step
            ((16, 8, 16, 256), 0),            # dsv32's step
            ((16 * 128, 8, 16, 256), 0),      # and its question block
            ((8, 8, 8, 128), 0),              # exaone's step
            ((8 * 128, 8, 8, 128), 0),        # and its block
            ((8200, 4, 2, 16), 0),            # no whole number of chunks
            ((TOKENS - 1, TOP, HELD, SCORED), 0)):
        assert moe_ops._chunk_rows(*shape) == chunk, shape


@functools.lru_cache(maxsize=None)
def _jitted_share(activation, chunked):
    """`_share` at the chunked shape's range as one jitted program; with
    `chunked` False the plain body at the same shape (the threshold is
    out of reach while it is traced)."""
    def step(*operands):
        saved = moe_ops._CHUNK_MIN_ROWS
        moe_ops._CHUNK_MIN_ROWS = saved if chunked else 2 * ROWS
        try:
            return _share(*operands, FIRST, HELD, activation)
        finally:
            moe_ops._CHUNK_MIN_ROWS = saved

    return jax.jit(step)


@pytest.mark.parametrize("activation", ["silu", "relu"])
@pytest.mark.parametrize("held_rows,trips", [
    (None, 1),                   # a near even router
    (0, 1),                      # none at all
    (1, 1),
    (CHUNK - 1, 1),
    (CHUNK, 2),                  # exactly a chunk: the trip of row 8192
    (CHUNK + 1, 2),              # one more
    (3 * CHUNK + 700, 4),        # several chunks, the last in part
    (ROWS, 4),                   # the range gets everything
])
def test_the_chunked_row_work_against_dense_experts(
        held_rows, trips, activation, monkeypatch, nan_where_nothing_wrote):
    """At a shape whose row work goes by chunks, Out and all five
    gradients against dense experts however many rows the range is
    sent, and bit for bit the plain body's on the same inputs where the
    order of the sums is the same: every sum adds the same terms in the
    same order (the plain body adds an absent assignment's 0 between
    them), so on the CPU the gradients agree to the bit as two jitted
    programs, and Out op by op (jitted, the plain body's sum over a
    token's rows is the compiler's to order, and a chunk's
    multiply-adds are its to contract)."""
    x, top_w, top_idx, weights, d_out = _compact_operands(17)
    if held_rows is not None:
        top_idx = _held_routing(held_rows)
    operands = (x, top_w, top_idx, weights, d_out)
    held = tuple(w[FIRST:FIRST + HELD] for w in weights)
    counts = []
    out, grads = _share(*operands, FIRST, HELD, activation, counts=counts)
    assert moe_ops._chunk_rows(TOKENS, TOP, HELD, SCORED) == CHUNK
    assert min(int(counts[0].sum()) // CHUNK + 1, ROWS // CHUNK) == trips
    if held_rows is not None:
        assert int(counts[0].sum()) == held_rows
    _close(out, _dense(x, top_w, top_idx, *held, FIRST, activation), "Out")
    want = jax.grad(
        lambda x, top_w, *w: jnp.sum(
            _dense(x, top_w, top_idx, *w, FIRST, activation) * d_out),
        argnums=(0, 1, 2, 3, 4))(x, top_w, *held)
    for slot, w in zip(GRADS, want):
        _close(grads[slot], w, slot)
    _, jitted = _jitted_share(activation, True)(*operands)
    _, plain_jitted = _jitted_share(activation, False)(*operands)
    for slot in GRADS:
        np.testing.assert_array_equal(jitted[slot], plain_jitted[slot], slot)
    monkeypatch.setattr(moe_ops, "_CHUNK_MIN_ROWS", 2 * ROWS)
    plain_out, _ = _share(*operands, FIRST, HELD, activation)
    np.testing.assert_array_equal(out, plain_out)


@pytest.mark.parametrize("held_rows", [CHUNK, CHUNK + 1])
def test_rows_no_product_wrote_reach_no_sum_in_the_chunked_row_work(
        held_rows, nan_where_nothing_wrote):
    """NaN in the kept rows past the held ones changes no gradient:
    where the held rows fill their last chunk no trip reads one, and
    one row on the chunk that holds row sum(Counts) masks its others."""
    x, top_w, _, weights, d_out = _compact_operands(5)
    operands = (x, top_w, _held_routing(held_rows), weights, d_out)
    _, clean = _share(*operands, FIRST, HELD, "relu")
    _, spoiled = _share(*operands, FIRST, HELD, "relu", spoil=True)
    for slot in GRADS:
        np.testing.assert_array_equal(spoiled[slot], clean[slot])


@pytest.mark.parametrize("in_slot_0", [0, CHUNK])
def test_the_tile_of_an_empty_held_expert_holds_what_a_trip_wrote(
        in_slot_0, monkeypatch, nan_where_nothing_wrote):
    """The grouped kernels' own bodies under the Pallas interpreter,
    which fills what nothing wrote with NaN: the range's second expert
    gets no row and the first none or exactly a chunk's, so `gmm_dw`
    gives the empty one its visit of the tile at row 0 or 8192, whose
    rows it multiplies by 0.  The loops run as far as that row, so the
    rows there are a trip's zeros and not what Gate and Up held, and
    every gradient is the plain path's."""
    from jax.experimental.pallas import tpu as pltpu

    from paddle_tpu.kernels import grouped_matmul

    x, top_w, _, weights, d_out = _compact_operands(23)
    operands = (x, top_w, _routing(in_slot_0, 0), weights, d_out)
    out, plain = _share(*operands, FIRST, HELD, "relu")

    def through(kernel):
        def call(a, b, counts):
            k, n = (a.shape[1], b.shape[1]) if kernel == "dw" else b.shape[1:]
            blocks = grouped_matmul.choose_blocks(a.shape[0], k, n, 4, kernel)
            if kernel == "dw":
                return grouped_matmul._dw_call(blocks, a, b, counts)
            return grouped_matmul._rows_call(kernel, blocks, a, b, counts)
        return call

    # off the TPU the products take their plain path: hand it the kernels
    for name, kernel in (("ragged_gmm", "fwd"), ("ragged_gmm_dx", "dx"),
                         ("ragged_gmm_dw", "dw")):
        monkeypatch.setattr(grouped_matmul, name, through(kernel))
    counts = []
    with pltpu.force_tpu_interpret_mode():
        got_out, got = _share(*operands, FIRST, HELD, "relu", counts=counts)
    assert list(counts[0]) == [in_slot_0, 0]
    _close(got_out, out, "Out")
    for slot in GRADS:
        assert np.isfinite(np.asarray(got[slot])).all(), slot
        _close(got[slot], plain[slot], slot)


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


def _choices_on_data(text):
    """The `stablehlo.case` operations of a lowering whose index is
    neither a constant nor another such operation's result: a choice
    between the platforms a computation may be lowered for
    (`lax.platform_dependent`: the grouped products, the arrays nothing
    has written) is on a constant; `lax.cond` on data is not."""
    cases = re.findall(r'(%\w+) = "stablehlo\.case"\((%\w+)\)', text)
    of_a_case = {result for result, _ in cases}
    return [index for _, index in cases
            if not re.fullmatch(r"%c(_\d+)?", index)
            and index not in of_a_case]


@pytest.mark.parametrize("shape", [(TOKENS, TOP, HELD, SCORED),
                                   (16384, 6, 8, 64)])
def test_a_share_under_32768_rows_has_one_body(shape):
    """pangu-decode-ep16's step (256 rows x 8, 16 of 256 held) opens
    no `moe_compact`, forward or backward: it has the one body it had.
    A shape whose row work goes by chunks (the test's; the rows of
    smallthinker-train-16k-ep8's) opens it under each of the op's three
    scopes around loops (`stablehlo.while`), and holds no scope
    `moe_all_rows` and no choice of a body on data (`stablehlo.case`,
    `.if`)."""
    plain = _traced(256, 8, 16, 256)
    assert "moe_compact" not in plain and "moe_all_rows" not in plain
    text = _traced(*shape)
    for phase in ("moe_route", "moe_experts", "moe_combine"):
        assert "/%s/moe_compact/while/" % phase in text, phase
    assert "moe_all_rows" not in text
    assert "stablehlo.case" in text and not _choices_on_data(text)
    assert "stablehlo.if" not in text and "stablehlo.if" not in plain
    chosen = jax.jit(lambda p, x: jax.lax.cond(p, jnp.sin, jnp.cos, x)) \
        .lower(True, 1.0).as_text()
    assert _choices_on_data(chosen)


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
    assert delta["moe_share_compact_lowerings_total{chunk=8192,rows=32768}"] \
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
