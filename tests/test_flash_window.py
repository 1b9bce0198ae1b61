"""The window bound of the flash-attention kernels
(kernels/flash_attention.py): a causal query folds its last `window`
keys alone, in the forward kernel and in all three forms of the
backward (the one kernel that holds a head's queries, the one that walks
the keys with a ring of dq^T, the pair that walks), against plain masked
attention; chunks wholly outside the window are
not folded, and `score_pairs`, the counter it feeds and the kernels'
names say so; with no window nothing of it is traced.
"""

import importlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu.fluid as fluid
from paddle_tpu.obs import telemetry

fa = importlib.import_module("paddle_tpu.kernels.flash_attention")


def _plain(q, k, v, heads, window):
    """Masked attention in float32 over [batch, seq, heads * dim]."""
    qh, kh, vh = (fa.split_heads(x.astype(jnp.float32), heads)
                  for x in (q, k, v))
    s = jnp.einsum("bhqd,bhkd->bhqk", qh, kh,
                   precision="highest") * qh.shape[-1] ** -0.5
    i = jnp.arange(q.shape[1])[:, None]
    j = jnp.arange(k.shape[1])[None, :]
    seen = j <= i
    if window:
        seen &= j > i - window
    p = jax.nn.softmax(jnp.where(seen, s, fa.NEG_INF), axis=-1)
    return fa.merge_heads(jnp.einsum("bhqk,bhkd->bhqd", p, vh,
                                     precision="highest"))


def _operands(t, heads, d, seed):
    rs = np.random.RandomState(seed)
    return [jnp.asarray(0.5 * rs.randn(1, t, heads * d), jnp.float32)
            for _ in range(4)]


def _walking_budget(monkeypatch, lanes, heads_a_step):
    """Room for the walking pair's 128 x 128 blocks and none for a grid
    step's whole sequence beside them: the backward takes its two
    kernels that walk, and the forward walks its keys a block a step."""
    monkeypatch.setattr(fa, "_VMEM_BUDGET", fa._bwd_step_bytes(
        128, 128, lanes, 4, None, heads_a_step))


def _ring_budget(monkeypatch, lanes, heads_a_step, t, window, itemsize=4,
                 bq=128, bk=128):
    """Room for the ring kernel's (bq, bk) blocks with the slots `window`
    asks for, which is more than the walking pair needs and less than a
    grid step's whole sequence does."""
    monkeypatch.setattr(fa, "_VMEM_BUDGET", fa._bwd_step_bytes(
        bq, bk, lanes, itemsize, None, heads_a_step,
        fa._ring_slots(bq, bk, t, window)))


# window < / = / > the sequence, one that is no multiple of a block, one
# inside a single block, one key
WINDOWS = [1, 100, 128, 200, 384, 512, 600]


@pytest.mark.parametrize("kernels", ["one", "walking", "ring"])
@pytest.mark.parametrize("d", [64, 128])
@pytest.mark.parametrize("window", WINDOWS)
def test_window_kernels_are_plain_masked_attention(monkeypatch, window, d,
                                                   kernels):
    """Values and all three gradients, 64- and 128-wide heads side by
    side, through the backward kernel that holds a head's queries,
    through the pair that walks and through the kernel that walks the
    keys with a ring of dq^T (where the budget has room for the ring and
    not for the whole head; a window no query reaches past leaves that
    budget the pair)."""
    heads, t = 2, 512
    q, k, v, do = _operands(t, heads, d, seed=window + d)
    call = fa._Call.of(q.shape, k.shape, heads)
    if kernels == "walking":
        _walking_budget(monkeypatch, call.lanes, call.g)
    elif kernels == "ring":
        _ring_budget(monkeypatch, call.lanes, call.g, t, window)
    live = window if window < t else 0
    form = {"one": "one", "walking": "pair",
            "ring": "ring" if live else "pair"}[kernels]
    assert fa._choose_bwd_blocks(*call.step_shapes, 4, heads=call.g,
                                 window=live)[2] == form

    def flash(q, k, v):
        return fa.flash_attention_with_lse(q, k, v, None, True,
                                           num_heads=heads, window=window)[0]

    before = telemetry.snapshot()
    out, vjp = jax.vjp(flash, q, k, v)
    grads = vjp(do)
    want, want_vjp = jax.vjp(lambda q, k, v: _plain(q, k, v, heads, window),
                             q, k, v)
    np.testing.assert_allclose(out, want, atol=2e-5, rtol=2e-5)
    for got, ref, name in zip(grads, want_vjp(do), "qkv"):
        np.testing.assert_allclose(got, ref, atol=5e-5, rtol=5e-5,
                                   err_msg="d" + name)
    rose = {key: n - before.get(key, 0)
            for key, n in telemetry.snapshot().items()
            if key.startswith("flash_attention_window_lowerings_total")
            and n != before.get(key, 0)}
    # a window no query reaches past is the causal kernel's business
    names = sorted(key.split("kernel=")[1].split(",")[0] for key in rose)
    if not live:
        assert not rose
    else:
        assert names == sorted(fa._BWD_KERNELS[form] + ("fwd",))
        assert all("window=%d}" % window in key for key in rose)


def _saved(q, k, v, do, heads, window):
    """What the backward's kernels take beside q, k, v and do: the
    forward's log-sum-exp and the row sums of do * o."""
    o, lse = fa.flash_attention_with_lse(q, k, v, None, True,
                                         num_heads=heads, window=window)
    return lse, fa.row_sums(do, o, heads)


# (t, window, bq, bk): sequences several rings long, t >= 4 * (window +
# block), so that every slot is taken again and the last blocks of keys
# have steps past the sequence's end; blocks of one size, queries' the
# wider (a block of keys starts inside one of queries), keys' the wider
# (several blocks of queries leave at one block of keys); a window inside
# a block, of one key, and as long as the sequence less one
RINGS = [(1024, 100, 128, 128), (1024, 128, 128, 128), (2048, 200, 256, 128),
         (3072, 129, 128, 384), (1536, 1, 128, 128), (2048, 300, 512, 128),
         (512, 511, 128, 256)]


@pytest.mark.parametrize("d", [64, 128])
@pytest.mark.parametrize("t,window,bq,bk", RINGS)
def test_the_ring_gives_the_walking_pairs_gradients(t, window, bq, bk, d):
    """dq, dk and dv of the kernel that walks the keys with a ring of
    dq^T against the walking pair's at the same blocks on the same
    operands, within float32 rounding (the same products, summed in
    another order), and against plain masked attention's."""
    heads = 2
    q, k, v, do = _operands(t, heads, d, seed=t + window)
    lse, delta = _saved(q, k, v, do, heads, window)
    slots = fa._ring_slots(bq, bk, t, window)
    assert slots < t // bq or window == t - 1

    def grads(form):
        return fa._bwd_kernels(
            q, k, v, do, lse, delta, num_heads=heads, sm_scale=d ** -0.5,
            causal=True, q_offset=0, bq=bq, bk=bk, form=form, window=window)

    ring = grads("ring")
    for got, pair, name in zip(ring, grads("pair"), "qkv"):
        np.testing.assert_allclose(got, pair, atol=2e-6, rtol=2e-6,
                                   err_msg="d" + name)
    want = jax.vjp(lambda q, k, v: _plain(q, k, v, heads, window),
                   q, k, v)[1](do)
    for got, ref, name in zip(ring, want, "qkv"):
        np.testing.assert_allclose(got, ref, atol=5e-5, rtol=5e-5,
                                   err_msg="d" + name)


def test_the_ring_rounds_as_the_walking_pair_does():
    """bfloat16 operands: p and ds rounded to the operands' type before
    their second products and float32 sums, in the ring kernel as in the
    pair, so the two agree to a rounding of the result's type."""
    heads, t, d, window = 2, 1024, 128, 200
    q, k, v, do = (x.astype(jnp.bfloat16)
                   for x in _operands(t, heads, d, seed=17))
    lse, delta = _saved(q, k, v, do, heads, window)
    ring, pair = (fa._bwd_kernels(
        q, k, v, do, lse, delta, num_heads=heads, sm_scale=d ** -0.5,
        causal=True, q_offset=0, bq=128, bk=128, form=form, window=window)
        for form in ("ring", "pair"))
    for got, ref in zip(ring, pair):
        assert got.dtype == jnp.bfloat16
        scale = float(jnp.max(jnp.abs(ref.astype(jnp.float32))))
        np.testing.assert_allclose(got.astype(jnp.float32),
                                   ref.astype(jnp.float32),
                                   atol=2 ** -7 * scale)


@pytest.mark.parametrize("window,bq,bk", [(100, 128, 128), (300, 256, 128),
                                          (200, 128, 256), (384, 128, 128)])
def test_window_with_heads_held_apart_and_named_blocks(window, bq, bk):
    """[batch, heads, seq, dim] operands at blocks the caller names,
    wider and narrower than the window."""
    rs = np.random.RandomState(window)
    q, k, v, do = (jnp.asarray(0.5 * rs.randn(1, 2, 512, 64), jnp.float32)
                   for _ in range(4))

    def flash(q, k, v):
        return fa.flash_attention(q, k, v, None, True, bq, bk, 0, window)

    def plain(q, k, v):
        return fa.split_heads(_plain(*(fa.merge_heads(x) for x in (q, k, v)),
                                     2, window), 2)

    out, vjp = jax.vjp(flash, q, k, v)
    want, want_vjp = jax.vjp(plain, q, k, v)
    np.testing.assert_allclose(out, want, atol=2e-5, rtol=2e-5)
    for got, ref in zip(vjp(do), want_vjp(do)):
        np.testing.assert_allclose(got, ref, atol=5e-5, rtol=5e-5)


@pytest.mark.parametrize("window,bq,bk", [(100, 128, 128), (300, 256, 128),
                                          (200, 128, 256), (384, 128, 128)])
def test_ring_with_heads_held_apart_and_named_blocks(monkeypatch, window, bq,
                                                     bk):
    """[batch, heads, seq, dim] operands (64 lanes a head, the scratch
    padded to 128) at blocks the caller names, where the budget has room
    for the ring: the kernel's name and both window counters' label say
    which form ran."""
    t = 1024
    rs = np.random.RandomState(window)
    q, k, v, do = (jnp.asarray(0.5 * rs.randn(1, 2, t, 64), jnp.float32)
                   for _ in range(4))
    _ring_budget(monkeypatch, 64, 1, t, window, bq=bq, bk=bk)
    assert fa._choose_bwd_blocks(q.shape, k.shape, 4, bq, bk,
                                 window=window) == (bq, bk, "ring")

    def flash(q, k, v):
        return fa.flash_attention(q, k, v, None, True, bq, bk, 0, window)

    def plain(q, k, v):
        return fa.split_heads(_plain(*(fa.merge_heads(x) for x in (q, k, v)),
                                     2, window), 2)

    before = telemetry.snapshot()
    out, vjp = jax.vjp(flash, q, k, v)
    want, want_vjp = jax.vjp(plain, q, k, v)
    np.testing.assert_allclose(out, want, atol=2e-5, rtol=2e-5)
    for got, ref in zip(vjp(do), want_vjp(do)):
        np.testing.assert_allclose(got, ref, atol=5e-5, rtol=5e-5)
    after = telemetry.snapshot()
    for counter, labels in (
            ("flash_attention_window_lowerings_total",
             "block_k=%d,block_q=%d,kernel=ring,window=%d"
             % (bk, bq, window)),
            ("flash_attention_bwd_lowerings_total",
             "block_k=%d,block_q=%d,heads_per_step=1,kernel=ring"
             % (bk, bq))):
        key = "%s{%s}" % (counter, labels)
        assert after[key] - before.get(key, 0) == 1, key
    names = _kernel_names(lambda q, k, v: jax.vjp(flash, q, k, v)[1](do),
                          q, k, v)
    assert [n for n in names if "_bwd" in n] == [
        "flash_attention_bwd_ring_q%d_k%d_s128_w%d" % (bq, bk, window)]


def _kernel_names(fn, *args):
    return sorted(set(
        eqn.params["name"]
        for eqn in _pallas_calls(jax.make_jaxpr(fn)(*args).jaxpr)))


def _pallas_calls(jaxpr):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _pallas_calls(sub)


@pytest.mark.parametrize("window", [0, 256, 300])
def test_no_window_traces_the_causal_kernels(window):
    """With `window` 0, or one that reaches the whole sequence, the
    traced program is, text for text, the one a call that never heard of
    a window traces; with a window the kernels' names carry it."""
    q, k, v, do = _operands(256, 2, 64, seed=3)

    def step(window):
        def run(q, k, v):
            out, vjp = jax.vjp(
                lambda q, k, v: fa.flash_attention_with_lse(
                    q, k, v, None, True, num_heads=2, **window)[0], q, k, v)
            return out, vjp(do)
        return run

    plain = str(jax.make_jaxpr(step({}))(q, k, v))
    assert str(jax.make_jaxpr(step({"window": window}))(q, k, v)) == plain
    assert "_w" not in " ".join(_kernel_names(step({}), q, k, v))
    names = _kernel_names(step({"window": 100}), q, k, v)
    assert len(names) == 2 and all(n.endswith("_w100_h2") for n in names)
    assert {n.split("_q")[0] for n in names} == {"flash_attention_fwd",
                                                 "flash_attention_bwd"}


def test_window_without_causal_is_refused():
    q, k, v, _ = _operands(128, 2, 64, seed=0)
    with pytest.raises(ValueError, match="window"):
        fa.flash_attention_with_lse(q, k, v, None, False, num_heads=2,
                                    window=64)
    data = fluid.layers.data(name="x", shape=[1, 128, 128],
                             dtype="float32", append_batch_size=False)
    with pytest.raises(ValueError, match="window"):
        fluid.layers.flash_attention(data, data, data, num_heads=2,
                                     window=64)


@pytest.mark.parametrize("t,window,bq,bk,widest", [
    (512, 100, 128, 128, 256), (512, 300, 256, 128, None),
    (1024, 256, 256, 256, 256), (1024, 700, 512, 256, None),
    (512, 1, 128, 128, 128)])
def test_score_pairs_under_a_window(t, window, bq, bk, widest):
    """Attended pairs are the mask's; folded ones hold every attended
    pair and no chunk wholly outside the window."""
    i = np.arange(t)[:, None]
    j = np.arange(t)[None, :]
    seen = (j <= i) & (j > i - window)
    folded, attended = fa.score_pairs(t, t, True, 0, bq, bk, widest, window)
    assert attended == seen.sum()
    touched = sum(
        bq * bk for a in range(t // bq) for c in range(t // bk)
        if seen[a * bq:(a + 1) * bq, c * bk:(c + 1) * bk].any())
    assert attended <= folded <= touched
    causal = fa.score_pairs(t, t, True, 0, bq, bk, widest)
    assert folded <= causal[0] and attended < causal[1]


def test_score_pairs_at_the_whole_context():
    """16,384 positions under a window of 4096: 58,722,304 attended
    pairs a head of the causal mask's 134,225,920, and the forward's
    blocks fold under a fifth more."""
    t, window = 16384, 4096
    shape = (1, 1, t, 128)
    bq, bk, resident = fa._choose_blocks(shape, shape, 2, window=window)
    assert not resident
    folded, attended = fa.score_pairs(t, t, True, 0, bq, bk, fa._STAIR,
                                      window)
    assert attended == 58722304
    assert fa.score_pairs(t, t, True, 0, bq, bk, fa._STAIR)[1] == 134225920
    assert attended < folded < 1.2 * attended


@pytest.mark.parametrize("window,largest", [(0, 1024), (4096, 1024),
                                            (512, 512), (300, 512),
                                            (128, 128), (7, 128)])
def test_choosers_bound_the_blocks_by_the_window(window, largest):
    """No chosen block is wider than the smallest that holds the
    window: a wider one folds mostly scores nobody attends."""
    shape = (1, 1, 4096, 128)
    for blocks in (fa._choose_blocks(shape, shape, 2, window=window),
                   fa._choose_bwd_blocks(shape, shape, 2, window=window)):
        assert max(blocks[:2]) <= largest
    if not window:
        assert fa._choose_blocks(shape, shape, 2) \
            == fa._choose_blocks(shape, shape, 2, window=0)


def test_pairs_counter_counts_under_the_window():
    """`flash_attention_pairs_total` rises by what `score_pairs` says of
    the window kernels, forward and backward."""
    heads, t, window = 2, 512, 128
    q, k, v, do = _operands(t, heads, 64, seed=5)
    call = fa._Call.of(q.shape, k.shape, heads)
    bq, bk, _ = fa._choose_blocks(*call.step_shapes, 4, window=window)
    bbq, bbk, one = fa._choose_bwd_blocks(*call.step_shapes, 4,
                                          heads=call.g, window=window)
    assert one
    expected = {
        "fwd": fa.score_pairs(t, t, True, 0, bq, bk, fa._STAIR, window),
        "bwd": fa.score_pairs(t, t, True, 0, bbq, bbk, fa._STAIR, window)}
    before = telemetry.snapshot()
    # a shape of its own: `_fwd_kernels` traces under jit, the counters
    # count in `_fwd` and `_bwd`, which run every time
    _, vjp = jax.vjp(lambda q, k, v: fa.flash_attention_with_lse(
        q, k, v, None, True, num_heads=heads, window=window)[0], q, k, v)
    vjp(do)
    after = telemetry.snapshot()
    for kernel_pass, (folded, attended) in expected.items():
        for kind, n in (("folded", folded), ("attended", attended)):
            key = "flash_attention_pairs_total{kind=%s,pass=%s}" \
                % (kind, kernel_pass)
            assert after[key] - before.get(key, 0) == heads * n, key


def test_pairs_counter_counts_the_rings_grid(monkeypatch):
    """The ring kernel folds the chunks the one kernel folds, the
    diagonal's as a staircase and the lower edge's whole, and
    `flash_attention_pairs_total` says so: fewer than the walking pair
    folds at the same blocks, which folds every crossed chunk whole."""
    heads, t, window = 2, 2048, 600
    q, k, v, do = _operands(t, heads, 128, seed=6)
    call = fa._Call.of(q.shape, k.shape, heads)
    _ring_budget(monkeypatch, call.lanes, call.g, t, window, bq=512, bk=512)
    # named: smaller blocks would leave the one kernel room
    assert fa._choose_bwd_blocks(*call.step_shapes, 4, 512, 512, call.g,
                                 window) == (512, 512, "ring")
    folded, attended = fa.score_pairs(t, t, True, 0, 512, 512, fa._STAIR,
                                      window)
    assert attended < folded \
        < fa.score_pairs(t, t, True, 0, 512, 512, None, window)[0]
    before = telemetry.snapshot()
    _, vjp = jax.vjp(lambda q, k, v: fa.flash_attention_with_lse(
        q, k, v, None, True, 512, 512, 0, heads, window)[0], q, k, v)
    vjp(do)
    after = telemetry.snapshot()
    for kind, n in (("folded", folded), ("attended", attended)):
        key = "flash_attention_pairs_total{kind=%s,pass=bwd}" % kind
        assert after[key] - before.get(key, 0) == heads * n, key


def test_flash_attention_op_takes_a_window():
    """The `flash_attention` op and its gradient op carry `window`: a
    Program's loss and its gradients against plain masked attention."""
    heads, t, d, window = 2, 256, 64, 100
    q, k, v, _ = _operands(t, heads, d, seed=9)
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        feeds = [fluid.layers.data(name=n, shape=[1, t, heads * d],
                                   dtype="float32", append_batch_size=False,
                                   stop_gradient=False) for n in "qkv"]
        out = fluid.layers.flash_attention(*feeds, num_heads=heads,
                                           causal=True, window=window)
        loss = fluid.layers.mean(x=out * out)
        fluid.backward.append_backward(loss)
    op = [o for o in main.global_block().desc.ops
          if o.type == "flash_attention"][0]
    assert op.attrs["window"] == window
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    exe.run(startup, scope=scope)
    got = exe.run(main, feed=dict(zip("qkv", (q, k, v))),
                  fetch_list=[loss] + [n + "@GRAD" for n in "qkv"],
                  scope=scope)

    def plain_loss(q, k, v):
        return jnp.mean(_plain(q, k, v, heads, window) ** 2)

    want, grads = jax.value_and_grad(plain_loss, (0, 1, 2))(q, k, v)
    np.testing.assert_allclose(got[0].reshape(()), want, rtol=1e-5)
    for g, ref in zip(got[1:], grads):
        np.testing.assert_allclose(g, ref, atol=1e-7, rtol=2e-4)
