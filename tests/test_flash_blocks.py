"""How the flash-attention forward kernel tiles itself from the shapes it
sees (kernels/flash_attention.py: `_choose_blocks`, `_step_bytes`,
`_VMEM_BUDGET`): the chooser as a pure function, the kernel's agreement
with dense attention at the chosen blocks under the Pallas interpreter,
the backward kernels' own chooser and their agreement with dense
attention's gradients, the two layouts the kernels index
([batch, heads, seq, dim] and [batch, seq, heads * dim]) against each
other, and the counters that name the tilings."""

import collections
import importlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.obs import telemetry

# the package exports the function under the module's name
fa = importlib.import_module("paddle_tpu.kernels.flash_attention")


# -- the chooser --------------------------------------------------------------

CHOOSER_SHAPES = [
    (1024, 1024, 64, jnp.bfloat16),
    (512, 512, 64, jnp.bfloat16),
    (4096, 4096, 128, jnp.bfloat16),
    (32768, 32768, 128, jnp.bfloat16),
    (1024, 1024, 64, jnp.float32),
    (200, 200, 16, jnp.float32),
    (128, 1024, 64, jnp.bfloat16),
]


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("tq,tk,d,dtype", CHOOSER_SHAPES)
def test_chosen_blocks_tile_the_sequences_within_the_budget(
        tq, tk, d, dtype, causal):
    itemsize = jnp.dtype(dtype).itemsize
    bq, bk, resident = fa._choose_blocks(
        (8, 16, tq, d), (8, 16, tk, d), itemsize)
    # a block divides its sequence; one that is no multiple of 128 is
    # the whole of a sequence nothing else tiles
    assert tq % bq == 0 and tk % bk == 0
    assert bq % 128 == 0 or bq == tq
    assert bk % 128 == 0 or bk == tk
    kv_rows = tk if resident else bk
    assert fa._step_bytes(bq, bk, kv_rows, d, itemsize) <= fa._VMEM_BUDGET
    if not resident:
        assert fa._step_bytes(bq, bk, tk, d, itemsize) > fa._VMEM_BUDGET
    if (tq, tk, d) == (1024, 1024, 64):
        # the benchmark's shape: 8 x 16 heads; 128 x 128 blocks made
        # 128 x 8 x 8 = 8192 grid steps a call
        assert 8 * 16 * (tq // bq) * (tk // kv_rows) <= 1024
    _pairs_are_bounded(tq, tk, causal, bq, bk, fa._STAIR)


def _pairs_are_bounded(tq, tk, causal, bq, bk, widest):
    """The mask does not enter the choice of blocks: whatever they are,
    a staircase holds under half a piece's width of pairs a query beyond
    those it attends; only a ragged sequence, one block that no piece
    divides, is folded whole."""
    folded, attended = fa.score_pairs(tq, tk, causal, 0, bq, bk, widest)
    if not causal:
        assert folded == attended == tq * tk
    elif tq % 128 == 0 and widest:
        assert attended <= folded < attended + tq * widest / 2
    else:
        assert folded == fa.score_pairs(tq, tk, True, 0, bq, bk, None)[0]
        assert folded > attended

def test_a_named_block_is_kept_beside_a_chosen_one():
    shape = (1, 8, 4096, 128)
    assert fa._choose_blocks(shape, shape, 2, 128, 128)[:2] \
        == (128, 128)
    bq, bk, _ = fa._choose_blocks(shape, shape, 2, block_q=256)
    assert bq == 256 and bk > 256
    bq, bk, _ = fa._choose_blocks(shape, shape, 2, block_k=256)
    assert bk == 256 and bq > 256


def test_a_long_sequence_nothing_tiles_is_refused():
    shape = (1, 8, 32769, 128)
    with pytest.raises(ValueError, match=r"32769.*\(1, 8, 32769, 128\)"):
        fa._choose_blocks(shape, shape, 2)
    with pytest.raises(ValueError, match=r"32769.*\(1, 8, 32769, 128\)"):
        fa.flash_attention(*(jnp.zeros(shape, jnp.bfloat16),) * 3)


# -- the kernel at the blocks it chooses --------------------------------------

def _qkv(tq, tk, d=16, heads=2, seed=0):
    rs = np.random.RandomState(seed)

    def mk(t):
        return jnp.asarray(rs.randn(1, heads, t, d).astype(np.float32))

    return mk(tq), mk(tk), mk(tk)


# (Tq, Tk, q_offset): a square of three blocks a side, and the last
# query shard of a longer key side (what ring attention hands over);
# 128 is the only block size that divides any of them
SHAPES = [(384, 384, 0), (384, 640, 256)]
# 128 x 128 blocks at head size 16 in float32 hold 811008 bytes by
# `_step_bytes` with one chunk of K/V in VMEM, 1351680 with 384 keys and
# 1892352 with 640, and by `_bwd_step_bytes` 1327104 in the two kernels
# that walk and 2482176 in the one that holds a head's 384 queries: the
# first budget keeps K/V resident in the forward and gives the backward
# its one kernel, the second makes the grids walk in both
BUDGETS = {"resident": 2500000, "walked": 1340000}


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("tq,tk,q_offset", SHAPES)
@pytest.mark.parametrize("kv", sorted(BUDGETS))
def test_chosen_blocks_match_dense_attention(monkeypatch, kv, tq, tk,
                                             q_offset, causal):
    monkeypatch.setattr(fa, "_VMEM_BUDGET", BUDGETS[kv])
    q, k, v = _qkv(tq, tk)
    bq, bk, resident = fa._choose_blocks(q.shape, k.shape, 4)
    # several blocks on both axes, and the path the budget asks for
    assert tq // bq >= 2 and tk // bk >= 2
    assert resident == (kv == "resident")
    assert fa._choose_bwd_blocks(q.shape, k.shape, 4) \
        == (bq, bk, "one" if resident else "pair")

    def loss(attention):
        return lambda q, k, v: jnp.sum(jnp.sin(attention(q, k, v)))

    flash = lambda q, k, v: fa.flash_attention(   # noqa: E731
        q, k, v, None, causal, None, None, q_offset)
    dense = lambda q, k, v: fa.reference_attention(   # noqa: E731
        q, k, v, None, causal, q_offset)
    np.testing.assert_allclose(flash(q, k, v), dense(q, k, v), atol=2e-5)
    got = jax.grad(loss(flash), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss(dense), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, atol=5e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_the_log_sum_exp_is_an_output_with_a_gradient_of_its_own(causal):
    """`flash_attention_with_lse` gives each row's log-sum-exp beside o,
    and a cotangent of it reaches dq and dk (d lse / d s = p) as dense
    attention's does."""
    q, k, v = _qkv(256, 256)

    def dense(q, k, v):
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * q.shape[-1] ** -0.5
        if causal:
            s = jnp.where(jnp.tril(jnp.ones(s.shape[-2:], bool)), s,
                          fa.NEG_INF)
        return (fa.reference_attention(q, k, v, None, causal),
                jax.nn.logsumexp(s, axis=-1))

    def flash(q, k, v):
        return fa.flash_attention_with_lse(q, k, v, None, causal)

    def loss(attention):
        def f(q, k, v):
            o, lse = attention(q, k, v)
            return jnp.sum(jnp.sin(o)) + jnp.sum(jnp.cos(lse))
        return f

    for got, want in zip(flash(q, k, v), dense(q, k, v)):
        np.testing.assert_allclose(got, want, atol=2e-5)
    assert flash(q, k, v)[1].dtype == jnp.float32
    got = jax.grad(loss(flash), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss(dense), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, atol=5e-5)


def test_forward_statistics_keep_their_shapes_and_meaning():
    """(o, m, l) as the backward and ring attention read them: m the row
    maximum of the scaled, masked scores, l the row sum of exp(s - m)."""
    q, k, v = _qkv(256, 256)
    o, m, l = fa._fwd(q, k, v, 0.25, True, None, None, 0)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * 0.25
    s = jnp.where(jnp.tril(jnp.ones((256, 256), bool)), s, fa.NEG_INF)
    assert o.shape == q.shape and m.shape == l.shape == q.shape[:3]
    np.testing.assert_allclose(m, s.max(-1), rtol=1e-6)
    np.testing.assert_allclose(l, jnp.exp(s - m[..., None]).sum(-1),
                               rtol=1e-5)


# -- the backward's blocks are its own -----------------------------------------

@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("tq,tk,d,dtype", CHOOSER_SHAPES)
def test_backward_blocks_tile_the_sequences_within_the_budget(
        tq, tk, d, dtype, causal):
    itemsize = jnp.dtype(dtype).itemsize
    q_shape, k_shape = (8, 16, tq, d), (8, 16, tk, d)
    bq, bk, form = fa._choose_bwd_blocks(q_shape, k_shape, itemsize)
    one_kernel = form == "one"
    assert form in ("one", "pair")
    assert tq % bq == 0 and tk % bk == 0
    assert bq % 128 == 0 or bq == tq
    assert bk % 128 == 0 or bk == tk
    # a step holds four chunks of scores where the forward's holds two
    assert fa._bwd_step_bytes(bq, bk, d, itemsize) \
        > fa._step_bytes(bq, bk, bk, d, itemsize)
    assert fa._bwd_step_bytes(bq, bk, d, itemsize,
                              tq if one_kernel else None) <= fa._VMEM_BUDGET
    if not one_kernel:
        # not even the smallest blocks leave room for a head's queries
        assert fa._bwd_step_bytes(min(128, tq), min(128, tk), d, itemsize,
                                  tq) > fa._VMEM_BUDGET
    # the benchmark's shapes get the one kernel, 32k positions the two
    assert one_kernel == (tq <= 4096)
    # the one kernel folds a crossed chunk as a staircase, the two that
    # walk fold it whole
    _pairs_are_bounded(tq, tk, causal, bq, bk,
                       fa._STAIR if one_kernel else None)
    # a named block is kept beside a chosen one
    if tq % 128 == 0:
        assert fa._choose_bwd_blocks(q_shape, k_shape, itemsize,
                                     block_q=128)[0] == 128
        assert fa._choose_bwd_blocks(q_shape, k_shape, itemsize,
                                     128, 128)[:2] == (128, 128)


def _grads(attention, q, k, v, do):
    return jax.vjp(attention, q, k, v)[1](do)


@pytest.mark.parametrize("d,tolerance", [(64, 0.03), (128, 0.03)])
def test_bf16_gradients_agree_with_dense_float32_attention(d, tolerance):
    """Operands of every product in bf16, as both cells run it, float32
    to accumulate: each gradient lies within 3% of its largest entry of
    what dense attention gives in float32 on the same (bf16) values."""
    q, k, v = (x.astype(jnp.bfloat16) for x in _qkv(256, 256, d=d, seed=3))
    do = _qkv(256, 256, d=d, seed=4)[0].astype(jnp.bfloat16)
    assert fa._choose_bwd_blocks(q.shape, k.shape, 2) == (256, 256, "one")
    got = _grads(lambda q, k, v: fa.flash_attention(q, k, v, None, True),
                 q, k, v, do)
    want = _grads(
        lambda q, k, v: fa.reference_attention(q, k, v, None, True),
        *(x.astype(jnp.float32) for x in (q, k, v, do)))
    for a, b in zip(got, want):
        assert a.dtype == jnp.bfloat16
        np.testing.assert_allclose(
            a.astype(jnp.float32), b,
            atol=tolerance * float(jnp.max(jnp.abs(b))))


@pytest.mark.parametrize("tq,tk,q_offset,unseen_from", [
    # the first 128 queries of 384 positions: key blocks 1 and 2 are
    # seen by no query
    (128, 384, 0, 128),
    # a query shard that starts at position 128 and stops before the
    # last key block
    (256, 512, 128, 384),
])
def test_key_blocks_no_query_sees_get_exact_zeros(tq, tk, q_offset,
                                                  unseen_from):
    q, k, v = _qkv(tq, tk, seed=5)
    do = _qkv(tq, tk, seed=6)[0]
    got = _grads(lambda q, k, v: fa.flash_attention(
        q, k, v, None, True, None, None, q_offset), q, k, v, do)
    want = _grads(lambda q, k, v: fa.reference_attention(
        q, k, v, None, True, q_offset), q, k, v, do)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, atol=5e-5)
    dq, dk, dv = got
    assert np.abs(np.asarray(dk[:, :, :unseen_from])).max() > 0
    assert not np.asarray(dk[:, :, unseen_from:]).any()
    assert not np.asarray(dv[:, :, unseen_from:]).any()


@pytest.mark.parametrize("causal", [False, True])
def test_a_ragged_sequence_is_one_whole_block_in_the_backward(causal):
    q, k, v = _qkv(200, 200, seed=7)
    do = _qkv(200, 200, seed=8)[0]
    assert fa._choose_bwd_blocks(q.shape, k.shape, 4) \
        == (200, 200, "one")
    got = _grads(lambda q, k, v: fa.flash_attention(q, k, v, None, causal),
                 q, k, v, do)
    want = _grads(lambda q, k, v: fa.reference_attention(
        q, k, v, None, causal), q, k, v, do)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, atol=5e-5)


@pytest.mark.parametrize("budget", [None, 0])
def test_named_blocks_of_16_are_kept_by_one_kernel_and_by_two(
        monkeypatch, budget):
    if budget is not None:
        # named blocks are kept though nothing fits: the two kernels
        monkeypatch.setattr(fa, "_VMEM_BUDGET", budget)
    q, k, v = _qkv(64, 64, seed=9)
    do = _qkv(64, 64, seed=10)[0]
    assert fa._choose_bwd_blocks(q.shape, k.shape, 4, 16, 16) \
        == (16, 16, "one" if budget is None else "pair")
    got = _grads(lambda q, k, v: fa.flash_attention(
        q, k, v, None, True, 16, 16), q, k, v, do)
    want = _grads(lambda q, k, v: fa.reference_attention(
        q, k, v, None, True), q, k, v, do)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, atol=5e-5)


# -- a chunk the diagonal crosses is folded as a staircase ---------------------

# name: (tq, tk, q_offset, head dim, heads a lane block, bq, bk, the places
# the diagonal enters a crossed chunk, None where it is folded whole;
# a block of one piece's width is one piece, all of it)
STAIRCASES = {
    # square blocks, the diagonal through their corners; two 64-wide
    # heads a grid step, as gpt2m-train runs them
    "start": (512, 512, 0, 64, 2, 256, 256, [0]),
    # one block is the whole sequence: two pieces and no other chunk
    "one_block": (512, 512, 0, 128, 1, 512, 512, [0]),
    # two key chunks a query block, as Ouro's forward: the second is
    # entered in the middle of its queries; in the backward every other
    # key block is
    "middle": (512, 512, 0, 128, 1, 512, 256, [0, 256]),
    # two query chunks a key block: the first keys of every other chunk
    # lie behind its first query and are folded unmasked
    "behind": (512, 512, 0, 32, 4, 256, 512, [-256, 0]),
    # a query shard at an offset that is a multiple of a piece and of no
    # block
    "shard": (256, 512, 128, 64, 1, 256, 256, [-128, 128]),
    # an offset that is no multiple of 128: every crossed chunk whole
    "unaligned": (256, 512, 200, 64, 2, 256, 256, None),
    # a decode step's few queries at the end of the keys
    "decode": (8, 512, 504, 64, 1, 8, 256, None),
}


@pytest.mark.parametrize("case", sorted(STAIRCASES))
def test_a_staircase_is_dense_float32_attention(case):
    """Where the blocks and the offset are multiples of a piece, a chunk
    the diagonal crosses is folded a piece of keys at a time over the
    queries at or after it alone; elsewhere whole, every score compared:
    o, the log-sum-exp and dq, dk, dv are dense attention's either way,
    and the pairs counted say which way it went."""
    tq, tk, q_offset, d, g, bq, bk, leads = STAIRCASES[case]
    heads = 2 * g
    rs = np.random.RandomState(12)
    q, k, v, do = (jnp.asarray(0.5 * rs.randn(1, t, heads * d), jnp.float32)
                   for t in (tq, tk, tk, tq))
    s = fa._stair_width(bq, bk, q_offset, fa._STAIR)
    assert (s is None) == (leads is None)
    if s is not None:
        assert fa._leads(bq, bk, q_offset) == leads
    folded, attended = fa.score_pairs(tq, tk, True, q_offset, bq, bk,
                                      fa._STAIR)
    whole = fa.score_pairs(tq, tk, True, q_offset, bq, bk, None)[0]
    assert attended == sum(min(q_offset + i + 1, tk) for i in range(tq))
    assert attended <= folded <= whole
    assert (folded == whole) == (leads is None or s == bq == bk)

    def flash(q, k, v):
        return fa.flash_attention_with_lse(q, k, v, None, True, bq, bk,
                                           q_offset, heads)

    def dense(q, k, v):
        q, k, v = (fa.split_heads(x, heads) for x in (q, k, v))
        scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) * d ** -0.5
        sees = (q_offset + jnp.arange(tq))[:, None] >= jnp.arange(tk)
        return (fa.merge_heads(fa.reference_attention(
                    q, k, v, None, True, q_offset)),
                jax.nn.logsumexp(jnp.where(sees, scores, fa.NEG_INF), -1))

    before = telemetry.snapshot()
    got, vjp = jax.vjp(flash, q, k, v)
    want, dense_vjp = jax.vjp(dense, q, k, v)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, atol=2e-5)
    for a, b in zip(vjp((do, jnp.cos(got[1]))),
                    dense_vjp((do, jnp.cos(want[1])))):
        np.testing.assert_allclose(a, b, atol=5e-5)
    # the forward and the backward's one kernel each counted their pairs
    rose = telemetry.snapshot_delta(before)
    for kernel_pass in ("fwd", "bwd"):
        assert [rose["flash_attention_pairs_total{kind=%s,pass=%s}"
                     % (kind, kernel_pass)]
                for kind in ("folded", "attended")] \
            == [heads * folded, heads * attended]


def test_stairs_cover_what_the_queries_see_once():
    """The pieces of a crossed chunk hold every pair a query attends
    exactly once and, but for the triangle above the diagonal of each
    piece's leading square, nothing else."""
    for bq, bk, s in ((512, 512, 128), (1024, 512, 128), (512, 256, 256),
                      (256, 512, 128)):
        for lead in fa._leads(bq, bk, 0) + fa._leads(bq, bk, s):
            held = np.zeros((bk, bq), int)
            for key, keys, query, crossed in fa._stairs(lead, bq, bk, s):
                assert keys % s == 0 and query % s == 0
                held[key:key + keys, query:] += 1
                assert crossed == (lead + key >= 0)
            sees = np.arange(bq)[None, :] - np.arange(bk)[:, None] >= lead
            assert held.max() == 1 and (held >= sees).all()
            # a piece is s keys wide: it holds under s / 2 pairs a query
            # beyond the attended ones
            assert held.sum() - sees.sum() < bq * s / 2
            assert held.sum() == fa._chunk_pairs(lead, bq, bk, s)


# -- operands as a projection writes them: the heads side by side -------------

def _merged(t, heads, d, seed, dtype=jnp.float32):
    rs = np.random.RandomState(seed)
    return [jnp.asarray(0.5 * rs.randn(2, t, heads * d), dtype)
            for _ in range(4)]


def _both_layouts(q, k, v, do, heads, causal):
    """((o, lse), (dq, dk, dv)) from operands [batch, seq, heads * dim]
    as they are, and the same through [batch, heads, seq, dim], every
    result put back side by side."""
    def merged(q, k, v):
        return fa.flash_attention_with_lse(q, k, v, None, causal,
                                           num_heads=heads)

    def apart(q, k, v):
        o, lse = fa.flash_attention_with_lse(
            *(fa.split_heads(x, heads) for x in (q, k, v)), None, causal)
        return fa.merge_heads(o), lse

    results = []
    for attention in (merged, apart):
        out, vjp = jax.vjp(attention, q, k, v)
        results.append((out, vjp((do, jnp.cos(out[1])))))
    return results


# the head sizes a 128-lane block holds whole (four, two, one a grid
# step), and one it does not: that call splits its heads around the
# kernel as every call once did
HEADS_A_STEP = {32: 4, 64: 2, 128: 1, 96: "split"}


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("kernels", ["one", "walking"])
@pytest.mark.parametrize("d", sorted(HEADS_A_STEP))
def test_heads_side_by_side_are_the_heads_held_apart(monkeypatch, d,
                                                     kernels, causal):
    """[batch, seq, heads * dim] operands, the head picked by the
    BlockSpecs, give what [batch, heads, seq, dim] operands give: o, the
    log-sum-exp, dq, dk and dv, through the one backward kernel and
    through the pair that walks, and the counters say how many heads a
    grid step held."""
    heads, t = 4, 256
    q, k, v, do = _merged(t, heads, d, seed=11)
    call = fa._Call.of(q.shape, k.shape, heads)
    assert (call.g or "split") == HEADS_A_STEP[d]
    apart = fa._Call.of((2, heads, t, d), (2, heads, t, d), None)
    if kernels == "walking":
        # room for the pair's 128 x 128 blocks, none for a grid step's
        # 256 queries beside them
        monkeypatch.setattr(fa, "_VMEM_BUDGET", fa._bwd_step_bytes(
            128, 128, (call if call.g else apart).lanes, 4, None,
            call.g or 1))
    # what each call lowers: the side-by-side one under its own label
    # (its inner call's, where it splits its heads), the other under 1
    expected = collections.Counter()
    for inner, label in ((call if call.g else apart, HEADS_A_STEP[d]),
                         (apart, "apart")):
        one_kernel = fa._choose_bwd_blocks(*inner.step_shapes, 4,
                                           heads=inner.g)[2] == "one"
        if label == "apart":
            label = 1
        else:
            assert one_kernel == (kernels == "one")
        expected["flash_attention_lowerings_total{%s}" % label] += 1
        expected["flash_attention_bwd_lowerings_total{%s}" % label] \
            += 1 if one_kernel else 2
    before = telemetry.snapshot()
    (out, grads), (out_apart, grads_apart) = _both_layouts(
        q, k, v, do, heads, causal)
    rose = collections.Counter()
    for key, n in telemetry.snapshot().items():
        if key.startswith("flash_attention_") and "heads_per_step" in key:
            name, labels = key.rstrip("}").split("{")
            labels = dict(pair.split("=") for pair in labels.split(","))
            rose["%s{%s}" % (name, labels["heads_per_step"])] \
                += n - before.get(key, 0)
    rose = {key: n for key, n in rose.items() if n}
    assert rose == dict(expected)
    assert out[0].shape == q.shape and out[1].shape == (2, heads, t)
    for a, b in zip(out + grads, out_apart + grads_apart):
        assert a.shape == b.shape and a.dtype == b.dtype
        np.testing.assert_allclose(a, b, atol=2e-6)
    # and both are dense attention's
    want = fa.merge_heads(fa.reference_attention(
        *(fa.split_heads(x, heads) for x in (q, k, v)), None, causal))
    np.testing.assert_allclose(out[0], want, atol=2e-5)


@pytest.mark.parametrize("shape,heads,forward,backward,apart", [
    # gpt2m-train: 16 heads of 64, two a grid step
    ((8, 1024, 1024), 16, (1024, 1024, True), (512, 512, "one"),
     (1024, 512, "one")),
    # ouro-train-4k and olmoe-train-4k: 16 heads of 128
    ((1, 4096, 2048), 16, (1024, 512, True), (512, 256, "one"),
     (512, 256, "one")),
])
def test_the_cells_tilings_are_those_of_heads_held_apart(
        shape, heads, forward, backward, apart):
    """The blocks chosen for the benchmark's attention, bfloat16, are
    the same whether a grid step sees one [batch, heads, seq, dim] head
    or the heads that share a lane block of [batch, seq, heads * dim],
    but for the backward at GPT-2's shape.  Since a crossed chunk is
    folded as a staircase the mask does not enter the choice: a causal
    block was at most half its sequence so that the diagonal cut work
    off, which the staircase now does whatever the blocks.  GPT-2's
    forward holds all 1024 queries and keys of a grid step's heads in
    one block (half the grid steps, the same pairs: 0.403 ms a call on
    the chip against 0.467 at 512 x 512).  Its backward holds 512 x 512
    chunks as before where two heads' accumulators share the step, and
    1024 x 512 where one head leaves room for them (0.705 ms a call
    against 0.721, heads held apart)."""
    b, t, width = shape
    for call, blocks in (
            (fa._Call.of(shape, shape, heads), backward),
            (fa._Call.of((b, heads, t, width // heads),
                         (b, heads, t, width // heads), None), apart)):
        assert fa._choose_blocks(*call.step_shapes, 2) == forward
        assert fa._choose_bwd_blocks(*call.step_shapes, 2,
                                     heads=call.g) == blocks
        # and two heads a step fit where one did
        assert fa._bwd_step_bytes(
            backward[0], backward[1], call.lanes, 2, t, call.g) \
            <= fa._VMEM_BUDGET


# -- the counter names the tiling ---------------------------------------------

def _lowerings(bq, bk, resident, heads_per_step=1):
    return telemetry.snapshot().get(
        "flash_attention_lowerings_total{block_k=%d,block_q=%d,"
        "heads_per_step=%s,kv_resident=%s}"
        % (bk, bq, heads_per_step, str(resident).lower()), 0)


@pytest.mark.parametrize("named", [None, 128])
def test_counter_rises_once_per_lowering(named):
    x = jax.ShapeDtypeStruct((8, 16, 1024, 64), jnp.bfloat16)
    labels = fa._choose_blocks(x.shape, x.shape, 2, named, named)
    assert labels == ((1024, 1024, True) if named is None
                      else (128, 128, True))
    before = _lowerings(*labels)
    fn = jax.jit(lambda q, k, v: fa.flash_attention(
        q, k, v, None, True, named, named))
    fn.lower(x, x, x)
    assert _lowerings(*labels) == before + 1
    # forward and backward of one call hold the kernel once
    jax.jit(jax.grad(lambda q, k, v: fn(q, k, v).astype(
        jnp.float32).sum())).lower(x, x, x)
    assert _lowerings(*labels) == before + 2


def _pairs_counted():
    snapshot = telemetry.snapshot()
    return {(kernel_pass, kind): snapshot.get(
        "flash_attention_pairs_total{kind=%s,pass=%s}"
        % (kind, kernel_pass), 0)
        for kernel_pass in ("fwd", "bwd") for kind in ("folded", "attended")}


# a head's pairs, worked out by hand.  gpt2m-train, 1024 tokens: a query
# attends the keys up to its own, 1024 * 1025 / 2 = 524800 pairs.  The
# forward's one 1024 x 1024 block is four pieces of 256 keys over 1024,
# 768, 512 and 256 queries: 256 * 2560 = 655360.  The backward's four
# 512 x 512 chunks: one above the diagonal (none), one below (262144),
# two crossed, each two pieces of 256 keys over 512 and 256 queries,
# 256 * 768 = 196608: 655360 too.  Whole, the crossed chunks were
# 262144 each: 786432, 1.50 times the attended.
# ouro-train-4k, 4096 tokens: 4096 * 4097 / 2 = 8390656 attended.  The
# forward's 1024-query blocks meet 512-key chunks: 0 + 2 + 4 + 6 = 12
# below the diagonal (524288 each) and two crossed a block, the first
# entered at its corner (256 * (1024 + 768) = 458752), the second in the
# middle of its queries (256 * (512 + 256) = 196608): 6291456 + 4 *
# 655360 = 8912896; whole 20 chunks, 10485760, 1.25 times.  The
# backward's 256-key blocks meet 512-query chunks: block j sees 7 - j //
# 2 chunks whole (2 * 28 = 56, 131072 each) and crosses one, entered at
# its corner (one piece, all of it: 131072) or in the middle (256 * 256
# = 65536): 7340032 + 8 * 196608 = 8912896 too; whole 72 chunks,
# 9437184, 1.125 times.
CELL_PAIRS = [
    ((8, 1024, 1024), 16, 524800, (655360, 655360), (786432, 786432),
     (512, 512), (512, 512)),
    ((1, 4096, 2048), 16, 8390656, (8912896, 8912896), (10485760, 9437184),
     (1024, 512), (512, 256)),
]


@pytest.mark.parametrize(
    "shape,heads,attended,folded,whole,parent_fwd,parent_bwd", CELL_PAIRS)
def test_pairs_counter_says_what_the_mask_throws_away(
        shape, heads, attended, folded, whole, parent_fwd, parent_bwd):
    """`flash_attention_pairs_total` at the two attention cells' shapes:
    folded and attended pairs are the numbers worked out by hand above,
    folded / attended under 1.25 (GPT-2) and 1.07 (Ouro); a crossed
    chunk folded whole, as every one was before the staircase, costs
    1.50, 1.25 and 1.125 times the attended pairs at the blocks chosen
    then; and an offset that is no multiple of a piece takes the
    staircase out: the kernels' names lose `_s<width>` and the pairs
    counted are the whole chunks'."""
    batch, t, width = shape
    call = fa._Call.of(shape, shape, heads)
    blocks = (fa._choose_blocks(*call.step_shapes, 2)[:2],
              fa._choose_bwd_blocks(*call.step_shapes, 2, heads=call.g)[:2])
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16)

    def lowered(q_offset):
        before = _pairs_counted()
        text = jax.jit(jax.grad(
            lambda q, k, v: fa.flash_attention_with_lse(
                q, k, v, None, True, None, None, q_offset, heads)[0]
            .astype(jnp.float32).sum(), argnums=(0, 1, 2))).lower(x, x, x) \
            .as_text(debug_info=True)
        return text, {key: (n - before[key]) // (batch * heads)
                      for key, n in _pairs_counted().items()}

    text, rose = lowered(0)
    assert rose == {("fwd", "folded"): folded[0], ("fwd", "attended"): attended,
                    ("bwd", "folded"): folded[1], ("bwd", "attended"): attended}
    limit = 1.25 if t == 1024 else 1.07
    assert max(folded) / attended < limit
    for kernel_pass, (bq, bk) in zip(("fwd", "bwd"), blocks):
        assert "flash_attention_%s_q%d_k%d" % (kernel_pass, bq, bk) in text
    assert text.count("_s256") >= 2
    # what the parent's kernels folded, at the parent's blocks
    for (bq, bk), n, ratio in zip((parent_fwd, parent_bwd), whole,
                                  (1.5, 1.5) if t == 1024 else (1.25, 1.125)):
        assert fa.score_pairs(t, t, True, 0, bq, bk, None) == (n, attended)
        assert n / attended == pytest.approx(ratio, abs=2e-3)
    # 64 positions on: no piece of 128 starts on the diagonal
    text, rose = lowered(64)
    assert "_s256" not in text and "_s128" not in text
    for kernel_pass, (bq, bk) in zip(("fwd", "bwd"), blocks):
        assert (rose[kernel_pass, "folded"], rose[kernel_pass, "attended"]) \
            == fa.score_pairs(t, t, True, 64, bq, bk, None)
        assert rose[kernel_pass, "folded"] / rose[kernel_pass, "attended"] \
            > limit


def _bwd_lowerings(kernel, bq, bk, heads_per_step=1):
    return telemetry.snapshot().get(
        "flash_attention_bwd_lowerings_total{block_k=%d,block_q=%d,"
        "heads_per_step=%s,kernel=%s}" % (bk, bq, heads_per_step, kernel),
        0)


@pytest.mark.parametrize("shape,named,blocks,kernels", [
    ((8, 16, 1024, 64), None, (1024, 512), {"dq_dkv": ""}),
    ((8, 16, 1024, 64), 128, (128, 128), {"dq_dkv": ""}),
    ((1, 8, 32768, 128), None, (1024, 512),
     {"dkv": "_dkv", "dq": "_dq"}),
])
def test_backward_counter_rises_once_per_kernel_and_lowering(
        shape, named, blocks, kernels):
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    bq, bk, form = fa._choose_bwd_blocks(shape, shape, 2, named, named)
    assert (bq, bk) == blocks and tuple(kernels) == fa._BWD_KERNELS[form]
    before = [_bwd_lowerings(kernel, bq, bk) for kernel in kernels]

    def loss(q, k, v):
        return fa.flash_attention(q, k, v, None, True, named, named) \
            .astype(jnp.float32).sum()

    # the forward alone holds no backward kernel
    jax.jit(loss).lower(x, x, x)
    assert [_bwd_lowerings(kernel, bq, bk) for kernel in kernels] == before
    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(x, x, x) \
        .as_text(debug_info=True)
    assert [_bwd_lowerings(kernel, bq, bk) for kernel in kernels] \
        == [n + 1 for n in before]
    for suffix in kernels.values():
        assert "flash_attention_bwd%s_q%d_k%d" % (suffix, bq, bk) in text
    assert "flash_attention_bwd/" in text or "flash_attention_bwd)" in text
    # another program with the same attention counts again, though it
    # shares the kernels' trace
    jax.jit(jax.grad(lambda q, k, v: 2 * loss(q, k, v))).lower(x, x, x)
    assert [_bwd_lowerings(kernel, bq, bk) for kernel in kernels] \
        == [n + 2 for n in before]


# -- the three forms of the backward -------------------------------------------

def _pallas_calls(jaxpr):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _pallas_calls(sub)


def _backward_calls(shape, heads, q_offset=0, **window):
    """The backward's `pallas_call` equations of one bfloat16 call over
    [batch, seq, heads * dim] operands of `shape`."""
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda q, k, v: fa.flash_attention_with_lse(
            q, k, v, None, True, None, None, q_offset, heads, **window)[0]
        .astype(jnp.float32).sum(), argnums=(0, 1, 2)))(x, x, x)
    return _by_name(jaxpr, "flash_attention_bwd")


def _by_name(jaxpr, part):
    """One equation a kernel name holding `part`, in the order traced:
    `_on_platform` traces each kernel for Mosaic and for the
    interpreter."""
    calls = {}
    for eqn in _pallas_calls(jaxpr.jaxpr):
        if part in eqn.params["name"]:
            calls.setdefault(eqn.params["name"], eqn)
    return list(calls.values())


# smallthinker-train-16k-ep8's attention: 28 heads of 128 over 16,384
# positions, three layers of four under a window of 4096
SMALLTHINKER = ((1, 16384, 28 * 128), 28)


@pytest.mark.parametrize("window,blocks,names", [
    (4096, (512, 512, "ring"),
     ["flash_attention_bwd_ring_q512_k512_s256_w4096_h1"]),
    (0, (1024, 512, "pair"), ["flash_attention_bwd_dkv_q1024_k512_h1",
                              "flash_attention_bwd_dq_q1024_k512_h1"]),
    # a window no query reaches past is no window
    (16384, (1024, 512, "pair"), ["flash_attention_bwd_dkv_q1024_k512_h1",
                                  "flash_attention_bwd_dq_q1024_k512_h1"]),
])
def test_the_long_cells_backward_takes_the_ring_under_its_window(
        window, blocks, names):
    """A head's 16,384 queries do not fit VMEM: under the window the one
    kernel that walks the keys with nine slots of dq^T does, at 512 x
    512 (the largest chunk that fits beside the ring); the full layer,
    where every later query sees a block of keys, keeps the pair that
    walks at the blocks it had."""
    shape, heads = SMALLTHINKER
    call = fa._Call.of(shape, shape, heads)
    live = fa._live_window(window, True, call.tq, 0)
    assert fa._choose_bwd_blocks(*call.step_shapes, 2, heads=call.g,
                                 window=live) == blocks
    if blocks[2] == "ring":
        slots = fa._ring_slots(512, 512, call.tq, window)
        assert slots == 9
        assert fa._bwd_step_bytes(512, 512, 128, 2, ring=slots) \
            <= fa._VMEM_BUDGET < fa._bwd_step_bytes(1024, 512, 128, 2,
                                                    ring=5)
        assert fa._bwd_step_bytes(128, 128, 128, 2, call.tq) \
            > fa._VMEM_BUDGET
    calls = _backward_calls(shape, heads, window=window)
    assert sorted(eqn.params["name"] for eqn in calls) == names
    if blocks[2] == "ring":
        # a grid as long as the work: 32 blocks of keys, 9 of queries
        # each, where the pair's two grids are 16 x 32 and 32 x 16
        assert calls[0].params["grid_mapping"].grid == (1, 28, 32, 9)


@pytest.mark.parametrize("cell,shape,heads,blocks,name", [
    ("gpt2m-train", (8, 1024, 1024), 16, (512, 512),
     "flash_attention_bwd_q512_k512_s256_h2"),
    ("ouro-train-4k", (1, 4096, 2048), 16, (512, 256),
     "flash_attention_bwd_q512_k256_s256_h1"),
    ("olmoe-train-4k", (1, 4096, 2048), 16, (512, 256),
     "flash_attention_bwd_q512_k256_s256_h1"),
    ("granite-train-4k", (1, 4096, 2048), 32, (512, 256),
     "flash_attention_bwd_q512_k256_s256_h2"),
])
def test_the_other_cells_keep_the_one_kernel_at_their_blocks(
        cell, shape, heads, blocks, name):
    """The four other cells that train through these kernels: a head's
    queries fit, so the one kernel, under the name the ledger's
    `device_ops` hold (PR 50), whatever else the chooser has learned."""
    call = fa._Call.of(shape, shape, heads)
    assert fa._choose_bwd_blocks(*call.step_shapes, 2, heads=call.g) \
        == blocks + ("one",)
    calls = _backward_calls(shape, heads)
    assert [eqn.params["name"] for eqn in calls] == [name]
    assert calls[0].params["grid_mapping"].grid \
        == (shape[0], heads // call.g, shape[1] // blocks[1])


# what the ring kernel does not take, at the long cell's step shapes
# unless a row says otherwise: (q rows, k rows, named blocks, window,
# q_offset)
DECLINED = {
    "no window": (16384, 16384, None, 0, 0),
    "a shard's offset": (16384, 16384, None, 4096, 512),
    "more keys than queries": (8192, 16384, None, 4096, 0),
    "a block of no whole lane blocks": (400, 400, (200, 200), 100, 0),
    "blocks neither a multiple of the other": (1536, 1536, (256, 384),
                                               300, 0),
}


@pytest.mark.parametrize("why", sorted(DECLINED))
def test_calls_the_ring_declines_fall_back_to_the_pair(monkeypatch, why):
    tq, tk, named, window, q_offset = DECLINED[why]
    bq, bk = named or (None, None)
    if named:
        # room for the ring these blocks would ask for, none for the head
        monkeypatch.setattr(fa, "_VMEM_BUDGET", fa._bwd_step_bytes(
            bq, bk, 128, 2, ring=fa._ring_slots(bq, bk, tq, window)))
    chosen = fa._choose_bwd_blocks((1, 28, tq, 128), (1, 28, tk, 128), 2,
                                   bq, bk, 1, window, q_offset)
    assert chosen[2] == "pair"
    if named:
        assert chosen[:2] == named


def test_no_room_for_the_ring_falls_back_to_the_pair(monkeypatch):
    """A budget that holds the pair's smallest blocks and no ring beside
    a chunk: the pair, and its gradients are the ring's."""
    shape = (1, 16384, 128)
    call = fa._Call.of(shape, shape, 1)
    monkeypatch.setattr(fa, "_VMEM_BUDGET",
                        fa._bwd_step_bytes(128, 128, 128, 2))
    assert fa._choose_bwd_blocks(*call.step_shapes, 2, window=4096) \
        == (128, 128, "pair")
    assert [eqn.params["name"] for eqn in _backward_calls(
        shape, 1, window=4096)] == [
            "flash_attention_bwd_dkv_q128_k128_w4096_h1",
            "flash_attention_bwd_dq_q128_k128_w4096_h1"]


def test_an_offset_call_under_a_window_traces_the_pair():
    """The chooser reads `q_offset` where `_bwd` hands it over: a
    sequence shard's backward under a window keeps the pair."""
    shape, heads = SMALLTHINKER
    names = [eqn.params["name"] for eqn in _backward_calls(
        shape, heads, q_offset=1024, window=4096)]
    assert names == ["flash_attention_bwd_dkv_q1024_k512_w4096_h1",
                     "flash_attention_bwd_dq_q1024_k512_w4096_h1"]


def _padded_bytes(shape, dtype):
    """Bytes of a VMEM array: the minor dimension padded to 128 lanes,
    a float32 statistic's rows to 8 sublanes."""
    shape = tuple(shape[:-1]) + (-(-shape[-1] // 128) * 128,)
    if len(shape) == 2 and shape[0] < 8:
        shape = (8, shape[1])
    return int(np.prod(shape)) * jnp.dtype(dtype).itemsize


@pytest.mark.parametrize("shape,heads,dtype,bq,bk,window", [
    (SMALLTHINKER[0], 28, jnp.bfloat16, 512, 512, 4096),
    ((1, 2048, 4 * 64), 4, jnp.float32, 256, 128, 300),
    ((1, 2, 2048, 64), None, jnp.float32, 128, 256, 200),
])
def test_ring_step_bytes_are_what_the_call_declares(shape, heads, dtype, bq,
                                                    bk, window):
    """`_bwd_step_bytes(ring=)` less its chunk is the tiles the
    `pallas_call` declares, double-buffered, and its scratch: q, do and
    dq [bq, lanes], K, V, dk and dv [bk, lanes], lse and delta rows, the
    ring's slots, K transposed and two accumulators a head."""
    call = fa._Call.of(shape, shape, heads)
    x = jax.ShapeDtypeStruct(shape, dtype)
    rows = jax.ShapeDtypeStruct((call.batch, call.heads, call.tq),
                                jnp.float32)
    jaxpr = jax.make_jaxpr(lambda *operands: fa._bwd_kernels(
        *operands, num_heads=heads, sm_scale=1.0, causal=True, q_offset=0,
        bq=bq, bk=bk, form="ring", window=window))(x, x, x, x, rows, rows)
    eqn, = _by_name(jaxpr, "_ring_")
    mapping = eqn.params["grid_mapping"]
    slots = fa._ring_slots(bq, bk, call.tq, window)
    assert mapping.grid == call.steps + (call.tk // bk, slots)
    tiles = sum(
        2 * _padded_bytes([n.block_size for n in block.block_shape
                           if hasattr(n, "block_size")],
                          block.array_aval.dtype)
        for block in mapping.block_mappings)
    scratch = [v.aval for v in
               eqn.params["jaxpr"].invars[-mapping.num_scratch_operands:]]
    assert scratch[0].shape == (slots, 128 * -(-call.lanes // 128), bq)
    itemsize = jnp.dtype(dtype).itemsize
    declared = tiles + sum(_padded_bytes(a.shape, a.dtype) for a in scratch)
    assert declared == fa._bwd_step_bytes(
        bq, bk, call.lanes, itemsize, None, call.g, slots) \
        - bq * bk * (4 * 4 + 2 * itemsize)
