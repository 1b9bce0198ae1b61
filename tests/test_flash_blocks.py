"""How the flash-attention forward kernel tiles itself from the shapes it
sees (kernels/flash_attention.py: `_choose_blocks`, `_step_bytes`,
`_VMEM_BUDGET`): the chooser as a pure function, the kernel's agreement
with dense attention at the chosen blocks under the Pallas interpreter,
the backward kernels' own chooser and their agreement with dense
attention's gradients, and the counters that name the tilings."""

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
        (8, 16, tq, d), (8, 16, tk, d), itemsize, causal)
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


def test_a_named_block_is_kept_beside_a_chosen_one():
    shape = (1, 8, 4096, 128)
    assert fa._choose_blocks(shape, shape, 2, True, 128, 128)[:2] \
        == (128, 128)
    bq, bk, _ = fa._choose_blocks(shape, shape, 2, False, block_q=256)
    assert bq == 256 and bk > 256
    bq, bk, _ = fa._choose_blocks(shape, shape, 2, False, block_k=256)
    assert bk == 256 and bq > 256


def test_a_long_sequence_nothing_tiles_is_refused():
    shape = (1, 8, 32769, 128)
    with pytest.raises(ValueError, match=r"32769.*\(1, 8, 32769, 128\)"):
        fa._choose_blocks(shape, shape, 2, True)
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
    bq, bk, resident = fa._choose_blocks(q.shape, k.shape, 4, causal)
    # several blocks on both axes, and the path the budget asks for
    assert tq // bq >= 2 and tk // bk >= 2
    assert resident == (kv == "resident")
    assert fa._choose_bwd_blocks(q.shape, k.shape, 4, causal) \
        == (bq, bk, resident)

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
    bq, bk, one_kernel = fa._choose_bwd_blocks(q_shape, k_shape, itemsize,
                                               causal)
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
    if causal:
        # the diagonal still cuts work off, unless nothing tiles
        assert bq <= max(128, tq // 2) or tq % 128
        assert bk <= max(128, tk // 2) or tk % 128
    # a named block is kept beside a chosen one
    if tq % 128 == 0:
        assert fa._choose_bwd_blocks(q_shape, k_shape, itemsize, causal,
                                     block_q=128)[0] == 128
        assert fa._choose_bwd_blocks(q_shape, k_shape, itemsize, causal,
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
    assert fa._choose_bwd_blocks(q.shape, k.shape, 2, True) \
        == (128, 128, True)
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
    assert fa._choose_bwd_blocks(q.shape, k.shape, 4, causal) \
        == (200, 200, True)
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
    assert fa._choose_bwd_blocks(q.shape, k.shape, 4, True, 16, 16) \
        == (16, 16, budget is None)
    got = _grads(lambda q, k, v: fa.flash_attention(
        q, k, v, None, True, 16, 16), q, k, v, do)
    want = _grads(lambda q, k, v: fa.reference_attention(
        q, k, v, None, True), q, k, v, do)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, atol=5e-5)


# -- the counter names the tiling ---------------------------------------------

def _lowerings(bq, bk, resident):
    return telemetry.snapshot().get(
        "flash_attention_lowerings_total{block_k=%d,block_q=%d,"
        "kv_resident=%s}" % (bk, bq, str(resident).lower()), 0)


@pytest.mark.parametrize("named", [None, 128])
def test_counter_rises_once_per_lowering(named):
    x = jax.ShapeDtypeStruct((8, 16, 1024, 64), jnp.bfloat16)
    labels = fa._choose_blocks(x.shape, x.shape, 2, True, named, named)
    assert labels == ((512, 512, True) if named is None
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


def _bwd_lowerings(kernel, bq, bk):
    return telemetry.snapshot().get(
        "flash_attention_bwd_lowerings_total{block_k=%d,block_q=%d,"
        "kernel=%s}" % (bk, bq, kernel), 0)


@pytest.mark.parametrize("shape,named,blocks,kernels", [
    ((8, 16, 1024, 64), None, (512, 512), {"dq_dkv": ""}),
    ((8, 16, 1024, 64), 128, (128, 128), {"dq_dkv": ""}),
    ((1, 8, 32768, 128), None, (1024, 512),
     {"dkv": "_dkv", "dq": "_dq"}),
])
def test_backward_counter_rises_once_per_kernel_and_lowering(
        shape, named, blocks, kernels):
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    bq, bk, one_kernel = fa._choose_bwd_blocks(shape, shape, 2, True,
                                               named, named)
    assert (bq, bk) == blocks and one_kernel == (len(kernels) == 1)
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
