"""A chosen set through `cached_attention` and `kernels/gqa_decode.py`'s
`gqa_decode_chosen` (the interpreter on the CPU): the slots are gathered
whole, a slot's heads side by side, and the kernel reads the copies as
the gather leaves them.  Held to masked attention over the whole caches
in float64: even and odd slots (a 32-bit word of a bfloat16 cache holds
two), both slots of such a pair, the first and the last slot, dead
entries that hold anything, one live entry, one query a head and eight,
one key/value head and four, both operand types, several chunks a row,
a live count a row; and the sets the kernel does not take keep the plain
path."""

import numpy as np
import pytest

import jax.numpy as jnp

import paddle_tpu.fluid  # noqa: F401  (registers the ops)
from paddle_tpu.ops import registry
from paddle_tpu.kernels import gqa_decode
from paddle_tpu.obs import telemetry

SLOTS, TOP_K, DIM = 512, 128, 128
TYPES = {"float32": (jnp.float32, 2e-5), "bfloat16": (jnp.bfloat16, 2e-2)}


def _sets(rs, rows, pos):
    """{case: (selected [rows, TOP_K], live)}: the step's own slot `pos`
    is among the live entries of each."""
    def fill(first, pool):
        rest = [s for s in rs.permutation(pool) if s not in first]
        return np.array(list(first) + rest[:TOP_K - len(first)])

    evens, odds = np.arange(0, pos, 2), np.arange(1, pos, 2)
    out = {
        "even": (np.stack([fill([], evens) for _ in range(rows)]), TOP_K),
        "odd": (np.stack([fill([pos] if pos % 2 else [], odds)
                          for _ in range(rows)]), TOP_K),
        "pairs": (np.stack([fill([pos, 6, 7, 200, 201], np.arange(pos))
                            for _ in range(rows)]), TOP_K),
        "ends": (np.stack([fill([0, SLOTS - 1, pos], np.arange(pos))
                           for _ in range(rows)]), TOP_K),
        "one": (np.stack([fill([pos], np.arange(pos))
                          for _ in range(rows)]), 1),
    }
    dead = np.stack([fill([pos], np.arange(pos)) for _ in range(rows)])
    dead[:, TOP_K - 37:] = rs.choice([-1, 0, SLOTS, 10 ** 6],
                                     (rows, 37))
    out["dead"] = (dead, TOP_K - 37)
    return out


def _dense(q, k_cache, v_cache, selected, live, heads):
    rows, kv_heads, slots, dim = k_cache.shape
    q = np.asarray(q, np.float64).reshape(rows, heads, dim)
    k, v = (np.repeat(np.asarray(c, np.float64), heads // kv_heads, axis=1)
            for c in (k_cache, v_cache))
    s = np.einsum("bhd,bhsd->bhs", q, k) / np.sqrt(dim)
    keep = np.zeros((rows, slots), bool)
    for b in range(rows):
        keep[b, np.asarray(selected)[b, :live]] = True
    s = np.where(keep[:, None], s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    return np.einsum("bhs,bhsd->bhd", p, v).reshape(rows, 1, heads * dim)


def _step(rs, dtype, kv_heads, group, selected, live, pos, dim=DIM):
    """(Out, the caches as written, the counter's line) of one step."""
    rows, heads = selected.shape[0], kv_heads * group
    draw = lambda *s: jnp.asarray(rs.randn(*s), dtype)  # noqa: E731
    q, k_new, v_new = (draw(rows, 1, n * dim)
                       for n in (heads, kv_heads, kv_heads))
    k_cache, v_cache = (draw(rows, kv_heads, SLOTS, dim) for _ in range(2))
    ins = {"Q": [q], "KNew": [k_new], "VNew": [v_new], "KCache": [k_cache],
           "VCache": [v_cache], "Position": [jnp.full((rows,), pos)],
           "Selected": [jnp.asarray(selected, jnp.int32)],
           "Live": [jnp.full((rows,), live, jnp.int32)]}
    before = telemetry.snapshot()
    out = registry.get_op_info("cached_attention").kernel(
        None, ins, {"num_heads": heads, "num_kv_heads": kv_heads})
    lowered = [k for k in telemetry.snapshot_delta(before)
               if k.startswith("sparse_attention_lowerings_total")]
    assert len(lowered) == 1
    written = [np.asarray(c.astype(jnp.float32)).copy()
               for c in (k_cache, v_cache)]
    for cache, new in zip(written, (k_new, v_new)):
        cache[:, :, pos] = np.asarray(new.astype(jnp.float32)).reshape(
            rows, kv_heads, dim)
    for name, cache in zip(("KCacheOut", "VCacheOut"), written):
        np.testing.assert_array_equal(
            np.asarray(out[name][0].astype(jnp.float32)), cache)
    want = _dense(q.astype(jnp.float32), written[0], written[1], selected,
                  live, heads)
    return np.asarray(out["Out"][0].astype(jnp.float32)), want, lowered[0]


@pytest.mark.parametrize("dtype", sorted(TYPES))
@pytest.mark.parametrize("case", ["even", "odd", "pairs", "ends", "one",
                                  "dead"])
def test_a_chosen_set_as_its_gather_leaves_it(case, dtype):
    rs = np.random.RandomState(len(case) + len(dtype))
    pos = SLOTS - 4
    selected, live = _sets(rs, 2, pos)[case]
    dtype, atol = TYPES[dtype]
    got, want, lowered = _step(rs, dtype, 4, 8, selected, live, pos)
    np.testing.assert_allclose(got, want, atol=atol)
    assert "path=kernel" in lowered and "block_k=%d}" % TOP_K in lowered \
        or "block_k=%d," % TOP_K in lowered


@pytest.mark.parametrize("dtype", sorted(TYPES))
@pytest.mark.parametrize("kv_heads,group", [(1, 1), (1, 8), (2, 1), (4, 1),
                                            (2, 8), (8, 2)])
def test_heads_and_groups_the_kernel_takes(kv_heads, group, dtype):
    rs = np.random.RandomState(kv_heads * 16 + group)
    pos = SLOTS - 9
    selected, live = _sets(rs, 2, pos)["dead"]
    dtype, atol = TYPES[dtype]
    got, want, lowered = _step(rs, dtype, kv_heads, group, selected, live,
                               pos)
    np.testing.assert_allclose(got, want, atol=atol)
    assert "path=kernel" in lowered


@pytest.mark.parametrize("why,kv_heads,top_k,dim,dtype", [
    ("a set no chunk tiles", 4, 200, 128, "float32"),
    ("an odd count of bfloat16 heads", 3, 128, 128, "bfloat16"),
    ("64-wide heads", 4, 128, 64, "bfloat16"),
    ("256-wide heads", 2, 128, 256, "float32")])
def test_sets_the_kernel_does_not_take_keep_the_plain_path(why, kv_heads,
                                                            top_k, dim,
                                                            dtype):
    rs = np.random.RandomState(top_k + dim)
    pos = SLOTS - 2
    selected = np.stack([np.append(pos, rs.permutation(pos)[:top_k - 1])
                         for _ in range(2)])
    dtype, atol = TYPES[dtype]
    got, want, lowered = _step(rs, dtype, kv_heads, 2, selected, top_k - 3,
                               pos, dim)
    np.testing.assert_allclose(got, want, atol=atol)
    assert "path=plain" in lowered and "block_k=0" in lowered


# -- the kernel alone: several chunks a row ----------------------------------

def _chosen(rs, dtype, kv_heads, group, top_k):
    draw = lambda *s: jnp.asarray(rs.randn(*s), dtype)  # noqa: E731
    return draw(2, kv_heads, group, DIM), draw(2, top_k, kv_heads, DIM), \
        draw(2, top_k, kv_heads, DIM)


def _attended(q, k, v, live):
    """float64, over the first `live` of [B, top_k, KV, D] entries."""
    q, k, v = (np.asarray(x.astype(jnp.float32), np.float64)
               for x in (q, k, v))
    s = np.einsum("bhgd,bkhd->bhgk", q, k[:, :live]) / np.sqrt(DIM)
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    return np.einsum("bhgk,bkhd->bhgd", p, v[:, :live])


@pytest.mark.parametrize("dtype", sorted(TYPES))
@pytest.mark.parametrize("live", [512, 385, 384, 129, 128, 1])
def test_the_live_entries_of_several_chunks(live, dtype):
    rs = np.random.RandomState(live)
    dtype, atol = TYPES[dtype]
    q, k, v = _chosen(rs, dtype, 4, 8, 512)
    # what a dead entry holds reaches no sum
    k = k.at[:, live:].set(jnp.nan)
    v = v.at[:, live:].set(jnp.inf)
    got = gqa_decode.gqa_decode_chosen(q, k, v, jnp.int32(live), DIM ** -0.5,
                                       chunk=128)
    np.testing.assert_allclose(np.asarray(got.astype(jnp.float32)),
                               _attended(q, k, v, live), atol=atol)


@pytest.mark.parametrize("dtype", sorted(TYPES))
@pytest.mark.parametrize("kv_heads,group", [(4, 8), (1, 8), (2, 1)])
def test_a_live_count_a_row(kv_heads, group, dtype):
    """`live` [batch]: every row attends its own count of entries, as
    the positions of a block do that lie a row each: one, a count inside
    the first chunk, a chunk's edge, one past it, all."""
    rs = np.random.RandomState(kv_heads + group)
    dtype, atol = TYPES[dtype]
    live = np.array([1, 77, 128, 129, 384, 512], np.int32)
    draw = lambda *s: jnp.asarray(rs.randn(*s), dtype)  # noqa: E731
    q = draw(live.size, kv_heads, group, DIM)
    k, v = (draw(live.size, 512, kv_heads, DIM) for _ in range(2))
    dead = np.arange(512)[None, :, None, None] >= live[:, None, None, None]
    # what a dead entry holds reaches no sum
    k, v = jnp.where(dead, jnp.nan, k), jnp.where(dead, jnp.inf, v)
    got = gqa_decode.gqa_decode_chosen(q, k, v, jnp.asarray(live),
                                       DIM ** -0.5, chunk=128)
    for b, n in enumerate(live):
        at = slice(b, b + 1)
        np.testing.assert_allclose(
            np.asarray(got[at].astype(jnp.float32)),
            _attended(q[at], k[at], v[at], int(n)), atol=atol)
        # and a row alone under one count gives the row bit for bit
        np.testing.assert_array_equal(
            np.asarray(got[at].astype(jnp.float32)),
            np.asarray(gqa_decode.gqa_decode_chosen(
                q[at], k[at], v[at], jnp.int32(n), DIM ** -0.5,
                chunk=128).astype(jnp.float32)))


def test_the_chunk_is_chosen_from_the_shapes():
    assert gqa_decode.choose_chunk(2048, 4, 8) == 2048
    assert gqa_decode.choose_chunk(2048, 4, 8, itemsize=4) == 1024
    assert gqa_decode.choose_chunk(1536, 4, 8) == 512
    assert gqa_decode.choose_chunk(200, 4, 8) == 0
    assert gqa_decode.choose_chunk(128, 3, 8) == 0
    assert gqa_decode.choose_chunk(128, 3, 8, itemsize=4) == 128
    assert gqa_decode.choose_chunk(128, 1, 8) == 128
    assert gqa_decode.choose_chunk(128, 4, 8, head_dim=64) == 0
    assert gqa_decode.choose_chunk(128, 4, 8, head_dim=256) == 0


def test_what_is_no_chosen_set_is_refused():
    rs = np.random.RandomState(0)
    q, k, v = _chosen(rs, jnp.float32, 4, 8, 128)
    for bad in ((q, k, v[:, :64]), (q, k.astype(jnp.bfloat16), v),
                (q[:, :3], k, v)):
        with pytest.raises(ValueError, match="no step the kernel takes"):
            gqa_decode.gqa_decode_chosen(*bad, jnp.int32(128), 1.0)
    with pytest.raises(ValueError, match="no step the kernel takes"):
        # three counts for two rows
        gqa_decode.gqa_decode_chosen(q, k, v, jnp.full((3,), 128), 1.0)
    with pytest.raises(ValueError, match="no step the kernel takes"):
        gqa_decode.gqa_decode_chosen(q, k, v, jnp.int32(128), 1.0, chunk=96)
