"""The chooser's selection without a sort (kernels/topk_select.py, the
`dsa_select` scope of `mla_index_select`): the set is `lax.top_k`'s,
its slots in ascending order, at both cells' shapes and where the
counting has to be careful (ties at the threshold, a relu's zeros,
`-0.0`, an extent that is no multiple of the lanes, every slot chosen,
fewer live slots than asked for); the op hands it on as `Selected` with
the live entries first; nothing under `dsa_select` sorts.

The kernel runs under the Pallas interpreter here; tests/
test_chip_bringup.py lowers it for the TPU.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.kernels import topk_select
from paddle_tpu.ops import registry


def _want(score, top_k):
    return np.sort(np.asarray(jax.lax.top_k(jnp.asarray(score), top_k)[1]),
                   axis=-1)


def _holds(score, top_k):
    got = np.asarray(topk_select.select_slots(jnp.asarray(score), top_k))
    assert got.dtype == np.int32 and got.shape == (score.shape[0], top_k)
    np.testing.assert_array_equal(got, _want(score, top_k))
    assert (np.diff(got, axis=-1) > 0).all()


# keye-turn-64k-ep8's selection and dsv32-turn-16k-ep16's (a grid step
# of its two)
@pytest.mark.parametrize("rows,slots,top_k", [(8, 65536, 2048),
                                              (8, 16384, 2048)])
def test_the_set_is_top_ks_at_the_cells_shapes(rows, slots, top_k):
    rs = np.random.RandomState(slots)
    # a chooser's scores: sums of weighted relus, -inf past the position
    score = (np.maximum(rs.randn(rows, slots), 0)
             * rs.randn(rows, slots)).astype(np.float32)
    score[:, slots - 500:] = -np.inf
    _holds(score, top_k)


def test_two_grid_steps_choose_for_their_own_rows():
    rs = np.random.RandomState(5)
    score = rs.randn(16, 1024).astype(np.float32)
    score[8:] = np.round(score[8:] * 2) / 2     # ties in the second only
    _holds(score, 100)


@pytest.mark.parametrize("name", ["continuous", "ties", "zeros",
                                  "negative_zeros", "one_value"])
@pytest.mark.parametrize("rows,slots,top_k", [(2, 512, 100), (3, 300, 37),
                                              (9, 2048, 128)])
def test_the_set_is_top_ks_where_scores_tie(name, rows, slots, top_k):
    """Of equal scores at the threshold the lower slots win, -0.0 is
    below +0.0 (`lax.top_k`'s total order), an extent that is no
    multiple of 128 and rows that are no multiple of 8 are padded
    inside."""
    rs = np.random.RandomState(slots + top_k)
    x = rs.randn(rows, slots).astype(np.float32)
    if name == "ties":
        x = np.round(x * 4) / 4
    elif name in ("zeros", "negative_zeros"):
        # more zeros than scores above them: the threshold is a zero
        x = np.where(rs.rand(rows, slots) < top_k / (4.0 * slots),
                     np.abs(x), 0.0).astype(np.float32)
        if name == "negative_zeros":
            x[:, ::3] *= -1.0
            assert np.signbit(x[x == 0]).any() \
                and not np.signbit(x[x == 0]).all()
    elif name == "one_value":
        x = np.full((rows, slots), -2.5, np.float32)
    _holds(x, top_k)


@pytest.mark.parametrize("slots", [128, 200, 384])
def test_every_slot_chosen(slots):
    score = np.random.RandomState(slots).randn(2, slots).astype(np.float32)
    got = np.asarray(topk_select.select_slots(jnp.asarray(score), slots))
    np.testing.assert_array_equal(got, np.tile(np.arange(slots), (2, 1)))


@pytest.mark.parametrize("live", [1, 300, 511])
def test_fewer_live_slots_than_asked_for(live):
    """The dead slots score -inf and tie: the live ones, having the
    lowest numbers, come first, and the dead entries are the next
    slots."""
    score = np.random.RandomState(live).randn(2, 4096).astype(np.float32)
    score[:, live:] = -np.inf
    got = np.asarray(topk_select.select_slots(jnp.asarray(score), 512))
    np.testing.assert_array_equal(got, np.tile(np.arange(512), (2, 1)))


def test_what_it_refuses():
    with pytest.raises(ValueError, match="no selection"):
        topk_select.select_slots(jnp.zeros((2, 128), jnp.float32), 129)
    with pytest.raises(ValueError, match="no selection"):
        topk_select.select_slots(jnp.zeros((2, 128), jnp.bfloat16), 8)
    # eight rows' keys and lists no longer fit fast memory
    with pytest.raises(ValueError, match="in VMEM"):
        topk_select.select_slots(jnp.zeros((1, 1 << 18), jnp.float32), 2048)


# -- through the op ------------------------------------------------------------

B, T, IH, ID = 2, 200, 4, 16


def _ins(rs, pos):
    def draw(*shape):
        return jnp.asarray(rs.randn(*shape), jnp.float32)

    cache = draw(B, T, ID).at[:, pos:].set(0)
    return {"Q": [draw(B, 1, IH * ID)],
            "W": [jnp.asarray(rs.uniform(0.2, 1.0, (B, 1, IH)), jnp.float32)],
            "KNew": [draw(B, 1, ID)], "Cache": [cache],
            "Position": [jnp.full((B,), pos, jnp.int32)]}


def _scores(ins, kept, pos):
    q = np.asarray(ins["Q"][0], np.float64).reshape(B, IH, ID)
    s = np.maximum(np.einsum("bhd,btd->bht", q, np.asarray(kept, np.float64)),
                   0)
    w = np.asarray(ins["W"][0], np.float64).reshape(B, IH)
    return np.where(np.arange(T) <= pos, np.einsum("bh,bht->bt", w, s), -np.inf)


@pytest.mark.parametrize("pos,top_k", [(0, 64), (62, 64), (63, 64),
                                       (150, 64), (T - 1, T)])
def test_selected_is_the_set_in_slot_order_live_entries_first(pos, top_k):
    """`Selected` ascends along a row; with `Position + 1 < top_k` its
    first `Live` entries are slots 0 .. Position and the rest name no
    live slot (and no slot twice)."""
    ins = _ins(np.random.RandomState(pos), pos)
    outs = registry.get_op_info("mla_index_select").kernel(
        None, ins, {"num_heads": IH, "top_k": top_k})
    selected = np.asarray(outs["Selected"][0])
    live = min(top_k, pos + 1)
    assert np.asarray(outs["Live"][0]).tolist() == [live] * B
    assert selected.shape == (B, top_k) and selected.dtype == np.int32
    assert (np.diff(selected, axis=-1) > 0).all()
    assert selected.min() >= 0 and selected.max() < T
    scores = _scores(ins, outs["CacheOut"][0], pos)
    for row in range(B):
        if live < top_k:
            assert selected[row, :live].tolist() == list(range(pos + 1))
            assert selected[row, live:].min() > pos
        else:
            # the chosen scores are the largest: none left out is above
            # the least of them (continuous seeded scores: no ties)
            left = np.setdiff1d(np.arange(pos + 1), selected[row])
            assert left.size == pos + 1 - top_k
            assert left.size == 0 \
                or scores[row, left].max() < scores[row, selected[row]].min()


def _primitives(jaxpr):
    """Every primitive's name in `jaxpr` and in what its equations hold
    (a jitted entry's body, a kernel's, a loop's)."""
    for eqn in jaxpr.eqns:
        yield eqn.primitive.name
        for value in eqn.params.values():
            for held in (value if isinstance(value, (list, tuple))
                         else [value]):
                inner = getattr(held, "jaxpr", held)
                if hasattr(inner, "eqns"):
                    yield from _primitives(inner)


def test_nothing_under_dsa_select_sorts():
    ins = _ins(np.random.RandomState(1), 100)
    kernel = registry.get_op_info("mla_index_select").kernel
    jaxpr = jax.make_jaxpr(lambda i: kernel(
        None, i, {"num_heads": IH, "top_k": 64})["Selected"][0])(ins)
    names = set(_primitives(jaxpr.jaxpr))
    assert "pallas_call" in names
    assert not names & {"sort", "top_k", "approx_top_k", "scatter",
                        "scatter-add", "gather"}
    # and the whole selection is the scope's: one jitted entry under it
    under = [e for e in jaxpr.jaxpr.eqns
             if "dsa_select" in str(e.source_info.name_stack)]
    assert {e.primitive.name for e in under} <= {"jit", "pjit", "slice"}
    assert any(e.params.get("name") == "_select" for e in under)
