"""A chosen set over grouped key/value caches (`cached_attention` with
`Selected` and `Live`), three-part rotary positions (`rope` with
`sections`), an expert layer without a shared expert
(`decoder_block.share_feed_forward`), and the cached step Program built
on them (models/sparse_kv_moe_program.py) against the plain float32
reference (models/reference/keye_vl2.py): the step driven position by
position through its three caches a layer against the reference's full
forward, with `top_k` smaller than the context so that slots are left
out and an image span inside the prefill; the shares of an expert layer
adding up to the uncut layer; the op against a masked dense computation,
kernel (interpreter) and plain path; a block of positions with a set each
through the op, through the Program and through `ProgramDecoder` against
its single steps; the counters; and the step Programs the repo had,
unchanged.

Tiny sizes on the CPU: 3 layers, hidden 64, 4 query / 2 key/value heads
of 16 (sections 2 : 3 : 3), a chooser of 4 heads of 8 that picks 5 slots,
8 experts scored of which 4 are held, 2 a token, vocabulary 97, seeded
random weights (norm scales moved off their initial 1).
"""

import hashlib
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu.fluid as fluid
from paddle_tpu.jit import FunctionalProgram
from paddle_tpu.models import latent_moe_program
from paddle_tpu.models.decoder_block import share_feed_forward
from paddle_tpu.models.reference import keye_vl2 as reference
from paddle_tpu.models.sparse_kv_moe_program import (
    build_sparse_kv_moe_cached_step_program, sparse_kv_moe_param_names)
from paddle_tpu.obs import telemetry
from paddle_tpu.ops import attention, registry

B, T, V, L = 2, 24, 97, 3
H, KV, DH, D, FE, E, K, HELD = 4, 2, 16, 64, 32, 8, 2, (2, 4)
SECTIONS, INDEXER = (2, 3, 3), (4, 8, 5)
SIZES = dict(n_layer=L, n_head=H, n_kv_head=KV, d_head=DH, d_model=D,
             d_expert=FE, n_experts=E, held=HELD, top_k=K,
             sections=SECTIONS, indexer=INDEXER)
CFG = {"num_attention_heads": H, "num_key_value_heads": KV, "head_dim": DH,
       "rms_norm_eps": 1e-6, "rope_theta": 1e7,
       "rope_scaling": {"mrope_section": list(SECTIONS)},
       "sa_config": {"indexer_num_heads": 4, "indexer_head_dim": 8,
                     "topk": 5},
       "num_experts_per_tok": K, "norm_topk_prob": True,
       "num_hidden_layers": L, "first_expert": HELD[0]}
NAMES = sparse_kv_moe_param_names(L)
# an image of 2 x 3 tokens at slots 4..9: the slots after it lag their
# positions by 6 - 3
SPAN = (4, 2, 3)
PREFILL = 14
# float32 on the CPU: the step reads gathered caches, the reference the
# whole score matrix under a mask; other sums in another order
LOGITS_RTOL = 2e-5


def _start(startup, seed=3):
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    startup.random_seed = seed
    exe.run(startup, scope=scope)
    rs = np.random.RandomState(seed)
    for name in jax.tree_util.tree_leaves(NAMES):
        value = np.asarray(scope.get(name))
        if value.ndim == 1:
            scope.set(name, jnp.asarray(
                value + 0.1 * rs.randn(*value.shape).astype("float32")))
    return scope


def _empty(dtype=jnp.float32):
    state = {"pos": jnp.zeros((B,), jnp.int32),
             "rope_delta": jnp.zeros((B,), jnp.int32)}
    for i in range(L):
        for which in "kv":
            state["%s_cache_%d" % (which, i)] = jnp.zeros((B, KV, T, DH),
                                                          dtype)
        state["index_cache_%d" % i] = jnp.zeros((B, T, INDEXER[1]), dtype)
    return state


@pytest.fixture(scope="module")
def built():
    """The step with image feeds driven over the prefill (a position a
    call, as a prefill pool would), then the decoder's own step over the
    rest, against the reference's full forward."""
    before = telemetry.snapshot()
    main, startup, logits, pairs, parts = \
        build_sparse_kv_moe_cached_step_program(B, T, V, **SIZES)
    at_build = telemetry.snapshot_delta(before)
    scope = _start(startup)
    seeing, _, seeing_logits, seeing_pairs, _ = \
        build_sparse_kv_moe_cached_step_program(B, T, V, images=True,
                                                **SIZES)
    rs = np.random.RandomState(1)
    tokens = rs.randint(0, V, (B, T)).astype("int32")
    slots = np.arange(SPAN[0], SPAN[0] + SPAN[1] * SPAN[2])
    vectors = rs.randn(B, slots.size, D).astype("float32")
    positions, after = reference.layout(T, [SPAN])
    delta = after - T
    feeds = ["tok", "mrope_pos", "image_embeds", "image_mask"] \
        + [f for f, _ in seeing_pairs]
    fp = FunctionalProgram(seeing.clone(for_test=True), feeds,
                           [seeing_logits.name]
                           + [o for _, o in seeing_pairs])
    params = {n: scope.get(n) for n in fp.state_in_names}
    state, got = _empty(), []
    for t in range(PREFILL):
        held = np.zeros((B, 1, D), "float32")
        if t in slots:
            held[:, 0] = vectors[:, t - SPAN[0]]
        fed = dict(state, tok=jnp.asarray(tokens[:, t:t + 1]),
                   mrope_pos=jnp.asarray(np.broadcast_to(
                       positions[:, None, t, None], (3, B, 1)), jnp.int32),
                   image_embeds=jnp.asarray(held),
                   image_mask=jnp.full((B, 1, 1), float(t in slots),
                                       jnp.float32))
        (z, *new), _ = fp(params, fed)
        state = {f: v for (f, _), v in zip(seeing_pairs, new)}
        got.append(np.asarray(z))
    # the decode pool's half: text alone, the lag handed over as a state
    state["rope_delta"] = jnp.full((B,), delta, jnp.int32)
    decoder = fluid.ProgramDecoder(
        main.clone(for_test=True), token_name="tok",
        logits_name=logits.name, state_pairs=pairs, scope=scope,
        max_positions=T)
    step = decoder._step_fn(decoder._params)
    for t in range(PREFILL, T):
        z, state = step(state, jnp.asarray(tokens[:, t]))
        got.append(np.asarray(z))
    tree = jax.tree_util.tree_map(scope.get, NAMES)
    want = reference.forward(
        CFG, tree, tokens,
        positions=np.broadcast_to(positions[:, None], (3, B, T)),
        vectors=vectors, image_slots=np.broadcast_to(slots, (B, slots.size)),
        held=HELD)
    return {"main": main, "pairs": pairs, "parts": parts, "scope": scope,
            "decoder": decoder, "tokens": tokens, "got": np.stack(got, 1),
            "state": state, "params": tree, "want": want, "delta": delta,
            "at_build": at_build, "positions": positions,
            "vectors": vectors, "slots": slots}


# -- (a) the step through its caches against the full forward -----------------

@pytest.mark.parametrize("position", range(T))
def test_step_logits_agree_with_the_reference_at_every_position(
        built, position):
    want = np.asarray(built["want"]["logits"])[:, position]
    got = built["got"][:, position]
    assert np.abs(got - want).max() <= LOGITS_RTOL * np.abs(want).max()


def test_slots_are_left_out_and_the_position_lags_the_slot(built):
    """What the fixture exercises: past slot 5 a query's set is smaller
    than its context, and after the image a text token's rotary position
    is three less than its slot."""
    chosen = np.asarray(built["want"]["selection"][0])
    assert chosen.shape == (B, T, T)
    assert (chosen.sum(-1) == np.minimum(np.arange(T) + 1, 5)).all()
    assert built["delta"] == -3
    assert (built["positions"][:, 10:] == np.arange(10, T) - 3).all()
    assert int(built["state"]["pos"][0]) == T
    assert int(built["state"]["rope_delta"][0]) == -3


def test_the_caches_hold_what_the_reference_would(built):
    for i in range(L):
        for feed, want in (("k_cache_%d" % i, built["want"]["keys"][i]),
                           ("v_cache_%d" % i, built["want"]["values"][i])):
            np.testing.assert_allclose(
                np.asarray(built["state"][feed]),
                np.asarray(want).transpose(0, 2, 1, 3), atol=3e-5)
        np.testing.assert_allclose(
            np.asarray(built["state"]["index_cache_%d" % i]),
            np.asarray(built["want"]["index_keys"][i]), atol=3e-5)


def test_greedy_through_the_decoder_continues_a_session(built):
    """`ProgramDecoder.greedy` from caches that hold the prefill: every
    served token is the reference's first given the tokens before it (or
    lies within rounding of it)."""
    tokens = built["tokens"]
    want = built["want"]
    state = _empty()
    for i in range(L):
        for which, made in (("k", want["keys"][i]), ("v", want["values"][i])):
            cache = np.zeros((B, KV, T, DH), "float32")
            cache[:, :, :PREFILL] = np.asarray(made).transpose(
                0, 2, 1, 3)[:, :, :PREFILL]
            state["%s_cache_%d" % (which, i)] = cache
        cache = np.zeros((B, T, INDEXER[1]), "float32")
        cache[:, :PREFILL] = np.asarray(want["index_keys"][i])[:, :PREFILL]
        state["index_cache_%d" % i] = cache
    state["pos"] = np.full((B,), PREFILL, np.int64)
    state["rope_delta"] = np.full((B,), built["delta"], np.int64)
    prompt = tokens[:, PREFILL:PREFILL + 3]
    served, lengths, last = built["decoder"].greedy(
        bos=0, eos=V, max_len=5, init_state=state, prompt=prompt,
        return_state=["rope_delta", "pos"])
    assert served.shape == (B, 5) and (lengths == 5).all()
    assert (last["rope_delta"] == built["delta"]).all()
    assert (last["pos"] == PREFILL + 3 + 4).all()
    whole = np.concatenate([tokens[:, :PREFILL + 3], served], axis=1)
    positions, _ = reference.layout(whole.shape[1], [SPAN])
    slots = built["slots"]
    z = reference.forward(
        CFG, built["params"], whole,
        positions=np.broadcast_to(positions[:, None],
                                  (3, B, whole.shape[1])),
        vectors=built["vectors"],
        image_slots=np.broadcast_to(slots, (B, slots.size)),
        held=HELD)["logits"]
    z = np.asarray(z)[:, PREFILL + 2:-1]
    picked = np.take_along_axis(z, served[..., None], axis=-1)[..., 0]
    assert (z.max(-1) - picked <= 1e-4 * np.abs(z).max()).all()


# -- (b) the op: a chosen set against a masked dense computation --------------

def _attend(q, k_new, v_new, k_cache, v_cache, pos, selected, live, heads,
            kv_heads):
    ins = {"Q": [q], "KNew": [k_new], "VNew": [v_new], "KCache": [k_cache],
           "VCache": [v_cache], "Position": [jnp.full((q.shape[0],), pos)],
           "Selected": [selected],
           "Live": [jnp.full((q.shape[0],), live, jnp.int32)]}
    return registry.get_op_info("cached_attention").kernel(
        None, ins, {"num_heads": heads, "num_kv_heads": kv_heads})


def _dense(q, k_cache, v_cache, selected, live, heads, kv_heads):
    """Masked attention over the whole caches: the slots the first
    `live` entries of `selected` name, in float64."""
    rows, kv, slots, dim = k_cache.shape
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


@pytest.mark.parametrize("dim,slots,top_k,path", [
    (128, 512, 256, "kernel"), (128, 512, 128, "kernel"),
    (16, 40, 8, "plain"), (64, 256, 128, "plain")])
@pytest.mark.parametrize("live", ["all", "some"])
def test_a_chosen_set_is_masked_attention_over_the_caches(dim, slots, top_k,
                                                          path, live):
    rs = np.random.RandomState(dim + top_k)
    rows, heads, kv_heads, pos = 2, 8, 2, slots - 3
    live = top_k if live == "all" else top_k - 5
    f32 = lambda *s: jnp.asarray(rs.randn(*s), jnp.float32)  # noqa: E731
    q, k_new, v_new = (f32(rows, 1, n * dim)
                       for n in (heads, kv_heads, kv_heads))
    k_cache, v_cache = f32(rows, kv_heads, slots, dim), \
        f32(rows, kv_heads, slots, dim)
    # the step's own slot, written before the read, among the chosen
    selected = np.stack([np.append(pos, rs.permutation(pos)[:top_k - 1])
                         for _ in range(rows)]).astype("int32")
    before = telemetry.snapshot()
    out = _attend(q, k_new, v_new, k_cache, v_cache, pos,
                  jnp.asarray(selected), live, heads, kv_heads)
    counted = telemetry.snapshot_delta(before)
    written = [np.asarray(c).copy() for c in (k_cache, v_cache)]
    for cache, new in zip(written, (k_new, v_new)):
        cache[:, :, pos] = np.asarray(new).reshape(rows, kv_heads, dim)
        # the caches come out with the slot written and nothing else
    np.testing.assert_array_equal(np.asarray(out["KCacheOut"][0]),
                                  written[0])
    np.testing.assert_array_equal(np.asarray(out["VCacheOut"][0]),
                                  written[1])
    want = _dense(q, written[0], written[1], selected, live, heads,
                  kv_heads)
    np.testing.assert_allclose(np.asarray(out["Out"][0]), want, atol=2e-5)
    lowered = [k for k in counted
               if k.startswith("sparse_attention_lowerings_total")]
    assert len(lowered) == 1 and "path=%s" % path in lowered[0]
    assert "top_k=%d}" % top_k in lowered[0]


@pytest.mark.parametrize("why,change", [
    ("a ring", {"window": 40}), ("one set for a block of positions",
                                 {"block": 2}),
    ("no Live", {"live": None})])
def test_what_a_chosen_set_cannot_be_is_refused(why, change):
    rs = np.random.RandomState(0)
    block = change.get("block", 1)
    ins = {"Q": [jnp.asarray(rs.randn(2, block, 64), jnp.float32)],
           "KNew": [jnp.asarray(rs.randn(2, block, 32), jnp.float32)],
           "VNew": [jnp.asarray(rs.randn(2, block, 32), jnp.float32)],
           "KCache": [jnp.zeros((2, 2, 40, 16))],
           "VCache": [jnp.zeros((2, 2, 40, 16))],
           "Position": [jnp.full((2,), 7)],
           "Selected": [jnp.zeros((2, 4), jnp.int32)],
           "Live": [jnp.full((2,), 4, jnp.int32)]}
    if "live" in change:
        del ins["Live"]
    attrs = {"num_heads": 4, "num_kv_heads": 2,
             "window": change.get("window", 0)}
    with pytest.raises(ValueError, match="chosen set"):
        registry.get_op_info("cached_attention").kernel(None, ins, attrs)


def test_a_block_longer_than_the_op_was_sized_for_is_refused():
    rs = np.random.RandomState(0)
    ins = {"Q": [jnp.asarray(rs.randn(2, 3, 64), jnp.float32)],
           "KNew": [jnp.asarray(rs.randn(2, 3, 32), jnp.float32)],
           "VNew": [jnp.asarray(rs.randn(2, 3, 32), jnp.float32)],
           "KCache": [jnp.zeros((2, 2, 40, 16))],
           "VCache": [jnp.zeros((2, 2, 40, 16))],
           "Position": [jnp.full((2,), 7)],
           "Selected": [jnp.zeros((2, 3, 4), jnp.int32)],
           "Live": [jnp.full((2, 3), 4, jnp.int32)]}
    with pytest.raises(ValueError, match="prefill_block"):
        registry.get_op_info("cached_attention").kernel(
            None, ins, {"num_heads": 4, "num_kv_heads": 2,
                        "prefill_block": 2})


# -- (b') a block of positions, a set each, against its single steps ------------

def _sets_of_a_block(rs, rows, slots, top_k, pos, block):
    """(Selected [rows, block, top_k], Live [rows, block]) as a chooser
    gives them: position t's set holds min(top_k, pos + t + 1) of the
    slots up to its own, ascending, then entries that name anything."""
    selected = rs.choice([-1, 0, slots, 10 ** 6], (rows, block, top_k))
    live = np.minimum(top_k, pos + 1 + np.arange(block))
    for b in range(rows):
        for t in range(block):
            selected[b, t, :live[t]] = np.sort(
                rs.permutation(pos + t + 1)[:live[t]])
    return selected.astype("int32"), \
        np.broadcast_to(live, (rows, block)).astype("int32")


BLOCKS = {
    # path, head width, slots, top_k, first position, q's and the caches'
    # types, tolerance
    "kernel from an empty cache": (
        "kernel", 128, 256, 128, 0, "float32", "float32", 2e-6),
    "kernel across the set's filling": (
        "kernel", 128, 256, 128, 124, "bfloat16", "bfloat16", 1e-2),
    "kernel over a narrower cache": (
        "kernel", 128, 256, 128, 124, "float32", "bfloat16", 2e-6),
    "plain from an empty cache": (
        "plain", 16, 40, 8, 0, "float32", "float32", 2e-6),
    "plain across the set's filling": (
        "plain", 16, 40, 8, 5, "float32", "float32", 2e-6),
    "plain over a narrower cache": (
        "plain", 16, 40, 8, 5, "float32", "bfloat16", 2e-6)}


@pytest.mark.parametrize("tile", [1, 2, 4], ids=lambda n: "tile %d" % n)
@pytest.mark.parametrize("case", sorted(BLOCKS))
def test_a_block_over_chosen_sets_is_its_single_steps(case, tile,
                                                      monkeypatch):
    """Seven positions through the op at once, a set and a `Live` each,
    against seven steps: the caches bit for bit, the outputs to rounding,
    with tiles of 1 (seven in one loop), of 2 (three in one loop and a
    remainder of one) and of 4 (one and a remainder of three)."""
    path, dim, slots, top_k, pos, q_type, cache_type, atol = BLOCKS[case]
    rs = np.random.RandomState(len(case) + tile)
    rows, heads, kv_heads, block = 2, 8, 2, 7
    draw = lambda dtype, *s: jnp.asarray(rs.randn(*s), dtype)  # noqa: E731
    q, k_new, v_new = (draw(q_type, rows, block, n * dim)
                       for n in (heads, kv_heads, kv_heads))
    caches = [draw(cache_type, rows, kv_heads, slots, dim) for _ in "kv"]
    selected, live = _sets_of_a_block(rs, rows, slots, top_k, pos, block)
    op = registry.get_op_info("cached_attention").kernel
    attrs = {"num_heads": heads, "num_kv_heads": kv_heads}

    def ins(at, caches, positions):
        cut = (slice(None), positions)
        chosen = selected[cut], live[cut]
        if chosen[0].shape[1] == 1:     # a step's: [rows, top_k], [rows]
            chosen = [x[:, 0] for x in chosen]
        return {"Q": [q[cut]], "KNew": [k_new[cut]], "VNew": [v_new[cut]],
                "KCache": [caches[0]], "VCache": [caches[1]],
                "Position": [jnp.full((rows,), at)],
                "Selected": [jnp.asarray(chosen[0])],
                "Live": [jnp.asarray(chosen[1])]}

    stepped, outs = caches, []
    for t in range(block):
        out = op(None, ins(pos + t, stepped, slice(t, t + 1)), attrs)
        stepped = [out["KCacheOut"][0], out["VCacheOut"][0]]
        outs.append(out["Out"][0])
    # a position's two copies, beside its scores on the plain path
    a_position = rows * top_k * (2 * kv_heads * dim * q.dtype.itemsize
                                 + (0 if path == "kernel" else heads * 4))
    monkeypatch.setattr(attention, "_CHOSEN_TILE_BYTES", tile * a_position)
    before = telemetry.snapshot()
    got = op(None, ins(pos, caches, slice(None)), attrs)
    lowered = [k for k in telemetry.snapshot_delta(before)
               if k.startswith("sparse_attention_lowerings_total")]
    assert len(lowered) == 1 and "path=%s" % path in lowered[0] \
        and "positions=7," in lowered[0] \
        and "tile=%d," % tile in lowered[0]
    assert got["Out"][0].shape == (rows, block, heads * dim) \
        and got["Out"][0].dtype == q.dtype
    for name, want in zip(("KCacheOut", "VCacheOut"), stepped):
        assert got[name][0].dtype == want.dtype
        np.testing.assert_array_equal(
            np.asarray(got[name][0].astype(jnp.float32)),
            np.asarray(want.astype(jnp.float32)))
    np.testing.assert_allclose(
        np.asarray(got["Out"][0].astype(jnp.float32)),
        np.asarray(jnp.concatenate(outs, axis=1).astype(jnp.float32)),
        atol=atol)


def _run(main, scope, feeds, fetches):
    fp = FunctionalProgram(main.clone(for_test=True), sorted(feeds),
                           fetches)
    return fp({n: scope.get(n) for n in fp.state_in_names},
              {k: jnp.asarray(v) for k, v in feeds.items()})[0]


@pytest.mark.parametrize("start,block", [(0, 7), (3, 8), (9, 1)])
def test_a_block_through_the_program_is_its_single_steps(built, start,
                                                         block):
    """T positions through the built Program at once against T
    applications of one: the logits, every state pair, and the `parts`
    (the last position's, in the shapes a step's have); the chosen sets
    are identical."""
    main, pairs, parts = built["main"], built["pairs"], built["parts"]
    probes = [(key, i, v.name) for key, made in sorted(parts.items())
              for i, v in enumerate(made)]
    fetches = [built["decoder"]._fp.fetch_names[0]] \
        + [o for _, o in pairs] + [name for _, _, name in probes]
    rs = np.random.RandomState(start + block)
    state = {f: jnp.asarray(rs.randn(*v.shape) * 0.3, v.dtype)
             for f, v in _empty().items()}
    state["pos"] = jnp.full((B,), start, jnp.int32)
    state["rope_delta"] = jnp.full((B,), -2, jnp.int32)
    tokens = built["tokens"][:, start:start + block]
    stepped = dict(state)
    for t in range(block):
        one = _run(main, built["scope"],
                   dict(stepped, tok=tokens[:, t:t + 1]), fetches)
        stepped = {f: v for (f, _), v in zip(pairs, one[1:])}
    got = _run(main, built["scope"], dict(state, tok=tokens), fetches)
    assert len(got) == len(one) == 1 + len(pairs) + len(probes)
    for (key, i, _), a, b in zip(probes, got[1 + len(pairs):],
                                 one[1 + len(pairs):]):
        assert a.shape == b.shape, key
        if key in ("selected", "live", "top_idx"):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        elif key != "counts":   # the experts' rows over the whole block
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=2e-5, err_msg=key)
    assert got[1 + len(pairs) + [k for k, _, _ in probes].index("live")] \
        .tolist() == [min(INDEXER[2], start + block)] * B
    for (feed, _), a, b in zip(pairs, got[1:], one[1:]):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5,
                                   err_msg=feed)
    assert np.asarray(got[len(pairs) - 1]).tolist() == [start + block] * B
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(one[0]),
                               atol=LOGITS_RTOL * np.abs(one[0]).max())


def test_a_block_with_an_image_span_is_the_references(built):
    """The prefill's 14 positions, the image span among them, through
    the step that takes a tower's vectors as one block: the logits after
    it are the reference's at its last position and the caches hold what
    the reference would."""
    seeing, _, logits, pairs, _ = build_sparse_kv_moe_cached_step_program(
        B, T, V, images=True, **SIZES)
    slots, positions = built["slots"], built["positions"]
    held = np.zeros((B, PREFILL, D), "float32")
    held[:, slots] = built["vectors"]
    mask = np.zeros((B, PREFILL, 1), "float32")
    mask[:, slots] = 1
    got = _run(seeing, built["scope"], dict(
        _empty(), tok=built["tokens"][:, :PREFILL],
        mrope_pos=np.broadcast_to(positions[:, None, :PREFILL],
                                  (3, B, PREFILL)).astype("int32"),
        image_embeds=held, image_mask=mask),
        [logits.name] + [o for _, o in pairs])
    want = np.asarray(built["want"]["logits"])[:, PREFILL - 1]
    assert np.abs(np.asarray(got[0]) - want).max() \
        <= LOGITS_RTOL * np.abs(want).max()
    state = {f: np.asarray(v) for (f, _), v in zip(pairs, got[1:])}
    assert state["pos"].tolist() == [PREFILL] * B
    for i in range(L):
        for feed, made in (("k_cache_%d" % i, built["want"]["keys"][i]),
                           ("v_cache_%d" % i, built["want"]["values"][i])):
            np.testing.assert_allclose(
                state[feed][:, :, :PREFILL],
                np.asarray(made).transpose(0, 2, 1, 3)[:, :, :PREFILL],
                atol=3e-5)
            assert not state[feed][:, :, PREFILL:].any()
        np.testing.assert_allclose(
            state["index_cache_%d" % i][:, :PREFILL],
            np.asarray(built["want"]["index_keys"][i])[:, :PREFILL],
            atol=3e-5)


@pytest.mark.parametrize("prompt_len,block", [(11, 4), (20, 8), (7, 64)],
                         ids=["3 + 2 x 4", "4 + 2 x 8", "one short block"])
def test_a_prompt_is_prefilled_in_blocks(built, prompt_len, block,
                                         monkeypatch):
    """`ProgramDecoder` reads the declaration and the attention op's
    `prefill_block`: the prompt goes through the step `block` positions
    an application (the remainder first), and serves the tokens the
    position-by-position prefill serves."""
    # so many token rows an application: B rows take `block` positions
    monkeypatch.setattr(latent_moe_program, "_CHOOSER_ROWS", B * block)
    main, _, logits, pairs, _ = build_sparse_kv_moe_cached_step_program(
        B, T, V, **SIZES)
    assert {od.attrs["prefill_block"] for od in main.global_block().desc.ops
            if od.type == "cached_attention"} == {block}
    prompt = built["tokens"][:, :prompt_len]
    gen = T - prompt_len + 1
    served = {}
    for how in ("blocks", "positions"):
        decoder = fluid.ProgramDecoder(
            main.clone(for_test=True), token_name="tok",
            logits_name=logits.name, state_pairs=pairs,
            scope=built["scope"], max_positions=T)
        assert decoder._takes_block and decoder._prefill_block == block
        if how == "positions":
            decoder._prefill_block = 1
        before = telemetry.snapshot()
        served[how], lengths, state = decoder.greedy(
            bos=0, eos=V, max_len=gen, init_state=_empty(), prompt=prompt,
            return_state=[f for f, _ in pairs])
        traced = telemetry.snapshot_delta(before)
        assert (lengths == gen).all()
        served[how + " state"] = state
        if how == "blocks":
            assert traced["prefill_lowerings_total{block=%d,form=block}"
                          % block] == 1
            # the attention was traced for the remainder, a block, a step
            sizes = {prompt_len % block, min(block, prompt_len), 1} - {0}
            assert {int(k.split("positions=")[1].split(",")[0])
                    for k in traced if k.startswith(
                        "sparse_attention_lowerings_total")} == sizes
    np.testing.assert_array_equal(served["blocks"], served["positions"])
    for feed, want in served["positions state"].items():
        np.testing.assert_allclose(
            np.asarray(served["blocks state"][feed]), np.asarray(want),
            atol=3e-5, err_msg=feed)


# -- (c) three-part positions ---------------------------------------------------

def _rope(x, positions, heads, **attrs):
    return registry.get_op_info("rope").kernel(
        None, {"X": [x], "Positions": [positions]},
        dict({"num_heads": heads, "theta": 1e7}, **attrs))["Out"][0]


@pytest.mark.parametrize("full_width", [False, True])
@pytest.mark.parametrize("seq", [1, 6])
def test_sectioned_rope_with_equal_rows_is_rope(seq, full_width):
    rs = np.random.RandomState(seq)
    x = jnp.asarray(rs.randn(2, seq, 4 * 16), jnp.float32)
    positions = jnp.asarray(rs.randint(0, 500, (2, seq)))
    plain = _rope(x, positions, 4, full_width=full_width)
    three = _rope(x, jnp.stack([positions] * 3), 4, sections=[2, 3, 3],
                  full_width=full_width)
    np.testing.assert_array_equal(np.asarray(three), np.asarray(plain))


@pytest.mark.parametrize("full_width", [False, True])
def test_sectioned_rope_is_the_references_where_the_rows_differ(full_width):
    rs = np.random.RandomState(5)
    x = jnp.asarray(rs.randn(2, 6, 4 * 16), jnp.float32)
    positions = jnp.asarray(rs.randint(0, 500, (3, 2, 6)))
    before = telemetry.snapshot()
    got = np.asarray(_rope(x, positions, 4, sections=[2, 3, 3],
                           full_width=full_width))
    counted = telemetry.snapshot_delta(before)
    for b in range(2):
        want = reference.mrope(x[b].reshape(6, 4, 16), positions[:, b],
                               1e7, (2, 3, 3))
        np.testing.assert_allclose(got[b], np.asarray(want).reshape(6, 64),
                                   atol=1e-5)
    assert any(k.startswith("sectioned_rope_lowerings_total")
               and "sections=2-3-3" in k for k in counted)
    # and it is not the rotation by any one row
    assert np.abs(got - np.asarray(_rope(x, positions[0], 4))).max() > 0.1


@pytest.mark.parametrize("why,sections,shape", [
    ("two counts", [4, 4], (3, 2, 6)), ("too few pairs", [2, 3, 2], (3, 2, 6)),
    ("one row of positions", [2, 3, 3], (2, 6))])
def test_sections_that_do_not_fit_are_refused(why, sections, shape):
    with pytest.raises(ValueError, match="sections"):
        _rope(jnp.zeros((2, 6, 64)), jnp.zeros(shape, jnp.int32), 4,
              sections=sections)


# -- (d) the expert layer without a shared expert -------------------------------

def _layer(held, shared):
    """F(u) of one layer's program over u [3, 5, 64], its parameters and
    the names of what it made."""
    main, startup = fluid.Program(), fluid.Program()
    block = {w: "b." + w for w in ("router", "w_gate", "w_up", "w_down")}
    if shared:
        block.update(shared_in="b.shared_in", shared_out="b.shared_out")
    with fluid.program_guard(main, startup):
        u = fluid.layers.data(name="u", shape=[3, 5, D], dtype="float32",
                              append_batch_size=False)
        f, routing = share_feed_forward(u, block, False, 0, FE, E, held, K,
                                        True, 1.0, scoring="softmax")
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    startup.random_seed = 11
    exe.run(startup, scope=scope)
    made = {p.name for p in main.global_block().all_parameters()}
    return main, exe, scope, f, routing, made


def test_share_feed_forward_without_a_shared_expert_is_the_held_part():
    main, exe, scope, f, routing, made = _layer(HELD, shared=False)
    assert made == {"b.router", "b.w_gate", "b.w_up", "b.w_down"}
    u = np.random.RandomState(2).randn(3, 5, D).astype("float32")
    got, part = exe.run(main, feed={"u": u}, scope=scope,
                        fetch_list=[f, routing["moe_out"]])
    np.testing.assert_array_equal(got, part)
    block = {w: np.asarray(scope.get("b." + w))
             for w in ("router", "w_gate", "w_up", "w_down")}
    with jax.default_matmul_precision("highest"):
        want, _ = reference.routed(CFG, block, jnp.asarray(u.reshape(-1, D)),
                                   HELD[0])
    np.testing.assert_allclose(got.reshape(-1, D), np.asarray(want),
                               atol=2e-5)


def test_with_a_shared_expert_it_is_the_layer_it_was():
    _, _, _, _, _, made = _layer(HELD, shared=True)
    assert made == {"b.router", "b.w_gate", "b.w_up", "b.w_down",
                    "b.shared_in", "b.shared_out"}


@pytest.mark.parametrize("ranges", [
    [(i, 1) for i in range(8)], [(0, 4), (4, 4)], [(0, 2), (2, 4), (6, 2)]])
def test_the_shares_add_up_to_the_uncut_layer(ranges):
    """The parts of every share of one layer (8 shares of one expert as
    the deployment's eight chips of sixteen) are the uncut layer's
    output: nothing is counted twice, nothing is lost."""
    rs = np.random.RandomState(4)
    u = jnp.asarray(rs.randn(10, D), jnp.float32)
    whole = {"router": jnp.asarray(rs.randn(D, E), jnp.float32),
             "w_gate": jnp.asarray(rs.randn(E, D, FE) * 0.1, jnp.float32),
             "w_up": jnp.asarray(rs.randn(E, D, FE) * 0.1, jnp.float32),
             "w_down": jnp.asarray(rs.randn(E, FE, D) * 0.1, jnp.float32)}
    with jax.default_matmul_precision("highest"):
        want, _ = reference.routed(CFG, whole, u, 0)
        total = jnp.zeros_like(want)
        for first, count in ranges:
            share = dict(whole, **{w: whole[w][first:first + count]
                                   for w in ("w_gate", "w_up", "w_down")})
            total = total + reference.routed(CFG, share, u, first)[0]
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               atol=1e-5)


# -- (e) counters, names, and the Programs the repo had -------------------------

def test_the_build_lowers_nothing(built):
    assert not any("lowerings_total" in k for k in built["at_build"])


def test_counters_say_what_was_lowered():
    main, startup, logits, pairs, _ = \
        build_sparse_kv_moe_cached_step_program(B, T, V, **SIZES)
    scope = _start(startup)
    decoder = fluid.ProgramDecoder(
        main.clone(for_test=True), token_name="tok",
        logits_name=logits.name, state_pairs=pairs, scope=scope,
        max_positions=T)
    before = telemetry.snapshot()
    decoder.greedy(bos=1, eos=V, max_len=3, init_state=_empty())
    counted = telemetry.snapshot_delta(before)

    def total(prefix):
        return sum(v for k, v in counted.items() if k.startswith(prefix))

    assert total("sparse_attention_lowerings_total") == L
    assert total("mla_index_select_lowerings_total") == L == sum(
        v for k, v in counted.items()
        if k.startswith("mla_index_select_lowerings_total")
        and "select=count" in k)
    # q and k a layer; the chooser's two rotations have one position
    assert total("sectioned_rope_lowerings_total") == 2 * L
    assert total("window_attention_lowerings_total") == 0
    assert total("kv_cache_slots_total{kind=sparse}") == L * T


def test_parameter_names_are_the_references_tree(built):
    made = {p.name for p in built["main"].global_block().all_parameters()}
    assert set(jax.tree_util.tree_leaves(NAMES)) == made
    assert not any("shared" in name for name in made)
    assert [f for f, _ in built["pairs"]][-2:] == ["pos", "rope_delta"]


@pytest.fixture(scope="module")
def attend_lowerings():
    import json

    import parent_lowerings
    with open(parent_lowerings.ATTEND_RECORDING) as f:
        return json.load(f), parent_lowerings.attend_lowerings()


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("width", ["64-wide", "64-wide ungrouped",
                                   "128-wide"])
@pytest.mark.parametrize("what", ["step chosen", "step", "step ring",
                                  "step read-only", "block"])
def test_what_the_op_lowered_to_it_lowers_to(attend_lowerings, what, width,
                                             dtype):
    """PR 66 gave `cached_attention` a chosen set a position of a block.
    A step over a chosen set (the kernel's body included) and every form
    without one trace to the jaxpr they traced to before, equation for
    equation: the decoding step of keye's cell and the three cells that
    run the op without `Selected` are what they were.  Against the
    recording made on commit 56cbe1e."""
    recorded, now = attend_lowerings
    name = "%s %s %s" % (what, width, dtype)
    # `gqa_decode_chosen`'s call tells the compiler what it costs since
    # PR 66 (one param of one equation, and no arithmetic)
    told = re.sub(r"cost_estimate=CostEstimate\([^)]*\)",
                  "cost_estimate=None", now[name])
    assert (told != now[name]) == (name.startswith("step chosen 128-wide"))
    assert told == recorded[name]


def _digest(program):
    block = program.global_block().desc
    text = repr([(od.type, sorted(od.inputs.items()),
                  sorted(od.outputs.items()),
                  sorted((k, repr(v)) for k, v in od.attrs.items()))
                 for od in block.ops])
    return hashlib.sha256(text.encode()).hexdigest()


# the digests of the step Programs PR 58's parent commit built (PR 57,
# 0e84e89; the latent step's with an `indexer` as PR 62 built it, when a
# chooser's step took a block of positions): a Program's names are its
# own counters', so a build is deterministic
DIGESTS = {
    "gpt2":
        "caaac60e420d0c495b0bb9d3bd421230d8887991792e9c7de610c7293ba46833",
    "window_moe":
        "c2faad61b6caf27eaa16f96dae4884f76c69fbe7ffa2c826284ce52af94f7f3e",
    "latent_moe":
        "8d2246a6ac1fb9e63617f92272f82752e12b69b03da05b4b87d63c1f1ff747aa"}


@pytest.mark.parametrize("which", sorted(DIGESTS))
def test_the_step_programs_the_repo_had_are_op_for_op_what_they_were(which):
    """GPT-2's, K-EXAONE's and DeepSeek-V3.2's steps at toy sizes, op
    for op, input for input and attr for attr what the recorded commits
    built: no cached_attention op has a `Selected`, no rope a
    `sections`, and an expert layer that names a shared expert still
    adds it.  The new inputs and attrs are said only where asked for."""
    from paddle_tpu.models.latent_moe_program import \
        build_latent_moe_cached_step_program
    from paddle_tpu.models.transformer_program import \
        build_transformer_cached_step_program
    from paddle_tpu.models.window_moe_program import \
        build_window_moe_cached_step_program
    main = {
        "gpt2": lambda: build_transformer_cached_step_program(
            2, 16, 97, n_layer=2, n_head=2, d_model=32, d_ff=64)[0],
        "window_moe": lambda: build_window_moe_cached_step_program(
            2, 16, 97)[0],
        "latent_moe": lambda: build_latent_moe_cached_step_program(
            2, 16, 97, indexer=(4, 8, 4))[0]}[which]()
    ops = main.global_block().desc.ops
    for od in ops:
        if od.type == "cached_attention":
            assert "Selected" not in od.inputs and "Live" not in od.inputs
        if od.type == "rope":
            assert "sections" not in od.attrs
    assert _digest(main) == DIGESTS[which]
