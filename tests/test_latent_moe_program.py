"""Latent attention over a cache of latents (`mla_cached_attention`), the
router's sigmoid scoring and the expert op's range form (ops/moe.py), and
the cached step Program built on them (models/latent_moe_program.py)
against the plain float32 reference (models/reference/pangu_moe.py): the
step driven position by position through its cache against the
reference's unabsorbed full-sequence forward; the shares of an expert
layer adding up to the uncut layer; the router; a bfloat16 cache against
the float32 one; the counters; `ProgramDecoder` taking the scope's
arrays as they are; and a block of T > 1 positions (PR 53): the op and
the kernel against T single steps, the step Program prefilled in blocks
of its own `prefill_block` against the scanned single steps and the
reference.

Tiny sizes on the CPU: 3 layers (1 dense), hidden 64, 4 heads of 16 + 8
(values 16), query rank 32, latent 16, 8 experts scored of which 4 are
held, 2 a token, vocabulary 97, seeded random weights (norm scales moved
off their initial 1, so that a scale left out shows).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid.param_attr import ParamAttr
from paddle_tpu.models import decode, latent_moe_program
from paddle_tpu.models.latent_moe_program import (
    build_latent_moe_cached_step_program, latent_moe_param_names)
from paddle_tpu.models.reference import pangu_moe as reference
from paddle_tpu.models.transformer_program import (
    build_transformer_cached_step_program)
from paddle_tpu.obs import telemetry
from paddle_tpu.ops import registry

B, T, V, L, DENSE = 3, 12, 97, 3, 1
H, D, QR, KVR, NOPE, ROPE, DV, FF, FE = 4, 64, 32, 16, 16, 8, 16, 128, 32
E, K, HELD = 8, 2, (2, 4)
SIZES = dict(n_layer=L, n_dense=DENSE, n_head=H, d_model=D, q_rank=QR,
             kv_rank=KVR, d_nope=NOPE, d_rope=ROPE, d_v=DV, d_ff=FF,
             d_expert=FE, n_experts=E, held=HELD, top_k=K)
CFG = {"num_attention_heads": H, "rms_norm_eps": 1e-5, "rope_theta": 1e4,
       "kv_lora_rank": KVR, "num_experts_per_tok": K,
       "norm_topk_prob": True, "routed_scaling_factor": 2.5}
NAMES = latent_moe_param_names(L, DENSE)

# float32 on the CPU.  The step absorbs the keys' and values'
# up-projections and reads a cache; the reference makes every head's keys
# and values and the whole score matrix: other sums in another order.
# Logits of size ~4 were seen to differ by 2e-6 (5e-7 of them); 1e-5 of
# the largest logit is a dozen times that and a hundred times under one
# bfloat16 rounding.
LOGITS_RTOL = 1e-5


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


def _decoder(main, logits, pairs, scope):
    return fluid.ProgramDecoder(
        main.clone(for_test=True), token_name="tok",
        logits_name=logits.name, state_pairs=pairs, scope=scope,
        max_positions=T)


def _empty(dtype=jnp.float32, layers=L):
    state = {"latent_cache_%d" % i: jnp.zeros((B, T, KVR + ROPE), dtype)
             for i in range(layers)}
    state["pos"] = jnp.zeros((B,), jnp.int32)
    return state


def _drive(decoder, tokens, state):
    """[B, T, V]: the step applied position by position."""
    step = decoder._step_fn(decoder._params)
    out = []
    for t in range(tokens.shape[1]):
        logits, state = step(state, jnp.asarray(tokens[:, t]))
        out.append(logits)
    return np.stack([np.asarray(z, np.float32) for z in out], axis=1), state


@pytest.fixture(scope="module")
def built():
    before = telemetry.snapshot()
    main, startup, logits, pairs, parts = \
        build_latent_moe_cached_step_program(B, T, V, **SIZES)
    at_build = telemetry.snapshot_delta(before)
    scope = _start(startup)
    decoder = _decoder(main, logits, pairs, scope)
    tokens = np.random.RandomState(1).randint(0, V, (B, T)).astype("int32")
    got, state = _drive(decoder, tokens, _empty())
    params = jax.tree_util.tree_map(scope.get, NAMES)
    want = reference.forward(CFG, params, jnp.asarray(tokens), held=HELD)
    return {"main": main, "logits": logits, "pairs": pairs, "scope": scope,
            "decoder": decoder, "tokens": tokens, "got": got,
            "state": state, "params": params, "want": want,
            "at_build": at_build}


# -- (a) the step through its cache against the full forward ------------------

@pytest.mark.parametrize("position", range(T))
def test_step_logits_agree_with_the_reference_at_every_position(
        built, position):
    want = np.asarray(built["want"]["logits"])[:, position]
    got = built["got"][:, position]
    assert np.abs(got - want).max() <= LOGITS_RTOL * np.abs(want).max()


def test_the_cache_holds_the_latents_and_no_heads(built):
    """576 values a token at the published widths: here 16 + 8, written
    at the slot of each position, the normed latent beside the rotated
    key."""
    cache = np.asarray(built["state"]["latent_cache_1"])
    assert cache.shape == (B, T, KVR + ROPE)
    assert np.all(np.abs(cache).sum(axis=-1) > 0)
    assert int(built["state"]["pos"][0]) == T
    # the normed latent has a root mean square near its scale's
    rms = np.sqrt(np.mean(np.square(cache[..., :KVR]), axis=-1))
    assert 0.5 < rms.min() and rms.max() < 2.0


def test_greedy_through_the_decoder_is_the_references_greedy(built):
    """Prefill then decode through `ProgramDecoder.greedy`: every served
    token is the reference's first given the tokens before it (or lies
    within rounding of it)."""
    prompt = built["tokens"][:, :5]
    tokens, lengths = built["decoder"].greedy(
        bos=0, eos=V, max_len=T - 4, init_state=_empty(), prompt=prompt)
    assert tokens.shape == (B, T - 4) and (lengths == T - 4).all()
    full = np.concatenate([prompt, tokens], axis=1)[:, :T]
    z = np.asarray(reference.forward(
        CFG, built["params"], jnp.asarray(full), held=HELD)["logits"])
    served = tokens[:, :T - 4]
    picked = np.take_along_axis(z[:, 4:4 + served.shape[1]],
                                served[..., None], axis=-1)[..., 0]
    gap = z[:, 4:4 + served.shape[1]].max(axis=-1) - picked
    assert gap.max() <= 1e-4


def test_the_shares_step_is_prefilled_in_blocks_of_its_own(built):
    """The share's step declares `tok` [batch, -1] and states the block
    it is prefilled by: at these tiny widths `PREFILL_BLOCK`, so a prompt
    of 3 is one application of 3."""
    decoder = built["decoder"]
    assert decoder._takes_block
    assert decoder._prefill_block == decode.PREFILL_BLOCK \
        == latent_moe_program.prefill_block(B, H, KVR, ROPE)
    before = telemetry.snapshot()
    decoder.greedy(bos=0, eos=V, max_len=2, init_state=_empty(),
                   prompt=built["tokens"][:, :3])
    lowered = telemetry.snapshot_delta(before)
    assert {k: v for k, v in lowered.items()
            if k.startswith("prefill_lowerings_total")} == {
        "prefill_lowerings_total{block=%d,form=block}"
        % decode.PREFILL_BLOCK: 1}
    assert {k: v for k, v in lowered.items()
            if k.startswith("mla_decode_lowerings_total")} == {
        "mla_decode_lowerings_total{block_k=0,path=plain,positions=3}": L,
        "mla_decode_lowerings_total{block_k=0,path=plain,positions=1}": L}


def test_a_position_past_the_cache_is_refused(built):
    with pytest.raises(ValueError, match="extent"):
        built["decoder"].greedy(bos=0, eos=V, max_len=T, init_state=_empty(),
                                prompt=built["tokens"][:, :5])


# -- the attention op alone ----------------------------------------------------

def _mla_ins(rs, dtype=jnp.float32, cache_dtype=jnp.float32, pos=5):
    def draw(*shape):
        return jnp.asarray(rs.randn(*shape), dtype)

    cache = jnp.asarray(rs.randn(B, T, KVR + ROPE), cache_dtype)
    cache = cache.at[:, pos:].set(0)
    return {"QNope": [draw(B, 1, H * NOPE)], "QRope": [draw(B, 1, H * ROPE)],
            "CNew": [draw(B, 1, KVR)], "RNew": [draw(B, 1, ROPE)],
            "Cache": [cache], "WUk": [0.3 * draw(KVR, H * NOPE)],
            "WUv": [0.3 * draw(KVR, H * DV)],
            "Position": [jnp.full((B,), pos, jnp.int32)]}


def _unabsorbed(ins, pos):
    """Attention over keys [c W_uk | r] and values c W_uv, made whole,
    at the inputs' own sizes (H heads)."""
    f32 = jnp.float32
    b, kvr, rope = ins["CNew"][0].shape[0], ins["CNew"][0].shape[-1], \
        ins["RNew"][0].shape[-1]
    nope = ins["QNope"][0].shape[-1] // H
    cache = ins["Cache"][0].astype(f32)
    entry = jnp.concatenate([ins["CNew"][0], ins["RNew"][0]], -1).astype(f32)
    cache = cache.at[:, pos].set(entry[:, 0])[:, :pos + 1]
    c, r = cache[..., :kvr], cache[..., kvr:]
    k_nope = (c @ ins["WUk"][0].astype(f32)).reshape(b, pos + 1, H, nope)
    v = (c @ ins["WUv"][0].astype(f32)).reshape(b, pos + 1, H, -1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(r[:, :, None], (b, pos + 1, H, rope))], -1)
    q = jnp.concatenate(
        [ins["QNope"][0].astype(f32).reshape(b, H, nope),
         ins["QRope"][0].astype(f32).reshape(b, H, rope)], -1)
    s = jnp.einsum("bhd,bthd->bht", q, k) / np.sqrt(nope + rope)
    return jnp.einsum("bht,bthd->bhd", jax.nn.softmax(s, -1),
                      v).reshape(b, 1, -1)


@pytest.mark.parametrize("pos", [0, 5, T - 1])
def test_absorbed_attention_is_attention_over_the_heads_keys(pos):
    ins = _mla_ins(np.random.RandomState(pos), pos=pos)
    outs = registry.get_op_info("mla_cached_attention").kernel(
        None, ins, {"num_heads": H})
    np.testing.assert_allclose(outs["Out"][0], _unabsorbed(ins, pos),
                               atol=2e-5)
    kept = np.asarray(outs["CacheOut"][0])
    np.testing.assert_array_equal(kept[:, pos, :KVR],
                                  np.asarray(ins["CNew"][0])[:, 0])
    np.testing.assert_array_equal(kept[:, pos, KVR:],
                                  np.asarray(ins["RNew"][0])[:, 0])
    np.testing.assert_array_equal(kept[:, :pos],
                                  np.asarray(ins["Cache"][0])[:, :pos])


def test_a_slot_past_the_position_is_not_attended():
    ins = _mla_ins(np.random.RandomState(7), pos=4)
    kernel = registry.get_op_info("mla_cached_attention").kernel
    want = kernel(None, ins, {"num_heads": H})["Out"][0]
    dirty = dict(ins, Cache=[ins["Cache"][0].at[:, 5:].set(9.0)])
    np.testing.assert_array_equal(
        kernel(None, dirty, {"num_heads": H})["Out"][0], want)


def test_both_contractions_over_the_cache_are_matrix_products():
    """Two `dot_general`s over the cache with float32 sums, and no
    multiply followed by a reduction over its extent."""
    ins = _mla_ins(np.random.RandomState(2), jnp.bfloat16, jnp.bfloat16)
    kernel = registry.get_op_info("mla_cached_attention").kernel
    jaxpr = jax.make_jaxpr(
        lambda i: kernel(None, i, {"num_heads": H})["Out"][0])(ins)
    dots = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "dot_general"]
    over_cache = [e for e in dots
                  if (B, T, KVR + ROPE) in [v.aval.shape for v in e.invars]]
    assert len(over_cache) == 2 and len(dots) == 4
    assert all(e.params["preferred_element_type"] == jnp.float32
               for e in dots)
    assert all(v.aval.dtype == jnp.bfloat16
               for e in over_cache for v in e.invars)


def test_a_cache_of_another_width_is_refused():
    ins = _mla_ins(np.random.RandomState(2))
    ins["Cache"] = [jnp.zeros((B, T, KVR + ROPE + 1))]
    with pytest.raises(ValueError, match="cache holds"):
        registry.get_op_info("mla_cached_attention").kernel(
            None, ins, {"num_heads": H})


# -- the walk of the live slots (kernels/mla_decode.py) -----------------------
# a shape the kernel takes, small: 2 rows, 4 heads, three blocks of 128
# slots, latent 128 + rope 64, under the Pallas interpreter

WB, WP, WL, WR, WBK = 2, 384, 128, 64, 128


def _walk_ins(rs, dtype, pos, past=0.0):
    """The op's inputs at the walked shape; slots past `pos` hold `past`."""
    def draw(*shape):
        return jnp.asarray(rs.randn(*shape), dtype)

    cache = draw(WB, WP, WL + WR).at[:, pos + 1:].set(past)
    return {"QNope": [draw(WB, 1, H * NOPE)], "QRope": [draw(WB, 1, H * WR)],
            "CNew": [draw(WB, 1, WL)], "RNew": [draw(WB, 1, WR)],
            "Cache": [cache], "WUk": [0.1 * draw(WL, H * NOPE)],
            "WUv": [0.1 * draw(WL, H * DV)],
            "Position": [jnp.full((WB,), pos, jnp.int32)]}


def _decode_paths(delta):
    """{path: count} of `mla_decode_lowerings_total` in a snapshot's
    delta."""
    return {k[len("mla_decode_lowerings_total"):]: v
            for k, v in delta.items()
            if k.startswith("mla_decode_lowerings_total")}


def _mla_paths(ins, heads=H):
    """{path: count} of `mla_decode_lowerings_total` and the op's outputs
    for one trace of the op over `ins`."""
    before = telemetry.snapshot()
    outs = registry.get_op_info("mla_cached_attention").kernel(
        None, ins, {"num_heads": heads})
    return _decode_paths(telemetry.snapshot_delta(before)), outs


@pytest.mark.parametrize("dtype,atol", [(jnp.float32, 2e-5),
                                        (jnp.bfloat16, 6e-2)])
@pytest.mark.parametrize("pos", [0, WBK - 1, WBK, WP - 1])
def test_the_walk_of_live_slots_is_the_plain_path(pos, dtype, atol,
                                                  monkeypatch):
    """The kernel against the op's plain path on the same inputs (the
    test says the shape does not fit; the op has no switch), at the
    first slot, the last of a block, the first of the next and the last
    of the cache."""
    from paddle_tpu.kernels import mla_decode

    ins = _walk_ins(np.random.RandomState(pos), dtype, pos)
    paths, walked = _mla_paths(ins)
    assert paths == {"{block_k=%d,path=kernel,positions=1}" % WBK: 1}
    monkeypatch.setattr(mla_decode, "fits", lambda *shape: False)
    paths, plain = _mla_paths(ins)
    assert paths == {"{block_k=0,path=plain,positions=1}": 1}
    assert walked["Out"][0].dtype == plain["Out"][0].dtype == dtype
    np.testing.assert_allclose(
        np.asarray(walked["Out"][0], np.float32),
        np.asarray(plain["Out"][0], np.float32), atol=atol)
    np.testing.assert_array_equal(
        np.asarray(walked["CacheOut"][0], np.float32),
        np.asarray(plain["CacheOut"][0], np.float32))


@pytest.mark.parametrize("pos", [0, 5, WBK - 1, WBK, 2 * WBK + 9])
def test_the_walk_reads_nothing_past_the_position(pos):
    """NaN in every slot past the position, those of the block the
    position falls in and whole dead blocks alike, reaches no sum: a
    dead block is not folded, a dead slot's score is masked before the
    maximum and its values are zeroed before the product."""
    rs = np.random.RandomState(11)
    clean = _walk_ins(rs, jnp.float32, pos)
    dirty = dict(clean, Cache=[clean["Cache"][0].at[:, pos + 1:].set(
        jnp.nan)])
    paths, got = _mla_paths(dirty)
    assert list(paths) == ["{block_k=%d,path=kernel,positions=1}" % WBK]
    np.testing.assert_array_equal(got["Out"][0],
                                  _mla_paths(clean)[1]["Out"][0])


def test_the_walk_is_attention_over_the_heads_keys():
    """The kernel path against attention made whole, as the plain path
    is held above."""
    pos = WBK + 3
    ins = _walk_ins(np.random.RandomState(4), jnp.float32, pos)
    np.testing.assert_allclose(_mla_paths(ins)[1]["Out"][0],
                               _unabsorbed(ins, pos), atol=5e-5)


@pytest.mark.parametrize("why,change", [
    ("a chosen set of 4", {"Selected": [jnp.zeros((WB, 4), jnp.int32)],
                      "Live": [jnp.full((WB,), 4, jnp.int32)]}),
    ("6 positions", {"Cache": [jnp.zeros((WB, 6, WL + WR))],
                     "Position": [jnp.full((WB,), 3, jnp.int32)]}),
    ("a latent of 8", {"Cache": [jnp.zeros((WB, WP, 8 + WR))],
                       "CNew": [jnp.zeros((WB, 1, 8))],
                       "WUk": [jnp.zeros((8, H * NOPE))],
                       "WUv": [jnp.zeros((8, H * DV))]}),
])
def test_what_the_walk_does_not_take_is_the_plain_path(why, change):
    """The op chooses by what it sees in its inputs, and the counter's
    `path` says which way it went; no Pallas call is traced."""
    ins = dict(_walk_ins(np.random.RandomState(1), jnp.float32, 3),
               **change)
    kernel = registry.get_op_info("mla_cached_attention").kernel
    before = telemetry.snapshot()
    jaxpr = jax.make_jaxpr(
        lambda i: kernel(None, i, {"num_heads": H})["Out"][0])(ins)
    lowered = telemetry.snapshot_delta(before)
    assert lowered["mla_decode_lowerings_total{block_k=0,path=plain,"
                   "positions=1}"] == 1
    assert "pallas_call" not in str(jaxpr) and "platform_index" not in \
        str(jaxpr), why


@pytest.mark.parametrize("blocks", [(128, 1), (128, 2), (384, 2)])
def test_the_walk_gives_the_same_at_any_blocks(blocks):
    """One row a grid step or two, three blocks of slots or one: the
    same sums, up to the order the blocks are folded in."""
    from paddle_tpu.kernels import mla_decode

    rs = np.random.RandomState(5)
    q = jnp.asarray(rs.randn(WB, H, WL + WR), jnp.float32)
    cache = jnp.asarray(rs.randn(WB, WP, WL + WR), jnp.float32)
    for pos in (7, WBK, WP - 1):
        s = jnp.einsum("bhw,btw->bht", q, cache[:, :pos + 1]) * 0.1
        want = jnp.einsum("bht,btw->bhw", jax.nn.softmax(s, -1),
                          cache[:, :pos + 1, :WL])
        got = mla_decode.mla_decode(q, cache, jnp.int32(pos), 0.1, WL,
                                    blocks)
        np.testing.assert_allclose(got, want, atol=2e-5)


def test_the_walks_blocks_are_chosen_from_the_shapes():
    """pangu-decode-ep16's step (256 rows, 128 heads, 1024 slots of
    512 + 64 bfloat16 values) walks blocks of 512 slots, four rows a
    grid step; a cache that 512 does not tile takes the next block
    down, a batch that 4 does not tile fewer rows; a shape nothing
    tiles is the plain path's."""
    from paddle_tpu.kernels import mla_decode

    assert mla_decode.choose_blocks(256, 128, 1024, 576, 512, 2) == (512, 4)
    assert mla_decode.choose_blocks(256, 128, 768, 576, 512, 2) == (256, 4)
    assert mla_decode.choose_blocks(6, 128, 1024, 576, 512, 2) == (512, 2)
    assert mla_decode.choose_blocks(WB, 4, WP, WL + WR, WL, 4) == (WBK, 2)
    assert mla_decode.choose_blocks(3, 4, WP, WL + WR, WL, 4) == (WBK, 1)
    # a gathered set is live from end to end: one block a row where it
    # fits (the two chooser cells' 2048 of 576 values, 128 and 64 heads)
    for rows, heads in ((16, 128), (8, 64)):
        assert mla_decode.choose_blocks(
            rows, heads, 2048, 576, 512, 2, whole=True) == (2048, 1)
        assert mla_decode.choose_blocks(
            rows, heads, 2048, 576, 512, 2) == (512, 4)
    assert mla_decode.choose_blocks(
        16, 128, 8192, 576, 512, 2, whole=True) == (512, 4)
    assert mla_decode.fits(1, 1024, 512) and mla_decode.fits(1, 128, 128)
    assert not mla_decode.fits(1, 1000, 512)
    assert not mla_decode.fits(1, 1024, 320)
    with pytest.raises(ValueError, match="no step the kernel takes"):
        mla_decode.mla_decode(jnp.zeros((2, 4, 24)), jnp.zeros((2, 6, 24)),
                              0, 1.0, 16)


# -- a step over its gathered set through the same kernel (PR 70) --------------
# 2 rows choose 384 of 512 slots: the gathered rows are a cache whose first
# Live entries are live, walked as one block a row where that fits the
# kernel's VMEM budget and in three blocks of 128 where it does not

CP, CK = 512, 384


def _chosen_ins(rs, heads, live, sink, dtype=jnp.float32, top_k=CK):
    """The op's inputs for a step over a chosen set of `top_k` of CP
    slots a row, `live` of them live; slot CP - 1 is this step's."""
    def draw(*shape):
        return jnp.asarray(rs.randn(*shape), dtype)

    ins = {"QNope": [draw(WB, 1, heads * NOPE)],
           "QRope": [draw(WB, 1, heads * WR)],
           "CNew": [draw(WB, 1, WL)], "RNew": [draw(WB, 1, WR)],
           "Cache": [draw(WB, CP, WL + WR)],
           "WUk": [0.1 * draw(WL, heads * NOPE)],
           "WUv": [0.1 * draw(WL, heads * DV)],
           "Position": [jnp.full((WB,), CP - 1, jnp.int32)],
           "Selected": [jnp.asarray(np.stack(
               [np.sort(rs.choice(CP, top_k, replace=False))
                for _ in range(WB)]), jnp.int32)],
           "Live": [jnp.full((WB,), live, jnp.int32)]}
    if sink:
        ins["Sink"] = [jnp.asarray(rs.randn(heads) + 2.0, jnp.float32)]
    return ins


def _in_blocks_of_128(monkeypatch, heads):
    """The kernel's VMEM budget held to what a block of 128 slots takes:
    the set then fits as no one block (the op has no switch)."""
    from paddle_tpu.kernels import mla_decode

    monkeypatch.setattr(mla_decode, "_VMEM_BUDGET", mla_decode._step_bytes(
        1, heads, WBK, WL + WR, WL, 4))


def _attention_over_the_chosen(ins, heads):
    """Attention over the first Live of the chosen slots' keys [c W_uk |
    r] and values c W_uv made whole, a sink's term in the denominator,
    in float64."""
    f = lambda name: np.asarray(ins[name][0], np.float64)
    live = int(ins["Live"][0][0])
    cache = f("Cache")
    cache[:, CP - 1] = np.concatenate([f("CNew"), f("RNew")], -1)[:, 0]
    rows = np.take_along_axis(
        cache, np.asarray(ins["Selected"][0])[:, :live, None], axis=1)
    c, r = rows[..., :WL], rows[..., WL:]
    k = np.concatenate(
        [(c @ f("WUk")).reshape(WB, live, heads, NOPE),
         np.broadcast_to(r[:, :, None], (WB, live, heads, WR))], -1)
    v = (c @ f("WUv")).reshape(WB, live, heads, DV)
    q = np.concatenate([f("QNope").reshape(WB, heads, NOPE),
                        f("QRope").reshape(WB, heads, WR)], -1)
    s = np.einsum("bhd,bthd->bht", q, k) / np.sqrt(NOPE + WR)
    top = s.max(-1, keepdims=True)
    e = np.exp(s - top)
    total = e.sum(-1, keepdims=True)
    if "Sink" in ins:
        total = total + np.exp(f("Sink")[None, :, None] - top)
    return np.einsum("bht,bthd->bhd", e / total, v).reshape(WB, 1, -1)


@pytest.mark.parametrize("heads", [128, 64])
@pytest.mark.parametrize("sink", [False, True], ids=["no_sink", "sink"])
@pytest.mark.parametrize("live", [CK, 5, CK - 7],
                         ids=["all", "first_block", "last_block"])
@pytest.mark.parametrize("block_k", [CK, WBK], ids=["whole", "blocks"])
def test_a_steps_gathered_set_goes_through_the_kernel(block_k, live, sink,
                                                      heads, monkeypatch):
    """The kernel over the gathered rows against the op's plain products
    on the same inputs and against attention made whole, in float32:
    every entry live, the count inside the first 128 entries and inside
    the last, with a learned sink and without, at dsv32's 128 heads and
    hy4's 64; the set as one block a row, as the op chooses where it
    fits the kernel's VMEM budget, and in three blocks of 128 where the
    test says it does not."""
    from paddle_tpu.kernels import mla_decode

    if block_k != CK:
        _in_blocks_of_128(monkeypatch, heads)
    ins = _chosen_ins(np.random.RandomState(live + heads), heads, live,
                      sink)
    paths, walked = _mla_paths(ins, heads)
    assert paths == {"{block_k=%d,path=kernel_chosen,positions=1}"
                     % block_k: 1}
    monkeypatch.setattr(mla_decode, "fits", lambda *shape: False)
    paths, plain = _mla_paths(ins, heads)
    assert paths == {"{block_k=0,path=plain,positions=1}": 1}
    np.testing.assert_allclose(walked["Out"][0], plain["Out"][0], atol=2e-5)
    np.testing.assert_allclose(
        walked["Out"][0], _attention_over_the_chosen(ins, heads), atol=5e-5)
    np.testing.assert_array_equal(walked["CacheOut"][0],
                                  plain["CacheOut"][0])


@pytest.mark.parametrize("sink", [False, True], ids=["no_sink", "sink"])
def test_a_gathered_set_in_bfloat16_rounds_as_the_plain_products(
        sink, monkeypatch):
    from paddle_tpu.kernels import mla_decode

    ins = _chosen_ins(np.random.RandomState(3), H, CK - 40, sink,
                      jnp.bfloat16)
    paths, walked = _mla_paths(ins, H)
    assert list(paths) == ["{block_k=%d,path=kernel_chosen,positions=1}"
                           % CK]
    monkeypatch.setattr(mla_decode, "fits", lambda *shape: False)
    plain = _mla_paths(ins, H)[1]
    assert walked["Out"][0].dtype == plain["Out"][0].dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(walked["Out"][0], np.float32),
        np.asarray(plain["Out"][0], np.float32), atol=6e-2)


@pytest.mark.parametrize("live", [1, WBK, WBK + 9])
@pytest.mark.parametrize("block_k", [CK, WBK], ids=["whole", "blocks"])
def test_a_dead_entry_of_a_gathered_set_reaches_no_sum(block_k, live,
                                                       monkeypatch):
    """Past the first Live entries a set names slots that hold nothing:
    NaN there, in the block the count falls in and in a whole dead block
    alike, reaches no sum, and an entry past the extent is clipped into
    it, not filled in."""
    if block_k != CK:
        _in_blocks_of_128(monkeypatch, H)
    ins = _chosen_ins(np.random.RandomState(live), H, live, sink=True)
    chosen = np.asarray(ins["Selected"][0])
    dead = np.zeros((WB, CP), bool)
    np.put_along_axis(dead, chosen[:, live:], True, axis=1)
    np.put_along_axis(dead, chosen[:, :live], False, axis=1)
    dead[:, CP - 1] = False     # this step's slot is written, whatever
    dirty = dict(
        ins, Cache=[jnp.where(dead[:, :, None], jnp.nan, ins["Cache"][0])],
        Selected=[ins["Selected"][0].at[:, live:].add(
            jnp.where(jnp.arange(CK - live) % 2 == 0, 4 * CP, 0))])
    paths, got = _mla_paths(dirty, H)
    assert list(paths) == ["{block_k=%d,path=kernel_chosen,positions=1}"
                           % block_k]
    np.testing.assert_array_equal(got["Out"][0],
                                  _mla_paths(ins, H)[1]["Out"][0])


@pytest.mark.parametrize("why,top_k,path", [
    ("a set of 384", CK, "{block_k=%d,path=kernel_chosen,positions=1}"
     % CK),
    ("a set of 256", 256, "{block_k=256,path=kernel_chosen,positions=1}"),
    ("no multiple of 128", 200, "{block_k=0,path=plain,positions=1}"),
])
def test_the_counter_tells_a_chosen_step_through_the_kernel(why, top_k,
                                                            path):
    """`mla_decode_lowerings_total` by `path`: a chosen-set step through
    the kernel is "kernel_chosen" (its block the whole set, where that
    fits), one the kernel's `fits` refuses "plain", a whole-extent walk
    "kernel" (in blocks of 512 at most: a dead block is skipped); the op
    asks the shapes and has no switch."""
    ins = _chosen_ins(np.random.RandomState(8), H, top_k, False,
                      top_k=top_k)
    kernel = registry.get_op_info("mla_cached_attention").kernel
    before = telemetry.snapshot()
    jaxpr = jax.make_jaxpr(
        lambda i: kernel(None, i, {"num_heads": H})["Out"][0])(ins)
    assert _decode_paths(telemetry.snapshot_delta(before)) == {path: 1}, why
    assert ("pallas_call" in str(jaxpr)) == ("kernel" in path)
    whole = {k: v for k, v in ins.items() if k not in ("Selected", "Live")}
    assert list(_mla_paths(whole, H)[0]) == [
        "{block_k=%d,path=kernel,positions=1}" % CP]


@pytest.mark.parametrize("pos", [3, WBK, WP - 1])
def test_a_whole_extent_step_with_a_sink_walks_too(pos, monkeypatch):
    """The sink is one more term of the walk's last fold, whatever the
    cache the step walks: the whole extent through the kernel against
    the plain products."""
    from paddle_tpu.kernels import mla_decode

    rs = np.random.RandomState(pos)
    ins = dict(_walk_ins(rs, jnp.float32, pos),
               Sink=[jnp.asarray(rs.randn(H) + 2.0, jnp.float32)])
    paths, walked = _mla_paths(ins)
    assert paths == {"{block_k=%d,path=kernel,positions=1}" % WBK: 1}
    without = _mla_paths({k: v for k, v in ins.items() if k != "Sink"})[1]
    assert np.abs(np.asarray(walked["Out"][0])
                  - np.asarray(without["Out"][0])).max() > 1e-3
    monkeypatch.setattr(mla_decode, "fits", lambda *shape: False)
    np.testing.assert_allclose(walked["Out"][0],
                               _mla_paths(ins)[1]["Out"][0], atol=2e-5)


# -- a block of T positions: the op, the kernel, the step Program (PR 53) ------

def _block_ins(rs, block, pos, dtype=jnp.float32, shape=None):
    """The op's inputs for `block` consecutive positions from `pos` on;
    `shape` (rows, slots, latent, rope) defaults to the tiny one."""
    rows, slots, latent, rope = shape or (B, T + 8, KVR, ROPE)

    def draw(*size):
        return jnp.asarray(rs.randn(*size), dtype)

    cache = draw(rows, slots, latent + rope).at[:, pos:].set(0)
    return {"QNope": [draw(rows, block, H * NOPE)],
            "QRope": [draw(rows, block, H * rope)],
            "CNew": [draw(rows, block, latent)],
            "RNew": [draw(rows, block, rope)], "Cache": [cache],
            "WUk": [0.3 * draw(latent, H * NOPE)],
            "WUv": [0.3 * draw(latent, H * DV)],
            "Position": [jnp.full((rows,), pos, jnp.int32)]}


def _step_by_step(ins):
    """(Out [rows, T, .], CacheOut) of the op applied a position at a
    time over the block of `ins`."""
    kernel = registry.get_op_info("mla_cached_attention").kernel
    block = ins["QNope"][0].shape[1]
    pos, cache, outs = int(ins["Position"][0][0]), ins["Cache"][0], []
    for t in range(block):
        one = {k: [v[0][:, t:t + 1]] if k in ("QNope", "QRope", "CNew",
                                              "RNew") else v
               for k, v in ins.items()}
        got = kernel(None, dict(one, Cache=[cache], Position=[
            jnp.full_like(ins["Position"][0], pos + t)]), {"num_heads": H})
        cache = got["CacheOut"][0]
        outs.append(got["Out"][0])
    return jnp.concatenate(outs, axis=1), cache


@pytest.mark.parametrize("block", [1, 3, 16])
@pytest.mark.parametrize("pos", [0, 4])
def test_a_block_of_positions_is_so_many_single_steps(block, pos):
    """T positions at once against T applications of one: the output of
    every position and the cache, from an empty cache and from a
    position inside a session."""
    ins = _block_ins(np.random.RandomState(block + pos), block, pos)
    got = registry.get_op_info("mla_cached_attention").kernel(
        None, ins, {"num_heads": H})
    want, cache = _step_by_step(ins)
    assert got["Out"][0].shape == (B, block, H * DV)
    np.testing.assert_allclose(got["Out"][0], want, atol=2e-5)
    np.testing.assert_array_equal(got["CacheOut"][0], cache)
    # slots pos .. pos + T - 1 hold the block's entries, in order
    np.testing.assert_array_equal(
        np.asarray(got["CacheOut"][0])[:, pos:pos + block],
        np.concatenate([np.asarray(ins["CNew"][0]),
                        np.asarray(ins["RNew"][0])], axis=-1))


def test_a_block_with_a_chosen_set_wants_a_set_a_position():
    """A chosen set is one position's: a block of T > 1 positions takes
    `Selected` [batch, T, top_k] and `Live` [batch, T] (here every
    position the same four slots, so each is attention over those), and
    a step's [batch, top_k] with a block is refused and says so; with T
    = 1 it is the step it was."""
    ins = dict(_block_ins(np.random.RandomState(3), 2, 4),
               Selected=[jnp.zeros((B, 4), jnp.int32)],
               Live=[jnp.full((B,), 4, jnp.int32)])
    kernel = registry.get_op_info("mla_cached_attention").kernel
    with pytest.raises(ValueError, match="Selected"):
        kernel(None, ins, {"num_heads": H})
    one = {k: [v[0][:, :1]] if k in ("QNope", "QRope", "CNew", "RNew")
           else v for k, v in ins.items()}
    assert kernel(None, one, {"num_heads": H})["Out"][0].shape \
        == (B, 1, H * DV)
    slots = jnp.asarray([0, 2, 4, 5], jnp.int32)
    a_position = dict(ins, Selected=[jnp.tile(slots, (B, 2, 1))],
                      Live=[jnp.full((B, 2), 4, jnp.int32)])
    got = kernel(None, a_position, {"num_heads": H})
    assert got["Out"][0].shape == (B, 2, H * DV)
    # position 1 of the block alone, over the cache the block leaves
    last = {k: [v[0][:, 1:]] if k in ("QNope", "QRope", "CNew", "RNew")
            else v for k, v in ins.items()}
    want = kernel(None, dict(
        last, Cache=got["CacheOut"], Selected=[jnp.tile(slots, (B, 1))],
        Position=[jnp.full_like(ins["Position"][0], 5)]), {"num_heads": H})
    np.testing.assert_allclose(got["Out"][0][:, 1:], want["Out"][0],
                               atol=2e-5)


def test_a_block_longer_than_the_op_was_sized_for_is_refused():
    ins = _block_ins(np.random.RandomState(3), 4, 0)
    kernel = registry.get_op_info("mla_cached_attention").kernel
    with pytest.raises(ValueError, match="sized for 2"):
        kernel(None, ins, {"num_heads": H, "prefill_block": 2})
    assert kernel(None, ins, {"num_heads": H, "prefill_block": 4})[
        "Out"][0].shape == (B, 4, H * DV)


@pytest.mark.parametrize("dtype,atol", [(jnp.float32, 3e-5),
                                        (jnp.bfloat16, 6e-2)])
@pytest.mark.parametrize("block,pos", [(16, 0), (16, WBK - 5),
                                       (32, 2 * WBK - 32), (48, WBK - 17)])
def test_the_walk_takes_a_block_of_positions(block, pos, dtype, atol,
                                             monkeypatch):
    """The kernel at T > 1 against the op's plain path on the same
    inputs: from an empty cache, a block that straddles two blocks of
    slots (positions 123 .. 138 of blocks of 128), two tiles that end
    with a block of slots, and three tiles of which the middle one
    straddles."""
    from paddle_tpu.kernels import mla_decode

    ins = _block_ins(np.random.RandomState(pos), block, pos, dtype,
                     (WB, WP, WL, WR))
    paths, walked = _mla_paths(ins)
    assert paths == {"{block_k=%d,path=kernel,positions=%d}"
                     % (WBK, block): 1}
    monkeypatch.setattr(mla_decode, "fits", lambda *shape: False)
    paths, plain = _mla_paths(ins)
    assert paths == {"{block_k=0,path=plain,positions=%d}" % block: 1}
    # (an entry of 8 is a bfloat16 step of 0.06 from its neighbour)
    np.testing.assert_allclose(
        np.asarray(walked["Out"][0], np.float32),
        np.asarray(plain["Out"][0], np.float32), atol=atol, rtol=atol / 6)
    np.testing.assert_array_equal(
        np.asarray(walked["CacheOut"][0], np.float32),
        np.asarray(plain["CacheOut"][0], np.float32))


@pytest.mark.parametrize("block", [3, 8, 24])
def test_a_block_of_no_whole_tiles_is_the_plain_paths(block):
    """The kernel walks tiles of 16 positions: another count of them (a
    prompt's remainder block) takes the plain products, which take any."""
    paths, got = _mla_paths(_block_ins(np.random.RandomState(block), block,
                                       5, shape=(WB, WP, WL, WR)))
    assert paths == {"{block_k=0,path=plain,positions=%d}" % block: 1}
    assert got["Out"][0].shape == (WB, block, H * DV)


@pytest.mark.parametrize("blocks", [(128, 4), (128, 2), (128, 1), (384, 4)])
def test_a_block_gives_the_same_at_any_blocks(blocks):
    """Two tiles of 16 positions from slot 120 on (the first straddles
    two blocks of 128 slots, the second lies in one), every head a grid
    step, two or one, over blocks of 128 slots or one of 384: the same
    sums, each position's over its own live slots, and nothing past a
    position's bound (NaN there) reaches them."""
    from paddle_tpu.kernels import mla_decode

    rs = np.random.RandomState(6)
    block, pos = 32, 120
    q_lat = jnp.asarray(rs.randn(H, WB * block, WL), jnp.float32)
    q_rope = jnp.asarray(rs.randn(H, WB * block, WR), jnp.float32)
    cache = jnp.asarray(rs.randn(WB, WP, WL + WR), jnp.float32)
    got = mla_decode.mla_decode_block(
        q_lat, q_rope, cache.at[:, pos + block:].set(jnp.nan),
        jnp.int32(pos), 0.1, blocks).reshape(H, WB, block, WL)
    q = jnp.concatenate([q_lat, q_rope], axis=-1).reshape(
        H, WB, block, WL + WR)
    for t in range(block):
        live = cache[:, :pos + t + 1]
        s = jnp.einsum("hbw,bsw->bhs", q[:, :, t], live) * 0.1
        want = jnp.einsum("bhs,bsw->hbw", jax.nn.softmax(s, -1),
                          live[..., :WL])
        np.testing.assert_allclose(got[:, :, t], want, atol=3e-5)


def test_a_blocks_blocks_are_chosen_from_the_shapes():
    """pangu-decode-ep16's prefill application (256 rows x 16 positions
    of 128 heads) walks 64 heads a grid step, 1024 query rows, over
    blocks of 256 slots; fewer heads go whole; a block is whole tiles of
    16 positions."""
    from paddle_tpu.kernels import mla_decode

    assert mla_decode.choose_group(128, 1024, 64, 512, 2) == (256, 64)
    assert mla_decode.choose_group(128, 768, 64, 512, 2) == (256, 64)
    assert mla_decode.choose_group(96, 1024, 64, 512, 2) == (512, 48)
    assert mla_decode.choose_group(H, WP, WR, WL, 4) == (WBK, H)
    assert mla_decode.fits(16, 1024, 512) and mla_decode.fits(128, 128, 128)
    assert not mla_decode.fits(2, 1024, 512)
    assert not mla_decode.fits(24, 1024, 512)
    assert not mla_decode.fits(16, 1000, 512)
    zeros = jnp.zeros
    with pytest.raises(ValueError, match="do not tile"):
        mla_decode.mla_decode_block(
            zeros((H, 32, WL)), zeros((H, 32, WR)),
            zeros((2, WP, WL + WR)), 0, 1.0, (128, 3))
    with pytest.raises(ValueError, match="no block the kernel takes"):
        mla_decode.mla_decode_block(
            zeros((H, 16, WL)), zeros((H, 16, WR)),
            zeros((2, WP, WL + WR)), 0, 1.0)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("heads,head,rotary", [
    (4, 16, 0), (1, 64, 0), (3, 24, 8), (128, 64, 0), (2, 256, 64)])
def test_rope_turns_a_block_where_it_lies(heads, head, rotary, dtype):
    """`full_width` against the view of half heads on a block of 5
    positions: heads that tile 128 lanes, one head, a rotary part of a
    head that tiles nothing, pangu's 128 heads of 64, two lane tiles a
    head.  The same products and sums: equal to a rounding of the
    float32 sum (a compiler may fuse a product into it)."""
    rs = np.random.RandomState(heads)
    x = jnp.asarray(rs.randn(2, 5, heads * head), dtype)
    pos = jnp.asarray(rs.randint(0, 1000, (2, 5)), jnp.int32)
    attrs = {"num_heads": heads, "theta": 25600000.0}
    if rotary:
        attrs["rotary_dim"] = rotary
    rope = registry.get_op_info("rope").kernel

    def turned(**more):
        return np.asarray(jax.jit(lambda x, pos: rope(
            None, {"X": [x], "Positions": [pos]},
            dict(attrs, **more))["Out"][0])(x, pos), np.float32)

    np.testing.assert_allclose(
        turned(full_width=True), turned(), rtol=0,
        atol=1e-6 if dtype == jnp.float32 else 2 ** -7 * 4)


def test_rope_turns_one_position_as_it_did():
    """With `full_width` a single position lowers to what it lowers to
    without: the decode step of a block-taking builder is the parent's."""
    rope = registry.get_op_info("rope").kernel
    x, pos = jnp.zeros((3, 1, 4 * 16)), jnp.zeros((3, 1), jnp.int32)

    def jaxpr(**more):
        return str(jax.make_jaxpr(lambda x, pos: rope(
            None, {"X": [x], "Positions": [pos]},
            dict({"num_heads": 4}, **more))["Out"][0])(x, pos))

    assert jaxpr(full_width=True) == jaxpr()
    assert "dot_general" in str(jax.make_jaxpr(lambda x, pos: rope(
        None, {"X": [x], "Positions": [pos]},
        {"num_heads": 4, "full_width": True})["Out"][0])(
            jnp.zeros((3, 2, 64)), jnp.zeros((3, 2), jnp.int32)))


def test_only_the_block_taking_step_asks_for_it(built):
    """The builder sets `full_width` on its rope ops, the index queries'
    and keys' too where there is an `indexer` (a chooser's step takes a
    block since PR 62); the grouped-cache chooser's step
    (`models/sparse_kv_moe_program.py`), which takes a block since PR 66,
    sets none: an application's rotated values are a few megabytes."""
    from paddle_tpu.models.sparse_kv_moe_program import \
        build_sparse_kv_moe_cached_step_program
    ropes = [od for od in built["main"].global_block().desc.ops
             if od.type == "rope"]
    assert len(ropes) == 2 * L and all(od.attrs.get("full_width")
                                       for od in ropes)
    indexed = build_latent_moe_cached_step_program(
        B, T, V, **dict(SIZES, indexer=(2, 8, 4)))[0]
    ropes = [od for od in indexed.global_block().desc.ops
             if od.type == "rope"]
    assert len(ropes) == 4 * L and all(od.attrs.get("full_width")
                                       for od in ropes)
    one = build_sparse_kv_moe_cached_step_program(B, T, V)[0]
    assert [od for od in one.global_block().desc.ops if od.type == "rope"]
    assert not [od for od in one.global_block().desc.ops
                if od.type == "rope" and "full_width" in od.attrs]


SMALL_BLOCK = 4


@pytest.fixture(scope="module")
def blocked(built):
    """The step Program built where its byte bound allows SMALL_BLOCK
    positions an application, over `built`'s weights."""
    patch = pytest.MonkeyPatch()
    # 3 rows x 4 heads x (16 + 16 + 8) values x 2 bytes a position
    patch.setattr(latent_moe_program, "_BLOCK_BYTES",
                  2 * SMALL_BLOCK * B * H * (2 * KVR + ROPE) * 2 - 1)
    try:
        main, _, logits, pairs, parts = \
            build_latent_moe_cached_step_program(B, T, V, **SIZES)
    finally:
        patch.undo()
    return {"main": main, "parts": parts, "pairs": pairs, "logits": logits,
            "decoder": _decoder(main, logits, pairs, built["scope"])}


def test_the_step_states_the_block_it_is_prefilled_by(blocked, built):
    """The builder derives the block from its rows and widths under its
    byte bound, the attention ops carry it, and the decoder reads it off
    the Program (a clone keeps it); a step that states none (GPT-2's)
    gets `PREFILL_BLOCK`.  With an `indexer` the block also counts a
    position's index scores and the two tiles, and stops at 512 token
    rows an application: 32 positions at dsv32-turn-16k-ep16's 16 rows,
    64 at hy4-turn-32k-ep16's 8."""
    assert blocked["decoder"]._prefill_block == SMALL_BLOCK
    stated = [od.attrs["prefill_block"]
              for od in blocked["main"].global_block().desc.ops
              if od.type == "mla_cached_attention"]
    assert stated == [SMALL_BLOCK] * L
    assert latent_moe_program.prefill_block(256, 128, 512, 64) == 16
    assert latent_moe_program.prefill_block(8, 128, 512, 64) == 128
    assert latent_moe_program.prefill_block(4096, 128, 512, 64) == 1
    main, startup, logits, pairs = build_transformer_cached_step_program(
        B, T, 31, n_layer=1, n_head=2, d_model=32)
    scope = fluid.Scope()
    fluid.Executor(fluid.CPUPlace()).run(startup, scope=scope)
    gpt2 = fluid.ProgramDecoder(main.clone(for_test=True), "tok",
                                logits.name, pairs, scope=scope)
    assert gpt2._takes_block \
        and gpt2._prefill_block == decode.PREFILL_BLOCK
    before = telemetry.snapshot()
    jax.make_jaxpr(lambda p, s, t: decode.prefill(
        gpt2._step_fn(p), s, t, True, gpt2._prefill_block))(
            gpt2._params,
            {name: jnp.zeros((B, 2, T, 16)) if name != "pos"
             else jnp.zeros((B,), jnp.int32) for name, _ in pairs},
            jnp.zeros((B, 5), jnp.int32))
    assert telemetry.snapshot_delta(before)[
        "prefill_lowerings_total{block=%d,form=block}"
        % decode.PREFILL_BLOCK] == 1
    indexed = build_latent_moe_cached_step_program(
        B, T, V, **dict(SIZES, indexer=(2, 8, 4)))[0]
    assert tuple(indexed.global_block().var("tok").shape) == (B, -1)
    assert [od.attrs["prefill_block"]
            for od in indexed.global_block().desc.ops
            if "prefill_block" in od.attrs] == [decode.PREFILL_BLOCK] * L
    chooser = (64, 128, 2048)
    assert latent_moe_program.prefill_block(
        16, 128, 512, 64, chooser, 16384) == 32
    assert latent_moe_program.prefill_block(
        8, 64, 512, 64, (32, 128, 2048), 32768) == 64
    assert latent_moe_program.prefill_block(
        8, 128, 512, 64, chooser, 2304) == 64
    assert latent_moe_program.prefill_block(
        2048, 128, 512, 64, chooser, 16384) == 1


def _in_blocks(decoder, tokens, sizes, state):
    """([B, len(sizes), V] logits after each block's last position, the
    state): the step applied to consecutive blocks of `sizes`."""
    step = decoder._step_fn(decoder._params)
    out, at = [], 0
    for size in sizes:
        logits, state = step(state, jnp.asarray(tokens[:, at:at + size]))
        out.append(np.asarray(logits, np.float32))
        at += size
    return np.stack(out, axis=1), state


@pytest.mark.parametrize("sizes", [(SMALL_BLOCK,) * 3, (3, 4, 4, 1),
                                   (1, 2, 4, 4, 1)])
def test_blocks_of_positions_are_the_single_steps(blocked, built, sizes):
    """The step fed blocks of positions against the same step fed a
    position at a time (the fixture's drive) and against the float32
    reference: the logits after each block, and the caches."""
    got, state = _in_blocks(blocked["decoder"], built["tokens"], sizes,
                            _empty())
    ends = np.cumsum(sizes) - 1
    scale = np.abs(built["got"]).max()
    assert np.abs(got - built["got"][:, ends]).max() <= LOGITS_RTOL * scale
    want = np.asarray(built["want"]["logits"])[:, ends]
    assert np.abs(got - want).max() <= LOGITS_RTOL * np.abs(want).max()
    assert int(state["pos"][0]) == sum(sizes)
    for i in range(L):
        np.testing.assert_allclose(
            state["latent_cache_%d" % i][:, :sum(sizes)],
            built["state"]["latent_cache_%d" % i][:, :sum(sizes)],
            atol=1e-5)


@pytest.mark.parametrize("prompt_len", [SMALL_BLOCK, 2 * SMALL_BLOCK + 1])
def test_prefill_in_blocks_then_greedy_steps(blocked, built, prompt_len):
    """`ProgramDecoder.greedy` over the block-taking step (a remainder
    block first, then a scan of blocks of SMALL_BLOCK, then the scan of
    steps) gives the tokens of the same decoder prefilled a position at
    a time, and every one is the reference's first (or within rounding
    of it)."""
    decoder, gen = blocked["decoder"], T - prompt_len
    prompt = built["tokens"][:, :prompt_len]
    before = telemetry.snapshot()
    tokens, _ = decoder.greedy(bos=0, eos=V, max_len=gen,
                               init_state=_empty(), prompt=prompt)
    lowered = telemetry.snapshot_delta(before)
    assert lowered["prefill_lowerings_total{block=%d,form=block}"
                   % SMALL_BLOCK] == 1
    assert {k.split("positions=")[1][:-1] for k in lowered
            if k.startswith("mla_decode_lowerings_total")} == {
        str(n) for n in (prompt_len % SMALL_BLOCK, SMALL_BLOCK, 1) if n}

    def stepped(params, state, prompt):
        step = decoder._step_fn(params)
        state, first = decode.prefill(step, state, prompt)
        return first, decode.greedy_decode(step, state, first, V, gen - 1,
                                           B)[0]

    first, rest = jax.jit(stepped)(decoder._params, _empty(),
                                   jnp.asarray(prompt))
    np.testing.assert_array_equal(
        tokens, np.concatenate([np.asarray(first)[:, None],
                                np.asarray(rest)], axis=1))
    full = np.concatenate([prompt, tokens], axis=1)[:, :T]
    z = np.asarray(reference.forward(
        CFG, built["params"], jnp.asarray(full), held=HELD)["logits"])
    z = z[:, prompt_len - 1:prompt_len - 1 + gen]
    picked = np.take_along_axis(z, tokens[..., None], axis=-1)[..., 0]
    assert (z.max(axis=-1) - picked).max() <= 1e-4


@pytest.mark.parametrize("size", [1, 3, SMALL_BLOCK])
def test_parts_are_of_the_blocks_last_position(blocked, built, size):
    """`parts` keep their shapes at T = 1 and at T > 1 (a decoder carries
    them through its scans), and hold the block's last position: what
    the single steps gave at that position."""
    from paddle_tpu.jit import FunctionalProgram

    main, parts, pairs = blocked["main"], blocked["parts"], blocked["pairs"]
    keys = ("hidden", "attn_in", "attn_out", "top_w", "top_idx", "moe_in",
            "moe_out", "counts")
    names = [v.name for key in keys for v in parts[key]]
    feeds = ["tok"] + [f for f, _ in pairs]

    def fetch(tokens, state):
        fp = FunctionalProgram(main.clone(for_test=True), feeds,
                               names + [o for _, o in pairs])
        out, _ = fp(blocked["decoder"]._params,
                    dict(state, tok=jnp.asarray(tokens)))
        return out[:len(names)], dict(zip([f for f, _ in pairs],
                                          out[len(names):]))

    got, _ = fetch(built["tokens"][:, :size], _empty())
    state, counts = _empty(), 0
    for t in range(size):
        want, state = fetch(built["tokens"][:, t:t + 1], state)
        counts = counts + np.asarray(want[-(L - DENSE):])
    shapes = {"hidden": (B, 1, D), "attn_in": (B, 1, D),
              "attn_out": (B, 1, D), "top_w": (B, K), "top_idx": (B, K),
              "moe_in": (B, 1, D), "moe_out": (B, 1, D),
              "counts": (HELD[1],)}
    at = 0
    for key in keys:
        for _ in parts[key]:
            assert got[at].shape == shapes[key], key
            if key == "counts":     # the whole block's
                continue
            if key == "top_idx":
                np.testing.assert_array_equal(got[at], want[at])
            else:
                np.testing.assert_allclose(got[at], want[at], atol=2e-5,
                                           err_msg=key)
            at += 1
    np.testing.assert_array_equal(np.asarray(got[-(L - DENSE):]), counts)


# -- (b) the shares add up ------------------------------------------------------

def _expert_weights(rs, experts=E):
    w_gate, w_up = (jnp.asarray(rs.randn(experts, D, FE) * 0.2, jnp.float32)
                    for _ in range(2))
    return w_gate, w_up, jnp.asarray(rs.randn(experts, FE, D) * 0.2,
                                     jnp.float32)


def _held_part(x, top_w, top_idx, weights, first, count):
    ins = {"X": [x], "TopW": [top_w], "TopIdx": [top_idx],
           "WGate": [weights[0][first:first + count]],
           "WUp": [weights[1][first:first + count]],
           "WDown": [weights[2][first:first + count]]}
    return registry.get_op_info("moe_experts").kernel(
        None, ins, {"first_expert": first, "scored": E})


@pytest.mark.parametrize("ranges", [[(0, 4), (4, 4)], [(0, 2), (2, 4), (6, 2)],
                                    [(i, 1) for i in range(E)]])
def test_the_shares_add_up_to_the_uncut_layer(ranges):
    """The held parts of all N ranges plus the shared expert counted once
    equal the reference's uncut layer."""
    rs = np.random.RandomState(len(ranges))
    n = 24
    u = jnp.asarray(rs.randn(n, D), jnp.float32)
    weights = _expert_weights(rs)
    block = {"router": jnp.asarray(rs.randn(D, E) * 0.3, jnp.float32),
             "w_gate": weights[0], "w_up": weights[1], "w_down": weights[2],
             "shared_in": jnp.asarray(rs.randn(D, 2 * FE) * 0.2, jnp.float32),
             "shared_out": jnp.asarray(rs.randn(FE, D) * 0.2, jnp.float32)}
    want, indices = reference.feed_forward(CFG, block, u)
    routed = registry.get_op_info("moe_router").kernel(
        None, {"X": [u], "W": [block["router"]]},
        {"top_k": K, "scoring": "sigmoid", "norm_topk": True, "scale": 2.5})
    np.testing.assert_array_equal(routed["TopIdx"][0], indices)
    total = reference.gated(u, block["shared_in"], block["shared_out"])
    rows = 0
    for first, count in ranges:
        part = _held_part(u, routed["TopW"][0], routed["TopIdx"][0],
                          weights, first, count)
        rows += int(np.asarray(part["Counts"][0]).sum())
        assert part["Counts"][0].shape == (count,)
        total = total + part["Out"][0]
        # each share is the reference's own part of the routed sum
        cut = dict(block, **{w: block[w][first:first + count]
                             for w in ("w_gate", "w_up", "w_down")})
        np.testing.assert_allclose(
            part["Out"][0],
            reference.feed_forward(CFG, cut, u, first, shared=False)[0],
            atol=2e-5)
    assert rows == n * K        # every assignment computed once, somewhere
    np.testing.assert_allclose(total, want, atol=3e-5)


def test_a_share_none_of_whose_experts_is_chosen_adds_nothing():
    rs = np.random.RandomState(4)
    x = jnp.asarray(rs.randn(8, D), jnp.float32)
    top_idx = jnp.asarray(rs.randint(0, 4, (8, K)), jnp.int32)
    top_w = jnp.asarray(rs.uniform(0.1, 0.5, (8, K)), jnp.float32)
    part = _held_part(x, top_w, top_idx, _expert_weights(rs), 4, 4)
    assert not np.asarray(part["Out"][0]).any()
    assert not np.asarray(part["Counts"][0]).any()


def test_the_whole_range_is_the_op_as_it_was():
    """`first_expert` 0 with every scored expert held lowers as the op
    without the attrs does: the same jaxpr."""
    rs = np.random.RandomState(5)
    x = jnp.asarray(rs.randn(8, D), jnp.float32)
    top_idx = jnp.asarray(rs.randint(0, E, (8, K)), jnp.int32)
    top_w = jnp.asarray(rs.uniform(0.1, 0.5, (8, K)), jnp.float32)
    w = _expert_weights(rs)
    ins = {"X": [x], "TopW": [top_w], "TopIdx": [top_idx], "WGate": [w[0]],
           "WUp": [w[1]], "WDown": [w[2]]}
    kernel = registry.get_op_info("moe_experts").kernel
    plain = jax.make_jaxpr(lambda i: kernel(None, i, {})["Out"][0])(ins)
    whole = jax.make_jaxpr(lambda i: kernel(
        None, i, {"first_expert": 0, "scored": E})["Out"][0])(ins)
    assert str(plain) == str(whole)


def test_a_share_has_a_gradient():
    """The range form's gradient op traces (its values:
    tests/test_moe_share_grad.py)."""
    rs = np.random.RandomState(6)
    w = _expert_weights(rs, 4)
    x = jnp.asarray(rs.randn(8, D), jnp.float32)
    top_idx = jnp.asarray(rs.randint(0, E, (8, K)), jnp.int32)
    top_w = jnp.asarray(rs.uniform(0.1, 0.5, (8, K)), jnp.float32)
    info = registry.get_op_info("moe_experts")
    attrs = {"first_expert": 2, "scored": E}
    ins = {"X": [x], "TopW": [top_w], "TopIdx": [top_idx],
           "WGate": [w[0]], "WUp": [w[1]], "WDown": [w[2]]}
    outs = info.kernel(None, ins, attrs)
    grad_ins = dict(ins, **{"OG@Out": [jnp.ones_like(x)]})
    grad_ins.update({"O@" + s: v for s, v in outs.items()})
    grads = info.grad_kernel(None, grad_ins, attrs)
    assert grads["X@GRAD"][0].shape == x.shape
    assert grads["WGate@GRAD"][0].shape == w[0].shape
    assert np.isfinite(np.asarray(grads["X@GRAD"][0])).all()


def test_the_layer_refuses_a_range_outside_the_scored_experts():
    with fluid.program_guard(fluid.Program(), fluid.Program()):
        x = fluid.layers.data(name="x", shape=[4, D], dtype="float32",
                              append_batch_size=False)
        with pytest.raises(ValueError, match="held experts"):
            fluid.layers.moe(x, E, FE, K, held=(6, 4))


# -- (c) the router --------------------------------------------------------------

def _router(attrs, u, w):
    return registry.get_op_info("moe_router").kernel(
        None, {"X": [u], "W": [w]}, dict({"top_k": K}, **attrs))


def test_sigmoid_router_agrees_with_the_reference():
    rs = np.random.RandomState(8)
    u = jnp.asarray(rs.randn(40, D), jnp.float32)
    w = jnp.asarray(rs.randn(D, E) * 0.3, jnp.float32)
    got = _router({"scoring": "sigmoid", "norm_topk": True, "scale": 2.5},
                  u, w)
    weights, indices, scores = reference.route(CFG, {"router": w}, u)
    np.testing.assert_array_equal(got["TopIdx"][0], indices)
    np.testing.assert_allclose(got["TopW"][0].sum(-1), 2.5, rtol=1e-6)
    np.testing.assert_allclose(
        got["TopW"][0],
        np.take_along_axis(np.asarray(weights), np.asarray(indices), 1),
        rtol=1e-6)
    top = np.take_along_axis(np.asarray(scores), np.asarray(indices), 1)
    np.testing.assert_allclose(
        got["TopW"][0], 2.5 * top / top.sum(-1, keepdims=True), rtol=1e-6)
    # no auxiliary loss at serving: both are the softmax router's
    assert float(got["LbLoss"][0][0]) == 0.0 == float(got["ZLoss"][0][0])
    assert got["Logits"][0].dtype == jnp.float32


def test_softmax_scoring_gives_todays_outputs_exactly():
    rs = np.random.RandomState(9)
    u = jnp.asarray(rs.randn(40, D), jnp.float32)
    w = jnp.asarray(rs.randn(D, E) * 0.3, jnp.float32)
    was, now = _router({}, u, w), _router(
        {"scoring": "softmax", "norm_topk": False, "scale": 1.0}, u, w)
    for slot in ("Logits", "TopW", "TopIdx", "LbLoss", "ZLoss"):
        np.testing.assert_array_equal(was[slot][0], now[slot][0])
    kernel = registry.get_op_info("moe_router").kernel
    jaxprs = [str(jax.make_jaxpr(lambda a, b: kernel(
        None, {"X": [a], "W": [b]}, dict({"top_k": K}, **attrs))["TopW"][0])(
            u, w)) for attrs in ({}, {"scoring": "softmax", "scale": 1.0})]
    assert jaxprs[0] == jaxprs[1]
    probs = jax.nn.softmax(u @ w, axis=-1)
    np.testing.assert_allclose(was["TopW"][0], jax.lax.top_k(probs, K)[0],
                               rtol=1e-5)


def test_an_unknown_scoring_is_refused():
    with pytest.raises(ValueError, match="scoring"):
        _router({"scoring": "tanh"}, jnp.zeros((4, D)), jnp.zeros((D, E)))


# -- (d) a bfloat16 cache against the float32 one ------------------------------

def test_a_bfloat16_latent_cache_stays_near_the_float32_one(built):
    """Float32 weights and products, the cache alone in bfloat16: each
    cached value is off by at most 2^-9 of itself, and logits of size ~3
    were seen to move by at most 0.016 (5e-3 of the largest); 0.02 of the
    largest is four times that and fails a float8 cache (seen: 0.65 of
    the largest)."""
    got, state = _drive(built["decoder"], built["tokens"],
                        _empty(jnp.bfloat16))
    assert state["latent_cache_0"].dtype == jnp.bfloat16
    moved = np.abs(got - built["got"]).max()
    assert 0 < moved <= 0.02 * np.abs(built["got"]).max()
    eighth, _ = _drive(built["decoder"], built["tokens"],
                       _empty(jnp.float8_e4m3fn))
    assert np.abs(eighth - built["got"]).max() > \
        0.02 * np.abs(built["got"]).max()


def test_a_bfloat16_key_value_cache_stays_near_the_float32_one():
    """The GPT-2-shaped cached step (`cached_attention`): the same
    comparison on its key and value caches (PERF.md section 7 asked)."""
    heads, width, layers, vocab = 2, 32, 2, 31
    main, startup, logits, pairs = build_transformer_cached_step_program(
        B, T, vocab, n_layer=layers, n_head=heads, d_model=width)
    scope = fluid.Scope()
    startup.random_seed = 11
    fluid.Executor(fluid.CPUPlace()).run(startup, scope=scope)
    decoder = fluid.ProgramDecoder(
        main.clone(for_test=True), token_name="tok",
        logits_name=logits.name, state_pairs=pairs, scope=scope,
        max_positions=T)
    tokens = np.random.RandomState(2).randint(0, vocab, (B, T))

    def run(dtype):
        state = {name: jnp.zeros((B, heads, T, width // heads), dtype)
                 for name, _ in pairs if name != "pos"}
        state["pos"] = jnp.zeros((1,), jnp.int32)
        return _drive(decoder, tokens.astype("int32"), state)[0]

    whole, half = run(jnp.float32), run(jnp.bfloat16)
    moved = np.abs(half - whole).max()
    assert 0 < moved <= 0.02 * np.abs(whole).max()


# -- (e) the counters -------------------------------------------------------------

def test_the_build_lowers_nothing(built):
    assert not [k for k in built["at_build"]
                if k.startswith(("mla_cached_attention_lowerings_total",
                                 "moe_share_lowerings_total"))]


def test_counters_say_what_was_lowered(built):
    decoder = built["decoder"]
    before = telemetry.snapshot()
    jax.make_jaxpr(decoder._step_fn(decoder._params))(
        _empty(), jnp.asarray(built["tokens"][:, 0]))
    lowered = telemetry.snapshot_delta(before)
    mla = "mla_cached_attention_lowerings_total{cache_dtype=float32," \
        "heads=%d,latent=%d,positions=1,rope=%d,selected=all,tile=1}" \
        % (H, KVR, ROPE)
    share = "moe_share_lowerings_total{held=%d,scored=%d,top_k=%d}" \
        % (HELD[1], E, K)
    # one count an op instance a traced step holds
    assert lowered[mla] == L
    # a latent of 16 is nothing the walk of live slots takes
    assert lowered["mla_decode_lowerings_total{block_k=0,path=plain,"
                   "positions=1}"] == L
    assert lowered[share] == L - DENSE
    assert lowered["moe_lowerings_total{experts=%d,top_k=%d}"
                   % (HELD[1], K)] == L - DENSE


# -- (f) the decoder takes the scope's arrays as they are -----------------------

def test_program_decoder_params_are_the_scopes_arrays(built):
    scope, decoder = built["scope"], built["decoder"]
    names = jax.tree_util.tree_leaves(NAMES)
    assert set(names) <= set(decoder._params)
    for name in names:
        assert decoder._params[name] is scope.get(name)


def test_program_decoder_still_takes_host_arrays():
    """A scope filled from the host (a checkpoint's numpy arrays) is put
    on the device once."""
    main, startup, logits, pairs, _ = build_latent_moe_cached_step_program(
        B, T, V, **dict(SIZES, n_layer=1, n_dense=1))
    scope = fluid.Scope()
    fluid.Executor(fluid.CPUPlace()).run(startup, scope=scope)
    for name in jax.tree_util.tree_leaves(latent_moe_param_names(1, 1)):
        scope.set(name, np.asarray(scope.get(name)))
    decoder = _decoder(main, logits, pairs, scope)
    assert all(isinstance(v, jax.Array) for v in decoder._params.values())


# -- a write-only state pair shows an intermediate of the last step -------------

@pytest.mark.parametrize("gen", [1, 5])
def test_greedy_returns_the_last_steps_value_of_a_state_it_is_asked_for(gen):
    """`return_state`: the held experts' part and the router's choice of
    the last expert layer ride as state pairs whose feed the step does
    not read; after a call they are what the step that chose the last
    token computed, and the tokens are those of a call that carries no
    such pair."""
    main, startup, logits, pairs, parts = \
        build_latent_moe_cached_step_program(B, T, V, **SIZES)
    scope = _start(startup)
    probes = [("probe.idx", parts["top_idx"][-1].name),
              ("probe.out", parts["moe_out"][-1].name)]
    plain = _decoder(main, logits, pairs, scope)
    probed = _decoder(main, logits, pairs + probes, scope)
    prompt = np.random.RandomState(2).randint(0, V, (B, 4)).astype("int32")
    init = dict(_empty())
    want_toks, want_len = plain.greedy(bos=0, eos=V, max_len=gen,
                                       init_state=init, prompt=prompt)
    init.update({"probe.idx": np.zeros((B, K), np.int32),
                 "probe.out": np.zeros((B, 1, D), np.float32)})
    toks, lengths, last = probed.greedy(
        bos=0, eos=V, max_len=gen, init_state=init, prompt=prompt,
        return_state=["probe.idx", "probe.out"])
    np.testing.assert_array_equal(toks, want_toks)
    np.testing.assert_array_equal(lengths, want_len)
    assert sorted(last) == ["probe.idx", "probe.out"]
    # the step that chose the last token read the sequence before it
    fed = np.concatenate([prompt, toks[:, :-1]], axis=1)
    step = probed._step_fn(probed._params)
    state = {k: jnp.asarray(v) for k, v in init.items()}
    for t in range(fed.shape[1]):
        _, state = step(state, jnp.asarray(fed[:, t]))
    np.testing.assert_array_equal(last["probe.idx"],
                                  np.asarray(state["probe.idx"]))
    np.testing.assert_allclose(last["probe.out"],
                               np.asarray(state["probe.out"]), rtol=1e-4,
                               atol=1e-5)
    assert np.abs(last["probe.out"]).max() > 0
    # unasked, a call gives two things as before
    assert len(probed.greedy(bos=0, eos=V, max_len=gen, init_state=init,
                             prompt=prompt)) == 2


def test_greedy_without_a_prompt_returns_state_too():
    main, startup, logits, pairs, parts = \
        build_latent_moe_cached_step_program(B, T, V, **SIZES)
    decoder = _decoder(main, logits, pairs, _start(startup))
    toks, _, last = decoder.greedy(bos=1, eos=V, max_len=3,
                                   init_state=_empty(),
                                   return_state=["pos"])
    assert toks.shape == (B, 3) and last["pos"].tolist() == [3] * B


# -- the Program -------------------------------------------------------------------

def test_parameter_names_are_the_references_tree(built):
    block = built["main"].global_block()
    made = {p.name: tuple(p.shape) for p in block.all_parameters()}
    assert set(made) == set(jax.tree_util.tree_leaves(NAMES))
    assert "ffn_in" in NAMES["blocks"][0] and "router" in NAMES["blocks"][1]
    first, count = HELD
    b1 = NAMES["blocks"][1]
    assert made[b1["router"]] == (D, E)
    assert made[b1["w_gate"]] == (count, D, FE)
    assert made[b1["w_down"]] == (count, FE, D)
    assert made[b1["w_uk"]] == (KVR, H * NOPE)
    assert made[NAMES["blocks"][0]["ffn_in"]] == (D, 2 * FF)
    ops = [od.type for od in block.desc.ops]
    assert ops.count("mla_cached_attention") == L
    assert ops.count("moe_experts") == L - DENSE
    assert "cached_attention" not in ops and "flash_attention" not in ops
    # four norms a layer, two inside the attention, one at the end
    assert ops.count("rms_norm") == 6 * L + 1


def test_the_layer_function_names_its_parameters():
    with fluid.program_guard(fluid.Program(), fluid.Program()):
        def data(name, shape, dtype="float32"):
            return fluid.layers.data(name=name, shape=shape, dtype=dtype,
                                     append_batch_size=False)

        out, kept = fluid.layers.mla_cached_attention(
            data("qn", [B, 1, H * NOPE]), data("qr", [B, 1, H * ROPE]),
            data("c", [B, 1, KVR]), data("r", [B, 1, ROPE]),
            data("cache", [B, T, KVR + ROPE]), data("pos", [B], "int64"),
            H, DV, uk_attr=ParamAttr(name="uk"),
            uv_attr=ParamAttr(name="uv"))
        assert tuple(out.shape) == (B, 1, H * DV)
        assert tuple(kept.shape) == (B, T, KVR + ROPE)
