"""DeepSeek-V3.2's share on the generation path: the chooser of cache
slots (`mla_index_select`), latent attention over a chosen set
(`mla_cached_attention` with `Selected`), YaRN's rotary frequencies
(`rope` with `inv_freq`), the group-limited router with a selection bias
(`moe_router` with `Bias`, `n_group`), and the cached step Program that
`models/latent_moe_program.py` builds from them, against the plain
float32 reference (models/reference/deepseek_v32.py): the step from
position 0 and from a handed-in session against the reference's full
forward; the ops alone; the shares adding up under grouped routing; what
the ops lowered to before they grew their options
(tests/parent_lowerings.py); the counters; `ProgramDecoder`'s extent
check for a call that starts past position 0.

Tiny sizes on the CPU, where selection bites: 2 layers (1 dense), hidden
64, 4 heads of 16 + 8 (values 16), query rank 32, latent 16, 8 index
heads of 16 (the first 8 rotated) choosing 8 of up to 48 slots, 8
experts scored in 4 groups of which 2 are kept, 2 a token, 4 held, a
non-zero selection bias, YaRN x 40 over 16 original positions (so that
its ramp lies inside the 4 pairs), vocabulary 97, seeded weights.
"""

import json
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu.fluid as fluid
from paddle_tpu.models.latent_moe_program import (
    build_latent_moe_cached_step_program, latent_moe_param_names)
from paddle_tpu.models.reference import deepseek_v32 as reference
from paddle_tpu.obs import telemetry
from paddle_tpu.ops import registry
from paddle_tpu.ops.attention import yarn_inv_freq, yarn_mscale
from paddle_tpu.ops.moe import _kept_groups

import parent_lowerings

B, T, V, L, DENSE = 2, 48, 97, 2, 1
H, D, QR, KVR, NOPE, ROPE, DV, FF, FE = 4, 64, 32, 16, 16, 8, 16, 128, 32
E, K, HELD, GROUPS, KEPT = 8, 2, (2, 4), 4, 2
IH, ID, TOPK = 8, 16, 8
SESSION = 24
YARN = {"factor": 40, "original_positions": 16, "beta_fast": 32,
        "beta_slow": 1, "mscale": 1}
SIZES = dict(n_layer=L, n_dense=DENSE, n_head=H, d_model=D, q_rank=QR,
             kv_rank=KVR, d_nope=NOPE, d_rope=ROPE, d_v=DV, d_ff=FF,
             d_expert=FE, n_experts=E, held=HELD, top_k=K, eps=1e-6,
             sandwich_norm=False, indexer=(IH, ID, TOPK), n_group=GROUPS,
             topk_group=KEPT, router_bias=True, yarn=YARN)
CFG = {"num_attention_heads": H, "rms_norm_eps": 1e-6, "rope_theta": 1e4,
       "kv_lora_rank": KVR, "qk_nope_head_dim": NOPE,
       "qk_rope_head_dim": ROPE, "num_experts_per_tok": K,
       "norm_topk_prob": True, "routed_scaling_factor": 2.5,
       "n_group": GROUPS, "topk_group": KEPT, "index_n_heads": IH,
       "index_head_dim": ID, "index_topk": TOPK,
       "rope_scaling": {"factor": 40, "beta_fast": 32, "beta_slow": 1,
                        "mscale": 1, "mscale_all_dim": 1,
                        "original_max_position_embeddings": 16}}
NAMES = latent_moe_param_names(L, DENSE, sandwich_norm=False, indexer=True,
                               router_bias=True)

# float32 on the CPU.  The step absorbs the up-projections, reads two
# caches and attends a gathered set; the reference makes every head's
# keys and values, the whole [T, T] index scores and a masked softmax:
# other sums in another order.  Logits of size ~3 were seen to differ by
# 2e-6; 2e-5 of the largest logit is a dozen times that, and every
# wrong choice this file knows (a `top_k` off by one, a dropped bias, an
# ungrouped router: `test_a_wrong_choice_shows`) moves them by 1e-2 of it
# or more.  A chosen set that differs by one slot at one position would
# show too, so the seeds here have no near-tie (the margins are printed
# by `test_the_choices_are_no_near_ties`).
LOGITS_RTOL = 2e-5


def _start(startup, names=NAMES, seed=3):
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    startup.random_seed = seed
    exe.run(startup, scope=scope)
    rs = np.random.RandomState(seed)
    for name in jax.tree_util.tree_leaves(names):
        value = np.asarray(scope.get(name))
        if value.ndim == 1:
            # norm scales off their 1, biases off their 0 (the selection
            # bias wide enough to move a choice)
            wide = 0.3 if name.endswith("router_bias") else 0.1
            scope.set(name, jnp.asarray(
                value + wide * rs.randn(*value.shape).astype("float32")))
    return scope


def _decoder(main, logits, pairs, scope, extent=T):
    return fluid.ProgramDecoder(
        main.clone(for_test=True), token_name="tok",
        logits_name=logits.name, state_pairs=pairs, scope=scope,
        max_positions=extent)


def _empty(dtype=jnp.float32):
    state = {}
    for i in range(L):
        state["latent_cache_%d" % i] = jnp.zeros((B, T, KVR + ROPE), dtype)
        state["index_cache_%d" % i] = jnp.zeros((B, T, ID), dtype)
    state["pos"] = jnp.zeros((B,), jnp.int32)
    return state


def _drive(decoder, tokens, state):
    """([B, n, V] logits, state): the step applied token by token."""
    step = decoder._step_fn(decoder._params)
    out = []
    for t in range(tokens.shape[1]):
        logits, state = step(state, jnp.asarray(tokens[:, t]))
        out.append(logits)
    return np.stack([np.asarray(z, np.float32) for z in out], axis=1), state


def _build(**changed):
    return build_latent_moe_cached_step_program(
        B, T, V, **dict(SIZES, **changed))


@pytest.fixture(scope="module")
def built():
    before = telemetry.snapshot()
    main, startup, logits, pairs, parts = _build()
    at_build = telemetry.snapshot_delta(before)
    scope = _start(startup)
    decoder = _decoder(main, logits, pairs, scope)
    tokens = np.random.RandomState(1).randint(0, V, (B, T)).astype("int32")
    got, state = _drive(decoder, tokens, _empty())
    params = jax.tree_util.tree_map(scope.get, NAMES)
    want = reference.forward(CFG, params, jnp.asarray(tokens), held=HELD)
    return {"main": main, "logits": logits, "pairs": pairs, "parts": parts,
            "scope": scope, "decoder": decoder, "tokens": tokens,
            "got": got, "state": state, "params": params, "want": want,
            "at_build": at_build}


# -- (a) the step from position 0 against the full forward ---------------------

@pytest.mark.parametrize("position", range(T))
def test_step_logits_agree_with_the_reference_at_every_position(
        built, position):
    """Positions 0..7 attend every live slot (fewer live than `top_k`),
    8..47 the 8 chosen of 9..48."""
    want = np.asarray(built["want"]["logits"])[:, position]
    got = built["got"][:, position]
    assert np.abs(got - want).max() <= LOGITS_RTOL * np.abs(want).max()


def test_the_choices_are_no_near_ties(built):
    """What licenses comparing logits under each side's own choice: at
    every query the reference's score of the last slot taken lies a
    float32 rounding and more above the first left out."""
    for scores in built["want"]["index_scores"]:
        s = np.asarray(scores)
        for t in range(TOPK, T):
            live = np.sort(s[:, t, :t + 1], axis=-1)[:, ::-1]
            margin = (live[:, TOPK - 1] - live[:, TOPK]) \
                / np.abs(live[:, 0])
            assert margin.min() > 1e-4, (t, margin)


def test_both_caches_hold_what_the_reference_computes(built):
    """What licenses a session made by the reference: the latents and
    the index keys the step wrote are the reference's `c | r` and `k^I`
    of every position."""
    for i in range(L):
        for cache, want in (("latent_cache_%d", "latents"),
                            ("index_cache_%d", "index_keys")):
            got = np.asarray(built["state"][cache % i])
            ref = np.asarray(built["want"][want][i])
            assert got.shape == ref.shape
            np.testing.assert_allclose(got, ref, atol=2e-5 * np.abs(
                ref).max())
    assert int(built["state"]["pos"][0]) == T


def test_greedy_through_the_decoder_is_the_references_greedy(built):
    """Prefill then decode through `ProgramDecoder.greedy`: every served
    token is the reference's first given the tokens before it."""
    prompt = built["tokens"][:, :20]
    tokens, lengths = built["decoder"].greedy(
        bos=0, eos=V, max_len=T - 19, init_state=_empty(), prompt=prompt)
    assert tokens.shape == (B, T - 19) and (lengths == T - 19).all()
    full = np.concatenate([prompt, tokens], axis=1)[:, :T]
    z = np.asarray(reference.forward(
        CFG, built["params"], jnp.asarray(full), held=HELD)["logits"])
    served = tokens[:, :T - 19]
    picked = np.take_along_axis(z[:, 19:19 + served.shape[1]],
                                served[..., None], axis=-1)[..., 0]
    gap = z[:, 19:19 + served.shape[1]].max(axis=-1) - picked
    assert gap.max() <= 1e-4


# -- (a') a block of positions through the step --------------------------------

@pytest.fixture(scope="module")
def probed(built):
    """A decoder over the same step and weights that also carries each
    layer's `selected` and `live` out as state the step only writes."""
    probes = [("probe_%d.%s" % (i, what), built["parts"][what][i].name)
              for i in range(L) for what in ("selected", "live")]
    decoder = _decoder(built["main"], built["logits"],
                       built["pairs"] + probes, built["scope"])

    def state(held):
        held = dict(held)
        for i in range(L):
            held["probe_%d.selected" % i] = jnp.zeros((B, TOPK), jnp.int32)
            held["probe_%d.live" % i] = jnp.zeros((B,), jnp.int32)
        return held

    return decoder, state


@pytest.mark.parametrize("start,block,tile", [
    (0, 5, 0), (3, 9, 2), (TOPK - 2, 7, 4), (12, 16, 4)],
    ids=["under top_k", "tiles of 2 and one over", "across top_k",
         "past top_k"])
def test_a_block_through_the_step_is_so_many_single_steps(
        built, probed, start, block, tile, tile_bytes):
    """T tokens of every row in one application against T applications
    of one: the last position's logits, both caches a layer, the
    position, and each layer's set and `Live` of the block's last
    position, in the shapes a step gives them."""
    if tile:
        # a position's index scores are B * IH * T * 4 bytes, its
        # gathered rows and their scores B * TOPK * (24 + H) * 4: 3072
        # and 1792, so both ops work through tiles of `tile` positions
        tile_bytes(tile * B * IH * T * 4)
    decoder, with_probes = probed
    tokens = built["tokens"]
    step = decoder._step_fn(decoder._params)
    state = with_probes(_empty())
    if start:
        _, state = _drive(decoder, tokens[:, :start], state)
    want, after = _drive(decoder, tokens[:, start:start + block], state)
    logits, got = step(state, jnp.asarray(tokens[:, start:start + block]))
    assert logits.shape == (B, V)
    assert np.abs(np.asarray(logits) - want[:, -1]).max() \
        <= LOGITS_RTOL * np.abs(want[:, -1]).max()
    assert int(got["pos"][0]) == start + block == int(after["pos"][0])
    for i in range(L):
        for cache in ("latent_cache_%d" % i, "index_cache_%d" % i):
            np.testing.assert_allclose(
                got[cache], after[cache],
                atol=LOGITS_RTOL * np.abs(np.asarray(after[cache])).max())
            assert not np.asarray(got[cache])[:, start + block:].any()
        np.testing.assert_array_equal(got["probe_%d.selected" % i],
                                      after["probe_%d.selected" % i])
        assert got["probe_%d.selected" % i].shape == (B, TOPK)
        assert np.asarray(got["probe_%d.live" % i]).tolist() \
            == [min(TOPK, start + block)] * B


@pytest.mark.parametrize("prompt_len,block", [(11, 4), (20, 8), (7, 128)],
                         ids=["3 + 2 x 4", "4 + 2 x 8", "one short block"])
def test_a_prompt_is_prefilled_in_blocks(built, prompt_len, block):
    """`ProgramDecoder` reads the declaration: the prompt goes through
    the chooser's step `block` positions an application (the remainder
    first), and every served token is the reference's first given the
    tokens before it."""
    assert built["decoder"]._takes_block
    assert built["decoder"]._prefill_block == 128    # 2 rows: the most
    decoder = _decoder(built["main"], built["logits"], built["pairs"],
                       built["scope"])
    decoder._prefill_block = block
    prompt = built["tokens"][:, :prompt_len]
    gen = T - prompt_len + 1
    before = telemetry.snapshot()
    tokens, lengths = decoder.greedy(bos=0, eos=V, max_len=gen,
                                     init_state=_empty(), prompt=prompt)
    traced = telemetry.snapshot_delta(before)
    assert traced["prefill_lowerings_total{block=%d,form=block}"
                  % block] == 1
    # the chooser was traced for the remainder, a block and a step
    sizes = {prompt_len % block, min(block, prompt_len), 1} - {0}
    assert {int(k.split("positions=")[1].split(",")[0]) for k in traced
            if k.startswith("mla_index_select_lowerings_total")} == sizes
    assert tokens.shape == (B, gen) and (lengths == gen).all()
    full = np.concatenate([prompt, tokens], axis=1)[:, :T]
    z = np.asarray(reference.forward(
        CFG, built["params"], jnp.asarray(full), held=HELD)["logits"])
    at = prompt_len - 1
    served = tokens[:, :T - at]
    picked = np.take_along_axis(z[:, at:], served[..., None], axis=-1)[..., 0]
    assert (z[:, at:].max(axis=-1) - picked).max() <= 1e-4


# -- (b) continued from a handed-in session ------------------------------------

@pytest.fixture(scope="module")
def continued(built):
    """The caches made by the reference over the first SESSION tokens
    (its `c | r` and `k^I`, padded to the extent), `pos` = SESSION, and
    the step driven over the rest."""
    session = reference.forward(
        CFG, built["params"], jnp.asarray(built["tokens"][:, :SESSION]),
        held=HELD)
    state = _empty()
    for i in range(L):
        for cache, made in (("latent_cache_%d", "latents"),
                            ("index_cache_%d", "index_keys")):
            state[cache % i] = state[cache % i].at[:, :SESSION].set(
                session[made][i])
    state["pos"] = jnp.full((B,), SESSION, jnp.int32)
    return _drive(built["decoder"], built["tokens"][:, SESSION:], state)


@pytest.mark.parametrize("position", range(SESSION, T))
def test_a_handed_in_session_continues_as_the_reference(
        built, continued, position):
    """Session + turn + answer: the logits at the positions after the
    session are the reference's over the whole sequence."""
    want = np.asarray(built["want"]["logits"])[:, position]
    got = continued[0][:, position - SESSION]
    assert np.abs(got - want).max() <= LOGITS_RTOL * np.abs(want).max()


def test_greedy_from_a_session_starts_past_position_zero(built, continued):
    state = {k: np.asarray(v) for k, v in continued[1].items()}
    assert int(state["pos"][0]) == T
    init = _empty()
    for name in init:
        if name != "pos":
            init[name] = jnp.asarray(state[name]).at[:, SESSION:].set(0)
    init["pos"] = jnp.full((B,), SESSION, jnp.int32)
    turn = built["tokens"][:, SESSION:SESSION + 8]
    tokens, _ = built["decoder"].greedy(
        bos=0, eos=V, max_len=T - SESSION - 7, init_state=init, prompt=turn)
    full = np.concatenate([built["tokens"][:, :SESSION + 8], tokens],
                          axis=1)[:, :T]
    z = np.asarray(reference.forward(
        CFG, built["params"], jnp.asarray(full), held=HELD)["logits"])
    at = SESSION + 7
    picked = np.take_along_axis(z[:, at:at + tokens.shape[1]],
                                tokens[..., None], axis=-1)[..., 0]
    assert (z[:, at:at + tokens.shape[1]].max(-1) - picked).max() <= 1e-4


def test_the_extent_check_cannot_see_a_session(built):
    """`_check_extent` sees the prompt and `max_len`, not a `pos` inside
    `init_state`: its message says who answers for the session."""
    with pytest.raises(ValueError, match="init_state.*pos.*caller"):
        built["decoder"].greedy(bos=0, eos=V, max_len=T, init_state=_empty(),
                                prompt=built["tokens"][:, :5])


# -- (c) a wrong choice shows ----------------------------------------------------

@pytest.mark.parametrize("wrong", [
    {"indexer": (IH, ID, TOPK + 1)}, {"indexer": (IH, ID, TOPK - 1)},
    {"router_bias": False}, {"n_group": 0, "topk_group": 0},
    {"yarn": None}], ids=["top_k+1", "top_k-1", "no bias", "no groups",
                          "no yarn"])
def test_a_wrong_choice_shows(built, wrong):
    """The same weights under a program that chooses otherwise: the
    logits leave the tolerance by two orders of magnitude and more."""
    main, _, logits, pairs, _ = _build(**wrong)
    got, _ = _drive(_decoder(main, logits, pairs, built["scope"]),
                    built["tokens"], _empty())
    want = np.asarray(built["want"]["logits"])
    off = np.abs(got - want).max() / np.abs(want).max()
    assert off > 100 * LOGITS_RTOL, off


# -- the chooser alone -----------------------------------------------------------

def _index_ins(rs, pos, dtype=jnp.float32):
    q = jnp.asarray(rs.randn(B, 1, IH * ID), dtype)
    w = jnp.asarray(rs.uniform(0.2, 1.0, (B, 1, IH)), dtype)
    k_new = jnp.asarray(rs.randn(B, 1, ID), dtype)
    cache = jnp.asarray(rs.randn(B, T, ID), dtype).at[:, pos:].set(0)
    return {"Q": [q], "W": [w], "KNew": [k_new], "Cache": [cache],
            "Position": [jnp.full((B,), pos, jnp.int32)]}


@pytest.mark.parametrize("pos", [0, 3, TOPK - 1, TOPK, 20, T - 1])
def test_index_select_is_the_references_scores_and_set(pos):
    """Against the reference's `index_scores` and `choose` of the same
    query and keys (continuous seeded scores: no ties); with `pos + 1 <
    top_k` the first `Live` entries are the live slots and what follows
    names none of them twice."""
    ins = _index_ins(np.random.RandomState(pos), pos)
    outs = registry.get_op_info("mla_index_select").kernel(
        None, ins, {"num_heads": IH, "top_k": TOPK, "scale": 0.5})
    kept = np.asarray(outs["CacheOut"][0])
    np.testing.assert_array_equal(kept[:, pos],
                                  np.asarray(ins["KNew"][0])[:, 0])
    np.testing.assert_array_equal(kept[:, :pos],
                                  np.asarray(ins["Cache"][0])[:, :pos])
    scores = reference.index_scores(
        ins["Q"][0].reshape(B, 1, IH, ID), jnp.asarray(kept),
        ins["W"][0].reshape(B, 1, IH))
    mask = np.asarray(reference.choose(scores, TOPK, jnp.asarray([pos])))
    live = min(TOPK, pos + 1)
    assert np.asarray(outs["Live"][0]).tolist() == [live] * B
    selected = np.asarray(outs["Selected"][0])
    assert selected.shape == (B, TOPK) and selected.dtype == np.int32
    for row in range(B):
        assert sorted(selected[row, :live]) == \
            np.flatnonzero(mask[row, 0]).tolist()
        assert selected[row, :live].max() <= pos
        # slot order: the live entries first, every entry once
        assert (np.diff(selected[row]) > 0).all()


def test_index_scores_add_up_in_float32():
    """A bfloat16 cache gives bfloat16 operands and float32 sums: the
    set is the one float32 arithmetic picks on the rounded operands, and
    not the one sums rounded to bfloat16 would pick."""
    rs = np.random.RandomState(11)
    ins = _index_ins(rs, T - 1, jnp.bfloat16)
    kernel = registry.get_op_info("mla_index_select").kernel
    attrs = {"num_heads": IH, "top_k": TOPK}
    outs = kernel(None, ins, attrs)
    f32 = {k: [v[0].astype(jnp.float32) if k != "Position" else v[0]]
           for k, v in ins.items()}
    want = kernel(None, f32, attrs)
    np.testing.assert_array_equal(np.sort(outs["Selected"][0], -1),
                                  np.sort(want["Selected"][0], -1))
    jaxpr = jax.make_jaxpr(lambda i: kernel(None, i, attrs)["Selected"][0])(
        ins)
    dots = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "dot_general"]
    assert dots and all(
        e.params["preferred_element_type"] == jnp.float32 for e in dots)
    over_cache = [e for e in dots
                  if (B, T, ID) in [v.aval.shape for v in e.invars]]
    assert len(over_cache) == 1 and all(
        v.aval.dtype == jnp.bfloat16 for v in over_cache[0].invars)
    # the selection reads the float32 scores as they are: no `top_k`,
    # the kernel's one operand
    names = [e.primitive.name for e in jaxpr.jaxpr.eqns]
    assert "top_k" not in names and "sort" not in names
    picks = [e for e in jaxpr.jaxpr.eqns
             if e.primitive.name in ("pjit", "jit")
             and e.params["name"] == "_select"]
    assert [[(v.aval.shape, v.aval.dtype) for v in e.invars]
            for e in picks] == [[((8, 128), jnp.float32)]]


def test_index_select_refuses_what_it_cannot_do():
    ins = _index_ins(np.random.RandomState(2), 4)
    kernel = registry.get_op_info("mla_index_select").kernel
    with pytest.raises(ValueError, match="top_k"):
        kernel(None, ins, {"num_heads": IH, "top_k": T + 1})
    with pytest.raises(ValueError, match="cache holds"):
        kernel(None, dict(ins, KNew=[jnp.zeros((B, 1, ID + 1))]),
               {"num_heads": IH, "top_k": TOPK})
    assert registry.get_op_info("mla_index_select").stop_gradient_op


def _index_block_ins(rs, block, pos, dtype=jnp.float32):
    q = jnp.asarray(rs.randn(B, block, IH * ID), dtype)
    w = jnp.asarray(rs.uniform(-0.5, 1.0, (B, block, IH)), dtype)
    k_new = jnp.asarray(rs.randn(B, block, ID), dtype)
    cache = jnp.asarray(rs.randn(B, T, ID), dtype).at[:, pos:].set(0)
    return {"Q": [q], "W": [w], "KNew": [k_new], "Cache": [cache],
            "Position": [jnp.full((B,), pos, jnp.int32)]}


def _one_position(ins, t, at, **state):
    """Position t of a block's inputs as a step's, at slot `at`."""
    return dict(
        {k: [v[0][:, t:t + 1]] if v[0].ndim == 3 and k != "Cache"
         and not k.startswith("W") or k == "W" else v
         for k, v in ins.items()},
        Position=[jnp.full((B,), at, jnp.int32)],
        **{k: [v] for k, v in state.items()})


@pytest.fixture
def tile_bytes(monkeypatch):
    """`set(n)`: a tile of a chooser's block may hold n bytes (the tiny
    shapes here are one tile at the op's own 256 MB)."""
    from paddle_tpu.ops import attention
    return lambda n: monkeypatch.setattr(attention, "TILE_BYTES", n)


# a tile of index scores is B * IH * T * 4 = 3072 bytes a position here
@pytest.mark.parametrize("block,pos,tile", [
    (2, 0, 1), (5, 0, 8), (7, 3, 2), (16, TOPK - 3, 4), (13, 20, 4),
    (T - 1, 1, 16)],
    ids=["two from empty", "one tile", "tiles of 2 and one over",
         "across top_k", "tiles of 4 and one over", "to the extent's end"])
def test_a_block_through_the_chooser_is_so_many_single_steps(
        block, pos, tile, tile_bytes):
    """T positions at once against T applications of one: the same set
    a position bit for bit, `Live` a position (fewer live than `top_k`
    where `pos + t + 1 < top_k`), the same cache; with the block one
    tile, several tiles, and tiles that do not divide it."""
    tile_bytes(tile * B * IH * T * 4)
    ins = _index_block_ins(np.random.RandomState(block + pos), block, pos)
    kernel = registry.get_op_info("mla_index_select").kernel
    attrs = {"num_heads": IH, "top_k": TOPK, "scale": 0.5}
    before = telemetry.snapshot()
    got = kernel(None, ins, attrs)
    assert telemetry.snapshot_delta(before)[
        "mla_index_select_lowerings_total{cache_dtype=float32,dim=%d,"
        "heads=%d,positions=%d,select=count,tile=%d,top_k=%d}"
        % (ID, IH, block, min(tile, 1 << (block.bit_length() - 1)),
           TOPK)] == 1
    assert got["Selected"][0].shape == (B, block, TOPK)
    assert got["Selected"][0].dtype == jnp.int32
    assert got["Live"][0].shape == (B, block)
    cache = ins["Cache"][0]
    for t in range(block):
        one = kernel(None, _one_position(ins, t, pos + t, Cache=cache),
                     attrs)
        cache = one["CacheOut"][0]
        np.testing.assert_array_equal(got["Selected"][0][:, t],
                                      one["Selected"][0])
        np.testing.assert_array_equal(got["Live"][0][:, t], one["Live"][0])
        assert int(one["Live"][0][0]) == min(TOPK, pos + t + 1)
    np.testing.assert_array_equal(got["CacheOut"][0], cache)


def test_a_block_of_scores_is_never_whole(tile_bytes):
    """The [batch, T, heads, positions] scores are made a tile at a
    time: the largest float32 array a traced block holds is a tile's,
    inside one loop over the whole tiles."""
    tile_bytes(4 * B * IH * T * 4)
    ins = _index_block_ins(np.random.RandomState(0), 16, 4)
    kernel = registry.get_op_info("mla_index_select").kernel
    jaxpr = jax.make_jaxpr(lambda i: kernel(
        None, i, {"num_heads": IH, "top_k": TOPK})["Selected"][0])(ins)
    loops = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "scan"]
    assert len(loops) == 1 and loops[0].params["length"] == 4

    def shapes(eqns):
        for e in eqns:
            for v in e.outvars:
                yield tuple(v.aval.shape)
            for sub in jax.core.jaxprs_in_params(e.params):
                if e.primitive.name != "pjit":
                    yield from shapes(sub.eqns)

    found = set(shapes(jaxpr.jaxpr.eqns))
    assert (B, 4, IH, T) in found and (B, 16, IH, T) not in found
    assert (B, 16, T) in found


# -- attention over a chosen set -------------------------------------------------

def _mla_ins(rs, pos):
    def draw(*shape):
        return jnp.asarray(rs.randn(*shape), jnp.float32)

    cache = draw(B, T, KVR + ROPE).at[:, pos:].set(0)
    return {"QNope": [draw(B, 1, H * NOPE)], "QRope": [draw(B, 1, H * ROPE)],
            "CNew": [draw(B, 1, KVR)], "RNew": [draw(B, 1, ROPE)],
            "Cache": [cache], "WUk": [0.3 * draw(KVR, H * NOPE)],
            "WUv": [0.3 * draw(KVR, H * DV)],
            "Position": [jnp.full((B,), pos, jnp.int32)]}


@pytest.mark.parametrize("pos", [0, 5, T - 1])
def test_selecting_every_live_slot_is_the_op_without_a_selection(pos):
    """`Selected` = the live slots in any order, padded with dead ones
    past `Live`: the op without `Selected`."""
    rs = np.random.RandomState(pos)
    ins = _mla_ins(rs, pos)
    kernel = registry.get_op_info("mla_cached_attention").kernel
    want = kernel(None, ins, {"num_heads": H})
    order = np.stack([np.concatenate([
        rs.permutation(pos + 1), rs.permutation(np.arange(pos + 1, T))])
        for _ in range(B)]).astype(np.int32)
    chosen = dict(ins, Selected=[jnp.asarray(order)],
                  Live=[jnp.full((B,), pos + 1, jnp.int32)])
    got = kernel(None, chosen, {"num_heads": H})
    np.testing.assert_allclose(got["Out"][0], want["Out"][0], atol=1e-5)
    np.testing.assert_array_equal(got["CacheOut"][0], want["CacheOut"][0])


def test_a_dead_entry_of_the_selection_is_not_attended():
    ins = _mla_ins(np.random.RandomState(7), 9)
    kernel = registry.get_op_info("mla_cached_attention").kernel
    selected = jnp.asarray([[9, 2, 4, 30, 31], [0, 9, 7, 40, 41]], jnp.int32)
    chosen = dict(ins, Selected=[selected],
                  Live=[jnp.full((B,), 3, jnp.int32)])
    want = kernel(None, chosen, {"num_heads": H})["Out"][0]
    dirty = dict(chosen, Cache=[ins["Cache"][0].at[:, 30:].set(9.0)])
    np.testing.assert_array_equal(
        kernel(None, dirty, {"num_heads": H})["Out"][0], want)
    # and the three it does attend are not all of the live ones
    assert np.abs(np.asarray(want) - np.asarray(
        kernel(None, ins, {"num_heads": H})["Out"][0])).max() > 1e-3


def _chosen_block_ins(rs, block, pos):
    """A block's inputs with a set a position: `TOPK` slots of the live
    ones, or all of those and dead ones after them where fewer are
    live."""
    def draw(*shape):
        return jnp.asarray(rs.randn(*shape), jnp.float32)

    selected = np.zeros((B, block, TOPK), np.int32)
    live = np.zeros((B, block), np.int32)
    for row in range(B):
        for t in range(block):
            n = min(TOPK, pos + t + 1)
            selected[row, t] = np.concatenate([
                np.sort(rs.permutation(pos + t + 1)[:n]),
                np.arange(pos + t + 1, pos + t + 1 + TOPK - n)])
            live[row, t] = n
    cache = draw(B, T, KVR + ROPE).at[:, pos:].set(0)
    return {"QNope": [draw(B, block, H * NOPE)],
            "QRope": [draw(B, block, H * ROPE)],
            "CNew": [draw(B, block, KVR)], "RNew": [draw(B, block, ROPE)],
            "Cache": [cache], "WUk": [0.3 * draw(KVR, H * NOPE)],
            "WUv": [0.3 * draw(KVR, H * DV)],
            "Position": [jnp.full((B,), pos, jnp.int32)],
            "Selected": [jnp.asarray(selected)], "Live": [jnp.asarray(live)]}


# a tile of gathered rows and scores is B * TOPK * (24 * 4 + H * 4) bytes
# a position here
@pytest.mark.parametrize("sink", [False, True], ids=["", "sink"])
@pytest.mark.parametrize("block,pos,tile", [
    (2, 0, 1), (6, 2, 8), (7, 5, 2), (13, 20, 4)],
    ids=["two from empty", "one tile", "tiles of 2 and one over",
         "tiles of 4 and one over"])
def test_a_block_over_chosen_sets_is_so_many_single_steps(
        block, pos, tile, sink, tile_bytes):
    """T positions that each attend a set of their own against T steps:
    every position's output, the cache; a set may name the block's own
    slots (they are written first); with a sink and without."""
    tile_bytes(tile * B * TOPK * ((KVR + ROPE) * 4 + H * 4))
    rs = np.random.RandomState(block + pos)
    ins = _chosen_block_ins(rs, block, pos)
    if sink:
        ins["Sink"] = [jnp.asarray(rs.randn(H), jnp.float32)]
    kernel = registry.get_op_info("mla_cached_attention").kernel
    before = telemetry.snapshot()
    got = kernel(None, ins, {"num_heads": H})
    assert telemetry.snapshot_delta(before)[
        "mla_cached_attention_lowerings_total{cache_dtype=float32,"
        "heads=%d,latent=%d,positions=%d,rope=%d,selected=%d,tile=%d}"
        % (H, KVR, block, ROPE, TOPK,
           min(tile, 1 << (block.bit_length() - 1)))] == 1
    assert got["Out"][0].shape == (B, block, H * DV)
    cache = ins["Cache"][0]
    for t in range(block):
        one = kernel(None, _one_position(
            ins, t, pos + t, Cache=cache, Selected=ins["Selected"][0][:, t],
            Live=ins["Live"][0][:, t]), {"num_heads": H})
        cache = one["CacheOut"][0]
        np.testing.assert_allclose(got["Out"][0][:, t], one["Out"][0][:, 0],
                                   atol=2e-5)
    np.testing.assert_array_equal(got["CacheOut"][0], cache)
    # the last position's set holds a slot the block itself wrote
    assert int(ins["Selected"][0][0, -1, :int(ins["Live"][0][0, -1])]
               .max()) >= pos or block == 1


def test_a_blocks_sets_come_a_position_each():
    """`Selected` of a block is [batch, T, top_k]: one set for the whole
    block, or a step's set with a block, is refused."""
    ins = _chosen_block_ins(np.random.RandomState(3), 4, 6)
    kernel = registry.get_op_info("mla_cached_attention").kernel
    for wrong in (ins["Selected"][0][:, 0], ins["Selected"][0][:, :2]):
        with pytest.raises(ValueError, match="one position's"):
            kernel(None, dict(ins, Selected=[wrong]), {"num_heads": H})


def test_the_scale_is_the_attrs_where_given():
    ins = _mla_ins(np.random.RandomState(8), 6)
    kernel = registry.get_op_info("mla_cached_attention").kernel
    own = kernel(None, ins, {"num_heads": H})["Out"][0]
    same = kernel(None, ins, {"num_heads": H,
                              "sm_scale": (NOPE + ROPE) ** -0.5})["Out"][0]
    np.testing.assert_array_equal(own, same)
    other = kernel(None, ins, {"num_heads": H, "sm_scale": 0.5})["Out"][0]
    assert np.abs(np.asarray(other) - np.asarray(own)).max() > 1e-3


def test_the_layer_wants_selected_and_live_together():
    with fluid.program_guard(fluid.Program(), fluid.Program()):
        def data(name, shape, dtype="float32"):
            return fluid.layers.data(name=name, shape=shape, dtype=dtype,
                                     append_batch_size=False)

        with pytest.raises(ValueError, match="come together"):
            fluid.layers.mla_cached_attention(
                data("qn", [B, 1, H * NOPE]), data("qr", [B, 1, H * ROPE]),
                data("c", [B, 1, KVR]), data("r", [B, 1, ROPE]),
                data("cache", [B, T, KVR + ROPE]), data("pos", [B], "int64"),
                H, DV, selected=data("sel", [B, TOPK], "int32"))
        selected, live, kept = fluid.layers.mla_index_select(
            data("qi", [B, 1, IH * ID]), data("wi", [B, 1, IH]),
            data("ki", [B, 1, ID]), data("icache", [B, T, ID]),
            data("pos2", [B], "int64"), IH, TOPK)
        assert tuple(selected.shape) == (B, TOPK)
        assert tuple(live.shape) == (B,)
        assert tuple(kept.shape) == (B, T, ID)
        # a block axis, open or not, is the sets' too
        for n, steps in enumerate((-1, 5)):
            selected, live, _ = fluid.layers.mla_index_select(
                data("qi%d" % n, [B, steps, IH * ID]),
                data("wi%d" % n, [B, steps, IH]),
                data("ki%d" % n, [B, steps, ID]),
                data("icache%d" % n, [B, T, ID]),
                data("pos%d" % (3 + n), [B], "int64"), IH, TOPK)
            assert tuple(selected.shape) == (B, steps, TOPK)
            assert tuple(live.shape) == (B, steps)


# -- the router ------------------------------------------------------------------

def _router(attrs, u, w, bias=None):
    ins = {"X": [u], "W": [w]}
    if bias is not None:
        ins["Bias"] = [bias]
    return registry.get_op_info("moe_router").kernel(
        None, ins, dict({"top_k": K, "scoring": "sigmoid",
                         "norm_topk": True, "scale": 2.5}, **attrs))


def _routing_inputs(seed, n=40):
    rs = np.random.RandomState(seed)
    return (jnp.asarray(rs.randn(n, D), jnp.float32),
            jnp.asarray(rs.randn(D, E) * 0.3, jnp.float32),
            jnp.asarray(rs.randn(E) * 0.3, jnp.float32))


def test_the_grouped_router_agrees_with_the_reference():
    u, w, bias = _routing_inputs(8)
    got = _router({"n_group": GROUPS, "topk_group": KEPT}, u, w, bias)
    weights, indices, scores = reference.route(
        CFG, {"router": w, "router_bias": bias}, u)
    np.testing.assert_array_equal(got["TopIdx"][0], indices)
    np.testing.assert_allclose(
        got["TopW"][0],
        np.take_along_axis(np.asarray(weights), np.asarray(indices), 1),
        rtol=1e-6)
    np.testing.assert_allclose(got["TopW"][0].sum(-1), 2.5, rtol=1e-6)


def test_the_bias_moves_the_choice_and_not_the_weights():
    u, w, bias = _routing_inputs(9)
    plain = _router({}, u, w)
    biased = _router({}, u, w, bias)
    moved = (np.sort(plain["TopIdx"][0], 1)
             != np.sort(biased["TopIdx"][0], 1)).any(1)
    assert 0 < moved.sum() < moved.size
    s = np.asarray(jax.nn.sigmoid(biased["Logits"][0]))
    top = np.take_along_axis(s, np.asarray(biased["TopIdx"][0]), 1)
    # the weights are the unbiased scores of the experts chosen
    np.testing.assert_allclose(
        biased["TopW"][0], 2.5 * top / top.sum(-1, keepdims=True),
        rtol=1e-6)
    # and the choice is by score + bias
    np.testing.assert_array_equal(
        np.sort(biased["TopIdx"][0], 1),
        np.sort(np.argsort(-(s + np.asarray(bias)), 1)[:, :K], 1))
    # a bias of zeros chooses as no bias does
    zero = _router({}, u, w, jnp.zeros((E,)))
    np.testing.assert_array_equal(zero["TopIdx"][0], plain["TopIdx"][0])
    np.testing.assert_array_equal(zero["TopW"][0], plain["TopW"][0])


def test_an_expert_outside_the_kept_groups_is_never_chosen():
    """Expert 0's score is the largest of all at every token, its group
    mate's the smallest: the group's two-largest sum loses to the three
    other groups', and with 2 of 4 groups kept expert 0 is out."""
    n = 16
    rs = np.random.RandomState(10)
    logits = rs.uniform(0.0, 1.0, (n, E)).astype("float32")
    logits[:, 0], logits[:, 1] = 3.0, -9.0
    logits[:, 2:] += 1.5
    u = jnp.asarray(np.eye(n, D, dtype="float32"))
    w = jnp.asarray(np.concatenate(
        [logits, np.zeros((D - n, E), "float32")]))
    got = _router({"n_group": GROUPS, "topk_group": KEPT}, u, w)
    assert np.asarray(got["Logits"][0]).argmax(1).tolist() == [0] * n
    assert not (np.asarray(got["TopIdx"][0]) < 2).any()
    free = _router({}, u, w)
    assert (np.asarray(free["TopIdx"][0])[:, 0] == 0).all()
    # every chosen pair lies inside two groups at most, by construction
    assert got["TopIdx"][0].shape == (n, K)


def _kept_groups_by_sorting(choice, groups, kept):
    """What `ops.moe._kept_groups` returned while it sorted (until PR
    57), kept here as the reference: `lax.top_k` is a stable sort, so of
    equal scores the lower index comes first."""
    n, experts = choice.shape
    grouped = choice.reshape(n, groups, experts // groups)
    group_score = jnp.sum(jax.lax.top_k(grouped, 2)[0], axis=-1)
    _, best = jax.lax.top_k(group_score, kept)
    keep = jnp.any(best[:, :, None] == jnp.arange(groups), axis=1)
    return jnp.where(keep[:, :, None], grouped,
                     -jnp.inf).reshape(n, experts)


def _scores_with_ties(rs, n, experts, groups, kept):
    """Scores in steps of 2**-(1 + row % 6), so that maxima occur twice
    and groups score the same all over, and rows planted by hand (row i
    is i % n): an entry doubled inside a group, two groups that score
    the same across the `kept` boundary (twice, the other way round in
    index), a constant row, groups holding -inf."""
    width = experts // groups
    step = (2.0 ** -(1 + np.arange(n) % 6))[:, None]
    c = (np.floor(rs.uniform(0.0, 1.0, (n, experts)) / step)
         * step).astype("float32")
    grouped = c.reshape(n, groups, width)       # a view: writes reach c

    def row(i):
        grouped[i % n] = np.floor(
            rs.uniform(0.0, 0.5, (groups, width)) * 64) / 64
        return grouped[i % n]

    r = row(0)                                  # a doubled maximum
    r[1, 3] = r[1, width - 1] = 0.75
    for i, order in ((1, rs.permutation(groups)),
                     (2, rs.permutation(groups)[::-1])):
        # the groups score 2 + their place / 16, but the last kept and
        # the first left out: the same sum from different entries
        r = row(i)
        for place, g in enumerate(order):
            r[g, 0], r[g, 1] = 1.0 + place / 16.0, 1.0
        last, first = order[groups - kept - 1], order[groups - kept]
        r[last, 0], r[last, 1] = r[first, 0] - 1 / 16.0, 1.0 + 1 / 16.0
    row(3)[:] = 0.25                            # a constant row
    r = row(4)                                  # groups holding -inf
    r[0, 1:] = -np.inf
    r[groups - 1, :] = -np.inf
    r[1, ::2] = -np.inf
    return c


@pytest.mark.parametrize("scores", ["random", "ties"])
@pytest.mark.parametrize("n,experts,groups,kept", [
    (128, 512, 8, 4), (16, 256, 8, 4), (8192, 512, 8, 4), (1, 64, 4, 2),
    (7, 96, 8, 8)])
def test_the_groups_are_kept_as_a_stable_sort_keeps_them(
        n, experts, groups, kept, scores):
    """Bit for bit the array the sorting form returns: a maximum that
    occurs twice counts twice, and of groups that score the same the
    lower index is kept."""
    rs = np.random.RandomState(57)
    c = rs.uniform(0.0, 1.0, (n, experts)).astype("float32") \
        if scores == "random" \
        else _scores_with_ties(rs, n, experts, groups, kept)
    want = np.asarray(_kept_groups_by_sorting(jnp.asarray(c), groups, kept))
    np.testing.assert_array_equal(
        np.asarray(_kept_groups(jnp.asarray(c), groups, kept, 2)), want)
    # `kept` groups whole and the others out, in every row
    left = np.isfinite(want.reshape(n, groups, -1)).any(-1).sum(-1)
    assert (left <= kept).all() and (scores == "ties" or (left == kept).all())


def test_no_sort_is_traced_where_the_groups_are_chosen():
    """Under `moe_groups` no `sort` and no `top_k`; the router's one
    `top_k` is the choice of the experts itself."""
    u, w, bias = _routing_inputs(8)
    closed = jax.make_jaxpr(lambda *a: _router(
        {"n_group": GROUPS, "topk_group": KEPT}, *a)["TopIdx"][0])(
            u, w, bias)
    found = []

    def walk(jaxpr, inside):
        for eqn in jaxpr.eqns:
            here = inside or "moe_groups" in str(eqn.source_info.name_stack)
            found.append((eqn.primitive.name, here))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub, here)

    walk(closed.jaxpr, False)
    under = {name for name, here in found if here}
    assert {"argmax", "reduce_max", "reduce_sum"} <= under
    assert not under & {"sort", "top_k"}
    assert [name for name, _ in found
            if name in ("sort", "top_k")] == ["top_k"]


def test_the_grouped_router_refuses_what_it_cannot_do():
    u, w, bias = _routing_inputs(3, 4)
    with pytest.raises(ValueError, match="groups"):
        _router({"n_group": 3, "topk_group": 2}, u, w)
    with pytest.raises(ValueError, match="sigmoid"):
        _router({"scoring": "softmax", "n_group": 4, "topk_group": 2}, u, w)
    with pytest.raises(ValueError, match="sigmoid"):
        _router({"scoring": "softmax"}, u, w, bias)


# -- what the ops lowered to before this ------------------------------------------

@pytest.fixture(scope="module")
def lowerings():
    with open(parent_lowerings.RECORDING) as f:
        return json.load(f), parent_lowerings.lowerings()


@pytest.mark.parametrize("what", [
    "rope", "moe_router softmax", "moe_router sigmoid",
    "mla_cached_attention float32", "mla_cached_attention bfloat16"])
def test_without_the_new_inputs_everything_lowers_as_the_parent(
        lowerings, what):
    """The jaxpr of each op without its new inputs and attrs (one
    position a row: T = 1), against the recording made on the parent
    commit."""
    recorded, now = lowerings
    assert now[what] == recorded[what]


def _products(program_text):
    """[(op type, the parameters it reads)] of the ops of a recorded
    Program that read a parameter, in order."""
    found = []
    for line in program_text.split("\n"):
        kind, rest = line.split("(", 1)
        names = re.findall(
            r"((?:block_\d+\.|embed\.|head\.)\w+|norm_f)=",
            rest.split(") -> ")[0])
        if names:
            found.append((kind, names))
    return found


def test_pangus_program_keeps_its_products_in_their_order(lowerings):
    """The builder called with pangu's arguments takes a block of
    positions since PR 53 and no longer builds the recorded Program op
    for op (slices of the block's last position, positions read off the
    feed): it is held to the recording's products, every op that reads a
    parameter with the parameters it reads, in the recording's order;
    what lies between them moves no arithmetic at T = 1
    (tests/test_latent_moe_program.py holds the logits to the
    reference's at 1e-5)."""
    recorded, now = lowerings
    assert _products(now["program"]) == _products(recorded["program"])
    assert len(_products(recorded["program"])) > 40
    # and what changed is what was meant to: the feed, the attr
    assert "mla_cached_attention" in now["program"] \
        and "'prefill_block'" in now["program"] \
        and "'prefill_block'" not in recorded["program"]


@pytest.fixture(scope="module")
def block_lowerings():
    with open(parent_lowerings.BLOCK_RECORDING) as f:
        return json.load(f), parent_lowerings.block_lowerings()


@pytest.mark.parametrize("what", [
    "program indexer", "program window_moe", "program linear_moe",
    "call gpt2", "call window_moe", "call linear_moe"])
def test_what_the_block_form_leaves_alone_is_the_parents(block_lowerings,
                                                         what):
    """PR 53 gave the latent step a block of positions.  The
    window/full and linear/full builders moved onto the shared helpers
    of `decoder_block` and build their Programs op for op; and a
    generation call through each step that prefills in blocks of 128 (a
    remainder, a block, two steps) traces to the parent's jaxpr: against
    the recording made on commit b6c67fc.  With an `indexer` the builder
    built that commit's Program op for op until PR 62 gave a chooser's
    step the block form too: it is held to the recording's products in
    their order, as pangu's is."""
    recorded, now = block_lowerings
    if what == "program indexer":
        assert _products(now[what]) == _products(recorded[what])
        assert len(_products(recorded[what])) > 30
        assert "'prefill_block'" in now[what] \
            and "'prefill_block'" not in recorded[what]
    else:
        assert now[what] == recorded[what]


@pytest.fixture(scope="module")
def step_lowerings():
    with open(parent_lowerings.STEP_RECORDING) as f:
        return json.load(f), parent_lowerings.step_lowerings()


@pytest.mark.parametrize("what", [
    "mla_index_select float32", "mla_index_select bfloat16",
    "mla_cached_attention chosen float32",
    "mla_cached_attention chosen bfloat16",
    "mla_cached_attention chosen sink float32",
    "mla_cached_attention chosen sink bfloat16"])
def test_one_position_of_a_chooser_lowers_as_the_parent(step_lowerings,
                                                        what):
    """PR 62 gave the chooser and the attention over its set a block of
    positions.  At T = 1 both trace to the jaxpr they traced to before,
    equation for equation (the selection kernel's body included): the
    decoding step of a chooser's cell is the step it was.  Against the
    recording made on commit cc05484.  Since PR 70 the step's gather
    clips an entry into the extent, as the block's does, where it filled
    in behind itself; at the recording's sizes (a set of 6) the plain
    products stay, and nothing else of the jaxpr moved."""
    recorded, now = step_lowerings
    assert now[what] == recorded[what] \
        .replace("fill_value=nan", "fill_value=None") \
        .replace("mode=GatherScatterMode.FILL_OR_DROP",
                 "mode=GatherScatterMode.CLIP")
    assert ("GatherScatterMode.CLIP" in now[what]) \
        == what.startswith("mla_cached_attention")


# -- YaRN ---------------------------------------------------------------------------

def test_yarn_at_the_published_numbers():
    """rope_scaling of DeepSeek-V3.2: factor 40 over 4096 original
    positions, beta_fast 32, beta_slow 1, theta 1e4, 64 rotary values:
    the ramp runs from pair 10 to pair 23."""
    f = yarn_inv_freq(64, 1e4, 40, 4096, 32, 1)
    assert len(f) == 32
    plain = [1e4 ** (-2 * i / 64) for i in range(32)]
    # pairs up to 10 keep their frequency, from 23 on they are slowed 40x
    np.testing.assert_allclose(f[:11], plain[:11], rtol=1e-12)
    np.testing.assert_allclose(f[23:], [p / 40 for p in plain[23:]],
                               rtol=1e-12)
    assert f[10] == plain[10] and f[11] < plain[11]
    # inside the ramp: ramp_i = (i - 10) / 13
    for i in (11, 16, 22):
        ramp = (i - 10) / 13
        np.testing.assert_allclose(
            f[i], plain[i] / 40 * ramp + plain[i] * (1 - ramp), rtol=1e-12)
    np.testing.assert_allclose(f[16], 1e4 ** -0.5 * (6 / 13 / 40 + 7 / 13),
                               rtol=1e-12)
    assert round(yarn_mscale(40), 4) == 1.3689
    assert round(yarn_mscale(40) ** 2, 4) == 1.8739
    assert yarn_mscale(1) == 1.0
    # the reference makes the same from the configuration's own keys
    cfg = dict(CFG, qk_rope_head_dim=64, qk_nope_head_dim=128,
               rope_scaling=dict(CFG["rope_scaling"],
                                 original_max_position_embeddings=4096))
    np.testing.assert_allclose(reference.yarn_inv_freq(cfg), f, rtol=1e-6)
    np.testing.assert_allclose(reference.softmax_scale(cfg),
                               192 ** -0.5 * 1.8739, rtol=1e-4)


def test_rope_takes_the_frequencies_and_a_rotary_width():
    rs = np.random.RandomState(4)
    x = jnp.asarray(rs.randn(B, 3, IH * ID), jnp.float32)
    pos = jnp.asarray(rs.randint(0, 200, (B, 3)))
    kernel = registry.get_op_info("rope").kernel
    freq = yarn_inv_freq(ROPE, 1e4, 40, 16, 32, 1)
    got = kernel(None, {"X": [x], "Positions": [pos]},
                 {"num_heads": IH, "inv_freq": freq,
                  "rotary_dim": ROPE})["Out"][0]
    want = jnp.stack([reference.rope(
        x[b:b + 1].reshape(1, 3, IH, ID), pos[b],
        jnp.asarray(freq, jnp.float32)) for b in range(B)])
    np.testing.assert_allclose(got, want.reshape(B, 3, IH * ID), atol=1e-5)
    # the part past the rotary width is handed on as it is
    np.testing.assert_array_equal(
        np.asarray(got).reshape(B, 3, IH, ID)[..., ROPE:],
        np.asarray(x).reshape(B, 3, IH, ID)[..., ROPE:])
    # theta's powers, given as a list, are the op without a list
    plain = kernel(None, {"X": [x], "Positions": [pos]},
                   {"num_heads": IH, "theta": 1e4})["Out"][0]
    listed = kernel(None, {"X": [x], "Positions": [pos]},
                    {"num_heads": IH, "inv_freq": [
                        1e4 ** (-i / (ID // 2)) for i in range(ID // 2)]})
    np.testing.assert_allclose(listed["Out"][0], plain, atol=1e-5)
    with pytest.raises(ValueError, match="inverse frequencies"):
        kernel(None, {"X": [x], "Positions": [pos]},
               {"num_heads": IH, "inv_freq": freq})
    with pytest.raises(ValueError, match="rotary_dim"):
        kernel(None, {"X": [x], "Positions": [pos]},
               {"num_heads": IH, "rotary_dim": ID + 2})


# -- the shares add up under grouped routing ---------------------------------------

def _expert_weights(rs, experts=E):
    w_gate, w_up = (jnp.asarray(rs.randn(experts, D, FE) * 0.2, jnp.float32)
                    for _ in range(2))
    return w_gate, w_up, jnp.asarray(rs.randn(experts, FE, D) * 0.2,
                                     jnp.float32)


@pytest.mark.parametrize("ranges", [[(i, 1) for i in range(E)],
                                    [(0, 4), (4, 4)],
                                    [(0, 2), (2, 4), (6, 2)]],
                         ids=["8 shares", "2 shares", "3 shares"])
def test_the_shares_add_up_to_the_uncut_layer_under_grouped_routing(ranges):
    """model-configs section 4's share test: the held parts of all the
    shares, the shared expert counted once, add up to the uncut
    reference's expert layer, routed by score + bias inside the kept
    groups."""
    rs = np.random.RandomState(len(ranges))
    n = 24
    u = jnp.asarray(rs.randn(n, D), jnp.float32)
    weights = _expert_weights(rs)
    block = {"router": jnp.asarray(rs.randn(D, E) * 0.3, jnp.float32),
             "router_bias": jnp.asarray(rs.randn(E) * 0.3, jnp.float32),
             "w_gate": weights[0], "w_up": weights[1], "w_down": weights[2],
             "shared_in": jnp.asarray(rs.randn(D, 2 * FE) * 0.2, jnp.float32),
             "shared_out": jnp.asarray(rs.randn(FE, D) * 0.2, jnp.float32)}
    want, indices = reference.feed_forward(CFG, block, u)
    routed = _router({"n_group": GROUPS, "topk_group": KEPT}, u,
                     block["router"], block["router_bias"])
    np.testing.assert_array_equal(routed["TopIdx"][0], indices)
    total = reference.gated(u, block["shared_in"], block["shared_out"])
    rows = 0
    for first, count in ranges:
        part = registry.get_op_info("moe_experts").kernel(
            None, {"X": [u], "TopW": routed["TopW"],
                   "TopIdx": routed["TopIdx"],
                   "WGate": [weights[0][first:first + count]],
                   "WUp": [weights[1][first:first + count]],
                   "WDown": [weights[2][first:first + count]]},
            {"first_expert": first, "scored": E})
        rows += int(np.asarray(part["Counts"][0]).sum())
        total = total + part["Out"][0]
        cut = dict(block, **{w: block[w][first:first + count]
                             for w in ("w_gate", "w_up", "w_down")})
        np.testing.assert_allclose(
            part["Out"][0],
            reference.feed_forward(CFG, cut, u, first, shared=False)[0],
            atol=2e-5)
    assert rows == n * K
    np.testing.assert_allclose(total, want, atol=3e-5)


# -- the Program and its counters ---------------------------------------------------

def test_parameter_names_follow_the_options(built):
    block = built["main"].global_block()
    made = {p.name: tuple(p.shape) for p in block.all_parameters()}
    assert set(made) == set(jax.tree_util.tree_leaves(NAMES))
    b0, b1 = NAMES["blocks"]
    for absent in ("post_attn_norm", "post_mlp_norm"):
        assert absent not in b0 and absent not in b1
    assert "router_bias" in b1 and "router_bias" not in b0
    assert made[b1["router_bias"]] == (E,)
    assert made[b0["w_iq"]] == (QR, IH * ID)
    assert made[b0["w_ik"]] == (D, ID)
    assert made[b0["ik_norm"]] == (ID,) == made[b0["ik_norm_b"]]
    assert made[b0["w_iw"]] == (D, IH)
    # pangu's names and their order are what they were
    assert list(latent_moe_param_names(2, 1)["blocks"][1]) == [
        "input_norm", "w_dq", "q_norm", "w_uq_nope", "w_uq_rope", "w_dkv",
        "kv_norm", "w_uk", "w_uv", "wo", "post_attn_norm", "pre_mlp_norm",
        "shared_in", "shared_out", "router", "w_gate", "w_up", "w_down",
        "post_mlp_norm"]
    ops = [od.type for od in block.desc.ops]
    assert ops.count("mla_index_select") == L == \
        ops.count("mla_cached_attention")
    # two norms a layer, two inside the attention, one at the end
    assert ops.count("rms_norm") == 4 * L + 1
    assert ops.count("layer_norm") == L
    assert [f for f, _ in built["pairs"]] == [
        "latent_cache_0", "index_cache_0", "latent_cache_1",
        "index_cache_1", "pos"]
    assert len(built["parts"]["selected"]) == L == \
        len(built["parts"]["attn_out"])


def test_counters_say_what_was_lowered(built):
    assert not [k for k in built["at_build"] if k.startswith((
        "mla_index_select_lowerings_total",
        "moe_grouped_router_lowerings_total"))]
    decoder = built["decoder"]
    before = telemetry.snapshot()
    jax.make_jaxpr(decoder._step_fn(decoder._params))(
        _empty(), jnp.asarray(built["tokens"][:, 0]))
    lowered = telemetry.snapshot_delta(before)
    # one count an op instance a traced step holds
    assert lowered[
        "mla_index_select_lowerings_total{cache_dtype=float32,dim=%d,"
        "heads=%d,positions=1,select=count,tile=1,top_k=%d}"
        % (ID, IH, TOPK)] == L
    assert lowered[
        "mla_cached_attention_lowerings_total{cache_dtype=float32,"
        "heads=%d,latent=%d,positions=1,rope=%d,selected=%d,tile=1}"
        % (H, KVR, ROPE, TOPK)] == L
    # a chosen set of 8 entries is no step the kernel takes (`fits`): the
    # plain products
    assert lowered["mla_decode_lowerings_total{block_k=0,path=plain,"
                   "positions=1}"] == L
    assert not [k for k in lowered if "path=kernel" in k]
    assert lowered[
        "moe_grouped_router_lowerings_total{experts=%d,groups=%d,kept=%d,"
        "top_k=%d}" % (E, GROUPS, KEPT, K)] == L - DENSE


def test_the_shares_grouped_products_skip_the_experts_nobody_chose(built):
    """A traced step holds three grouped products an expert layer, all
    of them the row product whose list of visits leaves an empty group
    out: a share holds 16 experts for 8 assignments, and reads the
    weights of those that got one."""
    decoder = built["decoder"]
    before = telemetry.snapshot()
    jax.make_jaxpr(decoder._step_fn(decoder._params))(
        _empty(), jnp.asarray(built["tokens"][:, 0]))
    gmm = {k: v for k, v in telemetry.snapshot_delta(before).items()
           if k.startswith("moe_gmm_lowerings_total")}
    assert sum(gmm.values()) == 3 * (L - DENSE)
    assert all("kernel=fwd" in k and "empty_groups=skipped" in k
               for k in gmm)


def test_obs_dump_lists_the_new_counters(built, tmp_path):
    from paddle_tpu.tools import obs_dump

    decoder = built["decoder"]
    jax.make_jaxpr(decoder._step_fn(decoder._params))(
        _empty(), jnp.asarray(built["tokens"][:, 0]))
    path = str(tmp_path / "metrics.jsonl")
    assert obs_dump.main(["--metrics-out", path]) == 0
    with open(path) as f:
        text = f.read()
    for name in ("mla_index_select_lowerings_total",
                 "moe_grouped_router_lowerings_total", "selected"):
        assert name in text
