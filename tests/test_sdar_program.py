"""Generation by diffusion over blocks: the block-causal mask of
`cached_attention` (`diffusion_block`) on both of its paths against a
dense masked softmax, the cached step Program that hands out the logits
of every position it is fed (models/diffusion_moe_program.py) and
`fluid.ProgramDecoder.diffuse` (models/decode.py
`block_diffusion_decode`) against the plain float32 reference
(models/reference/sdar_moe.py): logits and trajectories for the three
strategies, T = B and T < B, a threshold some positions clear, a prompt
with P mod B != 0, one shorter than a block, one block only; the step's
applications of a call, a commit riding on the next block's first pass,
from a step that logs them; the rule alone (`_unmask`) against a plain
float64 one; the reference's replay of a trajectory
against its own whole forwards; the head dead in the prefill; the
counters and the spans' arguments; and two step Programs the repo had,
their jaxprs unchanged.

Tiny sizes on the CPU: 2 layers, hidden 64, 4 query / 2 key/value heads
of 16, 8 experts, 2 a token, vocabulary 97 (the mask id its last),
blocks of 4, seeded random weights (norm scales moved off their 1).
"""

import hashlib
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu.fluid as fluid
from paddle_tpu.jit import FunctionalProgram
from paddle_tpu.models import decode
from paddle_tpu.models.diffusion_moe_program import (
    build_diffusion_moe_cached_step_program, diffusion_moe_param_names)
from paddle_tpu.models.reference import sdar_moe as reference
from paddle_tpu.obs import telemetry
from paddle_tpu.ops import attention

ROWS, EXTENT, V, L, BLOCK = 2, 32, 97, 2, 4
H, KV, DH, D, FE, E, K = 4, 2, 16, 64, 32, 8, 2
MASK = V - 1
SIZES = dict(n_layer=L, n_head=H, n_kv_head=KV, d_head=DH, d_model=D,
             d_expert=FE, n_experts=E, top_k=K)
CFG = {"num_attention_heads": H, "num_key_value_heads": KV, "head_dim": DH,
       "rms_norm_eps": 1e-6, "rope_theta": 1e6, "num_experts_per_tok": K,
       "norm_topk_prob": True, "num_hidden_layers": L}
NAMES = diffusion_moe_param_names(L)
# float32 on the CPU: the step reads its caches, the reference the whole
# score matrix under a mask; sums in another order
RTOL = 3e-5


def _start(startup, seed=5, head_gain=1.0):
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
    # a peaked head: some positions clear a confidence threshold
    scope.set(NAMES["head"],
              jnp.asarray(np.asarray(scope.get(NAMES["head"])) * head_gain))
    return scope


def _params(scope):
    return jax.tree_util.tree_map(lambda n: np.asarray(scope.get(n)), NAMES)


def _empty(rows=ROWS, extent=EXTENT, dtype=jnp.float32):
    state = {"pos": jnp.zeros((rows,), jnp.int32)}
    for i in range(L):
        for which in "kv":
            state["%s_cache_%d" % (which, i)] = jnp.zeros(
                (rows, KV, extent, DH), dtype)
    return state


@pytest.fixture(scope="module")
def built():
    before = telemetry.snapshot()
    main, startup, logits, pairs, parts = \
        build_diffusion_moe_cached_step_program(
            ROWS, EXTENT, V, BLOCK, probe_rows=1, **SIZES)
    at_build = telemetry.snapshot_delta(before)
    probes = [("probe_k", parts["keys"][0].name),
              ("probe_v", parts["values"][0].name)]
    return {"main": main, "startup": startup, "logits": logits,
            "pairs": pairs, "probes": probes, "at_build": at_build}


def _decoder(built, scope):
    return fluid.ProgramDecoder(
        built["main"].clone(for_test=True), token_name="tok",
        logits_name=built["logits"].name,
        state_pairs=built["pairs"] + built["probes"], scope=scope,
        max_positions=EXTENT)


def _init():
    init = _empty()
    init.update(probe_k=jnp.zeros((1, KV, EXTENT, DH)),
                probe_v=jnp.zeros((1, KV, EXTENT, DH)))
    return init


# -- the step against the reference's whole forward ---------------------------------

def test_prefill_then_passes_against_the_whole_forward(built):
    """Two applications that prefill 8 + 8 positions, then two passes
    over a block of 4 of which the second is kept: the logits of every
    position fed and the caches against the reference's whole forward of
    the same 20 tokens."""
    scope = _start(built["startup"])
    pairs = built["pairs"]
    fp = FunctionalProgram(built["main"].clone(for_test=True),
                           ["tok"] + [f for f, _ in pairs],
                           [built["logits"].name] + [o for _, o in pairs])
    params = {n: scope.get(n) for n in fp.state_in_names}
    rs = np.random.RandomState(2)
    tokens = rs.randint(0, V - 1, (ROWS, 20)).astype("int32")
    want = reference.forward(CFG, _params(scope), tokens, BLOCK)

    def apply(state, toks):
        (logits, *new), _ = fp(params, dict(state, tok=jnp.asarray(toks)))
        return np.asarray(logits), {f: v for (f, _), v in zip(pairs, new)}

    state, got = _empty(), []
    for lo, hi in ((0, 8), (8, 16)):
        logits, state = apply(state, tokens[:, lo:hi])
        got.append(logits)
    assert int(state["pos"][0]) == 16
    # a pass over a block that still holds masks, handed on but for the
    # position: the next pass overwrites its slots
    noisy = tokens[:, 16:20].copy()
    noisy[:, 1:3] = MASK
    _, dirty = apply(state, noisy)
    logits, state = apply(dict(dirty, pos=state["pos"]), tokens[:, 16:20])
    got.append(logits)
    got = np.concatenate(got, axis=1)
    assert got.shape == (ROWS, 20, V)
    np.testing.assert_allclose(got, np.asarray(want["logits"]), rtol=RTOL,
                               atol=RTOL)
    for i in range(L):
        for which, key in (("k", "keys"), ("v", "values")):
            cache = np.asarray(state["%s_cache_%d" % (which, i)])
            np.testing.assert_allclose(
                cache[:, :, :20].transpose(0, 2, 1, 3),
                np.asarray(want[key][i]), rtol=RTOL, atol=RTOL)
            assert not cache[:, :, 20:].any()


# -- diffuse against the reference's generation loop ------------------------------

CASES = {
    # name: (prompt length, generated, T, strategy, threshold, head gain)
    "static_T=B": (8, 12, 4, "low_confidence_static", 0.9, 1.0),
    "static_T<B": (8, 12, 3, "low_confidence_static", 0.9, 1.0),
    "dynamic_floor": (8, 12, 4, "low_confidence_dynamic", 0.9, 1.0),
    "dynamic_T<B": (8, 8, 2, "low_confidence_dynamic", 0.9, 1.0),
    "dynamic_cleared": (8, 12, 4, "low_confidence_dynamic", 0.5, 40.0),
    "sequential_T=B": (8, 12, 4, "sequential", 0.9, 1.0),
    "sequential_T<B": (8, 8, 2, "sequential", 0.9, 1.0),
    "static_leftover": (10, 9, 4, "low_confidence_static", 0.9, 1.0),
    "dynamic_leftover": (11, 8, 4, "low_confidence_dynamic", 0.9, 1.0),
    "sequential_leftover": (9, 7, 2, "sequential", 0.9, 1.0),
    "static_one_block": (8, 4, 4, "low_confidence_static", 0.9, 1.0),
    # no whole block in the prompt: the first block starts on a plain pass
    "dynamic_short_prompt": (3, 9, 4, "low_confidence_dynamic", 0.9, 1.0),
    "sequential_short_one_block": (2, 2, 2, "sequential", 0.9, 1.0),
}


@pytest.fixture(scope="module")
def diffused(built):
    """{case: (the decoder's results, the reference's)}; one decoder a
    head gain."""
    out, decoders = {}, {}
    for name, (length, gen, steps, how, tau, gain) in CASES.items():
        if gain not in decoders:
            scope = _start(built["startup"], head_gain=gain)
            decoders[gain] = _decoder(built, scope), _params(scope)
        decoder, params = decoders[gain]
        prompt = np.random.RandomState(len(name)).randint(
            0, V - 1, (ROWS, length)).astype("int32")
        before = telemetry.snapshot()
        got = decoder.diffuse(
            prompt, gen, BLOCK, steps, how, tau, MASK,
            init_state=_init(), return_state=("probe_k", "probe_v", "pos"))
        counted = telemetry.snapshot_delta(before)
        want = reference.generate(CFG, params, prompt, gen, BLOCK, steps,
                                  MASK, how, tau)
        out[name] = got, want, counted, (prompt, params)
    return out


@pytest.mark.parametrize("case", sorted(CASES))
def test_diffuse_takes_the_references_trajectory(diffused, case):
    (tokens, lengths, info), want, _, _ = diffused[case]
    gen = CASES[case][1]
    assert tokens.shape == (ROWS, gen) and (lengths == gen).all()
    np.testing.assert_array_equal(tokens, want["tokens"])
    np.testing.assert_array_equal(info["fixed_pass"], want["fixed_pass"])
    np.testing.assert_allclose(info["fixed_conf"], want["fixed_conf"],
                               rtol=1e-3)
    assert info["denoise_passes"] == want["passes"]["denoise"]
    assert info["commit_passes"] == want["passes"]["commit"]


@pytest.mark.parametrize("case", sorted(CASES))
def test_diffuse_counts_its_passes(diffused, case):
    """The passes by kind, from the strategy's rule: the scans take T a
    block, the loop ends a block when nothing is masked."""
    length, gen, steps, how, _, gain = CASES[case]
    (_, _, info), _, counted, _ = diffused[case]
    blocks = -(-(length % BLOCK + gen) // BLOCK)
    assert info["commit_passes"] == blocks
    if how != "low_confidence_dynamic" or gain == 1.0 and not length % BLOCK:
        assert info["denoise_passes"] == blocks * steps
    else:
        assert blocks <= info["denoise_passes"] < blocks * steps
    # what the passes cost: every commit but the last rode on a
    # denoising pass, so the step was applied once a denoising pass and
    # once more
    assert info["folded_commits"] == blocks - 1
    assert info["step_applications"] == info["denoise_passes"] + 1
    assert counted["decoder_diffusion_passes_total{kind=denoise}"] \
        == info["denoise_passes"]
    assert counted["decoder_diffusion_passes_total{kind=commit}"] == blocks
    assert counted["decoder_diffusion_blocks_total"] == blocks
    assert counted.get("decoder_diffusion_folded_commits_total", 0) \
        == blocks - 1
    assert counted["decoder_diffusion_applications_total"] \
        == info["step_applications"]
    assert counted["decoder_diffusion_tokens_total"] == ROWS * gen
    assert counted["decoder_calls_total{mode=diffuse}"] == 1
    assert counted["decoder_tokens_total{kind=generated}"] == ROWS * gen


@pytest.mark.parametrize("case", ["dynamic_floor", "static_leftover",
                                  "dynamic_cleared", "sequential_T<B",
                                  "static_one_block",
                                  "dynamic_short_prompt"])
def test_the_cache_keeps_the_commit_pass(diffused, case):
    """The first layer's keys and values after the call are the whole
    forward's of the final sequence: no pass whose input held a mask
    left anything (rule 4), wherever a block's commit ran (on the next
    block's first pass, or alone after the last), and the position ends
    at the last block's end, B a block further and not 2B."""
    (tokens, _, info), _, _, (prompt, params) = diffused[case]
    final = np.concatenate([prompt, tokens], axis=1)
    whole = final.shape[1] // BLOCK * BLOCK
    want = reference.forward(CFG, params, final[:, :whole], BLOCK)
    for probe, key in (("probe_k", "keys"), ("probe_v", "values")):
        np.testing.assert_allclose(
            info["state"][probe][:, :, :whole].transpose(0, 2, 1, 3),
            np.asarray(want[key][0])[:1], rtol=RTOL, atol=RTOL)
    blocks = -(-final.shape[1] // BLOCK)
    assert int(info["state"]["pos"][0]) == blocks * BLOCK


@pytest.mark.parametrize("case", ["dynamic_floor", "static_leftover",
                                  "sequential_T<B", "dynamic_cleared"])
def test_replay_is_the_whole_forward_of_a_pass(diffused, case):
    """The reference's replay of a trajectory (one whole forward of the
    final sequence, a pass as its B queries over that forward's keys and
    values) gives the logits the loop's own whole forwards gave, pass by
    pass; and the inputs it makes from the decoder's results are the
    loop's."""
    (tokens, _, info), want, _, (prompt, params) = diffused[case]
    length, gen = prompt.shape[1], tokens.shape[1]
    whole, left = length // BLOCK * BLOCK, length % BLOCK
    blocks = -(-(left + gen) // BLOCK)
    final = np.concatenate([prompt, want["tokens"]], axis=1)
    # the last block's tail past the generated length is not returned:
    # replay the blocks that are whole
    known = (final.shape[1] - whole) // BLOCK
    fed = reference.pass_inputs(final, info["fixed_pass"], whole, left,
                                BLOCK, MASK, range(known))
    loop = {(n, s): (c, z) for (n, s, c), z
            in zip(want["inputs"], want["logits"])}
    keys = sorted(k for k in fed if k in loop)
    assert keys and all(k[0] < known for k in keys)
    for key in keys:
        np.testing.assert_array_equal(fed[key], loop[key][0])
    wanted = [(row, whole + n * BLOCK, fed[n, s][row])
              for n, s in keys for row in range(ROWS)]
    ends = {k: params[k] for k in ("embed", "norm_f", "head")}
    got, k0, v0 = reference.replay(
        CFG, ends, lambda i: params["blocks"][i],
        final[:, :whole + known * BLOCK], BLOCK, wanted, whole)
    got = np.asarray(got).reshape(len(keys), ROWS, BLOCK, V)
    for at, key in enumerate(keys):
        np.testing.assert_allclose(
            got[at], loop[key][1], rtol=RTOL,
            atol=RTOL * np.abs(loop[key][1]).max())
    # what `correct` reads of them: the program fixed the reference's
    # first token at the reference's first position
    served, fixed, conf = reference.fixed_by(
        keys, ROWS, final, info["fixed_pass"], info["fixed_conf"], whole,
        length, BLOCK)
    read = reference.trajectory(
        got.reshape(-1, BLOCK, V), np.stack([w[2] for w in wanted]), served,
        fixed, conf, MASK)
    assert read["fixed"] == fixed.sum() > 0
    assert read["gap_mean"] == 0.0 and read["not_first_share"] == 0.0
    assert read["conf_off"] < 1e-3
    if CASES[case][3] != "sequential":     # which fixes by place
        assert read["other_position_share"] == 0.0


@pytest.mark.parametrize("control", [
    {"causal_in_block": True}, {"causal_prefill": 8},
    {"kv_dtype": "float8_e4m3fn"}])
def test_a_control_moves_the_reference(built, control):
    scope = _start(built["startup"])
    tokens = np.random.RandomState(4).randint(0, V - 1, (1, 16))
    sound = reference.forward(CFG, _params(scope), tokens, BLOCK)["logits"]
    wrong = reference.forward(dict(CFG, control=control), _params(scope),
                              tokens, BLOCK)["logits"]
    assert reference.off(wrong, sound) > 1e-3


# -- the decoder's own -----------------------------------------------------------------

def test_diffuse_samples_from_a_seed(built):
    scope = _start(built["startup"])
    decoder = _decoder(built, scope)
    prompt = np.random.RandomState(0).randint(0, V - 1, (ROWS, 8))
    runs = [decoder.diffuse(prompt, 8, BLOCK, 4, "low_confidence_static",
                            0.9, MASK, temperature=1.0, top_k=5, seed=seed,
                            init_state=_init())[0] for seed in (1, 1, 2)]
    np.testing.assert_array_equal(runs[0], runs[1])
    assert (runs[0] != runs[2]).any()
    assert all((run >= 0).all() and (run < V).all() for run in runs)


@pytest.mark.parametrize("what, kwargs", [
    ("remasking", dict(remasking="confidence")),
    ("denoising steps", dict(denoising_steps=5)),
    ("extent", dict(max_len=EXTENT)),
])
def test_diffuse_refuses(built, what, kwargs):
    decoder = _decoder(built, _start(built["startup"]))
    args = dict(prompt=np.zeros((ROWS, 8), "int32"), max_len=8,
                block_length=BLOCK, denoising_steps=4,
                remasking="sequential", confidence_threshold=0.9,
                mask_id=MASK, init_state=_init())
    with pytest.raises(ValueError, match=what.split()[0]):
        decoder.diffuse(**dict(args, **kwargs))


def test_the_head_is_dead_in_the_prefill(built):
    """No product with the head's [hidden, vocabulary] is left in the
    compiled prefill: nothing reads a prompt position's logits."""
    scope = _start(built["startup"])
    decoder = _decoder(built, scope)
    step = decoder._step_fn(decoder._params)
    prompt = jnp.zeros((ROWS, 16), jnp.int32)

    def prefill_alone(state, prompt):
        return decode._prefill_blocks(step, state, prompt, 8)

    def with_logits(state, prompt):
        return step(state, prompt[:, :8])

    def vocab_wide(fn):
        text = jax.jit(fn).lower(_init(), prompt).compile().as_text()
        return len(re.findall(r"\[%d,\d+,%d\]" % (ROWS, V), text))

    assert vocab_wide(with_logits) > 0
    assert vocab_wide(prefill_alone) == 0


def test_the_spans_say_what_the_call_was(built):
    from paddle_tpu.obs import trace as obs_trace

    decoder = _decoder(built, _start(built["startup"]))
    with obs_trace.tracing():
        decoder.diffuse(np.zeros((ROWS, 8), "int32"), 8, BLOCK, 4,
                        "low_confidence_dynamic", 0.9, MASK,
                        init_state=_init())
    spans = [e for e in obs_trace.events() if e["ph"] == "X"]
    assert [e["name"] for e in spans] == [
        "decode/prep", "decode/dispatch", "decode/fetch", "decode/call"]
    call = spans[-1]["args"]
    assert call["mode"] == "diffuse" and call["max_len"] == 8
    assert call["block_length"] == BLOCK and call["denoising_steps"] == 4
    assert call["denoise_passes"] == 8 and call["commit_passes"] == 2
    assert call["folded_commits"] == 1 and call["step_applications"] == 9
    assert call["prompt_len"] == 8


def test_the_lowering_says_block_causal(built):
    scope = _start(built["startup"])
    before = telemetry.snapshot()
    _decoder(built, scope).diffuse(
        np.zeros((ROWS, 16), "int32"), 4, BLOCK, 4, "sequential", 0.9,
        MASK, init_state=_init())
    counted = telemetry.snapshot_delta(before)
    # the prefill's op (one application of 16), a block's first pass's
    # (the block before and its own), a later pass's and the last
    # commit's
    by_block = {key: value for key, value in counted.items()
                if key.startswith("block_causal_attention_lowerings_total")}
    assert by_block == {
        "block_causal_attention_lowerings_total{block=16,"
        "diffusion_block=4,path=plain}": L,
        "block_causal_attention_lowerings_total{block=8,"
        "diffusion_block=4,path=plain}": L,
        "block_causal_attention_lowerings_total{block=4,"
        "diffusion_block=4,path=plain}": 2 * L}
    assert any("kind=block_causal" in key for key in counted
               if key.startswith("window_attention_lowerings_total"))
    assert counted["prefill_lowerings_total{block=128,form=block}"] == 1


def test_the_scopes_of_a_call(built):
    """`diffusion_denoise` and `diffusion_unmask` inside the scan of
    blocks under `decode_steps`, a block's first pass under
    `diffusion_fold` inside `diffusion_denoise` and before the loop of
    the others, the last commit under `diffusion_commit` after the scan;
    the prefill under `decode_prefill`."""
    scope = _start(built["startup"])
    decoder = _decoder(built, scope)
    step = decoder._step_fn(decoder._params)
    text = jax.jit(lambda s, p: decode.block_diffusion_decode(
        step, s, p, 8, BLOCK, 4, MASK)).lower(
            _init(), jnp.zeros((ROWS, 16), jnp.int32)).compile().as_text()
    names = set(re.findall(r'op_name="([^"]*)"', text))
    steps = [n.split("/%s/" % decode.STEPS_SCOPE, 1)[1] for n in names
             if "/%s/" % decode.STEPS_SCOPE in n]
    for inner in (decode.DENOISE_SCOPE, decode.UNMASK_SCOPE):
        assert any(re.match(r"while/body/(.*/)?%s/" % inner, n)
                   for n in steps), inner
    fold = "%s/%s/" % (decode.DENOISE_SCOPE, decode.FOLD_SCOPE)
    folded = [n for n in steps if fold in n]
    # in the scan of blocks' body, not in the loop of the later passes
    assert folded and all(re.match(r"while/body/(closed_call/)?%s" % fold, n)
                          for n in folded)
    assert any(re.match(r"while/body/(.*/)?while/body/(.*/)?%s/(?!%s)"
                        % (decode.DENOISE_SCOPE, decode.FOLD_SCOPE), n)
               for n in steps)
    assert not [n for n in steps if decode.FOLD_SCOPE in n
                and decode.UNMASK_SCOPE in n]
    committed = [n for n in steps if decode.COMMIT_SCOPE in n]
    assert committed and all(n.startswith(decode.COMMIT_SCOPE + "/")
                             for n in committed)
    # the block-causal attention under a pass's scope, by its own name
    assert any(re.search(r"%s/.*cached_attention/.*attn_block_causal"
                         % decode.DENOISE_SCOPE, n) for n in names)
    prefill = [n for n in names if "/%s/" % decode.PREFILL_SCOPE in n]
    assert prefill      # one application: 16 positions are no scan
    assert not [n for n in prefill if decode.STEPS_SCOPE in n]


# -- the step's applications of a call ---------------------------------------------------

LOGGED_V, LOGGED_EXTENT, LOGGED_MOST = 13, 32, 32


def _logged_step(state, tokens):
    """A step of no model that logs its applications: (the first row's
    position, the positions fed) at the call's running count, the tokens
    fed stored at their positions; flat bfloat16 logits of a position
    and its token (rows of a table: nothing wider is made in another
    type), which clear no threshold."""
    rows, width = tokens.shape
    slots = state["pos"][:, None] + jnp.arange(width)
    table = jnp.sin(1.3 * jnp.arange(LOGGED_V)[:, None]
                    + 0.37 * jnp.arange(LOGGED_V)).astype(jnp.bfloat16)
    return table[(tokens + slots) % LOGGED_V], {
        "pos": state["pos"] + width,
        "stored": state["stored"].at[jnp.arange(rows)[:, None],
                                     slots].set(tokens),
        "log": state["log"].at[state["applied"]].set(
            jnp.stack([state["pos"][0], jnp.int32(width)])),
        "applied": state["applied"] + 1}


def _logged_state():
    return {"pos": jnp.zeros((ROWS,), jnp.int32),
            "stored": jnp.full((ROWS, LOGGED_EXTENT), -1, jnp.int32),
            "log": jnp.full((LOGGED_MOST, 2), -1, jnp.int32),
            "applied": jnp.int32(0)}


def _floor_passes(masked, steps, dynamic):
    """The denoising passes of a block of `masked` masks where no
    confidence clears the threshold."""
    if not dynamic:
        return steps
    fixed = np.cumsum(decode._transfers(BLOCK, steps))
    return int(np.searchsorted(fixed, masked)) + 1


@pytest.mark.parametrize("how", decode.REMASKING)
@pytest.mark.parametrize("length, gen, steps", [
    (8, 12, 4), (8, 12, 2), (10, 9, 4), (8, 4, 4), (3, 9, 4), (2, 2, 2)])
def test_a_commit_rides_on_the_next_blocks_first_pass(how, length, gen,
                                                      steps):
    """Every application of the step a call makes, in order, by where it
    stood and how many positions it took: the prefill, then a block's
    first pass over 2B positions from the block before's first position
    (the prompt's last whole block before the first generated one; a
    plain pass where the prompt has none), its later passes over B from
    its own, B further and not 2B, and one commit of B after the last
    block: `blocks * T + 1` applications at the floor where a commit
    pass a block took `blocks * (T + 1)`."""
    prompt = np.random.RandomState(length).randint(
        0, LOGGED_V - 1, (ROWS, length)).astype("int32")
    toks, _, passes, at, _, state = jax.jit(
        lambda state, prompt: decode.block_diffusion_decode(
            _logged_step, state, prompt, gen, BLOCK, steps, LOGGED_V - 1,
            how, 0.9))(_logged_state(), prompt)
    whole, left = length // BLOCK * BLOCK, length % BLOCK
    blocks = -(-(left + gen) // BLOCK)
    want = [(0, whole)] if whole else []
    for n in range(blocks):
        start = whole + n * BLOCK
        took = _floor_passes(BLOCK - left if n == 0 else BLOCK, steps,
                             how == "low_confidence_dynamic")
        first = (start - BLOCK, 2 * BLOCK) if start else (0, BLOCK)
        want += [first] + [(start, BLOCK)] * (took - 1)
    want.append((whole + (blocks - 1) * BLOCK, BLOCK))
    applied = int(state["applied"])
    assert [tuple(entry) for entry in np.asarray(state["log"])[:applied]] \
        == want
    prefills = 1 if whole else 0
    assert int(passes["applications"]) == applied - prefills \
        == int(passes["denoise"]) + 1
    if how != "low_confidence_dynamic" or not left:
        assert int(passes["applications"]) == blocks * steps + 1
    assert int(passes["commit"]) == blocks
    assert int(passes["folded"]) == blocks - 1
    wide = sum(1 for _, width in want[prefills:] if width == 2 * BLOCK)
    assert wide == (blocks if whole else blocks - 1)
    # what the applications left: every position's final token, the
    # position at the last block's end
    assert (np.asarray(state["pos"]) == whole + blocks * BLOCK).all()
    final = np.concatenate([prompt, np.asarray(toks)], axis=1)
    np.testing.assert_array_equal(
        np.asarray(state["stored"])[:, :length + gen], final)
    assert (np.asarray(at) >= 0).all()


def _equations(jaxpr, under=""):
    """(scopes, primitive, the shapes it reads, the shapes it writes) of
    every equation of a jaxpr and of the jaxprs inside it."""
    for eqn in jaxpr.eqns:
        scopes = "%s/%s" % (under, eqn.source_info.name_stack)
        yield (scopes, eqn.primitive.name,
               [v.aval for v in eqn.invars if hasattr(v.aval, "shape")],
               [v.aval for v in eqn.outvars])
        for inner in jax.core.jaxprs_in_params(eqn.params):
            yield from _equations(inner, scopes)


def test_the_rule_sees_its_own_blocks_logits_alone():
    """The rule reduces the logits flat, in the shape the head's product
    gave them: the traced call holds the step's bfloat16 [rows, T,
    vocab], T = B and 2B, and no float32 array of either shape; under
    `diffusion_unmask` every array with the vocabulary's extent but the
    rule's own input is [rows x T, vocab], a block's first pass's all 2B
    positions a row (no slice of the logits stands in front of the
    rule), and the greedy rule gathers nothing (the CPU's half; what the
    TPU's compiler makes of it, no float32 array at all:
    tests/test_compiled_placement.py)."""
    traced = jax.make_jaxpr(lambda state, prompt: (
        decode.block_diffusion_decode(
            _logged_step, state, prompt, 8, BLOCK, 4, LOGGED_V - 1)))(
                _logged_state(), jnp.zeros((ROWS, 8), jnp.int32))
    text = str(traced)
    for width in (BLOCK, 2 * BLOCK):
        wide = "[%d,%d,%d]" % (ROWS, width, LOGGED_V)
        assert "bf16" + wide in text and "f32" + wide not in text
    rule = [eqn for eqn in _equations(traced.jaxpr)
            if decode.UNMASK_SCOPE in eqn[0]]
    assert "gather" not in {name for _, name, _, _ in rule}
    shapes = {(name, aval.shape, str(aval.dtype))
              for _, name, read, written in rule
              for aval in read + written
              if aval.shape[-1:] == (LOGGED_V,) and len(aval.shape) > 1}
    flat = {(ROWS * BLOCK, LOGGED_V), (ROWS * 2 * BLOCK, LOGGED_V)}
    assert {shape for _, shape, _ in shapes if len(shape) == 2} == flat
    assert {entry for entry in shapes if len(entry[1]) != 2} == {
        ("reshape", (ROWS, width, LOGGED_V), "bfloat16")
        for width in (BLOCK, 2 * BLOCK)}
    reduced = {read[0].shape for _, name, read, _ in rule
               if name.startswith(("reduce", "argm")) and read
               and read[0].shape[-1] == LOGGED_V}
    assert reduced == flat


# -- the rule against a plain one -----------------------------------------------------

RULE_V, RULE_MASK, RULE_ROWS, RULE_K = 203, 131, 6, 2


def _plain_rule(logits, masked, k, remasking, threshold, mask_id):
    """The rule in float64 numpy over the last B positions of a row:
    softmax over the vocabulary without the mask token, the first
    largest, the three strategies."""
    width = masked.shape[1]
    scores = np.asarray(logits, np.float64)[:, -width:].copy()
    scores[..., mask_id] = -np.inf
    x0 = scores.argmax(-1)                      # the first on a tie
    probs = np.exp(scores - scores.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    conf = np.where(masked, np.take_along_axis(
        probs, x0[..., None], -1)[..., 0], -np.inf)
    if remasking == "sequential":
        return x0, conf, masked & (np.cumsum(masked, -1) <= k)
    rank = np.argsort(np.argsort(-conf, axis=-1, kind="stable"), axis=-1,
                      kind="stable")
    fix = masked & (rank < k)
    if remasking == "low_confidence_dynamic":
        high = conf > threshold
        fix = np.where(high.sum(-1, keepdims=True) >= k, high, fix)
    return x0, conf, fix


def _rule_logits(dtype, positions, cleared):
    """[rows, positions, V] in `dtype` and masked [rows, B]: the last B
    positions of a row are its own (a 2B pass's first B hold larger
    logits that count for nothing); at `cleared` the rows' first 0, 1, 2
    ... own positions carry a peak that clears 0.9, so that some rows
    have fewer than k over the threshold and some k or more; a planted
    tie of two largest logits, and a position whose largest is the mask
    token's."""
    rs = np.random.RandomState(positions + cleared)
    logits = rs.randn(RULE_ROWS, positions, RULE_V) * 2.0
    logits[:, :positions - BLOCK] += 30.0 * rs.rand(
        RULE_ROWS, positions - BLOCK, RULE_V)
    own = logits[:, positions - BLOCK:]
    if cleared:
        for row in range(RULE_ROWS):
            for at in range(min(row, BLOCK)):
                own[row, at, rs.randint(RULE_V - 1)] += 16.0
    own[0, 3, [57, 150]] = 11.0                 # a tie: 57 is taken
    own[1, 2, RULE_MASK] = 40.0                 # the mask token's: not taken
    masked = np.ones((RULE_ROWS, BLOCK), bool)
    masked[2] = [True, False, True, True]
    masked[-1] = [True, False, False, True]
    return jnp.asarray(logits, dtype), masked


@pytest.mark.parametrize("cleared", [False, True])
@pytest.mark.parametrize("positions", [BLOCK, 2 * BLOCK])
@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("remasking", decode.REMASKING)
def test_the_rule_against_a_plain_one(remasking, dtype, positions, cleared):
    """`_unmask` on [rows, T, V], T = B and 2B read at the last B, in the
    step's type and in float32, against the float64 rule on the same
    values: the same tokens (every position's), the same positions
    fixed, the confidences to float32's rounding."""
    logits, masked = _rule_logits(dtype, positions, cleared)
    x0, conf, fix = decode._unmask(
        logits, jnp.asarray(masked), jnp.int32(RULE_K), remasking, 0.9, 0.0,
        0, None, RULE_MASK)
    want_x0, want_conf, want_fix = _plain_rule(
        np.asarray(logits.astype(jnp.float32)), masked, RULE_K, remasking,
        0.9, RULE_MASK)
    assert x0.dtype == jnp.int32 and conf.dtype == jnp.float32
    np.testing.assert_array_equal(x0, want_x0)
    assert x0[0, 3] == 57 and x0[1, 2] != RULE_MASK
    np.testing.assert_array_equal(fix, want_fix)
    np.testing.assert_allclose(conf, want_conf, rtol=1e-5)
    assert not (np.asarray(fix) & ~masked).any()
    over = (want_conf > 0.9).sum(-1)
    if remasking == "low_confidence_dynamic" and cleared:
        # rows under k over the threshold and rows at k or over it
        assert (over < RULE_K).any() and (over > RULE_K).any()
        assert (np.asarray(fix).sum(-1) > RULE_K).any()
    if not cleared:
        assert not over.any()


# -- the op under the block-causal mask ------------------------------------------------

def _dense(q, k_cache, v_cache, pos, heads, kv_heads, block):
    """Out of the caches as written: a softmax over the slots to the end
    of each query's block of `block`, every head's own scores."""
    rows, positions, _ = q.shape
    dim = k_cache.shape[-1]
    qh = np.asarray(q, np.float64).reshape(rows, positions, heads, dim)
    group = heads // kv_heads
    out = np.zeros_like(qh)
    for i in range(positions):
        top = pos + (i // block + 1) * block
        for h in range(heads):
            keys = np.asarray(k_cache, np.float64)[:, h // group, :top]
            values = np.asarray(v_cache, np.float64)[:, h // group, :top]
            s = np.einsum("bd,bkd->bk", qh[:, i, h], keys) * dim ** -0.5
            p = np.exp(s - s.max(-1, keepdims=True))
            out[:, i, h] = np.einsum("bk,bkd->bd", p / p.sum(-1,
                                                            keepdims=True),
                                     values)
    return out.reshape(rows, positions, heads * dim)


@pytest.mark.parametrize("path, dim", [("plain", 16), ("kernel", 128)])
@pytest.mark.parametrize("positions, pos", [(4, 0), (4, 124), (128, 0),
                                            (128, 128), (8, 248), (8, 244)])
def test_the_op_under_the_block_causal_mask(path, dim, positions, pos):
    """`cached_attention` with `diffusion_block` 4 against a dense masked
    softmax: a pass (T = B), a prefill block (T = 128) and two blocks
    (a commit and the next block's first pass in one application, from a
    position that is a multiple of 2B and from one of B alone), from an
    empty cache and behind stored slots, the plain path (16-wide heads)
    and the walk of the live slots (128-wide heads, the kernel under the
    interpreter)."""
    heads, kv_heads, rows, extent = 4, 2, 2, 256
    rs = np.random.RandomState(positions + pos)
    q = rs.randn(rows, positions, heads * dim).astype("float32")
    k_new, v_new = (rs.randn(rows, positions, kv_heads * dim)
                    .astype("float32") for _ in range(2))
    k_cache, v_cache = (rs.randn(rows, kv_heads, extent, dim)
                        .astype("float32") for _ in range(2))
    before = telemetry.snapshot()
    out = attention.cached_attention_op(
        None, {"Q": [jnp.asarray(q)], "KNew": [jnp.asarray(k_new)],
               "VNew": [jnp.asarray(v_new)], "KCache": [jnp.asarray(k_cache)],
               "VCache": [jnp.asarray(v_cache)],
               "Position": [jnp.full((rows,), pos, jnp.int32)]},
        {"num_heads": heads, "num_kv_heads": kv_heads, "diffusion_block": 4})
    counted = {key: value for key, value
               in telemetry.snapshot_delta(before).items()
               if not key.startswith("jit_")}
    assert counted == {
        "cached_attention_lowerings_total{block=%d}" % positions: 1,
        "block_causal_attention_lowerings_total{block=%d,"
        "diffusion_block=4,path=%s}" % (positions, path): 1,
        "kv_cache_slots_total{kind=block_causal}": extent,
        [key for key in counted
         if key.startswith("window_attention_lowerings_total")][0]: 1}
    written = [np.asarray(out[name][0]) for name in ("KCacheOut",
                                                      "VCacheOut")]
    for cache, old, new in zip(written, (k_cache, v_cache), (k_new, v_new)):
        want = old.copy()
        want[:, :, pos:pos + positions] = new.reshape(
            rows, positions, kv_heads, dim).transpose(0, 2, 1, 3)
        np.testing.assert_array_equal(cache, want)
    np.testing.assert_allclose(
        np.asarray(out["Out"][0]),
        _dense(q, written[0], written[1], pos, heads, kv_heads, 4),
        rtol=2e-5, atol=2e-5)
    # and it is not the causal mask's: a block's first query sees its last
    causal = attention.cached_attention_op(
        None, {"Q": [jnp.asarray(q)], "KNew": [jnp.asarray(k_new)],
               "VNew": [jnp.asarray(v_new)], "KCache": [jnp.asarray(k_cache)],
               "VCache": [jnp.asarray(v_cache)],
               "Position": [jnp.full((rows,), pos, jnp.int32)]},
        {"num_heads": heads, "num_kv_heads": kv_heads})["Out"][0]
    apart = np.abs(np.asarray(causal) - np.asarray(out["Out"][0]))
    assert apart[:, 0].max() > 1e-3
    assert apart[:, 3::4].max() < 2e-5      # a block's last sees the same


@pytest.mark.parametrize("attrs, ins", [
    ({"diffusion_block": 4, "window": 256}, {}),
    ({"diffusion_block": 3}, {}),
    ({"diffusion_block": 4}, {"KNew": None}),
])
def test_the_op_refuses_a_block_causal_mask_it_does_not_have(attrs, ins):
    rows, extent, dim = 2, 256, 16
    given = {"Q": [jnp.zeros((rows, 4, 2 * dim))],
             "KNew": [jnp.zeros((rows, 4, 2 * dim))],
             "VNew": [jnp.zeros((rows, 4, 2 * dim))],
             "KCache": [jnp.zeros((rows, 2, extent, dim))],
             "VCache": [jnp.zeros((rows, 2, extent, dim))],
             "Position": [jnp.zeros((rows,), jnp.int32)]}
    for name in ins:
        given.pop(name)
        given.pop("VNew")
    with pytest.raises(ValueError, match="diffusion_block"):
        attention.cached_attention_op(None, given,
                                      dict({"num_heads": 2}, **attrs))


def test_the_kernel_says_its_block_in_its_name():
    from paddle_tpu.kernels import gqa_decode

    q = jnp.zeros((2, 2, 2 * 8, 128), jnp.float32)
    cache = jnp.zeros((2, 2, 256, 128), jnp.float32)
    text = str(jax.make_jaxpr(lambda q, k, v: gqa_decode.gqa_decode(
        q, k, v, jnp.int32(0), 1.0, positions=8, diffusion=4))(
            q, cache, cache))
    assert "gqa_decode_k256_t8_b4" in text
    text = str(jax.make_jaxpr(lambda q, k, v: gqa_decode.gqa_decode(
        q, k, v, jnp.int32(0), 1.0, positions=8))(q, cache, cache))
    assert "gqa_decode_k256_t8" in text and "_b4" not in text
    with pytest.raises(ValueError, match="diffusion block"):
        gqa_decode.gqa_decode(q, cache, cache, jnp.int32(0), 1.0,
                              positions=8, diffusion=3)


# -- what the repo had ---------------------------------------------------------------

# the jaxprs of one application of two cached steps the repo had, at
# 128-wide heads over an extent of 128 (where `cached_attention` walks the
# live slots), as PR 72's commit (51dd8fc) traced them
STEP_JAXPRS = {
    ("exaone", 1):
        "62408a4a74a8964d0e08acbabbc086844070bd398a64b5df1e705f13e04a363b",
    ("exaone", 16):
        "52cfe90db0f83834f8fff5e739ac480e323dc8c4fa64d23cf41d699c4c9288cd",
    ("olmohybrid", 1):
        "77e2a2626bb887197dcf0b143ea607c35e24206b35cc006cade0178c7a2f143a",
    ("olmohybrid", 16):
        "89fa2f8ac13ece85190db1430eb8f0260e9cdadcded8e232cf36fbd70683a6c3",
}


def _step_jaxpr(which, positions):
    from paddle_tpu.models.linear_moe_program import \
        build_linear_moe_cached_step_program
    from paddle_tpu.models.window_moe_program import \
        build_window_moe_cached_step_program
    fluid.framework.reset_unique_name()
    if which == "exaone":
        main, _, logits, pairs, _ = build_window_moe_cached_step_program(
            2, 128, 97, window=128, n_head=4, n_kv_head=2, d_head=128)
    else:
        main, _, logits, pairs, _ = build_linear_moe_cached_step_program(
            2, 128, 97, layer_types=("linear_attention", "full_attention"),
            n_head=2, n_kv_head=2, d_head=128, key_heads=2, value_heads=2,
            key_dim=8, value_dim=16, conv_width=4, d_model=64, n_dense=2,
            d_ff=96, rope_theta=None, norm_order="post", qk_norm="whole",
            attn_gate=False, beta_scale=2.0, chunk=4)
    feeds = ["tok"] + [f for f, _ in pairs]
    fp = FunctionalProgram(main.clone(for_test=True), feeds,
                           [logits.name] + [o for _, o in pairs])
    block = main.global_block()
    params = {n: jax.ShapeDtypeStruct(tuple(block.var(n).shape), jnp.float32)
              for n in fp.state_in_names}
    fed = {"tok": jax.ShapeDtypeStruct((2, positions), jnp.int32)}
    for f, _ in pairs:
        fed[f] = jax.ShapeDtypeStruct(
            tuple(block.var(f).shape),
            jnp.int64 if f == "pos" else jnp.float32)
    text = str(jax.make_jaxpr(lambda p, s: fp(p, s)[0])(params, fed))
    # a function's address is no part of what it computes
    return re.sub(r" at 0x[0-9a-f]+", "", text)


@pytest.mark.parametrize("which, positions", sorted(STEP_JAXPRS))
def test_the_steps_the_repo_had_trace_to_what_they_traced_to(which,
                                                             positions):
    """K-EXAONE's window/full step and Olmo-Hybrid's linear/full step, a
    decode step and a block of 16 positions: equation for equation the
    jaxpr the parent commit traced, the walk of the live slots in it
    under the names it had (no `diffusion_block`, no `_b`)."""
    text = _step_jaxpr(which, positions)
    assert "gqa_decode_" in text and not re.search(r"gqa_decode_\w*_b\d",
                                                   text)
    assert hashlib.sha256(text.encode()).hexdigest() \
        == STEP_JAXPRS[which, positions]
