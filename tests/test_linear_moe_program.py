"""The cached step Program of a decoder that mixes Gated DeltaNet and
gated full-attention layers with softmax-routed experts beside a gated
shared expert (models/linear_moe_program.py) against the plain float32
position-by-position reference (models/reference/qwen3_next.py): the
step from empty states at every position, the states and the parts of
the last position, a prompt as blocks and as a block then steps,
prefill then decode through `fluid.ProgramDecoder` with the carried
state read back, every control of the reference seen in its logits; the
shares of an expert layer adding up to the uncut layer and the shared
expert's gate; what must stay as it was (the three other shares'
Programs, a convolution without a tail); the counters.  The ops by
themselves are tests/test_gated_delta_rule.py's.

Tiny sizes on the CPU: 4 layers `LLFL`, hidden 64, linear layers of 2
key / 4 value heads of 8, full layers of 4 query / 2 key-value heads of
16 with 4 rotated, 8 experts scored of which 4 are held, 2 a token,
vocabulary 97, seeded random weights (norm scales moved off their
initial values, so that one left out shows).
"""

import functools
import hashlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu.fluid as fluid
from paddle_tpu.models.latent_moe_program import (
    build_latent_moe_cached_step_program)
from paddle_tpu.models.linear_moe_program import (
    FULL, LINEAR, build_linear_moe_cached_step_program,
    linear_moe_param_names)
from paddle_tpu.models.reference import qwen3_next as reference
from paddle_tpu.models.window_moe_program import (
    build_window_moe_cached_step_program)
from paddle_tpu.obs import telemetry

B, T, V = 3, 20, 97
H, KV, DH, ROT = 4, 2, 16, 4
HK, HV, DK, DV, CONV = 2, 4, 8, 8, 4
D, FE, E, K, HELD = 64, 32, 8, 2, (2, 4)
LAYERS = (LINEAR, LINEAR, FULL, LINEAR)
SIZES = dict(layer_types=LAYERS, n_head=H, n_kv_head=KV, d_head=DH,
             rotary_dim=ROT, key_heads=HK, value_heads=HV, key_dim=DK,
             value_dim=DV, conv_width=CONV, d_model=D, d_expert=FE,
             n_experts=E, held=HELD, top_k=K, chunk=4, state_rows=2)
CFG = {"full_attention_interval": 3, "rms_norm_eps": 1e-6,
       "linear_num_key_heads": HK, "linear_num_value_heads": HV,
       "linear_key_head_dim": DK, "linear_value_head_dim": DV,
       "linear_conv_kernel_dim": CONV, "num_attention_heads": H,
       "num_key_value_heads": KV, "head_dim": DH,
       "partial_rotary_factor": ROT / DH, "rope_theta": 1e7,
       "num_experts_per_tok": K, "norm_topk_prob": True,
       "first_expert": HELD[0], "scored_experts": E,
       "num_hidden_layers": len(LAYERS)}
NAMES = linear_moe_param_names(LAYERS)
CHANNELS = 2 * HK * DK + HV * DV


def _rule_lowering(form, path, chunk, heads, gate="head", dims=(DK, DV)):
    return ("gated_delta_rule_lowerings_total{chunk=%d,form=%s,gate=%s,"
            "heads=%d,key_dim=%d,path=%s,state_dtype=float32,"
            "value_dim=%d}"
            % (chunk, form, gate, heads, dims[0], path, dims[1]))


# -- (d) the step Program against the reference's full forward --------------------

def _start(startup, seed=3):
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    startup.random_seed = seed
    exe.run(startup, scope=scope)
    rs = np.random.RandomState(seed)
    for name in jax.tree_util.tree_leaves(NAMES):
        value = np.asarray(scope.get(name))
        if name.endswith(".conv"):
            scope.set(name, jnp.asarray(
                0.5 * rs.randn(*value.shape).astype("float32")))
        elif value.ndim == 1 and not name.endswith(("a_log", "dt_bias")):
            scope.set(name, jnp.asarray(    # the norms' scales
                value + 0.1 * rs.randn(*value.shape).astype("float32")))
    return scope


def _probed(program, scope, max_len=T):
    """(a decoder that carries every `parts` entry but "counts" out as a
    state pair the step only writes, the state a call starts from)."""
    parts = program[4]
    probes = {"probe.%s_%d" % (key, i): var.name
              for key, found in parts.items() if key != "counts"
              for i, var in enumerate(found)}
    decoder = fluid.ProgramDecoder(
        program[0].clone(for_test=True), token_name="tok",
        logits_name=program[2].name,
        state_pairs=program[3] + list(probes.items()), scope=scope,
        max_positions=max_len)
    state = _empty(max_len)
    for feed in probes:
        state[feed] = jnp.zeros((B, K), jnp.int32) if "top_idx" in feed \
            else jnp.zeros((B, K)) if "top_w" in feed \
            else jnp.zeros((2, HV, DK, DV)) if "delta_state" in feed \
            else jnp.zeros((B, 1, D))
    return decoder, state


def _empty(max_len=T):
    state = {"pos": jnp.zeros((B,), jnp.int32)}
    for i, kind in enumerate(LAYERS):
        if kind == LINEAR:
            state["conv_tail_%d" % i] = jnp.zeros((B, CONV - 1, CHANNELS))
            state["delta_state_%d" % i] = jnp.zeros((B, HV, DK, DV))
        else:
            for which in "kv":
                state["%s_cache_%d" % (which, i)] = jnp.zeros(
                    (B, KV, max_len, DH))
    return state


@functools.lru_cache(maxsize=None)
def _jitted_step(decoder):
    return jax.jit(decoder._step_fn(decoder._params))


def _drive(decoder, tokens, state, cuts=None):
    """[B, applications, V] and the last state: the step applied a block
    of positions an application (`cuts`: where the blocks begin; a
    position each by default)."""
    step = _jitted_step(decoder)
    cuts = list(range(tokens.shape[1])) if cuts is None else cuts
    out = []
    for lo, hi in zip(cuts, cuts[1:] + [tokens.shape[1]]):
        block = tokens[:, lo] if hi == lo + 1 else tokens[:, lo:hi]
        logits, state = step(state, jnp.asarray(block))
        out.append(logits)
    return np.stack([np.asarray(z, np.float32) for z in out], axis=1), state


@pytest.fixture(scope="module")
def built():
    before = telemetry.snapshot()
    program = build_linear_moe_cached_step_program(B, T, V, **SIZES)
    at_build = telemetry.snapshot_delta(before)
    scope = _start(program[1])
    decoder, empty = _probed(program, scope)
    tokens = np.random.RandomState(1).randint(0, V, (B, T)).astype("int32")
    before = telemetry.snapshot()
    got, state = _drive(decoder, tokens, empty)
    traced = telemetry.snapshot_delta(before)
    params = jax.tree_util.tree_map(scope.get, NAMES)
    want = reference.forward(CFG, params, jnp.asarray(tokens))
    return {"program": program, "scope": scope, "decoder": decoder,
            "empty": empty, "tokens": tokens, "got": got, "state": state,
            "params": params, "want": want, "at_build": at_build,
            "traced": traced}


def test_the_step_says_it_takes_a_block(built):
    assert built["decoder"]._takes_block


@pytest.mark.parametrize("position", range(T))
def test_step_logits_agree_with_the_reference_at_every_position(built,
                                                                position):
    want = np.asarray(built["want"]["logits"])[:, position]
    np.testing.assert_allclose(built["got"][:, position], want,
                               atol=1e-4 * np.abs(want).max())


def test_the_states_are_the_references_after_the_last_position(built):
    for i, kind in enumerate(LAYERS):
        if kind == LINEAR:
            np.testing.assert_allclose(
                np.asarray(built["state"]["delta_state_%d" % i]),
                np.asarray(built["want"]["states"][i]), atol=2e-5)
    assert built["state"]["delta_state_0"].dtype == jnp.float32


def test_the_parts_are_the_references(built):
    """Of the last position: each layer's output, each mixer's output,
    the router's choice, and the carried rows of a linear layer's
    state."""
    state, want = built["state"], built["want"]
    linear = 0
    for i, kind in enumerate(LAYERS):
        for key, name in (("hidden", "hidden"), ("mixer", "attn_out")):
            np.testing.assert_allclose(
                np.asarray(state["probe.%s_%d" % (name, i)])[:, 0],
                np.asarray(want[key][i])[:, -1], atol=3e-5)
        np.testing.assert_array_equal(
            np.sort(np.asarray(state["probe.top_idx_%d" % i]), axis=-1),
            np.sort(np.asarray(want["indices"][i]).reshape(B, T, K)[:, -1],
                    axis=-1))
        if kind == LINEAR:
            np.testing.assert_allclose(
                np.asarray(state["probe.delta_state_%d" % linear]),
                np.asarray(want["states"][i])[:2], atol=2e-5)
            linear += 1


@pytest.mark.parametrize("cuts", [[0], [0, 13], [0, 6, 7, 8],
                                  [0, 5] + list(range(6, T))])
def test_blocks_then_steps_are_the_steps(built, cuts):
    """A prompt as one block, as blocks, and as a block then steps:
    through the tail, the state and the cache alike."""
    got, state = _drive(built["decoder"], built["tokens"], built["empty"],
                        cuts)
    want = built["got"][:, -1]
    np.testing.assert_allclose(got[:, -1], want,
                               atol=1e-4 * np.abs(want).max())
    for feed in ("delta_state_0", "conv_tail_1", "k_cache_2",
                 "delta_state_3"):
        np.testing.assert_allclose(np.asarray(state[feed]),
                                   np.asarray(built["state"][feed]),
                                   atol=3e-5)


def test_prefill_then_decode_through_the_decoder_is_the_reference(built):
    """`ProgramDecoder.greedy` over a prompt (a block) and the steps
    after it: every served token is the reference's first at its
    position, and the carried state comes back through
    `return_state`."""
    prompt, new = built["tokens"][:, :9], 8
    tokens, lengths, last = built["decoder"].greedy(
        bos=0, eos=V, max_len=new, init_state=built["empty"], prompt=prompt,
        return_state=("delta_state_0", "probe.delta_state_0"))
    assert tokens.shape == (B, new) and (lengths == new).all()
    fed = np.concatenate([prompt, tokens], axis=1)[:, :-1]
    want = reference.forward(CFG, built["params"], jnp.asarray(fed))
    logits = np.asarray(want["logits"])[:, 8:]
    np.testing.assert_array_equal(tokens, logits.argmax(-1))
    np.testing.assert_allclose(last["delta_state_0"],
                               np.asarray(want["states"][0]), atol=2e-5)
    np.testing.assert_array_equal(last["probe.delta_state_0"],
                                  last["delta_state_0"][:2])


def test_a_state_of_another_shape_than_declared_is_refused(built):
    state = dict(built["empty"],
                 delta_state_0=jnp.zeros((B, HV, DK, 2 * DV)))
    with pytest.raises(ValueError, match="delta_state_0"):
        built["decoder"].greedy(bos=0, eos=V, max_len=2, init_state=state)


@pytest.mark.parametrize("control,moved", [
    ({"state": "zero"}, True), ({"decay": False}, True), ({"beta": 1}, True),
    ({"read": False}, True), ({"tail_cut": 9}, True), ({"rotary": DH}, True),
    ({"attn_gate": False}, True), ({"shared_gate": False}, True),
    ({"drop": True}, True), ({}, False)])
def test_a_control_moves_the_references_logits(built, control, moved):
    """Every way the reference can be made wrong (what the cell's
    controls switch) is seen in its logits at these sizes."""
    got = reference.forward(dict(CFG, control=control), built["params"],
                            jnp.asarray(built["tokens"]))["logits"]
    off = float(jnp.abs(got - built["want"]["logits"]).max())
    assert (off > 1e-3) == moved


# -- (e) the shares of an expert layer add up --------------------------------------

@pytest.mark.parametrize("count", [1, 2, 4, 8])
def test_the_shares_add_up_to_the_uncut_layer(built, count):
    """The guide's share test on the reference the cell is held to: the
    held parts of all E / count shares, the gated shared expert counted
    once, are the uncut layer's feed-forward (a softmax over all E
    scored and the chosen probabilities normalised over all K chosen,
    whichever share holds them)."""
    rs = np.random.RandomState(7)
    block = {k: jnp.asarray(v) for k, v in built["params"]["blocks"][1].items()}
    whole = dict(block, **{
        w: jnp.asarray(0.1 * rs.randn(E, *np.asarray(block[w]).shape[1:]),
                       jnp.float32) for w in ("w_gate", "w_up", "w_down")})
    u = jnp.asarray(rs.randn(10, D), jnp.float32)
    with jax.default_matmul_precision("highest"):
        want, _ = reference.feed_forward(CFG, whole, u, 0)
        total = reference.feed_forward(CFG, dict(whole, **{
            w: whole[w][:0] for w in ("w_gate", "w_up", "w_down")}), u, 0)[0]
        for first in range(0, E, count):
            share = dict(whole, **{w: whole[w][first:first + count]
                                   for w in ("w_gate", "w_up", "w_down")})
            total = total + reference.feed_forward(CFG, share, u, first,
                                                   shared=False)[0]
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               atol=1e-5)


def test_the_shared_gate_is_one_scalar_a_token(built):
    block = {k: jnp.asarray(v) for k, v in built["params"]["blocks"][0].items()}
    u = jnp.asarray(np.random.RandomState(2).randn(5, D), jnp.float32)
    gated, _ = reference.feed_forward(CFG, block, u, HELD[0])
    routed, _ = reference.routed(CFG, block, u, HELD[0])
    shared = reference.gated(u, block["shared_in"], block["shared_out"])
    np.testing.assert_allclose(
        np.asarray(gated - routed),
        np.asarray(shared * jax.nn.sigmoid(u @ block["shared_gate"])),
        atol=1e-6)
    assert block["shared_gate"].shape == (D, 1)


# -- (f) what the PR leaves as it was ----------------------------------------------

def _listing(main):
    return repr([(od.type, sorted((k, tuple(v)) for k, v in od.inputs.items()),
                  sorted((k, tuple(v)) for k, v in od.outputs.items()),
                  sorted((k, repr(v)) for k, v in od.attrs.items()))
                 for od in main.global_block().desc.ops])


@pytest.mark.parametrize("build,options,digest", [
    (build_latent_moe_cached_step_program, {}, "ad44034b7e904781"),
    (build_latent_moe_cached_step_program,
     dict(sandwich_norm=False, indexer=(2, 8, 4), n_group=4, topk_group=2,
          router_bias=True, yarn={
              "factor": 40, "original_positions": 4096, "beta_fast": 32,
              "beta_slow": 1, "mscale": 1}), "820727a57c275686"),
    (build_window_moe_cached_step_program, {}, None),
])
def test_the_other_shares_programs_are_op_for_op_what_they_were(
        build, options, digest):
    """`share_feed_forward` took `scoring` and `shared_gate`: with
    neither given, the three served shares' Programs are what they were
    (the latent builder's digests are tests/test_window_moe_program.py's,
    of PR 62's block-taking step with an `indexer` and of PR 53's
    without; the window builder's is taken from the parent's
    `share_feed_forward`, rebuilt here)."""
    main = build(2, 16, 97, **options)[0]
    got = hashlib.sha256(_listing(main).encode()).hexdigest()[:16]
    if digest is None:
        digest = "28a50da12a521ca8"
    assert got == digest


def test_a_program_without_the_new_inputs_says_nothing_of_them():
    main = fluid.Program()
    with fluid.program_guard(main, fluid.Program()):
        x = fluid.layers.data(name="x", shape=[2, 8, 4], dtype="float32",
                              append_batch_size=False)
        fluid.layers.causal_conv1d(x)
    conv = [od for od in main.global_block().desc.ops
            if od.type == "causal_conv1d"][0]
    assert sorted(conv.inputs) == ["Bias", "Filter", "X"]
    assert sorted(conv.outputs) == ["Out"]


# -- (g) the counters --------------------------------------------------------------

def test_the_build_lowers_nothing(built):
    assert not [k for k in built["at_build"] if "_lowerings_total" in k
                or k.startswith("recurrent_state_bytes_total")]


def test_counters_say_what_was_lowered(built):
    """One count an op instance a traced step holds; the step of one
    position was traced once here (`_drive` jits it)."""
    traced = built["traced"]
    assert traced[_rule_lowering("step", "plain", 0, HV)] == 3
    assert traced["recurrent_state_bytes_total{kind=delta}"] \
        == 3 * HV * DK * DV * 4
    assert traced["recurrent_state_bytes_total{kind=conv_tail}"] \
        == 3 * (CONV - 1) * CHANNELS * 4
    assert traced["causal_conv1d_tail_lowerings_total{width=4}"] == 3
    assert traced["causal_conv1d_lowerings_total{activation=silu,width=4}"] \
        == 3
    assert traced["moe_share_lowerings_total{held=%d,scored=%d,top_k=%d}"
                  % (HELD[1], E, K)] == 4
    assert traced["cached_attention_lowerings_total{block=1}"] == 1


def test_a_block_counts_the_block_form(built):
    before = telemetry.snapshot()
    _drive(built["decoder"], built["tokens"][:, :11], built["empty"], [0])
    traced = telemetry.snapshot_delta(before)
    assert traced[_rule_lowering("block", "plain", 4, HV)] == 3
