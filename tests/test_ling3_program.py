"""The cached step Program of a decoder that mixes Kimi Delta Attention
layers (the delta rule under a gate a key channel) with a
latent-attention layer, leading dense layers and sigmoid-routed experts
chosen inside groups (models/linear_moe_program.py under
Ling-3.0-flash's options) against the plain float32 reference
(models/reference/ling3_flash.py: the rule position by position, latent
attention unabsorbed): the step from empty states at every position, the
three kinds of state and the parts of the last position, a prompt as
blocks and as a block then steps, prefill then decode through
`fluid.ProgramDecoder` with the carried state read back, every control
of the reference seen in its logits; the sixteen-way shares of an expert
layer adding up to the uncut layer; the builder's Program digest; the
counters.  The op by itself is tests/test_gated_delta_rule.py's.

Tiny sizes on the CPU: 4 layers `K K L K` (layer_group_size 3), the
first dense, hidden 64, 4 heads of 8 on both sides of the KDA state,
latent attention over a latent of 16 + 4 rotated with 8 + 4 query values
a head, 8 experts scored in 4 groups of which the best 2 are kept, 4
held, 2 a token, vocabulary 97, seeded random weights (norm scales and
the router's bias moved off their initial values, so that one left out
shows).
"""

import hashlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu.fluid as fluid
from paddle_tpu.models.linear_moe_program import (
    LATENT, LINEAR, build_linear_moe_cached_step_program,
    linear_moe_param_names)
from paddle_tpu.models.reference import ling3_flash as reference
from paddle_tpu.obs import telemetry
# the step driven a block an application, and a Program's listing
from test_linear_moe_program import _drive, _listing

B, T, V = 3, 20, 97
H, DH, CONV = 4, 8, 4
RANK, NOPE, ROPE, DVAL = 16, 8, 4, 8
D, FF, FE, E, K, HELD = 64, 96, 32, 8, 2, (2, 4)
LAYERS = (LINEAR, LINEAR, LATENT, LINEAR)
DENSE = 1
SIZES = dict(layer_types=LAYERS, gate="channel", gate_floor=-5.0, n_head=H,
             key_heads=H, value_heads=H, key_dim=DH, value_dim=DH,
             conv_width=CONV, kv_rank=RANK, d_nope=NOPE, d_rope=ROPE,
             d_v=DVAL, d_model=D, n_dense=DENSE, d_ff=FF, d_expert=FE,
             n_experts=E, held=HELD, top_k=K, scoring="sigmoid",
             shared_gate=False, routed_scale=2.5, router_bias=True,
             n_group=4, topk_group=2, rope_theta=6e6, chunk=32, state_rows=2)
CFG = {"layer_group_size": 3, "rms_norm_eps": 1e-6, "head_dim": DH,
       "num_attention_heads": H, "kv_lora_rank": RANK, "rope_theta": 6e6,
       "kda_lower_bound": -5, "num_experts_per_tok": K,
       "norm_topk_prob": True, "routed_scaling_factor": 2.5, "n_group": 4,
       "topk_group": 2, "first_expert": HELD[0], "scored_experts": E,
       "num_hidden_layers": len(LAYERS)}
NAMES = linear_moe_param_names(LAYERS, DENSE, "channel", shared_gate=False,
                               router_bias=True)
CHANNELS = 3 * H * DH


def _rule_lowering(form, path, chunk, heads, gate="channel", dims=(DH, DH)):
    return ("gated_delta_rule_lowerings_total{chunk=%d,form=%s,gate=%s,"
            "heads=%d,key_dim=%d,path=%s,state_dtype=float32,"
            "value_dim=%d}"
            % (chunk, form, gate, heads, dims[0], path, dims[1]))


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
        elif name.endswith(".dt_bias"):     # gates spread over the range
            scope.set(name, jnp.asarray(
                rs.uniform(-7, 1, value.shape).astype("float32")))
        elif name.endswith(".a_log"):
            scope.set(name, jnp.asarray(np.log(
                rs.uniform(0.5, 1.5, value.shape)).astype("float32")))
        elif value.ndim == 1:       # the norms' scales, the router's bias
            scope.set(name, jnp.asarray(
                value + 0.1 * rs.randn(*value.shape).astype("float32")))
    return scope


def _empty(max_len=T):
    state = {"pos": jnp.zeros((B,), jnp.int32)}
    for i, kind in enumerate(LAYERS):
        if kind == LINEAR:
            state["conv_tail_%d" % i] = jnp.zeros((B, CONV - 1, CHANNELS))
            state["delta_state_%d" % i] = jnp.zeros((B, H, DH, DH))
        else:
            state["latent_cache_%d" % i] = jnp.zeros(
                (B, max_len, RANK + ROPE))
    return state


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
            else jnp.zeros((2, H, DH, DH)) if "delta_state" in feed \
            else jnp.zeros((B, 1, D))
    return decoder, state


@pytest.fixture(scope="module")
def built():
    program = build_linear_moe_cached_step_program(B, T, V, **SIZES)
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
            "params": params, "want": want, "traced": traced}


def test_the_step_takes_a_block_and_names_its_parameters(built):
    assert built["decoder"]._takes_block
    main = built["program"][0]
    assert {p.name for p in main.global_block().all_parameters()} \
        == set(jax.tree_util.tree_leaves(NAMES))
    assert "ffn_in" in NAMES["blocks"][0] \
        and "router_bias" in NAMES["blocks"][1] \
        and "shared_gate" not in NAMES["blocks"][1] \
        and "w_dkv" in NAMES["blocks"][2]


def test_the_gates_spread_between_the_bound_and_zero(built):
    """The seeded gates are worth testing on: g lies in [-5, 0) and a
    head's channels differ by orders."""
    block = {k: jnp.asarray(v) for k, v in
             built["params"]["blocks"][0].items()}
    x = jnp.asarray(built["params"]["embed"])[built["tokens"]]
    h = reference.rms_norm(x, block["input_norm"], 1e-6)
    f = (h @ block["w_qkvf"])[..., 3 * H * DH:]
    g = np.asarray(reference.kda_gate(CFG, block, f))
    assert g.min() >= -5.0 and g.max() < 0.0
    assert g.min() < -1.0 and g.max() > -0.01


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
    an expert layer's choice, and the carried rows of a KDA layer's
    state."""
    state, want = built["state"], built["want"]
    linear = 0
    for i, kind in enumerate(LAYERS):
        for key, name in (("hidden", "hidden"), ("mixer", "attn_out")):
            np.testing.assert_allclose(
                np.asarray(state["probe.%s_%d" % (name, i)])[:, 0],
                np.asarray(want[key][i])[:, -1], atol=3e-5)
        if i >= DENSE:
            np.testing.assert_array_equal(
                np.sort(np.asarray(state["probe.top_idx_%d" % (i - DENSE)]),
                        axis=-1),
                np.sort(np.asarray(want["indices"][i]).reshape(B, T, K)
                        [:, -1], axis=-1))
        if kind == LINEAR:
            np.testing.assert_allclose(
                np.asarray(state["probe.delta_state_%d" % linear]),
                np.asarray(want["states"][i])[:2], atol=2e-5)
            linear += 1


@pytest.mark.parametrize("cuts", [[0], [0, 13], [0, 6, 7, 8],
                                  [0, 5] + list(range(6, T))])
def test_blocks_then_steps_are_the_steps(built, cuts):
    """A prompt as one block, as blocks, and as a block then steps:
    through the tail, the state and the cache of latents alike."""
    got, state = _drive(built["decoder"], built["tokens"], built["empty"],
                        cuts)
    want = built["got"][:, -1]
    np.testing.assert_allclose(got[:, -1], want,
                               atol=1e-4 * np.abs(want).max())
    for feed in ("delta_state_0", "conv_tail_1", "latent_cache_2",
                 "delta_state_3"):
        np.testing.assert_allclose(np.asarray(state[feed]),
                                   np.asarray(built["state"][feed]),
                                   atol=3e-5)


def test_prefill_then_decode_through_the_decoder_is_the_reference(built):
    """`ProgramDecoder.greedy` over a prompt (a block) and the steps
    after it,
    the three kinds of state carried: the logits of the served path are
    the reference's full forward's (every served token its first), and
    the carried state comes back through `return_state`."""
    prompt, new = built["tokens"][:, :11], 8
    tokens, lengths, last = built["decoder"].greedy(
        bos=0, eos=V, max_len=new, init_state=built["empty"], prompt=prompt,
        return_state=("delta_state_0", "probe.delta_state_0",
                      "latent_cache_2"))
    assert tokens.shape == (B, new) and (lengths == new).all()
    fed = np.concatenate([prompt, tokens], axis=1)[:, :-1]
    want = reference.forward(CFG, built["params"], jnp.asarray(fed))
    logits = np.asarray(want["logits"])[:, 10:]
    np.testing.assert_array_equal(tokens, logits.argmax(-1))
    np.testing.assert_allclose(last["delta_state_0"],
                               np.asarray(want["states"][0]), atol=2e-5)
    np.testing.assert_array_equal(last["probe.delta_state_0"],
                                  last["delta_state_0"][:2])
    assert np.abs(last["latent_cache_2"][:, :18]).min(axis=-1).max() > 0 \
        and not last["latent_cache_2"][:, 18:].any()


def test_the_prompt_is_prefilled_a_chunk_an_application(built):
    mla = [od for od in built["program"][0].global_block().desc.ops
           if od.type == "mla_cached_attention"]
    rule = [od for od in built["program"][0].global_block().desc.ops
            if od.type == "gated_delta_rule"]
    assert [od.attrs["prefill_block"] for od in mla] == [32]
    assert [(od.attrs["chunk"], od.attrs["sub_chunk"]) for od in rule] \
        == [(32, 16)] * 3


@pytest.mark.parametrize("control,moved", [
    ({"gate": "head"}, True), ({"floor": False}, True),
    ({"state": "bfloat16"}, True), ({"beta": 1}, True),
    ({"read": False}, True), ({"tail_cut": 9}, True),
    ({"out_gate": False}, True), ({"latent_norm": False}, True),
    ({"rotary": NOPE + ROPE}, True), ({"drop": True}, True), ({}, False)])
def test_a_control_moves_the_references_logits(built, control, moved):
    """Every way the reference can be made wrong (what the cell's
    controls switch) is seen in its logits at these sizes."""
    got = reference.forward(dict(CFG, control=control), built["params"],
                            jnp.asarray(built["tokens"]))["logits"]
    off = float(jnp.abs(got - built["want"]["logits"]).max())
    assert (off > 1e-3) == moved


@pytest.mark.parametrize("count", [1, 2, 4, 8])
def test_the_shares_add_up_to_the_uncut_layer(built, count):
    """The guide's share test on the reference the cell is held to: the
    held parts of all E / count shares (at count 2 the four chips of a
    four-way cut; the cell's is sixteen ways of 512), the shared expert
    counted once, are the uncut layer's feed-forward: sigmoid scores of
    all E scored, the choice by score + bias inside the kept groups, the
    chosen scores normalised over all K chosen and scaled, whichever
    share holds them."""
    rs = np.random.RandomState(7)
    block = {k: jnp.asarray(v) for k, v in
             built["params"]["blocks"][1].items()}
    whole = dict(block, **{
        w: jnp.asarray(0.1 * rs.randn(E, *np.asarray(block[w]).shape[1:]),
                       jnp.float32) for w in ("w_gate", "w_up", "w_down")})
    u = jnp.asarray(rs.randn(10, D), jnp.float32)
    with jax.default_matmul_precision("highest"):
        want, _ = reference.feed_forward(CFG, whole, u, 0)
        total = reference.gated(u, whole["shared_in"], whole["shared_out"])
        for first in range(0, E, count):
            share = dict(whole, **{w: whole[w][first:first + count]
                                   for w in ("w_gate", "w_up", "w_down")})
            total = total + reference.feed_forward(CFG, share, u, first,
                                                   shared=False)[0]
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               atol=1e-5)


def test_the_choice_stays_inside_the_kept_groups(built):
    block = {k: jnp.asarray(v) for k, v in
             built["params"]["blocks"][1].items()}
    u = jnp.asarray(np.random.RandomState(2).randn(40, D), jnp.float32)
    weights, indices = reference.route(CFG, block, u)
    groups = np.asarray(indices) // (E // 4)
    assert all(len(set(row)) <= 2 for row in groups)
    np.testing.assert_allclose(np.asarray(weights).sum(-1), 2.5, atol=1e-5)


def test_the_builders_program_digest():
    """The hybrid step's Program, op for op: a change to the builder
    under these options shows here (the qwen3next options' digest is
    tests/test_linear_moe_program.py's)."""
    main = build_linear_moe_cached_step_program(2, 16, 97, **SIZES)[0]
    assert hashlib.sha256(_listing(main).encode()).hexdigest()[:16] \
        == DIGEST


DIGEST = "84da98dfe41e02e5"


def test_counters_say_what_was_lowered(built):
    """One count an op instance a traced step holds; the step of one
    position was traced once here (`_drive` jits it)."""
    traced = built["traced"]
    assert traced[_rule_lowering("step", "plain", 0, H)] == 3
    assert traced["recurrent_state_bytes_total{kind=delta}"] \
        == 3 * H * DH * DH * 4
    assert traced["recurrent_state_bytes_total{kind=conv_tail}"] \
        == 3 * (CONV - 1) * CHANNELS * 4
    assert traced["causal_conv1d_tail_lowerings_total{width=4}"] == 3
    assert traced["moe_share_lowerings_total{held=%d,scored=%d,top_k=%d}"
                  % (HELD[1], E, K)] == 3
    assert sum(v for k, v in traced.items()
               if k.startswith("mla_cached_attention_lowerings_total")) == 1


def test_a_block_counts_the_block_form(built):
    before = telemetry.snapshot()
    _drive(built["decoder"], built["tokens"][:, :11], built["empty"], [0])
    traced = telemetry.snapshot_delta(before)
    assert traced[_rule_lowering("block", "plain", 32, H)] == 3


@pytest.mark.parametrize("positions, rule", [(1, "kda_state"),
                                             (11, "kda_chunks")])
def test_the_lowered_step_carries_the_scopes_a_trace_is_read_by(
        built, positions, rule):
    """The op's scope for the form it took and the builder's names for
    the gates' elementwise ops are in the lowered step's op names: what
    the benchmark's readers find a trace's operations under."""
    tokens = jnp.asarray(built["tokens"][:, 0] if positions == 1
                         else built["tokens"][:, :positions])
    decoder = built["decoder"]
    lowered = jax.jit(decoder._step_fn(decoder._params)).lower(
        built["empty"], tokens).as_text(debug_info=True)
    for name in (rule, "kda_gates", "kda_out_norm", "latent_gate"):
        assert name in lowered, name
    assert "gdn_state" not in lowered and "gdn_chunks" not in lowered
