"""The cached step Program of Olmo-Hybrid's block
(models/linear_moe_program.py under Olmo-Hybrid's options: a sub-layer's
output normed, Gated DeltaNet with beta in (0, 2) over a state whose
value_dim is not its key_dim, two heads side by side in the state the
decoder carries, ungated attention a key/value head a query head with q
and k normed over their whole projection and no rotation, a dense
feed-forward on every layer and no router) against the plain float32
reference (models/reference/olmo_hybrid.py: the rule position by
position, whole rows of scores): the step from empty states at every
position, every linear layer's state and tail and the parts of the last
position, a prompt as blocks of 5 and as a block then steps, prefill
then decode through `fluid.ProgramDecoder` with the carried state read
back, every control of the reference seen in its logits and the two
`assumed` ones held against the sound Program; what the Program holds
(no expert op, no router) and its digest; the counters.  The op and the
step kernel by themselves are tests/test_gated_delta_rule.py's.

Tiny sizes on the CPU: 4 layers `L L F L`, hidden 64, 6 heads of 8 x 24
on the linear layers (pairs side by side: a state of [rows, 3, 8, 48]),
6 attention heads of 16, a feed-forward of 96, vocabulary 97, seeded
random weights (norm scales moved off their initial values, so that one
left out shows).
"""

import hashlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu.fluid as fluid
from paddle_tpu.kernels import gdn_step
from paddle_tpu.models.linear_moe_program import (
    FULL, LINEAR, build_linear_moe_cached_step_program,
    linear_moe_param_names)
from paddle_tpu.models.reference import olmo_hybrid as reference
from paddle_tpu.obs import telemetry
# the step driven a block an application, and a Program's listing
from test_linear_moe_program import _drive, _listing

B, T, V = 3, 20, 97
H, DH = 6, 16
HL, DK, DV, CONV, PACK = 6, 8, 24, 4, 2
D, FF = 64, 96
LAYERS = (LINEAR, LINEAR, FULL, LINEAR)
SIZES = dict(layer_types=LAYERS, n_head=H, n_kv_head=H, d_head=DH,
             key_heads=HL, value_heads=HL, key_dim=DK, value_dim=DV,
             conv_width=CONV, d_model=D, n_dense=len(LAYERS), d_ff=FF,
             rope_theta=None, norm_order="post", qk_norm="whole",
             attn_gate=False, beta_scale=2.0, state_pack=PACK, chunk=4,
             state_rows=2)
CFG = {"layer_types": list(LAYERS), "rms_norm_eps": 1e-6,
       "linear_num_key_heads": HL, "linear_num_value_heads": HL,
       "linear_key_head_dim": DK, "linear_value_head_dim": DV,
       "linear_conv_kernel_dim": CONV, "linear_allow_neg_eigval": True,
       "num_attention_heads": H, "num_key_value_heads": H, "head_dim": DH,
       "rope_parameters": {"rope_theta": None},
       "num_hidden_layers": len(LAYERS)}
NAMES = linear_moe_param_names(LAYERS, len(LAYERS), norm_order="post")
CHANNELS = 2 * HL * DK + HL * DV
# float32 on both sides, the program's sums in another order than the
# reference's: 1e-4 of the largest logit, as the sibling programs'
LOGITS = 1e-4
# a state holds T rank-one updates of unnormed values under beta up to 2
# (entries past 1, where qwen3next's test states stay under 0.5 and are
# held to 2e-5 absolute), and a layer past the full one reads an input
# that already differs in its last float32 bits: 1e-4 of the state's
# largest entry, the logits' measure (the stream, the mixers' outputs,
# the tails and the caches alike: the stream sums normed outputs of O(1)
# a layer, where a pre-norm block's sub-layers add 0.02-weights' worth)


def _state_close(got, want):
    want = np.asarray(want)
    np.testing.assert_allclose(np.asarray(got), want,
                               atol=1e-4 * np.abs(want).max())


def _rule_lowering(form, path, chunk):
    return ("gated_delta_rule_lowerings_total{chunk=%d,form=%s,gate=head,"
            "heads=%d,key_dim=%d,path=%s,state_dtype=float32,"
            "value_dim=%d}" % (chunk, form, HL, DK, path, DV))


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


def _empty(max_len=T):
    state = {"pos": jnp.zeros((B,), jnp.int32)}
    for i, kind in enumerate(LAYERS):
        if kind == LINEAR:
            state["conv_tail_%d" % i] = jnp.zeros((B, CONV - 1, CHANNELS))
            state["delta_state_%d" % i] = jnp.zeros(
                (B, HL // PACK, DK, PACK * DV))
        else:
            for which in "kv":
                state["%s_cache_%d" % (which, i)] = jnp.zeros(
                    (B, H, max_len, DH))
    return state


def _probed(program, scope, max_len=T):
    """(a decoder that carries every `parts` entry out as a state pair
    the step only writes, the state a call starts from)."""
    probes = {"probe.%s_%d" % (key, i): var.name
              for key, found in program[4].items()
              for i, var in enumerate(found)}
    decoder = fluid.ProgramDecoder(
        program[0].clone(for_test=True), token_name="tok",
        logits_name=program[2].name,
        state_pairs=program[3] + list(probes.items()), scope=scope,
        max_positions=max_len)
    state = _empty(max_len)
    for feed in probes:
        state[feed] = jnp.zeros((2, HL, DK, DV)) if "delta_state" in feed \
            else jnp.zeros((B, 1, D))
    return decoder, state


def _logical(state, feed):
    """A linear layer's state as the recurrence has it."""
    return np.asarray(gdn_step.unpack_state(state[feed], PACK))


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
                               atol=LOGITS * np.abs(want).max())


@pytest.mark.parametrize("layer", [i for i, kind in enumerate(LAYERS)
                                   if kind == LINEAR])
def test_a_linear_layers_state_and_tail_are_the_references(built, layer):
    """After the last position: the state the step hands on, two heads
    side by side as the decoder carries it and taken apart here, and the
    tail, the last three positions of the convolution's input."""
    state, want = built["state"], built["want"]
    assert state["delta_state_%d" % layer].shape \
        == (B, HL // PACK, DK, PACK * DV)
    assert state["delta_state_%d" % layer].dtype == jnp.float32
    _state_close(_logical(state, "delta_state_%d" % layer),
                 want["states"][layer])
    block = built["params"]["blocks"][layer]
    entered = want["hidden"][layer - 1] if layer \
        else jnp.asarray(built["params"]["embed"])[built["tokens"]]
    tail = (entered[:, -(CONV - 1):] @ block["w_qkvz"])[..., :CHANNELS]
    _state_close(state["conv_tail_%d" % layer], tail)


def test_the_parts_are_the_references(built):
    """Of the last position: each layer's output, each mixer's output
    (before its norm), and the carried rows of a linear layer's state,
    the heads apart."""
    state, want = built["state"], built["want"]
    linear = 0
    for i, kind in enumerate(LAYERS):
        for key, name in (("hidden", "hidden"), ("mixer", "attn_out")):
            _state_close(np.asarray(state["probe.%s_%d" % (name, i)])[:, 0],
                         np.asarray(want[key][i])[:, -1])
        if kind == LINEAR:
            _state_close(state["probe.delta_state_%d" % linear],
                         want["states"][i][:2])
            linear += 1


@pytest.mark.parametrize("cuts", [[0], [0, 13], [0, 5, 10, 15],
                                  [0, 5] + list(range(6, T)),
                                  [0, 6, 7, 8]])
def test_blocks_then_steps_are_the_steps(built, cuts):
    """A prompt as one block (no multiple of the chunk of 4), as blocks
    of 5, and as a block then steps: through the tail, the state and the
    cache alike."""
    got, state = _drive(built["decoder"], built["tokens"], built["empty"],
                        cuts)
    want = built["got"][:, -1]
    np.testing.assert_allclose(got[:, -1], want,
                               atol=LOGITS * np.abs(want).max())
    for feed in ("delta_state_0", "conv_tail_1", "k_cache_2", "v_cache_2",
                 "delta_state_3", "conv_tail_3"):
        _state_close(state[feed], built["state"][feed])


def test_prefill_then_decode_through_the_decoder_is_the_reference(built):
    """`ProgramDecoder.greedy` over a prompt (a block) and the steps
    after it: every served token is the reference's first at its
    position, and the carried state comes back through `return_state`,
    whole (side by side) and its carried rows (apart)."""
    prompt, new = built["tokens"][:, :9], 8
    tokens, lengths, last = built["decoder"].greedy(
        bos=0, eos=V, max_len=new, init_state=built["empty"], prompt=prompt,
        return_state=("delta_state_0", "probe.delta_state_0"))
    assert tokens.shape == (B, new) and (lengths == new).all()
    fed = np.concatenate([prompt, tokens], axis=1)[:, :-1]
    want = reference.forward(CFG, built["params"], jnp.asarray(fed))
    logits = np.asarray(want["logits"])[:, 8:]
    np.testing.assert_array_equal(tokens, logits.argmax(-1))
    _state_close(_logical(last, "delta_state_0"), want["states"][0])
    np.testing.assert_array_equal(last["probe.delta_state_0"],
                                  _logical(last, "delta_state_0")[:2])


def test_a_state_with_its_heads_apart_is_refused(built):
    state = dict(built["empty"], delta_state_0=jnp.zeros((B, HL, DK, DV)))
    with pytest.raises(ValueError, match="delta_state_0"):
        built["decoder"].greedy(bos=0, eos=V, max_len=2, init_state=state)


CONTROLS = [{"state": "zero"}, {"state": "bfloat16"}, {"decay": False},
            {"beta_scale": 1}, {"tail_cut": 9}, {"norm_order": "pre"},
            {"rotary": 5e5}, {"qk_norm": "head"}, {"qk_norm": "none"},
            {"z_gate": "sigmoid"}]


@pytest.mark.parametrize("control", CONTROLS + [{}], ids=str)
def test_a_control_moves_the_references_logits(built, control):
    """Every way the reference can be made wrong (what the cell's
    controls switch) is seen in its logits at these sizes: further from
    the sound Program's than ten times the tolerance the sound reference
    is held to (the `assumed` two, the pre-norm reading and rotation at
    the family's theta, among them); the sound reference is within
    it."""
    got = np.asarray(reference.forward(
        dict(CFG, control=control), built["params"],
        jnp.asarray(built["tokens"]))["logits"])
    off = np.abs(got - built["got"]).max()
    limit = LOGITS * np.abs(np.asarray(built["want"]["logits"])).max()
    assert (off > 10 * limit) if control else (off <= limit)


def test_the_program_holds_no_expert_and_no_router(built):
    """A dense feed-forward on every layer: no `moe_*` op, no expert or
    router parameter, no expert state pair; the feed-forward's ops
    between its products and the whole-projection norms are named."""
    main = built["program"][0]
    ops = main.global_block().desc.ops
    assert not [od.type for od in ops if od.type.startswith("moe")]
    assert not [p.name for p in main.global_block().all_parameters()
                if p.name.rsplit(".", 1)[-1] in (
                    "router", "w_gate", "w_up", "w_down", "shared_in")]
    assert sorted(feed for feed, _ in built["program"][3]) == sorted(
        [f for f in _empty() if f != "pos"] + ["pos"])
    assert not any(built["program"][4][key] for key in (
        "top_w", "top_idx", "counts", "moe_in", "moe_out"))
    outs = [od.output_names()[0] for od in ops]
    assert sum(n.startswith("dense_ffn") for n in outs) == 3 * len(LAYERS)
    assert sum(n.startswith("mha_attn") for n in outs) \
        == 2 * LAYERS.count(FULL)
    assert not [od for od in ops if od.type == "rope"]
    assert {tuple(main.global_block().var("block_2.%s" % w).shape)
            for w in ("q_norm", "k_norm")} == {(H * DH,)}


def test_the_builders_program_digest():
    """Olmo-Hybrid's options' Program, op for op (qwen3next's and
    ling3's digests are tests/test_linear_moe_program.py's and
    tests/test_ling3_program.py's, unchanged by these options)."""
    main = build_linear_moe_cached_step_program(2, 16, 97, **SIZES)[0]
    assert hashlib.sha256(_listing(main).encode()).hexdigest()[:16] \
        == DIGEST


DIGEST = "797033fcefdf5a6f"


def test_the_benchmarks_copy_of_the_reference_is_this_one():
    """benchmark/reference/olmo_hybrid.py is models/reference/
    olmo_hybrid.py to the letter (the benchmark brings its own copy)."""
    import os

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(reference.__file__) as own, open(os.path.join(
            root, "benchmark", "reference", "olmo_hybrid.py")) as copy:
        assert own.read() == copy.read()


def test_the_state_pack_is_chosen_from_the_value_dim():
    """192 values a head fill no lane block: two heads side by side;
    128 and these tiny sizes are left a head a unit."""
    assert gdn_step.state_pack(30, 192) == 2
    assert gdn_step.state_pack(32, 128) == 1
    assert gdn_step.state_pack(HL, DV) == 1
    assert gdn_step.state_pack(31, 192) == 1
    program = build_linear_moe_cached_step_program(
        2, 16, 97, **dict(SIZES, state_pack=None, key_dim=8, value_dim=192,
                          key_heads=2, value_heads=2))
    assert tuple(program[0].global_block().var("delta_state_0").shape) \
        == (2, 1, 8, 384)


def test_at_the_published_head_every_linear_layer_asks_for_the_kernel():
    """Two periods at Olmo-Hybrid's own head shape (30 heads of 96 x
    192, pairs side by side; the other widths small): a traced step
    holds six `gated_delta_rule` instances and each asks for the step
    kernel (on the CPU its plain stand-in runs), by the shape a head's
    state has; none is plain."""
    layers = 2 * (LINEAR, LINEAR, LINEAR, FULL)
    program = build_linear_moe_cached_step_program(
        4, 8, V, **dict(SIZES, layer_types=layers, n_dense=len(layers),
                        key_heads=30, value_heads=30, key_dim=96,
                        value_dim=192, state_pack=None, state_rows=0))
    assert tuple(program[0].global_block().var("delta_state_0").shape) \
        == (4, 15, 96, 384)
    scope = fluid.Scope()
    program[1].random_seed = 5
    fluid.Executor(fluid.CPUPlace()).run(program[1], scope=scope)
    decoder = fluid.ProgramDecoder(
        program[0].clone(for_test=True), token_name="tok",
        logits_name=program[2].name, state_pairs=program[3], scope=scope,
        max_positions=8)
    state = {"pos": jnp.zeros((4,), jnp.int32)}
    for i, kind in enumerate(layers):
        if kind == LINEAR:
            state["conv_tail_%d" % i] = jnp.zeros(
                (4, CONV - 1, 2 * 30 * 96 + 30 * 192))
            state["delta_state_%d" % i] = jnp.zeros((4, 15, 96, 384))
        else:
            for which in "kv":
                state["%s_cache_%d" % (which, i)] = jnp.zeros((4, H, 8, DH))
    before = telemetry.snapshot()
    logits, _ = jax.jit(decoder._step_fn(decoder._params))(
        state, jnp.zeros((4,), jnp.int32))
    traced = telemetry.snapshot_delta(before)
    assert np.isfinite(np.asarray(logits)).all()
    wide = ("gated_delta_rule_lowerings_total{chunk=0,form=step,gate=head,"
            "heads=30,key_dim=96,path=%s,state_dtype=float32,"
            "value_dim=192}")
    assert traced[wide % "kernel"] == 6
    assert wide % "plain" not in traced


def test_at_the_published_head_the_full_layers_share_a_grid_step():
    """Olmo-Hybrid's full layers at their own head shape (30 ungrouped
    heads of 128 over a 512-slot extent; the other widths small): one
    head's block of slots is no grid step's worth of bytes, so a traced
    step's `cached_attention` asks the walk of the live slots for a grid
    step that several key/value heads share (kernels/gqa_decode.py
    `choose_step`), and the counter says so."""
    program = build_linear_moe_cached_step_program(
        4, 512, V, **dict(SIZES, n_head=30, n_kv_head=30, d_head=128))
    scope = fluid.Scope()
    fluid.Executor(fluid.CPUPlace()).run(program[1], scope=scope)
    decoder = fluid.ProgramDecoder(
        program[0].clone(for_test=True), token_name="tok",
        logits_name=program[2].name, state_pairs=program[3], scope=scope,
        max_positions=512)
    block = program[0].global_block()
    state = {feed: jax.ShapeDtypeStruct(
        tuple(4 if n == -1 else n for n in block.var(feed).shape),
        jnp.int32 if feed == "pos" else jnp.float32)
        for feed, _ in program[3]}
    assert state["k_cache_2"].shape == (4, 30, 512, 128)
    before = telemetry.snapshot()
    jax.eval_shape(decoder._step_fn(decoder._params), state,
                   jax.ShapeDtypeStruct((4,), jnp.int32))
    traced = {key: n for key, n in telemetry.snapshot_delta(before).items()
              if key.startswith("window_attention_lowerings_total")}
    assert traced == {
        "window_attention_lowerings_total{block=1,block_k=512,kind=full,"
        "kv_heads=30,path=kernel,step_heads=6,step_rows=1,window=0}": 1}


def test_the_build_lowers_nothing(built):
    assert not [k for k in built["at_build"] if "_lowerings_total" in k
                or k.startswith("recurrent_state_bytes_total")]


def test_counters_say_what_was_lowered(built):
    """One count an op instance a traced step holds, a head's state by
    its shape; the step of one position was traced once here."""
    traced = built["traced"]
    assert traced[_rule_lowering("step", "plain", 0)] == 3
    assert traced["recurrent_state_bytes_total{kind=delta}"] \
        == 3 * HL * DK * DV * 4
    assert traced["causal_conv1d_tail_lowerings_total{width=%d}"
                  % CONV] == 3
    # blocks of 11 and 9 positions, which no other test of this file
    # has traced (a shape traced before is not lowered again)
    before = telemetry.snapshot()
    _drive(built["decoder"], built["tokens"], built["empty"], [0, 11])
    block = telemetry.snapshot_delta(before)
    assert block[_rule_lowering("block", "plain", 4)] == 6
