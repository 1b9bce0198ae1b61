"""The cached step Program of granite-4.0-h-small's share
(models/hybrid_program.py `build_granite_hybrid_cached_step_program`:
Mamba-2 layers that carry a convolution tail and the scan's state
through `causal_conv1d(Tail=)` and `ssd_scan(State=)`, grouped attention
without positions at the model's own softmax scale over a cache, every
layer's feed-forward the held range of softmax-routed experts beside a
shared expert of another width, the three multipliers, the tied head)
against the plain float32 reference
(models/reference/granite_moe_hybrid.py: the recurrence position by
position, whole rows of scores, every held expert applied densely): the
step from empty states at every position, every mamba layer's state and
tail and the parts of the last position, a prompt as blocks of whole
chunks and as a block then steps, prefill then decode through
`fluid.ProgramDecoder` with the carried state read back, every control
of the reference seen in its logits; that the four shares' routed parts
and the shared expert once add up to the uncut layer; what the Program
holds and its digest; the counters.  The op and its kernels by
themselves are tests/test_ssd_state.py's.

Tiny sizes on the CPU: 4 layers `M M A M`, hidden 64, 4 state-space
heads of 8 over a state of 16, chunk 4, 4 query / 2 key-value heads of
16, 8 experts of 16 of which 3 a token and experts 2..5 held, a shared
expert of 24, vocabulary 97, seeded random weights (norm scales moved
off their initial values, so that one left out shows).
"""

import hashlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu.fluid as fluid
from paddle_tpu.models.hybrid_program import (
    ATTENTION, MAMBA, build_granite_hybrid_cached_step_program,
    granite_moe_hybrid_param_names)
from paddle_tpu.models.reference import granite_moe_hybrid as reference
from paddle_tpu.obs import telemetry
from paddle_tpu.ops import ssm
# the step driven a block an application, and a Program's listing
from test_linear_moe_program import _drive, _listing

B, T, V = 3, 20, 97
H, KV, DH = 4, 2, 16
MH, MP, N, CONV, CHUNK = 4, 8, 16, 4, 4
D, FE, FS, E, K = 64, 16, 24, 8, 3
HELD = (2, 4)
LAYERS = (MAMBA, MAMBA, ATTENTION, MAMBA)
SIZES = dict(layer_types=LAYERS, d_model=D, n_head=H, n_kv_head=KV,
             d_head=DH, mamba_heads=MH, mamba_d_head=MP, d_state=N,
             d_conv=CONV, chunk=CHUNK, d_expert=FE, d_shared=FS,
             n_experts=E, held=HELD, top_k=K, sm_scale=0.0625,
             embedding_multiplier=12.0, residual_multiplier=0.22,
             logits_scaling=16.0, state_rows=2)
CFG = {"layer_types": list(LAYERS), "rms_norm_eps": 1e-5,
       "mamba_n_heads": MH, "mamba_d_head": MP, "mamba_d_state": N,
       "mamba_d_conv": CONV, "num_attention_heads": H,
       "num_key_value_heads": KV, "attention_multiplier": 0.0625,
       "embedding_multiplier": 12, "residual_multiplier": 0.22,
       "logits_scaling": 16, "num_experts_per_tok": K,
       "scored_experts": E, "first_expert": HELD[0],
       "num_hidden_layers": len(LAYERS)}
NAMES = granite_moe_hybrid_param_names(LAYERS)
CHANNELS = MH * MP + 2 * N
MAMBAS = [i for i, kind in enumerate(LAYERS) if kind == MAMBA]
# float32 on both sides, the program's sums in another order than the
# reference's: 1e-4 of the largest logit, as the sibling programs'
LOGITS = 1e-4


def _close(got, want):
    want = np.asarray(want)
    np.testing.assert_allclose(np.asarray(got), want,
                               atol=1e-4 * np.abs(want).max())


def _start(startup, names=NAMES, seed=3):
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    startup.random_seed = seed
    exe.run(startup, scope=scope)
    rs = np.random.RandomState(seed)
    for name in jax.tree_util.tree_leaves(names):
        value = np.asarray(scope.get(name))
        if name.endswith(".conv_w"):
            scope.set(name, jnp.asarray(
                0.5 * rs.randn(*value.shape).astype("float32")))
        elif name.endswith(".router"):
            # scores apart: a near-tie falls either way between two
            # orders of summation
            scope.set(name, jnp.asarray(
                rs.randn(*value.shape).astype("float32")))
        elif value.ndim == 1 and not name.endswith(("a_log", "dt_bias")):
            scope.set(name, jnp.asarray(    # the norms' scales, D, bias
                value + 0.1 * rs.randn(*value.shape).astype("float32")))
    return scope


def _empty(max_len=T):
    state = {"pos": jnp.zeros((B,), jnp.int32)}
    for i, kind in enumerate(LAYERS):
        if kind == MAMBA:
            state["conv_tail_%d" % i] = jnp.zeros((B, CONV - 1, CHANNELS))
            state["ssd_state_%d" % i] = jnp.zeros((B, N, MH * MP))
        else:
            for which in "kv":
                state["%s_cache_%d" % (which, i)] = jnp.zeros(
                    (B, KV, max_len, DH))
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
    block = program[0].global_block()
    for feed, out in probes.items():
        var = block.var(out)
        state[feed] = jnp.zeros(
            tuple(var.shape),
            jnp.int32 if "top_idx" in feed or "counts" in feed
            else jnp.float32)
    return decoder, state


@pytest.fixture(scope="module")
def built():
    program = build_granite_hybrid_cached_step_program(B, T, V, **SIZES)
    scope = _start(program[1])
    decoder, empty = _probed(program, scope)
    tokens = np.random.RandomState(1).randint(0, V, (B, T)).astype("int32")
    before = telemetry.snapshot()
    got, state = _drive(decoder, tokens, empty)
    traced = telemetry.snapshot_delta(before)
    before = telemetry.snapshot()
    _drive(decoder, tokens, empty, [0])
    as_block = telemetry.snapshot_delta(before)
    params = jax.tree_util.tree_map(scope.get, NAMES)
    want = reference.forward(CFG, params, jnp.asarray(tokens))
    return {"program": program, "scope": scope, "decoder": decoder,
            "empty": empty, "tokens": tokens, "got": got, "state": state,
            "params": params, "want": want, "traced": traced,
            "as_block": as_block}


def test_the_step_says_it_takes_a_block_of_a_chunk(built):
    assert built["decoder"]._takes_block
    assert built["decoder"]._prefill_block == CHUNK


@pytest.mark.parametrize("position", range(T))
def test_step_logits_agree_with_the_reference_at_every_position(built,
                                                                position):
    want = np.asarray(built["want"]["logits"])[:, position]
    np.testing.assert_allclose(built["got"][:, position], want,
                               atol=LOGITS * np.abs(want).max())


@pytest.mark.parametrize("layer", MAMBAS)
def test_a_mamba_layers_state_and_tail_are_the_references(built, layer):
    """After the last position: the state the step hands on, state
    entries by head lanes as the decoder carries it and a head at a time
    here, and the tail, the last three positions of the convolution's
    input."""
    state, want = built["state"], built["want"]
    assert state["ssd_state_%d" % layer].shape == (B, N, MH * MP)
    assert state["ssd_state_%d" % layer].dtype == jnp.float32
    _close(ssm.heads_apart(state["ssd_state_%d" % layer], MH),
           want["states"][layer])
    block = built["params"]["blocks"][layer]
    entered = want["hidden"][layer - 1] if layer else \
        12.0 * jnp.asarray(built["params"]["embed"])[built["tokens"]]
    normed = reference.rms_norm(entered, block["norm_1"], 1e-5)
    inner = MH * MP
    tail = (normed[:, -(CONV - 1):] @ block["in_proj"])[
        ..., inner:inner + CHANNELS]
    _close(state["conv_tail_%d" % layer], tail)


def test_the_parts_are_the_references(built):
    """Of the last position: each layer's output, each mixer's output,
    the held experts' part under the step's own choice, and the carried
    rows of a mamba layer's state, a head at a time."""
    state, want = built["state"], built["want"]
    for i, kind in enumerate(LAYERS):
        for key, name in (("hidden", "hidden"), ("mixer", "attn_out")):
            _close(np.asarray(state["probe.%s_%d" % (name, i)])[:, 0],
                   np.asarray(want[key][i])[:, -1])
        routed = np.asarray(want["routed"][i]).reshape(B, T, D)[:, -1]
        _close(np.asarray(state["probe.moe_out_%d" % i])[:, 0], routed)
        chosen = np.asarray(want["indices"][i]).reshape(B, T, K)[:, -1]
        np.testing.assert_array_equal(
            np.sort(np.asarray(state["probe.top_idx_%d" % i]), axis=-1),
            np.sort(chosen, axis=-1))
    for at, layer in enumerate(MAMBAS):
        _close(state["probe.ssd_state_%d" % at], want["states"][layer][:2])


@pytest.mark.parametrize("at", range(len(MAMBAS)))
def test_the_last_steps_update_is_the_recurrences(built, at):
    """What a mamba layer's step hands out of itself, the state it was
    handed and what its scan read, make the state it handed on by one
    update of the reference's recurrence; rounded to bfloat16 they do
    not."""
    probe = {what: built["state"]["probe.ssd_%s_%d" % (what, at)]
             for what in ("state", "state_in", "step_in")}
    assert probe["step_in"].shape == (2, 1, MH * MP + 2 * N + MH)
    block = jax.tree_util.tree_map(
        jnp.asarray, built["params"]["blocks"][MAMBAS[at]])
    assert reference.state_step_off(CFG, block, probe) < 1e-5
    rounded = dict(CFG, control={"state": "bfloat16"})
    assert reference.state_step_off(rounded, block, probe) > 5e-4


def test_a_state_in_of_fewer_heads_is_held_to_those_heads(monkeypatch):
    """With more heads than `STATE_IN_HEADS` (granite-4.0-h-small's 128
    against 16) the handed-in state's probe holds the first of them, and
    the reference holds those heads of the state handed on to it."""
    from paddle_tpu.models import hybrid_program

    monkeypatch.setattr(hybrid_program, "STATE_IN_HEADS", 2)
    program = build_granite_hybrid_cached_step_program(
        B, T, V, **dict(SIZES, layer_types=(MAMBA,)))
    names = granite_moe_hybrid_param_names((MAMBA,))
    scope = _start(program[1], names)
    decoder, empty = _probed(program, scope)
    assert empty["probe.ssd_state_in_0"].shape == (2, 2, MP, N)
    assert empty["probe.ssd_state_0"].shape == (2, MH, MP, N)
    tokens = np.random.RandomState(2).randint(0, V, (B, 6)).astype("int32")
    _, state = _drive(decoder, tokens, empty)
    probe = {what: state["probe.ssd_%s_0" % what]
             for what in ("state", "state_in", "step_in")}
    block = jax.tree_util.tree_map(
        lambda name: jnp.asarray(scope.get(name)), names["blocks"][0])
    cfg = dict(CFG, layer_types=[MAMBA], num_hidden_layers=1)
    assert reference.state_step_off(cfg, block, probe) < 1e-5
    assert reference.state_step_off(
        dict(cfg, control={"state": "bfloat16"}), block, probe) > 5e-4


@pytest.mark.parametrize("cuts", [[0], [0, 8], [0, 4, 8, 12, 16],
                                  [0, 12] + list(range(13, T)),
                                  [0, 1, 2, 3, 4]], ids=str)
def test_blocks_then_steps_are_the_steps(built, cuts):
    """A prompt as one block of five chunks, as blocks of one and two,
    as a block then steps, and as steps then a block: through the tail,
    the state and the cache alike."""
    got, state = _drive(built["decoder"], built["tokens"], built["empty"],
                        cuts)
    want = built["got"][:, -1]
    np.testing.assert_allclose(got[:, -1], want,
                               atol=LOGITS * np.abs(want).max())
    for feed in ("ssd_state_0", "conv_tail_1", "k_cache_2", "v_cache_2",
                 "ssd_state_3", "conv_tail_3"):
        _close(state[feed], built["state"][feed])


def test_a_block_off_the_chunk_is_refused(built):
    with pytest.raises(ValueError, match="no multiple of the chunk"):
        _drive(built["decoder"], built["tokens"], built["empty"], [0, 6])


def test_prefill_then_decode_through_the_decoder_is_the_reference(built):
    """`ProgramDecoder.greedy` over a prompt (two chunks, an
    application each) and the steps after it: every served token is the
    reference's first at its position, and the carried state comes back
    through `return_state`, whole and its carried rows a head at a
    time: the tail and the state crossed the prefill/decode border."""
    prompt, new = built["tokens"][:, :8], 9
    tokens, lengths, last = built["decoder"].greedy(
        bos=0, eos=V, max_len=new, init_state=built["empty"], prompt=prompt,
        return_state=("ssd_state_0", "probe.ssd_state_0", "conv_tail_0"))
    assert tokens.shape == (B, new) and (lengths == new).all()
    fed = np.concatenate([prompt, tokens], axis=1)[:, :-1]
    want = reference.forward(CFG, built["params"], jnp.asarray(fed))
    logits = np.asarray(want["logits"])[:, 7:]
    np.testing.assert_array_equal(tokens, logits.argmax(-1))
    apart = ssm.heads_apart(last["ssd_state_0"], MH)
    _close(apart, want["states"][0])
    np.testing.assert_array_equal(last["probe.ssd_state_0"],
                                  np.asarray(apart)[:2])


CONTROLS = [{"decay": False}, {"skip": False},
            {"state_cut": 8}, {"tail_cut": 8},
            {"attention_multiplier": DH ** -0.5},
            {"residual_multiplier": 1.0}, {"shared_width": FE},
            {"drop": True}]


@pytest.mark.parametrize("control", CONTROLS + [{}], ids=str)
def test_a_control_moves_the_references_logits(built, control):
    """Every way the reference can be made wrong (what the cell's
    controls switch) is seen in its logits at these sizes: further from
    the sound Program's than ten times the tolerance the sound reference
    is held to; the sound reference is within it."""
    got = np.asarray(reference.forward(
        dict(CFG, control=control), built["params"],
        jnp.asarray(built["tokens"]))["logits"])
    off = np.abs(got - built["got"]).max()
    limit = LOGITS * np.abs(np.asarray(built["want"]["logits"])).max()
    assert (off > 10 * limit) if control else (off <= limit)


def test_a_state_kept_in_bfloat16_moves_the_references_state(built):
    """The one control the logits cannot tell from rounding at these
    sizes is what `state_off_first` is for: the first layer's state
    after the last position, a hundred times further from the Program's
    than the sound reference's."""
    got = reference.forward(
        dict(CFG, control={"state": "bfloat16"}), built["params"],
        jnp.asarray(built["tokens"]))["states"][0]
    served = ssm.heads_apart(built["state"]["ssd_state_0"], MH)
    assert reference.state_off(served, got) \
        > 100 * reference.state_off(served, built["want"]["states"][0])


# -- the shares add up ------------------------------------------------------------

@pytest.fixture(scope="module")
def shares():
    """The first layer's output of the last position from the Programs
    of all four shares (experts 0-1, 2-3, 4-5, 6-7 of one seeded set),
    and the uncut set's parameters."""
    whole = build_granite_hybrid_cached_step_program(
        B, T, V, **dict(SIZES, held=None))
    scope = _start(whole[1])
    params = jax.tree_util.tree_map(scope.get, NAMES)
    tokens = np.random.RandomState(2).randint(0, V, (B, 8)).astype("int32")
    hidden, routed = [], []
    for first in range(0, E, 2):
        program = build_granite_hybrid_cached_step_program(
            B, T, V, **dict(SIZES, held=(first, 2)))
        own = fluid.Scope()
        for name in jax.tree_util.tree_leaves(NAMES):
            value = scope.get(name)
            if name.rsplit(".", 1)[-1] in ("w_gate", "w_up", "w_down"):
                value = value[first:first + 2]
            own.set(name, value)
        decoder, empty = _probed(program, own)
        _, state = _drive(decoder, tokens, empty, [0])
        hidden.append(np.asarray(state["probe.hidden_0"])[:, 0])
        routed.append(np.asarray(state["probe.moe_out_0"])[:, 0])
    return {"params": params, "tokens": tokens, "hidden": hidden,
            "routed": routed}


def test_the_four_shares_and_the_shared_expert_once_add_up(shares):
    """The routed parts of all four shares, with the shared expert
    counted once, are the uncut reference's layer: sum_q (a + r (routed_q
    + shared)) - 3 (a + r shared) = a + r (routed + shared)."""
    cfg = dict(CFG, first_expert=0)
    tokens = jnp.asarray(shares["tokens"])
    uncut = reference.forward(cfg, shares["params"], tokens)
    none_held = reference.forward(cfg, shares["params"], tokens,
                                  held=(0, 0))
    want = np.asarray(uncut["hidden"][0])[:, -1]
    base = np.asarray(none_held["hidden"][0])[:, -1]
    _close(sum(shares["hidden"]) - 3 * base, want)
    _close(sum(shares["routed"]),
           np.asarray(uncut["routed"][0]).reshape(B, -1, D)[:, -1])
    # and no share is idle: each held part is a real share of the sum
    assert all(np.abs(part).max() > 0 for part in shares["routed"])


def test_the_references_shares_add_up_too(shares):
    cfg = dict(CFG, first_expert=0)
    tokens = jnp.asarray(shares["tokens"])
    uncut = reference.forward(cfg, shares["params"], tokens)
    parts = [reference.forward(cfg, shares["params"], tokens,
                               held=(first, 2), shared=False)["routed"][0]
             for first in range(0, E, 2)]
    _close(sum(np.asarray(p) for p in parts), uncut["routed"][0])


def test_a_vocabulary_slice_is_the_tables_rows(built):
    tokens = jnp.asarray(built["tokens"] % 40)
    whole = reference.forward(CFG, built["params"], tokens + 30)
    cut = reference.forward(CFG, built["params"], tokens, vocab=(30, 50))
    _close(cut["logits"], np.asarray(whole["logits"])[..., 30:80])


# -- what the Program holds ---------------------------------------------------------

def test_what_the_program_holds(built):
    main = built["program"][0]
    block = main.global_block()
    ops = block.desc.ops
    kinds = [od.type for od in ops]
    assert kinds.count("ssd_scan") == kinds.count("causal_conv1d") == 3
    assert kinds.count("cached_attention") == 1
    assert kinds.count("moe_experts") == kinds.count("moe_router") == 4
    assert not [od for od in ops if od.type == "rope"]
    scans = [od for od in ops if od.type == "ssd_scan"]
    assert all(od.input("State") and od.output("StateOut")
               and not od.output("States")
               and od.attrs["prefill_block"] == CHUNK for od in scans)
    # the shared expert is as wide as it is said to be, the routed ones
    # as they are; the table is also the head
    assert tuple(block.var("block_0.shared_in").shape) == (D, 2 * FS)
    assert tuple(block.var("block_0.w_gate").shape) == (HELD[1], D, FE)
    assert tuple(block.var("block_0.router").shape) == (D, E)
    built_names = {p.name for p in block.all_parameters()}
    assert built_names == set(jax.tree_util.tree_leaves(NAMES))
    assert "head.w" not in built_names
    outs = [od.output_names()[0] for od in ops]
    assert sum(n.startswith("ssd_gated_norm") for n in outs) == 3 * 3
    assert sorted(feed for feed, _ in built["program"][3]) \
        == sorted(_empty())


def test_counters_say_what_was_lowered(built):
    traced = built["traced"]
    step = ("ssd_scan_lowerings_total{chunk=0,form=step,heads=%d,path=plain,"
            "state_dtype=float32}" % MH)
    assert traced[step] == 3
    assert traced["recurrent_state_bytes_total{kind=ssd}"] \
        == 3 * N * MH * MP * 4
    assert traced["recurrent_state_bytes_total{kind=conv_tail}"] \
        == 3 * (CONV - 1) * CHANNELS * 4
    block = ("ssd_scan_lowerings_total{chunk=%d,form=block,heads=%d,"
             "path=plain,state_dtype=float32}" % (CHUNK, MH))
    assert built["as_block"][block] == 3


def test_the_builders_program_digest():
    main = build_granite_hybrid_cached_step_program(2, 16, 97, **SIZES)[0]
    assert hashlib.sha256(_listing(main).encode()).hexdigest()[:16] \
        == DIGEST


DIGEST = "abe2978260fb3df0"


def test_the_benchmarks_copy_of_the_reference_is_this_one():
    """benchmark/reference/granite_moe_hybrid.py is models/reference/
    granite_moe_hybrid.py to the letter (the benchmark brings its own
    copy)."""
    import os

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(reference.__file__) as own, open(os.path.join(
            root, "benchmark", "reference",
            "granite_moe_hybrid.py")) as copy:
        assert own.read() == copy.read()
