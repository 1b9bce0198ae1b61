"""Phi-4-mini-flash-reasoning on the generation path: a self-decoder of
Mamba-1 layers (`selective_scan` with its state, `causal_conv1d` with
its tail) and differential attention over rings and one whole-extent
cache, and a cross-decoder of gated memory units and cross layers that
read that one cache and write none (`cached_attention` without KNew /
VNew), in the cached step Program `models/sambay_program.py` builds,
against the plain float32 reference (models/reference/phi4_flash.py):
the step from position 0 and prefill + decode through `ProgramDecoder`
against the reference's full forward; a block against its steps for all
four kinds of state; the cross-decoder's skip; the ops alone; the
counters and scopes; what the builder leaves as it was.

Tiny sizes on the CPU: 8 layers (Mamba, window, Mamba, window, Mamba
that gives the memory, full, memory unit, cross), hidden 64, 4 query and
2 key/value heads of 16 (2 pairs over 1), a window of 4 of 24 positions,
d_inner 128, 4 state entries, vocabulary 97, seeded weights.
"""

import hashlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu.fluid as fluid
from paddle_tpu.models.latent_moe_program import \
    build_latent_moe_cached_step_program
from paddle_tpu.models.linear_moe_program import \
    build_linear_moe_cached_step_program
from paddle_tpu.models.reference import phi4_flash as reference
from paddle_tpu.models.sambay_program import (
    CROSS, FULL, GMU, MAMBA, WINDOW, build_sambay_cached_step_program,
    lambda_init, layer_kinds, sambay_param_names)
from paddle_tpu.models.sparse_kv_moe_program import \
    build_sparse_kv_moe_cached_step_program
from paddle_tpu.models.window_moe_program import \
    build_window_moe_cached_step_program
from paddle_tpu.obs import telemetry
from paddle_tpu.ops import registry

B, T, V, L = 2, 24, 97, 8
H, KV, DH, D, FF, N, W = 4, 2, 16, 64, 128, 4, 4
DI, CONV = 2 * D, 4
SIZES = dict(n_layer=L, window=W, n_head=H, n_kv_head=KV, d_head=DH,
             d_model=D, d_ff=FF, d_state=N, d_conv=CONV, eps=1e-5)
CFG = {"num_hidden_layers": L, "hidden_size": D, "num_attention_heads": H,
       "num_key_value_heads": KV, "sliding_window": W, "mb_per_layer": 2,
       "layer_norm_eps": 1e-5, "mamba_d_state": N, "mamba_d_conv": CONV}
KINDS = layer_kinds(L)
NAMES = sambay_param_names(L)
# float32 on the CPU: the step and the reference add the same products
# in another order.  Logits of size ~2 were seen to differ by 3e-6 of
# the largest; every wrong wiring this file knows moves them by 1e-2.
LOGITS_RTOL = 5e-5


def _start(startup, names=NAMES, seed=5):
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    startup.random_seed = seed
    exe.run(startup, scope=scope)
    rs = np.random.RandomState(seed)
    for name in jax.tree_util.tree_leaves(names):
        value = np.asarray(scope.get(name))
        if value.ndim == 1 and not name.endswith(("dt_bias", ".d")):
            # norm scales off their 1, biases off their 0, lambda's
            # vectors wide enough that lambda is not its constant part
            wide = 0.4 if name[-3:] in ("lq1", "lk1", "lq2", "lk2") else 0.1
            scope.set(name, jnp.asarray(
                value + wide * rs.randn(*value.shape).astype("float32")))
    return scope


def _decoder(main, logits, pairs, scope, extent=T):
    return fluid.ProgramDecoder(
        main.clone(for_test=True), token_name="tok",
        logits_name=logits.name, state_pairs=pairs, scope=scope,
        max_positions=extent)


def _empty(extent=T, window=W, kinds=KINDS, batch=B, width=2 * DH,
           d_inner=DI, kv_pairs=KV // 2):
    state = {}
    for i, kind in enumerate(kinds):
        if kind == MAMBA:
            state["ssm_state_%d" % i] = jnp.zeros((batch, N, d_inner))
            state["conv_tail_%d" % i] = jnp.zeros((batch, CONV - 1, d_inner))
        elif kind in (WINDOW, FULL):
            stem = "%s_ring_%d" if kind == WINDOW else "%s_cache_%d"
            for which in "kv":
                state[stem % (which, i)] = jnp.zeros(
                    (batch, kv_pairs, window if kind == WINDOW else extent,
                     width))
    state["pos"] = jnp.zeros((batch,), jnp.int32)
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
    return build_sambay_cached_step_program(B, T, V,
                                            **dict(SIZES, **changed))


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
    want, states = reference.forward(CFG, params, jnp.asarray(tokens),
                                     with_states=True)
    return {"main": main, "logits": logits, "pairs": pairs, "parts": parts,
            "scope": scope, "decoder": decoder, "tokens": tokens,
            "got": got, "state": state, "params": params,
            "want": np.asarray(want), "states": states,
            "at_build": at_build}


# -- (a) the step against the reference's full forward -------------------------

def test_the_layers_are_the_published_order():
    kinds = layer_kinds(32)
    assert kinds[:16:2] == (MAMBA,) * 8 and kinds[1:16:2] == (WINDOW,) * 8
    assert kinds[16] == MAMBA and kinds[17] == FULL
    assert kinds[18::2] == (GMU,) * 7 and kinds[19::2] == (CROSS,) * 7
    assert kinds == reference.layer_kinds({"num_hidden_layers": 32,
                                           "mb_per_layer": 2})
    assert lambda_init(17) == reference.lambda_init(17)
    with pytest.raises(ValueError):
        layer_kinds(6)


@pytest.mark.parametrize("position", range(T))
def test_step_logits_agree_with_the_reference_at_every_position(
        built, position):
    """The reference runs every layer at every position and holds no
    cache; positions 4..23 lie past the window layers' rings."""
    want = built["want"][:, position]
    got = built["got"][:, position]
    assert got.dtype == np.float32
    assert np.abs(got - want).max() <= LOGITS_RTOL * np.abs(want).max()


@pytest.mark.parametrize("prompt_len", [3, 12])
def test_prefill_then_greedy_is_the_references_greedy(built, prompt_len):
    """Prefill (the step's block form over the prompt) then decode
    through `ProgramDecoder.greedy`: every served token is the
    reference's first given the tokens before it."""
    prompt = built["tokens"][:, :prompt_len]
    gen = T - prompt_len + 1
    tokens, lengths = built["decoder"].greedy(
        bos=0, eos=V, max_len=gen, init_state=_empty(), prompt=prompt)
    assert tokens.shape == (B, gen) and (lengths == gen).all()
    full = np.concatenate([prompt, tokens], axis=1)[:, :T]
    z = np.asarray(reference.forward(CFG, built["params"],
                                     jnp.asarray(full)))
    at = prompt_len - 1
    served = tokens[:, :T - at]
    picked = np.take_along_axis(z[:, at:], served[..., None], axis=-1)[..., 0]
    assert (z[:, at:].max(axis=-1) - picked).max() <= 1e-4


# -- (b) a block against its steps, state by state -------------------------------

def _as_the_step_lays_it(states, i, kind, upto):
    """The reference's states of layer i after `upto` positions, as the
    step's feeds hold them."""
    found = states[i]
    if kind == MAMBA:
        raise AssertionError("the scan's state is of the whole sequence")
    k, v = (np.asarray(found[w])[:, :upto] for w in "kv")
    out = []
    for value in (k, v):
        value = value.reshape(B, upto, KV // 2, 2 * DH).transpose(0, 2, 1, 3)
        if kind == WINDOW:
            ring = np.zeros((B, KV // 2, W, 2 * DH), np.float32)
            for p in range(max(0, upto - W), upto):
                ring[:, :, p % W] = value[:, :, p]
            value = ring
        out.append(value)
    return out


@pytest.mark.parametrize("first,block", [(0, 9), (5, 7), (11, 1)])
def test_a_block_leaves_what_its_steps_leave(built, first, block):
    """Every state after `first` single steps and then one block of
    `block` positions is what `first + block` steps leave: the scan's
    state, the convolution's tail, the rings (a block longer than the
    window wraps them) and the full cache; and the block's logits, of
    its last position alone, are the steps' there, the cross-decoder
    having run at that position only."""
    decoder, tokens = built["decoder"], built["tokens"]
    step = decoder._step_fn(decoder._params)
    state = _drive(decoder, tokens[:, :first], _empty())[1] if first \
        else _empty()
    logits, state = step(state, jnp.asarray(tokens[:, first:first + block]))
    _, want = _drive(decoder, tokens[:, :first + block], _empty())
    assert int(state["pos"][0]) == first + block
    for name in want:
        np.testing.assert_allclose(
            np.asarray(state[name]), np.asarray(want[name]), rtol=2e-5,
            atol=2e-6, err_msg=name)
    np.testing.assert_allclose(
        np.asarray(logits), built["got"][:, first + block - 1], rtol=1e-4,
        atol=1e-5)
    # and the skip is exact against the reference, which skips nothing
    ref = built["want"][:, first + block - 1]
    assert np.abs(np.asarray(logits) - ref).max() \
        <= LOGITS_RTOL * np.abs(ref).max()


def test_the_states_are_the_references(built):
    """After all T steps: a Mamba layer's state and tail are the
    reference's after the sequence ([d_inner, N] there, state entries by
    channels here), a window layer's ring holds the last W positions'
    keys and values at position mod W, the full cache every position's,
    a pair's two heads side by side."""
    state, states = built["state"], built["states"]
    for i, kind in enumerate(KINDS):
        if kind == MAMBA:
            np.testing.assert_allclose(
                np.asarray(state["ssm_state_%d" % i]),
                np.asarray(states[i]["state"]).transpose(0, 2, 1),
                rtol=1e-4, atol=1e-5)
            np.testing.assert_allclose(
                np.asarray(state["conv_tail_%d" % i]),
                np.asarray(states[i]["tail"]), rtol=1e-4, atol=1e-5)
        elif kind in (WINDOW, FULL):
            stem = "%s_ring_%d" if kind == WINDOW else "%s_cache_%d"
            for which, want in zip("kv", _as_the_step_lays_it(
                    states, i, kind, T)):
                np.testing.assert_allclose(
                    np.asarray(state[stem % (which, i)]), want, rtol=1e-4,
                    atol=1e-5)
        else:
            assert not any(("_%d" % i) in name for name in state)


# -- (c) the one cache and its readers ---------------------------------------------

def test_cross_layers_read_the_full_layers_cache_as_it_wrote_it(built):
    """One cache Variable pair with one writer and its readers: the
    cross layer's op has no KNew / VNew, gives no cache out, and its
    KCache / VCache are the full layer's KCacheOut / VCacheOut of the
    same step; its position is the block's last."""
    ops = [od for od in built["main"].global_block().desc.ops
           if od.type == "cached_attention"]
    writers = [od for od in ops if "KNew" in od.inputs]
    readers = [od for od in ops if "KNew" not in od.inputs]
    assert len(writers) == KINDS.count(WINDOW) + 1
    assert len(readers) == KINDS.count(CROSS) == 1
    full = [od for od in writers if not od.attrs.get("window")]
    assert len(full) == 1 and full[0].attrs["shared_readers"] == 2
    for od in readers:
        assert set(od.outputs) == {"Out"} and od.attrs["reader"] == 1
        assert od.input("KCache") == full[0].output("KCacheOut")
        assert od.input("VCache") == full[0].output("VCacheOut")
        assert od.input("Position") != full[0].input("Position")
    held = {p.name for p in built["main"].global_block().all_parameters()}
    cross = KINDS.index(CROSS)
    assert "block_%d.wq" % cross in held
    assert "block_%d.wkv" % cross not in held
    assert built["at_build"]["program_shared_cache_readers{program=%s}"
                             % built["main"]._cache_token] == 2


def test_a_cross_layer_sees_the_slot_written_in_its_own_step(built):
    """Wired to the cache as it was fed, a cross layer misses its own
    position: at position 0 it attends one empty slot, and the logits
    are wrong from the first step on."""
    main, startup, logits, pairs, _ = _build(cross_before_write=True)
    decoder = _decoder(main, logits, pairs, built["scope"])
    got, _ = _drive(decoder, built["tokens"][:, :6], _empty())
    want = built["want"][:, :6]
    off = np.abs(got - want).max(axis=(0, 2)) / np.abs(want).max()
    assert (off > 1e-2).all()


@pytest.mark.parametrize("changed", [{"subtract": False},
                                     {"memory_after_gate": True}])
def test_a_wrong_wiring_shows(built, changed):
    main, startup, logits, pairs, _ = _build(**changed)
    decoder = _decoder(main, logits, pairs, built["scope"])
    got, _ = _drive(decoder, built["tokens"][:, :8], _empty())
    want = built["want"][:, :8]
    assert np.abs(got - want).max() > 1e-2 * np.abs(want).max()


def test_the_readonly_form_refuses_what_it_cannot_read():
    kernel = registry.get_op_info("cached_attention").kernel
    q = jnp.zeros((1, 1, 32))
    ring = jnp.zeros((1, 1, 4, 32))
    ins = {"Q": [q], "KCache": [ring], "VCache": [ring],
           "Position": [jnp.zeros((1,), jnp.int32)]}
    with pytest.raises(ValueError, match="without KNew"):
        kernel(None, ins, {"num_heads": 1, "window": 4})
    out = kernel(None, ins, {"num_heads": 1})
    assert set(out) == {"Out"}


# -- (d) the ops alone -----------------------------------------------------------------

def _scan_inputs(rs, rows, length, channels, entries):
    f = lambda *shape: rs.randn(*shape).astype("float32")
    return {"X": [f(rows, length, channels)],
            "Dt": [f(rows, length, channels) - 2.0],
            "DtBias": [f(channels)],
            "ALog": [np.log(np.tile(np.arange(1, entries + 1,
                                              dtype="float32"),
                                    (channels, 1)))],
            "B": [f(rows, length, entries)], "C": [f(rows, length, entries)],
            "D": [f(channels)], "State": [f(rows, entries, channels)]}


def _scan_loop(ins):
    """The recurrence position by position, in float64."""
    x, dt, b, c = (np.asarray(ins[k][0], np.float64) for k in "X Dt B C"
                   .split())
    dt = np.log1p(np.exp(dt + np.asarray(ins["DtBias"][0], np.float64)))
    a = -np.exp(np.asarray(ins["ALog"][0], np.float64))    # [D, N]
    s = np.asarray(ins["State"][0], np.float64).transpose(0, 2, 1)
    ys = []
    for t in range(x.shape[1]):
        s = np.exp(dt[:, t, :, None] * a) * s \
            + (dt[:, t] * x[:, t])[:, :, None] * b[:, t, None, :]
        ys.append(np.einsum("bdn,bn->bd", s, c[:, t])
                  + np.asarray(ins["D"][0], np.float64) * x[:, t])
    return np.stack(ys, axis=1), s.transpose(0, 2, 1)


@pytest.mark.parametrize("length", [1, 7])
def test_selective_scan_is_the_recurrence(length):
    kernel = registry.get_op_info("selective_scan").kernel
    ins = _scan_inputs(np.random.RandomState(3), 2, length, 24, 4)
    before = telemetry.snapshot()
    got = kernel(None, ins, {})
    delta = telemetry.snapshot_delta(before)
    want_y, want_s = _scan_loop(ins)
    np.testing.assert_allclose(np.asarray(got["Out"][0]), want_y,
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(got["StateOut"][0]), want_s,
                               rtol=1e-5, atol=1e-6)
    form = "step" if length == 1 else "block"
    assert delta["selective_scan_lowerings_total{form=%s,"
                 "state_dtype=float32}" % form] == 1
    assert delta["recurrent_state_bytes_total{kind=ssm}"] == 4 * 24 * 4


def test_selective_scan_block_is_its_steps_and_keeps_the_states_type():
    kernel = registry.get_op_info("selective_scan").kernel
    ins = _scan_inputs(np.random.RandomState(4), 2, 6, 24, 4)
    whole = kernel(None, ins, {})
    state, ys = ins["State"][0], []
    for t in range(6):
        one = dict(ins, State=[state],
                   **{k: [ins[k][0][:, t:t + 1]] for k in "X Dt B C".split()})
        out = kernel(None, one, {})
        state = out["StateOut"][0]
        ys.append(np.asarray(out["Out"][0]))
    np.testing.assert_allclose(np.concatenate(ys, axis=1),
                               np.asarray(whole["Out"][0]), rtol=1e-6,
                               atol=1e-7)
    np.testing.assert_allclose(np.asarray(state),
                               np.asarray(whole["StateOut"][0]), rtol=1e-6,
                               atol=1e-7)
    narrow = kernel(None, dict(ins, State=[jnp.asarray(
        ins["State"][0], jnp.bfloat16)]), {})
    assert narrow["StateOut"][0].dtype == jnp.bfloat16
    assert narrow["Out"][0].dtype == jnp.float32


def test_selective_scan_has_no_gradient_and_refuses_other_shapes():
    with pytest.raises(NotImplementedError, match="forward only"):
        registry.get_op_info("selective_scan").grad_kernel(None, {}, {})
    ins = _scan_inputs(np.random.RandomState(5), 2, 3, 24, 4)
    ins["State"] = [np.zeros((2, 24, 4), "float32")]
    with pytest.raises(ValueError, match="selective_scan"):
        registry.get_op_info("selective_scan").kernel(None, ins, {})


def test_differential_attention_is_the_four_products():
    """`cached_attention` over a pair's heads side by side with queries
    zero in the other's half, then `diff_combine`, against the
    reference's four products a pair, two softmaxes subtracted; with
    `subtract` false the second map is left out."""
    rs = np.random.RandomState(6)
    n, heads, kv_heads, dim = 5, 8, 4, 16
    pairs, width = heads // 2, 2 * dim
    cfg = {"num_attention_heads": heads, "num_key_value_heads": kv_heads,
           "hidden_size": heads * dim}
    f = lambda *shape: jnp.asarray(rs.randn(*shape).astype("float32"))
    h = f(n, heads * dim)
    block = {"wq": jnp.eye(heads * dim), "wo": jnp.eye(heads * dim),
             "lq1": 0.3 * f(dim), "lk1": 0.3 * f(dim), "lq2": 0.3 * f(dim),
             "lk2": 0.3 * f(dim), "subln": 1.0 + 0.1 * f(width)}
    k, v = f(n, kv_heads, dim), f(n, kv_heads, dim)
    at = jnp.arange(n)
    want = reference.attend(cfg, block, lambda_init(3), h, at, k, v, at, 0)

    halves = np.kron(np.eye(2), np.ones((1, dim))).astype("float32")
    q = jnp.asarray(np.asarray(h).reshape(1, n, pairs, 1, width) * halves) \
        .reshape(1, n, heads * width)
    empty = jnp.zeros((1, kv_heads // 2, 8, width))
    attend = registry.get_op_info("cached_attention").kernel
    out = attend(None, {
        "Q": [q], "KNew": [k.reshape(1, n, -1)], "VNew": [v.reshape(1, n, -1)],
        "KCache": [empty], "VCache": [empty],
        "Position": [jnp.zeros((1,), jnp.int32)]},
        {"num_heads": heads, "num_kv_heads": kv_heads // 2,
         "sm_scale": dim ** -0.5})
    combine = registry.get_op_info("diff_combine").kernel
    ins = {"X": [out["Out"][0]], "Scale": [block["subln"]],
           "LambdaQ1": [block["lq1"]], "LambdaK1": [block["lk1"]],
           "LambdaQ2": [block["lq2"]], "LambdaK2": [block["lk2"]]}
    attrs = {"width": width, "lambda_init": lambda_init(3)}
    got = combine(None, ins, attrs)["Out"][0]
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want),
                               rtol=2e-5, atol=2e-6)
    dropped = combine(None, ins, dict(attrs, subtract=False))["Out"][0]
    assert np.abs(np.asarray(dropped) - np.asarray(got)).max() > 1e-2
    with pytest.raises(ValueError, match="diff_combine"):
        combine(None, dict(ins, Scale=[block["subln"][:dim]]), attrs)


# -- (e) counters and scopes -------------------------------------------------------------

@pytest.mark.parametrize("block", [1, 5])
def test_a_lowering_counts_its_parts_positions(built, block):
    """The block form runs the self-decoder over its T positions and
    the cross-decoder over one: `decoder_positions_total` counts T and
    1, a step 1 and 1; each scan counts its form; the cross layer's op
    is counted as read-only and holds no slots."""
    decoder = built["decoder"]
    step = decoder._step_fn(decoder._params)
    before = telemetry.snapshot()
    jax.jit(step).lower(_empty(), jnp.zeros((B, block), jnp.int32))
    delta = telemetry.snapshot_delta(before)
    assert delta["decoder_positions_total{part=self}"] == block
    assert delta["decoder_positions_total{part=cross}"] == 1
    form = "step" if block == 1 else "block"
    assert delta["selective_scan_lowerings_total{form=%s,"
                 "state_dtype=float32}" % form] == KINDS.count(MAMBA)
    assert delta["cached_attention_readonly_lowerings_total{block=1,"
                 "block_k=0,path=plain}"] == KINDS.count(CROSS)
    assert delta["kv_cache_slots_total{kind=full}"] == 2 * T // 2 \
        or delta["kv_cache_slots_total{kind=full}"] == T
    assert "kv_cache_slots_total{kind=cross}" not in delta


def test_the_scopes_are_in_the_lowered_step(built):
    decoder = built["decoder"]
    step = decoder._step_fn(decoder._params)
    text = jax.jit(step).lower(
        _empty(), jnp.zeros((B, 1), jnp.int32)).as_text(debug_info=True)
    for scope in ("selective_scan", "causal_conv1d", "gmu_6", "attn_window",
                  "attn_full", "attn_cross", "kv_write", "diff_combine"):
        assert scope in text, scope


# -- (f) the kernel's path at the published head width -----------------------------------

def test_heads_of_64_in_pairs_take_the_decode_kernel():
    """At the published head width a pair is one 128-wide head of the
    cache, which `kernels/gqa_decode.py` walks (under the interpreter
    here): a step through the ring, through the full cache and through
    the cross layer's read, and a block over the whole extent, against
    the reference."""
    sizes = dict(n_layer=8, window=128, n_head=2, n_kv_head=2, d_head=64,
                 d_model=128, d_ff=128, d_state=4)
    extent, count = 256, 6
    main, startup, logits, pairs, _ = build_sambay_cached_step_program(
        1, extent, V, **sizes)
    names = sambay_param_names(8)
    scope = _start(startup, names)
    decoder = _decoder(main, logits, pairs, scope, extent)
    tokens = np.random.RandomState(2).randint(0, V, (1, count)) \
        .astype("int32")
    empty = _empty(extent, 128, layer_kinds(8), 1, 128, 256, 1)
    step = decoder._step_fn(decoder._params)
    before = telemetry.snapshot()
    z_block, state = step(empty, jnp.asarray(tokens[:, :count - 1]))
    z_step, _ = step(state, jnp.asarray(tokens[:, count - 1:]))
    delta = telemetry.snapshot_delta(before)
    assert delta["cached_attention_readonly_lowerings_total{block=1,"
                 "block_k=256,path=kernel}"] == 2
    cfg = dict(CFG, hidden_size=128, num_attention_heads=2,
               num_key_value_heads=2, sliding_window=128)
    want = np.asarray(reference.forward(
        cfg, jax.tree_util.tree_map(scope.get, names), jnp.asarray(tokens)))
    for got, at in ((z_block, count - 2), (z_step, count - 1)):
        assert np.abs(np.asarray(got) - want[:, at]).max() \
            <= 2e-3 * np.abs(want[:, at]).max()


# -- (g) what the PR leaves as it was ----------------------------------------------------

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
    (build_window_moe_cached_step_program, {}, "28a50da12a521ca8"),
    (build_linear_moe_cached_step_program, {}, "f284162f98cd8cb3"),
    (build_sparse_kv_moe_cached_step_program, {}, "e865ced12acdb4b2"),
], ids=["pangu", "dsv32", "exaone", "qwen3next", "keye"])
def test_the_other_steps_programs_are_op_for_op_what_they_were(
        build, options, digest):
    """`cached_attention` took a read-only form and two counters' attrs:
    a Program that asks for neither is, op for op and attr for attr,
    the Program it was (the first three digests are
    tests/test_linear_moe_program.py's; qwen3next's was taken on the
    parent commit; keye's is PR 66's, whose step takes a block of
    positions: its attention op carries `prefill_block` and no other
    new attr)."""
    main = build(2, 16, 97, **options)[0]
    assert hashlib.sha256(_listing(main).encode()).hexdigest()[:16] == digest


def test_this_steps_program_digest(built):
    """A change to what the builder makes shows here."""
    assert hashlib.sha256(_listing(built["main"]).encode()).hexdigest()[:16] \
        == DIGEST


DIGEST = "373078e5af77f010"
