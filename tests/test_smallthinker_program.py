"""The window/full mixture-of-experts decoder as a training Program
(models/smallthinker_program.py) against the plain float32 reference
(models/reference/smallthinker.py): the pattern of one full, unrotated
layer and three windowed, rotated ones, a window shorter than the
sequence, seven query heads a key/value head, a router that reads the
layer's input norm, ReGLU experts of which a range is held, a sliced
vocabulary: logits, every layer's attention and expert output, the
routing, the loss and every parameter's gradient; faults the comparison
must tell (controls); and the counters.

Tiny sizes on the CPU: 4 layers, hidden 64, 7 query heads of 16 over 1
key/value head, 8 experts of 32 scored and 4 held from the third on, 2 a
token, vocabulary 97, 256 tokens under a window of 100 (no multiple of a
block, so the lower edge crosses chunks), seeded random weights (norm
scales moved off their initial 1, so that a scale left out shows).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu.fluid as fluid
from paddle_tpu.models.reference import smallthinker as reference
from paddle_tpu.models.smallthinker_program import (
    build_smallthinker_program, smallthinker_param_names)
from paddle_tpu.models.transformer_program import transformer_program_feeds
from paddle_tpu.obs import telemetry

B, T, V, H, KV, DH, D, F, E, K, W = 1, 256, 97, 7, 1, 16, 64, 32, 8, 2, 100
HELD = (2, 4)
LAYOUT = [0, 1, 1, 1]
L = len(LAYOUT)
CFG = {"num_attention_heads": H, "num_key_value_heads": KV, "head_dim": DH,
       "rope_theta": 1.5e6, "rms_norm_eps": 1e-6, "rope_layout": LAYOUT,
       "sliding_window_layout": LAYOUT, "sliding_window_size": W,
       "moe_num_active_primary_experts": K, "scored_experts": E,
       "first_expert": HELD[0], "moe_num_primary_experts": HELD[1],
       "hidden_act": "relu", "router_reads": "input_layernorm"}
NAMES = smallthinker_param_names(L)
PARAMS = jax.tree_util.tree_leaves(NAMES)

# float32 on the CPU; the limits of tests/test_moe_program.py, for its
# reasons: the flash kernel's summation order against dense rows of
# scores, grouped products over gathered rows against dense products
# masked afterwards
FORWARD_ATOL = 1e-5
LOSS_RTOL = 2e-6
GRAD_RTOL = 2e-5


def _build(held=HELD):
    return build_smallthinker_program(
        B, T, V, rope_layout=LAYOUT, window_layout=LAYOUT, window=W,
        n_head=H, n_kv_head=KV, d_model=D, d_head=DH, d_expert=F,
        n_experts=E, top_k=K, held=held)


def _start(startup, seed=3):
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    startup.random_seed = seed
    exe.run(startup, scope=scope)
    rs = np.random.RandomState(seed)
    for name in PARAMS:
        value = np.asarray(scope.get(name))
        if value.ndim == 1:
            scope.set(name, jnp.asarray(
                value + 0.1 * rs.randn(*value.shape).astype("float32")))
    return exe, scope


@pytest.fixture(scope="module")
def trained_once():
    """The program run once in float32 beside the reference on the same
    weights and batch."""
    before = telemetry.snapshot()
    main, startup, loss, parts = _build()
    with fluid.program_guard(main, startup):
        grads = dict((p.name, g) for p, g in
                     fluid.backward.append_backward(loss))
    exe, scope = _start(startup)
    feeds = transformer_program_feeds(B, T, V, seed=1)
    per_layer = ("attn_out", "moe_out", "router_logits", "top_idx", "top_w",
                 "counts")
    fetch = [loss, parts["logits"]] \
        + [v for key in per_layer for v in parts[key]] \
        + [grads[n] for n in PARAMS]
    out = exe.run(main, feed=feeds, fetch_list=fetch, scope=scope)
    lowered = telemetry.snapshot_delta(before)
    params = jax.tree_util.tree_map(scope.get, NAMES)
    jfeeds = {k: jnp.asarray(v) for k, v in feeds.items()}
    found = {"main": main, "lowered": lowered, "params": params,
             "feeds": jfeeds, "loss": float(out[0].reshape(-1)[0]),
             "logits": out[1],
             "grads": dict(zip(PARAMS, out[2 + len(per_layer) * L:]))}
    for i, key in enumerate(per_layer):
        found[key] = out[2 + i * L:2 + (i + 1) * L]
    found["want"] = reference.loss_terms(CFG, params, jfeeds)
    found["want_grads"] = dict(zip(PARAMS, jax.tree_util.tree_leaves(
        jax.grad(lambda p: reference.loss(CFG, p, jfeeds))(params))))
    return found


def test_loss_agrees_with_the_reference(trained_once):
    assert trained_once["loss"] == pytest.approx(
        float(trained_once["want"]["loss"]), rel=LOSS_RTOL)


def test_logits_agree_with_the_reference(trained_once):
    np.testing.assert_allclose(
        trained_once["logits"], np.asarray(trained_once["want"]["logits"]),
        atol=FORWARD_ATOL, rtol=0)


@pytest.mark.parametrize("layer", range(L))
def test_each_layers_attention_agrees_with_the_reference(trained_once,
                                                         layer):
    """The full, unrotated layer and the windowed, rotated ones apart."""
    np.testing.assert_allclose(
        trained_once["attn_out"][layer],
        np.asarray(trained_once["want"]["attn_out"][layer]),
        atol=FORWARD_ATOL, rtol=0)


@pytest.mark.parametrize("layer", range(L))
def test_each_expert_layer_agrees_with_the_reference(trained_once, layer):
    want = trained_once["want"]
    np.testing.assert_allclose(
        trained_once["router_logits"][layer],
        np.asarray(want["router_logits"][layer]), atol=FORWARD_ATOL, rtol=0)
    idx = trained_once["top_idx"][layer]
    np.testing.assert_array_equal(idx, np.asarray(want["indices"][layer]))
    # the chosen probabilities, renormalised over the chosen
    top_w = trained_once["top_w"][layer]
    np.testing.assert_allclose(top_w.sum(axis=1), 1.0, atol=1e-6)
    chosen = np.take_along_axis(
        np.asarray(want["router_logits"][layer]), idx, axis=1)
    np.testing.assert_allclose(
        top_w, np.asarray(jax.nn.softmax(chosen, axis=-1)), atol=3e-6)
    np.testing.assert_allclose(
        trained_once["moe_out"][layer].reshape(B * T, D),
        np.asarray(want["moe_out"][layer]), atol=FORWARD_ATOL, rtol=0)
    # the held experts' rows and no others
    counts = trained_once["counts"][layer]
    assert counts.shape == (HELD[1],)
    np.testing.assert_array_equal(
        counts, np.bincount(idx.reshape(-1), minlength=E)[
            HELD[0]:HELD[0] + HELD[1]])
    assert 0 < counts.sum() < B * T * K


@pytest.mark.parametrize("name", PARAMS)
def test_gradients_agree_with_the_reference(trained_once, name):
    got, want = trained_once["grads"][name], trained_once["want_grads"][name]
    want = np.asarray(want)
    assert got.shape == want.shape
    assert np.abs(want).max() > 0
    assert np.abs(got - want).max() <= GRAD_RTOL * np.abs(want).max()


# what each fault does to the reference: (the attention outputs that
# move, whether the expert layers' do)
CONTROLS = {
    "the window off by one, short": ({"sliding_window_size": W - 1},
                                     [1, 2, 3]),
    "the window off by one, long": ({"sliding_window_size": W + 1},
                                    [1, 2, 3]),
    "the window ignored": ({"sliding_window_layout": [0] * L}, [1, 2, 3]),
    "positions applied to the full layer": ({"rope_layout": [1] * L}, [0]),
    "the router fed the experts' input": (
        {"router_reads": "post_attention_layernorm"}, []),
    "SiLU for ReLU": ({"hidden_act": "silu"}, []),
    "a token's last expert dropped": (
        {"moe_num_active_primary_experts": K - 1}, []),
}


@pytest.mark.parametrize("fault", sorted(CONTROLS))
def test_the_comparison_tells_a_fault(trained_once, fault):
    """The reference with one thing wrong is outside the limits the
    program is held to: in the attention output of exactly the layers
    the fault touches first, or in the first expert layer, and in the
    gradients."""
    change, attention_moves = CONTROLS[fault]
    cfg = dict(CFG, **change)
    wrong = reference.loss_terms(cfg, trained_once["params"],
                                 trained_once["feeds"])
    if attention_moves:
        first = min(attention_moves)
        for layer in range(first + 1):
            off = np.abs(trained_once["attn_out"][layer]
                         - np.asarray(wrong["attn_out"][layer])).max()
            assert (off > 10 * FORWARD_ATOL) == (layer == first), layer
    else:
        np.testing.assert_allclose(
            trained_once["attn_out"][0], np.asarray(wrong["attn_out"][0]),
            atol=FORWARD_ATOL, rtol=0)
        off = np.abs(trained_once["moe_out"][0].reshape(B * T, D)
                     - np.asarray(wrong["moe_out"][0])).max()
        assert off > 10 * FORWARD_ATOL
    assert abs(trained_once["loss"] - float(wrong["loss"])) \
        > 10 * LOSS_RTOL * trained_once["loss"]


def test_the_reference_in_bfloat16_is_outside_the_limits(trained_once):
    low = reference.loss_terms(CFG, trained_once["params"],
                               trained_once["feeds"], dtype=jnp.bfloat16)
    assert abs(float(low["loss"]) - trained_once["loss"]) \
        > 10 * LOSS_RTOL * trained_once["loss"]


def test_the_reference_takes_indices_that_are_handed_to_it(trained_once):
    params, feeds = trained_once["params"], trained_once["feeds"]
    want = trained_once["want"]
    again = reference.loss_terms(CFG, params, feeds, want["indices"])
    np.testing.assert_allclose(again["logits"], want["logits"], atol=1e-6)
    other = [jnp.stack([i[:, 0], (i[:, 0] + 1) % E], axis=1)
             for i in want["indices"]]
    moved = reference.loss_terms(CFG, params, feeds, other)
    np.testing.assert_array_equal(moved["indices"][0], other[0])
    assert np.abs(np.asarray(moved["logits"] - want["logits"])).max() > 1e-4


def test_the_program_is_the_pattern(trained_once):
    """One full layer with no positions, three windowed rotated ones;
    the router reads the layer's input norm; the experts are ReGLU and a
    held range."""
    ops = trained_once["main"].global_block().desc.ops
    flash = [o for o in ops if o.type == "flash_attention"]
    assert [o.attrs.get("window", 0) for o in flash] == [0, W, W, W]
    assert all(o.attrs["num_heads"] == H for o in flash)
    # rotated layers turn q and k; the full layer nothing
    assert sum(o.type == "rope" for o in ops) == 2 * sum(LAYOUT)
    routers = [o for o in ops if o.type == "moe_router"]
    experts = [o for o in ops if o.type == "moe_experts"]
    assert len(routers) == len(experts) == L
    norms = [o for o in ops if o.type == "rms_norm"]
    for layer, (router, expert) in enumerate(zip(routers, experts)):
        first, second = norms[2 * layer:2 * layer + 2]
        assert router.input("X") == first.output("Y")
        assert expert.input("X") == second.output("Y")
        assert router.attrs["norm_topk"] is True
        assert expert.attrs == {"first_expert": HELD[0], "scored": E,
                                "activation": "relu"}
    grads = [o for o in ops if o.type == "flash_attention_grad"]
    assert sorted(o.attrs.get("window", 0) for o in grads) == [0, W, W, W]
    assert sum(o.type == "moe_experts_grad" for o in ops) == L


def test_counters_say_what_was_lowered(trained_once):
    lowered = trained_once["lowered"]

    def total(prefix, *fragments):
        return sum(v for key, v in lowered.items() if key.startswith(prefix)
                   and all(f in key for f in fragments))

    # three window layers: a forward kernel and the one backward kernel
    # each; the full layer counts under no window
    assert total("flash_attention_window_lowerings_total",
                 "window=%d" % W, "kernel=fwd") >= 1
    assert total("flash_attention_window_lowerings_total",
                 "window=%d" % W, "kernel=dq_dkv") >= 1
    assert total("moe_share_lowerings_total",
                 "held=%d,scored=%d,top_k=%d" % (HELD[1], E, K)) >= L
    assert total("moe_share_bwd_lowerings_total",
                 "held=%d,scored=%d,top_k=%d" % (HELD[1], E, K)) >= L


def test_a_ragged_layout_is_refused():
    with pytest.raises(ValueError, match="layers"):
        build_smallthinker_program(B, T, V, rope_layout=[0, 1],
                                   window_layout=[0, 1, 1])
