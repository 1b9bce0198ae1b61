"""Hy4-preview's share on the generation path: a chosen set that a
"shared" layer inherits from the "full" layer below it (`indexer_types`),
a residual of several streams mixed through Sinkhorn mappings (`hc_maps`,
`hc_pre`, `hc_post`), a gate on the latent attention's output, a learned
sink in its softmax (`mla_cached_attention` with `Sink`), a clamp in
every gated feed-forward (`swiglu_limit`) and a float32 head, in the
cached step Program `models/latent_moe_program.py` builds from them,
against the plain float32 reference (models/reference/hy4_preview.py):
the step from position 0 and prefill + decode through `ProgramDecoder`
against the reference's full forward; the ops alone; the shares adding
up; the counters; what the builder leaves as it was.

Tiny sizes on the CPU, where selection bites: 4 layers (1 dense; full,
full, shared, shared), hidden 64 in 4 streams, 4 heads of 16 + 8 (values
16), query rank 32, latent 16, 4 index heads of 16 (the first 8 rotated)
choosing 8 of up to 24 slots, 8 experts scored, 2 a token, 4 held, a
non-zero selection bias, a clamp of 10 (which does not bite: that has a
test of its own), vocabulary 97, seeded weights.
"""

import hashlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu.fluid as fluid
from paddle_tpu.models.latent_moe_program import (
    build_latent_moe_cached_step_program, latent_moe_param_names)
from paddle_tpu.models.reference import hy4_preview as reference
from paddle_tpu.obs import telemetry
from paddle_tpu.ops import registry

B, T, V, L, DENSE = 2, 24, 97, 4, 1
H, D, QR, KVR, NOPE, ROPE, DV, FF, FE = 4, 64, 32, 16, 16, 8, 16, 128, 32
E, K, HELD = 8, 2, (2, 4)
IH, ID, TOPK = 4, 16, 8
N, ITERATIONS = 4, 20
TYPES = ["full", "full", "shared", "shared"]
HC = {"streams": N, "eps": 1e-6, "magnitude": 2.0, "iterations": ITERATIONS}
SIZES = dict(n_layer=L, n_dense=DENSE, n_head=H, d_model=D, q_rank=QR,
             kv_rank=KVR, d_nope=NOPE, d_rope=ROPE, d_v=DV, d_ff=FF,
             d_expert=FE, n_experts=E, held=HELD, top_k=K, eps=1e-5,
             routed_scale=2.827, rope_theta=1e7, sandwich_norm=False,
             indexer=(IH, ID, TOPK), router_bias=True, indexer_types=TYPES,
             hc=HC, gated=True, sink=True, swiglu_limit=10.0,
             head_float32=True)
CFG = {"num_hidden_layers": L, "num_attention_heads": H,
       "rms_norm_eps": 1e-5, "rope_parameters": {"rope_theta": 1e7},
       "kv_lora_rank": KVR, "qk_nope_head_dim": NOPE,
       "qk_rope_head_dim": ROPE, "v_head_dim": DV,
       "num_experts_per_tok": K, "norm_topk_prob": True,
       "routed_scaling_factor": 2.827, "index_n_heads": IH,
       "index_head_dim": ID, "index_topk": TOPK, "swiglu_limit": 10.0,
       "hc_mult": N, "hc_eps": 1e-6, "hc_magnitude": 2.0,
       "hc_sinkhorn_iterations": ITERATIONS}
NAMES = latent_moe_param_names(L, DENSE, sandwich_norm=False, indexer=True,
                               router_bias=True, indexer_types=TYPES,
                               hc=True, gated=True, sink=True)

# float32 on the CPU.  As tests/test_dsv32_program.py: the step absorbs
# the up-projections and attends a gathered set where the reference makes
# every head's keys and a masked softmax; here twenty Sinkhorn divisions
# a sub-layer lie on the way too.  Logits of size ~3 were seen to differ
# by 4e-6 of the largest; every wrong choice this file knows
# (`test_a_wrong_choice_shows`) moves them by 1e-2 of it or more.
LOGITS_RTOL = 5e-5


def _start(startup, names=NAMES, seed=5):
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    startup.random_seed = seed
    exe.run(startup, scope=scope)
    rs = np.random.RandomState(seed)
    for name in jax.tree_util.tree_leaves(names):
        value = np.asarray(scope.get(name))
        if value.ndim == 1:
            # norm scales and the mappings' scalars off their 1, biases
            # off their 0; a sink wide enough to take a share of a head
            wide = 1.0 if name.endswith("sink") else \
                0.3 if name.endswith(("router_bias", "_b")) else 0.1
            scope.set(name, jnp.asarray(
                value + wide * rs.randn(*value.shape).astype("float32")))
    return scope


def _decoder(main, logits, pairs, scope, extent=T):
    return fluid.ProgramDecoder(
        main.clone(for_test=True), token_name="tok",
        logits_name=logits.name, state_pairs=pairs, scope=scope,
        max_positions=extent)


def _empty(types=TYPES):
    state = {}
    for i, kind in enumerate(types):
        state["latent_cache_%d" % i] = jnp.zeros((B, T, KVR + ROPE))
        if kind == "full":
            state["index_cache_%d" % i] = jnp.zeros((B, T, ID))
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
    want = np.asarray(reference.forward(CFG, params, jnp.asarray(tokens),
                                        held=HELD))
    return {"main": main, "logits": logits, "pairs": pairs, "parts": parts,
            "scope": scope, "decoder": decoder, "tokens": tokens,
            "got": got, "state": state, "params": params, "want": want,
            "at_build": at_build}


# -- (a) the step against the reference's full forward -------------------------

@pytest.mark.parametrize("position", range(T))
def test_step_logits_agree_with_the_reference_at_every_position(
        built, position):
    """Positions 0..7 attend every live slot (fewer live than `top_k`),
    8..23 the 8 chosen of 9..24; layers 2 and 3 attend what layer 1
    chose, at every position."""
    want = built["want"][:, position]
    got = built["got"][:, position]
    assert got.dtype == np.float32
    assert np.abs(got - want).max() <= LOGITS_RTOL * np.abs(want).max()


@pytest.mark.parametrize("prompt_len", [4, 12])
def test_prefill_then_greedy_is_the_references_greedy(built, prompt_len):
    """Prefill (the step's scan over the prompt) then decode through
    `ProgramDecoder.greedy`, the prompt ending under `top_k` live slots
    and over it: every served token is the reference's first given the
    tokens before it."""
    prompt = built["tokens"][:, :prompt_len]
    gen = T - prompt_len + 1
    tokens, lengths = built["decoder"].greedy(
        bos=0, eos=V, max_len=gen, init_state=_empty(), prompt=prompt)
    assert tokens.shape == (B, gen) and (lengths == gen).all()
    full = np.concatenate([prompt, tokens], axis=1)[:, :T]
    z = np.asarray(reference.forward(CFG, built["params"],
                                     jnp.asarray(full), held=HELD))
    at = prompt_len - 1
    served = tokens[:, :T - at]
    picked = np.take_along_axis(z[:, at:], served[..., None], axis=-1)[..., 0]
    assert (z[:, at:].max(axis=-1) - picked).max() <= 1e-4


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
    (0, 6, 0), (TOPK - 3, 7, 2), (10, 13, 4)],
    ids=["under top_k", "across top_k in tiles of 2 and one over",
         "past top_k in tiles of 4 and one over"])
def test_a_block_through_the_step_is_so_many_single_steps(
        built, probed, start, block, tile, monkeypatch):
    """T tokens of every row in one application, through the inherited
    sets, the sink, the gate and the four streams, against T
    applications of one: the last position's logits, every cache, and
    each layer's set of the block's last position, a shared layer's the
    one it inherits."""
    if tile:
        # a position's index scores are B * IH * T * 4 bytes, its
        # gathered rows and scores B * TOPK * (24 + H) * 4: both ops
        # work through tiles of `tile` positions
        from paddle_tpu.ops import attention
        monkeypatch.setattr(attention, "TILE_BYTES",
                            tile * B * TOPK * (KVR + ROPE + H) * 4)
        assert B * IH * T * 4 <= B * TOPK * (KVR + ROPE + H) * 4
    decoder, with_probes = probed
    tokens = built["tokens"]
    step = decoder._step_fn(decoder._params)
    state = with_probes(_empty())
    if start:
        _, state = _drive(decoder, tokens[:, :start], state)
    want, after = _drive(decoder, tokens[:, start:start + block], state)
    logits, got = step(state, jnp.asarray(tokens[:, start:start + block]))
    assert logits.shape == (B, V) and logits.dtype == jnp.float32
    assert np.abs(np.asarray(logits) - want[:, -1]).max() \
        <= LOGITS_RTOL * np.abs(want[:, -1]).max()
    assert int(got["pos"][0]) == start + block
    for feed, _ in built["pairs"]:
        if feed != "pos":
            np.testing.assert_allclose(
                got[feed], after[feed],
                atol=LOGITS_RTOL * np.abs(np.asarray(after[feed])).max())
    for i in range(L):
        np.testing.assert_array_equal(got["probe_%d.selected" % i],
                                      after["probe_%d.selected" % i])
        assert np.asarray(got["probe_%d.live" % i]).tolist() \
            == [min(TOPK, start + block)] * B
    np.testing.assert_array_equal(got["probe_2.selected"],
                                  got["probe_1.selected"])
    if start + block > TOPK + 1:
        assert (np.asarray(got["probe_0.selected"])
                != np.asarray(got["probe_1.selected"])).any()


def test_a_shared_layer_holds_no_index_cache_and_no_index_weights(built):
    feeds = {name for name, _ in built["pairs"]}
    assert feeds == {"latent_cache_%d" % i for i in range(L)} \
        | {"index_cache_0", "index_cache_1", "pos"}
    params = {p.name for p in
              built["main"].global_block().all_parameters()}
    assert params == set(jax.tree_util.tree_leaves(NAMES))
    for i, kind in enumerate(TYPES):
        held = {w for w in ("w_iq", "w_ik", "ik_norm", "ik_norm_b", "w_iw")
                if "block_%d.%s" % (i, w) in params}
        assert len(held) == (5 if kind == "full" else 0)
    ops = built["main"].global_block().desc.ops
    assert sum(od.type == "mla_index_select" for od in ops) == 2
    assert sum(od.type == "mla_cached_attention" for od in ops) == L


def test_a_shared_layer_attends_the_set_of_the_full_layer_below(built):
    """Layers 2 and 3 read layer 1's `Selected` and `Live`, the very
    Variables; layer 1 its own and not layer 0's."""
    ops = [od for od in built["main"].global_block().desc.ops
           if od.type == "mla_cached_attention"]
    sets = [(od.input("Selected")[0], od.input("Live")[0]) for od in ops]
    assert sets[0] != sets[1] and sets[1] == sets[2] == sets[3]
    # and what a decoder is handed of a layer's set, the block's last
    # position's, is of those Variables: a shared layer's is the very
    # Variable of the layer it inherits from
    handed = [v.name for v in built["parts"]["selected"]]
    assert handed[0] != handed[1] and handed[1] == handed[2] == handed[3]
    made = {od.output("Out")[0]: od.input("X")[0]
            for od in built["main"].global_block().desc.ops
            if od.type in ("gather", "reshape")}
    assert [made[made[name]] for name in handed] == [s for s, _ in sets]


def test_the_sets_reused_are_counted_at_build(built):
    token = built["main"]._cache_token
    assert built["at_build"].get(
        "program_index_sets_reused{program=%s}" % token) == 2
    before = telemetry.snapshot()
    _build(indexer_types=["full"] * L)
    assert not any(k.startswith("program_index_sets_reused")
                   for k in telemetry.snapshot_delta(before))


@pytest.mark.parametrize("wrong", [
    {"indexer_types": ["full", "full", "shared", "shared"],
     "indexer": (IH, ID, TOPK - 1)},
    {"gated": False}, {"sink": False}, {"swiglu_limit": 0.05},
    {"hc": dict(HC, iterations=1)}, {"hc": dict(HC, magnitude=1.0)},
    {"head_float32": False, "rope_theta": 1e4}],
    ids=["top_k-1", "no gate", "no sink", "another clamp",
         "one Sinkhorn iteration", "another magnitude", "another theta"])
def test_a_wrong_choice_shows(built, wrong):
    """The same weights under a program that computes otherwise: the
    logits leave the tolerance by two orders of magnitude and more."""
    main, _, logits, pairs, _ = _build(**wrong)
    got, _ = _drive(_decoder(main, logits, pairs, built["scope"]),
                    built["tokens"], _empty())
    off = np.abs(got - built["want"]).max() / np.abs(built["want"]).max()
    assert off > 100 * LOGITS_RTOL, off


def test_a_shared_layer_that_chose_for_itself_would_show(built):
    """Were the inherited set not read (layer 2 handed layer 0's set in
    place of layer 1's), the logits would leave the tolerance: the
    reference's shared layers attend the nearest full layer below."""
    main, _, logits, pairs, _ = _build()
    ops = [od for od in main.global_block().desc.ops
           if od.type == "mla_cached_attention"]
    ops[2].inputs["Selected"] = list(ops[0].input("Selected"))
    got, _ = _drive(_decoder(main, logits, pairs, built["scope"]),
                    built["tokens"], _empty())
    late = slice(TOPK + 1, None)    # before that every live slot is chosen
    off = np.abs(got[:, late] - built["want"][:, late]).max() \
        / np.abs(built["want"]).max()
    assert off > 100 * LOGITS_RTOL, off


def test_indexer_types_are_checked():
    for types in (["shared", "full", "full", "full"], ["full"] * 3,
                  ["full", "full", "none", "full"]):
        with pytest.raises(ValueError, match="indexer_types"):
            _build(indexer_types=types)
    with pytest.raises(ValueError, match="indexer_types"):
        _build(indexer=None)


# -- (b) the hyper-connection's ops alone --------------------------------------

def _streams(rs, seq=6, dtype=jnp.float32):
    X = jnp.asarray(rs.randn(B, seq, N, D), dtype)
    block = {"hc_attn_p": jnp.asarray(rs.randn(N * D, N * N + 2 * N)
                                      * (N * D) ** -0.5, jnp.float32),
             "hc_attn_a": jnp.asarray(1 + 0.1 * rs.randn(3), jnp.float32),
             "hc_attn_b": jnp.asarray(0.3 * rs.randn(N * N + 2 * N),
                                      jnp.float32)}
    return X, block


def _maps(X, block, iterations=ITERATIONS):
    outs = registry.get_op_info("hc_maps").kernel(
        None, {"X": [X], "P": [block["hc_attn_p"]],
               "Alpha": [block["hc_attn_a"]], "Bias": [block["hc_attn_b"]]},
        {"epsilon": 1e-6, "magnitude": 2.0, "iterations": iterations})
    return outs["Pre"][0], outs["Post"][0], outs["Res"][0]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_hc_maps_are_the_references(dtype):
    X, block = _streams(np.random.RandomState(2), dtype=dtype)
    pre, post, res = _maps(X, block)
    assert pre.dtype == post.dtype == res.dtype == jnp.float32
    for row in range(B):
        with jax.default_matmul_precision("highest"):
            want = reference.hc_maps(CFG, block, "attn",
                                     X[row].astype(jnp.float32))
        for got, ref in zip((pre[row], post[row], res[row]), want):
            np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                       atol=2e-6)


def test_the_stream_mix_is_doubly_stochastic():
    X, block = _streams(np.random.RandomState(3))
    _, post, res = _maps(X, block)
    res = np.asarray(res)
    assert (res > 0).all()
    np.testing.assert_allclose(res.sum(-1), 1.0, atol=1e-5)
    np.testing.assert_allclose(res.sum(-2), 1.0, atol=1e-5)
    assert (np.asarray(post) > 0).all() and (np.asarray(post) < 2).all()
    once = np.asarray(_maps(X, block, iterations=1)[2])
    assert np.abs(once.sum(-1) - 1.0).max() > 1e-2   # what 20 are for


@pytest.mark.parametrize("dtype,atol", [(jnp.float32, 1e-5),
                                        (jnp.bfloat16, 5e-2)])
def test_hc_pre_and_hc_post_are_the_references(dtype, atol):
    rs = np.random.RandomState(4)
    X, block = _streams(rs, dtype=dtype)
    y = jnp.asarray(rs.randn(B, X.shape[1], D), dtype)
    pre, post, res = _maps(X, block)
    u = registry.get_op_info("hc_pre").kernel(
        None, {"X": [X], "Pre": [pre]}, {})["U"][0]
    out = registry.get_op_info("hc_post").kernel(
        None, {"X": [X], "Res": [res], "Post": [post], "Y": [y]},
        {})["XOut"][0]
    assert u.dtype == out.dtype == dtype
    assert u.shape == (B, X.shape[1], D) and out.shape == X.shape
    f32 = jnp.float32
    for row in range(B):
        np.testing.assert_allclose(
            np.asarray(u[row], np.float32),
            np.asarray(reference.hc_pre(X[row].astype(f32), pre[row])),
            atol=atol)
        np.testing.assert_allclose(
            np.asarray(out[row], np.float32),
            np.asarray(reference.hc_post(X[row].astype(f32), res[row],
                                         post[row], y[row].astype(f32))),
            atol=atol)


def test_hc_maps_refuse_parameters_of_another_shape():
    X, block = _streams(np.random.RandomState(5))
    with pytest.raises(ValueError, match="hc_maps"):
        _maps(X, dict(block, hc_attn_b=block["hc_attn_b"][:-1]))


# -- (c) the sink ---------------------------------------------------------------

def _mla_ins(rs, pos, chosen=None):
    ins = {
        "QNope": [jnp.asarray(rs.randn(B, 1, H * NOPE), jnp.float32)],
        "QRope": [jnp.asarray(rs.randn(B, 1, H * ROPE), jnp.float32)],
        "CNew": [jnp.asarray(rs.randn(B, 1, KVR), jnp.float32)],
        "RNew": [jnp.asarray(rs.randn(B, 1, ROPE), jnp.float32)],
        "Cache": [jnp.asarray(rs.randn(B, T, KVR + ROPE),
                              jnp.float32).at[:, pos:].set(0)],
        "WUk": [jnp.asarray(0.2 * rs.randn(KVR, H * NOPE), jnp.float32)],
        "WUv": [jnp.asarray(0.2 * rs.randn(KVR, H * DV), jnp.float32)],
        "Position": [jnp.full((B,), pos, jnp.int32)]}
    if chosen is not None:
        ins["Selected"] = [jnp.asarray(chosen, jnp.int32)]
        ins["Live"] = [jnp.full((B,), min(len(chosen[0]), pos + 1),
                                jnp.int32)]
    return ins


@pytest.mark.parametrize("pos,chosen", [
    (5, None), (T - 1, None),
    (12, [[0, 3, 4, 7, 9, 10, 11, 12], [1, 2, 3, 5, 8, 9, 11, 12]]),
    (2, [[0, 1, 2, 23, 22, 21, 20, 19]] * 2)],
    ids=["every slot", "a full cache", "a chosen set", "fewer live"])
def test_the_sink_is_a_slot_with_no_value(pos, chosen):
    """With `Sink` the softmax is the one over the attended slots and
    one more whose logit is the head's sink and whose value is zero:
    appended to the cache as a slot of latents 0 that every head scores
    Sink_h (the test makes such a slot through the rotated key)."""
    rs = np.random.RandomState(pos)
    ins = _mla_ins(rs, pos, chosen)
    sink = jnp.asarray(rs.randn(H), jnp.float32)
    kernel = registry.get_op_info("mla_cached_attention").kernel
    got = kernel(None, dict(ins, Sink=[sink]), {"num_heads": H})["Out"][0]
    without = kernel(None, ins, {"num_heads": H})["Out"][0]
    assert np.abs(np.asarray(got) - np.asarray(without)).max() > 1e-3

    # attention over the heads' keys, a sink column beside the scores
    live = np.arange(pos + 1) if chosen is None \
        else np.asarray(chosen)[:, :min(len(chosen[0]), pos + 1)]
    cache = np.asarray(ins["Cache"][0]).copy()
    cache[:, pos] = np.concatenate([np.asarray(ins["CNew"][0])[:, 0],
                                    np.asarray(ins["RNew"][0])[:, 0]], -1)
    w_uk = np.asarray(ins["WUk"][0]).reshape(KVR, H, NOPE)
    w_uv = np.asarray(ins["WUv"][0]).reshape(KVR, H, DV)
    for row in range(B):
        slots = live if chosen is None else live[row]
        c, r = cache[row, slots, :KVR], cache[row, slots, KVR:]
        k = np.einsum("tc,chd->thd", c, w_uk)
        v = np.einsum("tc,chd->thd", c, w_uv)
        q_n = np.asarray(ins["QNope"][0])[row, 0].reshape(H, NOPE)
        q_r = np.asarray(ins["QRope"][0])[row, 0].reshape(H, ROPE)
        s = (np.einsum("hd,thd->ht", q_n, k)
             + np.einsum("hd,td->ht", q_r, r)) * (NOPE + ROPE) ** -0.5
        s = np.concatenate([s, np.asarray(sink)[:, None]], axis=1)
        p = np.exp(s - s.max(-1, keepdims=True))
        p = p / p.sum(-1, keepdims=True)
        v = np.concatenate([v, np.zeros((1, H, DV))], axis=0)
        want = np.einsum("ht,thd->hd", p, v).reshape(-1)
        np.testing.assert_allclose(np.asarray(got)[row, 0], want, atol=2e-5)


def test_a_sink_far_below_the_scores_changes_nothing():
    ins = _mla_ins(np.random.RandomState(9), 7)
    kernel = registry.get_op_info("mla_cached_attention").kernel
    got = kernel(None, dict(ins, Sink=[jnp.full((H,), -80.0)]),
                 {"num_heads": H})["Out"][0]
    want = kernel(None, ins, {"num_heads": H})["Out"][0]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-6)


# -- (d) the clamp ----------------------------------------------------------------

def _expert_ins(rs, n=6):
    x = jnp.asarray(rs.randn(n, D), jnp.float32)
    top_idx = jnp.asarray(np.stack([rs.choice(E, K, replace=False)
                                    for _ in range(n)]), jnp.int32)
    top_w = jnp.asarray(rs.uniform(0.2, 1.0, (n, K)), jnp.float32)
    weights = [jnp.asarray(0.3 * rs.randn(E, a, b), jnp.float32)
               for a, b in ((D, FE), (D, FE), (FE, D))]
    return x, top_w, top_idx, weights


@pytest.mark.parametrize("limit", [0.3, 1.0])
def test_the_experts_clamp_bites(limit):
    """`moe_experts` under `swiglu_limit`: down(silu(min(gate, L)) *
    clip(up, -L, L)), against dense experts; the limit is small enough
    that a fifth of the pre-activations and more are clamped."""
    x, top_w, top_idx, (w_gate, w_up, w_down) = _expert_ins(
        np.random.RandomState(6))
    ins = {"X": [x], "TopW": [top_w], "TopIdx": [top_idx],
           "WGate": [w_gate], "WUp": [w_up], "WDown": [w_down]}
    kernel = registry.get_op_info("moe_experts").kernel
    got = kernel(None, ins, {"swiglu_limit": limit})["Out"][0]
    plain = kernel(None, ins, {})["Out"][0]
    gate = np.einsum("nd,edf->nef", x, w_gate)
    up = np.einsum("nd,edf->nef", x, w_up)
    assert (np.abs(up) > limit).mean() > 0.2
    g = np.minimum(gate, limit)
    hidden = g / (1 + np.exp(-g)) * np.clip(up, -limit, limit)
    each = np.einsum("nef,efd->ned", hidden, w_down)
    want = sum(np.asarray(top_w)[:, j, None]
               * each[np.arange(x.shape[0]), np.asarray(top_idx)[:, j]]
               for j in range(K))
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-5)
    assert np.abs(np.asarray(got) - np.asarray(plain)).max() > 1e-2


def test_the_clamped_experts_have_a_gradient():
    """The clamp is flat past the limit on both sides of the product:
    the op's explicit gradient against jax's of the dense form."""
    x, top_w, top_idx, (w_gate, w_up, w_down) = _expert_ins(
        np.random.RandomState(7))
    limit = 0.5
    rows = np.arange(x.shape[0])

    def dense(x, w_gate, w_up, w_down):
        gate = jnp.einsum("nd,edf->nef", x, w_gate)
        up = jnp.einsum("nd,edf->nef", x, w_up)
        hidden = jax.nn.silu(jnp.minimum(gate, limit)) \
            * jnp.clip(up, -limit, limit)
        each = jnp.einsum("nef,efd->ned", hidden, w_down)
        return sum(top_w[:, j, None] * each[rows, top_idx[:, j]]
                   for j in range(K))

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        xv = fluid.layers.data(name="x", shape=[x.shape[0], D],
                               dtype="float32", append_batch_size=False)
        xv.stop_gradient = False
        out, _, _, routing = fluid.layers.moe(
            xv, E, FE, K, swiglu_limit=limit, scoring="sigmoid",
            gate_attr=fluid.ParamAttr(name="g"),
            up_attr=fluid.ParamAttr(name="u"),
            down_attr=fluid.ParamAttr(name="d"))
        loss = fluid.layers.reduce_sum(out * out)
        grads = fluid.backward.append_backward(loss)
    by_name = {p.name: g for p, g in grads}
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup, scope=scope)
    for name, value in (("g", w_gate), ("u", w_up), ("d", w_down)):
        scope.set(name, value)
    fetched = exe.run(main, feed={"x": np.asarray(x)}, scope=scope,
                      fetch_list=[routing["top_w"], routing["top_idx"],
                                  by_name["g"], by_name["u"], by_name["d"]])
    top_w, top_idx = jnp.asarray(fetched[0]), jnp.asarray(fetched[1])
    want = jax.grad(lambda *w: jnp.sum(jnp.square(dense(x, *w))),
                    argnums=(0, 1, 2))(w_gate, w_up, w_down)
    for got, ref in zip(fetched[2:], want):
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=2e-4)


def test_the_dense_clamp_bites(built):
    """`gated_feed_forward` under a limit, through a Program."""
    from paddle_tpu.models.decoder_block import gated_feed_forward

    rs = np.random.RandomState(8)
    u = rs.randn(B, 3, D).astype("float32")
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        uv = fluid.layers.data(name="u", shape=[B, 3, D], dtype="float32",
                               append_batch_size=False)
        out = gated_feed_forward(uv, FF, {"w_in": "w_in", "w_out": "w_out"},
                                 limit=0.4)
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup, scope=scope)
    got, = exe.run(main, feed={"u": u}, scope=scope, fetch_list=[out])
    cfg = dict(CFG, swiglu_limit=0.4)
    want = reference.gated(cfg, jnp.asarray(u.reshape(-1, D)),
                           scope.get("w_in"), scope.get("w_out"))
    np.testing.assert_allclose(np.asarray(got).reshape(-1, D),
                               np.asarray(want), atol=2e-5)
    free = reference.gated(dict(CFG, swiglu_limit=None),
                           jnp.asarray(u.reshape(-1, D)),
                           scope.get("w_in"), scope.get("w_out"))
    assert np.abs(np.asarray(free) - np.asarray(want)).max() > 1e-2


# -- (e) the shares of an expert layer add up ---------------------------------

@pytest.mark.parametrize("count", [1, 2, 4, 8])
def test_the_shares_add_up_to_the_uncut_layer(built, count):
    """The guide's share test on the reference the cell is held to: the
    held parts of all E / count shares, the shared expert counted once,
    are the uncut layer's feed-forward (clamped experts, no groups)."""
    rs = np.random.RandomState(7)
    block = {k: jnp.asarray(v) for k, v in built["params"]["blocks"][1].items()}
    whole = dict(block, **{
        w: jnp.asarray(0.1 * rs.randn(E, *np.asarray(block[w]).shape[1:]),
                       jnp.float32) for w in ("w_gate", "w_up", "w_down")})
    cfg = dict(CFG, swiglu_limit=0.2)   # a clamp that bites in every share
    u = jnp.asarray(rs.randn(10, D), jnp.float32)
    with jax.default_matmul_precision("highest"):
        want = reference.feed_forward(cfg, whole, u)
        total = reference.gated(cfg, u, whole["shared_in"],
                                whole["shared_out"])
        for first in range(0, E, count):
            share = dict(whole, **{w: whole[w][first:first + count]
                                   for w in ("w_gate", "w_up", "w_down")})
            total = total + reference.feed_forward(cfg, share, u, first,
                                                   shared=False)
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               atol=1e-5)


# -- (f) the float32 head ---------------------------------------------------------

def test_a_float32_head_rounds_neither_operand():
    """`mul` with `float32` over a bfloat16 weight: the product of the
    float32 input and the weight as it lies, summed in float32, where
    the op without it rounds the input to bfloat16 first."""
    rs = np.random.RandomState(10)
    x = jnp.asarray(rs.randn(5, 3, D), jnp.float32)
    w = jnp.asarray(rs.randn(D, 40), jnp.bfloat16)
    kernel = registry.get_op_info("mul").kernel
    attrs = {"x_num_col_dims": 2, "y_num_col_dims": 1}
    got = kernel(None, {"X": [x], "Y": [w]}, dict(attrs, float32=True))
    got = got["Out"][0]
    want = np.asarray(x, np.float64).reshape(-1, D) \
        @ np.asarray(w.astype(jnp.float32), np.float64)
    assert got.dtype == jnp.float32 and got.shape == (5, 3, 40)
    np.testing.assert_allclose(np.asarray(got).reshape(-1, 40), want,
                               atol=1e-5)
    w32 = w.astype(jnp.float32)
    also = kernel(None, {"X": [x], "Y": [w32]},
                  dict(attrs, float32=True))["Out"][0]
    np.testing.assert_allclose(np.asarray(also), np.asarray(got), atol=1e-5)


# -- (g) what the PR leaves as it was ---------------------------------------------

def _listing(main):
    return repr([(od.type, sorted((k, tuple(v)) for k, v in od.inputs.items()),
                  sorted((k, tuple(v)) for k, v in od.outputs.items()),
                  sorted((k, repr(v)) for k, v in od.attrs.items()))
                 for od in main.global_block().desc.ops])


@pytest.mark.parametrize("options,digest", [
    ({}, "ad44034b7e904781"),
    (dict(sandwich_norm=False, indexer=(2, 8, 4), n_group=4, topk_group=2,
          router_bias=True, yarn={
              "factor": 40, "original_positions": 4096, "beta_fast": 32,
              "beta_slow": 1, "mscale": 1}), "820727a57c275686"),
], ids=["openpangu-ultra-moe-718b", "deepseek-v3.2"])
def test_the_builders_other_programs_are_op_for_op_the_parents(options,
                                                               digest):
    """The options PR 61 added default to what the builder did: the
    step Programs of the two configurations that share it are, op for op
    and attr for attr, what they are without them (the digests are
    tests/test_window_moe_program.py's: pangu's as PR 53 set it,
    DeepSeek-V3.2's as PR 62 did, when a chooser's step took a block)."""
    main = build_latent_moe_cached_step_program(2, 16, 97, **options)[0]
    assert hashlib.sha256(_listing(main).encode()).hexdigest()[:16] == digest


def test_this_steps_program_digest(built):
    """A change to what the builder makes under Hy4-preview's options
    shows here."""
    assert hashlib.sha256(_listing(built["main"]).encode()).hexdigest()[:16] \
        == DIGEST


DIGEST = "913f924a169bce6d"
