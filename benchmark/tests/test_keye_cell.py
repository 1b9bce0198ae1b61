"""The sparse key/value cell `keye-turn-64k-ep8`: its driver end to end as
a CPU rehearsal at a toy size (fixture `keye-tiny-turn`, found through
`--search-path`), the controls that `correct` has to refuse, the cell's
copy of the reference against the program's own, the session it makes
and the images it lays out, the bytes and operations of a decode step
against counts made by hand, the new readers on a written trace, and
BENCHMARK.json's entries for the cell.
"""

import json
import os
import types

import pytest

from benchmark.harness import CHECKOUT, Lookup
from benchmark.reduce import op_scopes, sparse_ops, xplane
from benchmark.tests import session_control, sparse_control
from benchmark.tests.test_run import FIXTURE, last_line, run_cell

CELL = "keye-turn-64k-ep8"
CONFIG = "keye-vl-2.0-30b-a3b"
TOY, TOY_CONFIG = "keye-tiny-turn", "keye-tiny"
NEW_READERS = ("sparse_kv_ms_per_step", "sparse_kv_select_ms_per_step",
               "sparse_kv_index_roofline", "sparse_kv_attend_roofline",
               "sparse_moe_ms_per_step", "sparse_decode_step_ms",
               "sparse_prefill_ms_per_call", "sparse_restore_ms_per_call",
               "sparse_decode_hbm_roofline")
JOINED = ("decode_trace_lower_s", "decoder_prep_ms_per_call",
          "decoder_idle_ms_per_call", "prefill_device_ms_per_call",
          "decode_device_step_ms", "decode_unscoped_ms_per_step")
# each with the limit that has to refuse it at the toy size
CONTROLS = {
    "serve_dtype=float8_e4m3fn": "attn_off_first",
    "index_dtype=float8_e4m3fn": "selected_share",
    "index_topk=4": "selected_share",
    session_control.RECENT: "selected_share",
    "rope_delta_zero=true": "attn_off_first",
    'session_control={"swap_hw":true}': "attn_off_first"}
FLOORS = ("selected_share",)
PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
LOOKUP = Lookup([FIXTURE])
sparse_kv = LOOKUP.module("flops", "sparse_kv")


# -- the driver, end to end -----------------------------------------------------

def test_untraced_rehearsal_has_exactly_the_two_metrics():
    result = last_line(run_cell(TOY, 0))
    assert result["correct"] is True and result["failed"] == 0
    assert set(result["metrics"]) == {"decode_tok_per_s", "setup_s"}
    assert result["metrics"]["decode_tok_per_s"]["unit"] == "tok/s"
    assert result["attempted"] % 4 == 0 and result["attempted"] >= 8
    assert result["device"]["platform"] == "cpu"


def test_traced_rehearsal_reads_counters_and_no_device_metric():
    proc = run_cell(TOY, 1)
    result = last_line(proc)
    metrics = result["metrics"]
    assert result["correct"] is True
    assert metrics["compiles_in_window"] == {"value": 0, "unit": "count"}
    assert {"setup_compile_s", "setup_cache_misses",
            "decode_trace_lower_s"} <= set(metrics)
    # what only a chip can say: this cell's and its neighbours'
    assert not (set(NEW_READERS) | {
        "dsa_ms_per_step", "session_decode_step_ms", "kv_attn_ms_per_step",
        "long_decode_step_ms", "decode_hbm_roofline"}) & set(metrics)
    for stream in (proc.stdout, proc.stderr):
        for name in ("gap_mean", "selected_share", "attn_off",
                     "attn_off_first", "held_part_off"):
            assert "check ok  : %s" % name in stream
        assert "check ok  : no compile inside the windows (0), limit 0" \
            in stream
    assert "rope_delta -6" in proc.stdout


# -- what `correct` has to refuse -----------------------------------------------

def _limits(workload):
    limits = workload["correct"]
    return limits, sorted(set(limits) - {"why"})


def _kept(got, limits, name):
    return got[name] >= limits[name] if name in FLOORS \
        else got[name] <= limits[name]


@pytest.mark.parametrize("seed", [5, 4800000123])
def test_the_sound_path_keeps_the_limits(seed):
    import jax

    workload = dict(LOOKUP.json("workloads", TOY), name=TOY)
    limits, names = _limits(workload)
    sound = sparse_control.read(LOOKUP, workload, seed, jax.devices()[:1],
                                None)
    assert all(_kept(sound, limits, n) for n in names), sound
    assert sound["rows"] == workload["checked_rows"]
    assert sound["tokens"] == workload["checked_rows"] * workload["gen_len"]
    assert len(sound["selected_share_by_layer"]) == 3 == \
        len(sound["attn_off_by_layer"]) == \
        len(sound["held_part_off_by_layer"])


@pytest.mark.parametrize("control", sorted(CONTROLS))
def test_the_control_is_not_correct(control):
    """The program's own path with keys and values cached in float8, with
    the chooser's keys cached in float8, with half as many slots chosen,
    with the most recent slots in place of the chosen, with the slot
    taken for the position, and over a session whose images were laid
    out with height and width exchanged, each fail the limit named for
    it, which the cell as stated keeps."""
    import jax

    workload = dict(LOOKUP.json("workloads", TOY), name=TOY)
    limits, names = _limits(workload)
    got = sparse_control.read(LOOKUP, workload, 5, jax.devices()[:1], None,
                              control)
    assert not _kept(got, limits, CONTROLS[control]), got


# -- the seeded weights, the images and the session -----------------------------

def _toy(dtype="float32"):
    cfg = LOOKUP.json("configs", TOY_CONFIG)
    workload = LOOKUP.json("workloads", TOY)
    spec = dict(workload["weights"], dtype=dtype)
    return cfg, workload, spec, LOOKUP.module("models", "keye_decode")


def test_the_weights_draw():
    """A block made alone is the block of the whole tree (the reference
    asks for one layer at a time); the program's parameters are the
    tree's; the spec's keys do what they say."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    cfg, _, spec, model = _toy("bfloat16")
    key = jax.random.PRNGKey(3000000019)
    tree = jax.jit(lambda k: model.weights(cfg, spec, k))(key)
    alone = jax.jit(lambda k: model.block(cfg, spec, model.root(k), 2))(key)
    for name, value in alone.items():
        np.testing.assert_array_equal(value, tree["blocks"][2][name])
    block = tree["blocks"][1]
    assert block["ik_norm_b"].dtype == jnp.float32
    assert block["w_iq"].dtype == jnp.bfloat16
    assert not any("shared" in name or "bias" in name.replace(
        "ik_norm_b", "") for name in block)
    assert block["router"].shape == (cfg["hidden_size"],
                                     cfg["scored_experts"])
    assert block["w_gate"].shape[0] == cfg["num_experts"]
    plain = model.weights(cfg, dict(spec, dtype="float32"), key)
    gained = model.weights(cfg, dict(spec, dtype="float32", qk_gain=5.0),
                           key)
    for was, now in zip(plain["blocks"], gained["blocks"]):
        np.testing.assert_allclose(now["q_norm"], 2.0 * was["q_norm"],
                                   rtol=1e-6)
        np.testing.assert_array_equal(now["k_norm"], was["k_norm"])
    built = model.build(cfg, 4)
    assert set(jax.tree_util.tree_leaves(built["param_names"])) == {
        p.name for p in built["main"].global_block().all_parameters()}
    assert sorted(built["cache_shapes"]) == sorted(
        "%s_cache_%d" % (kind, i) for kind in ("k", "v", "index")
        for i in range(3))
    assert [sorted(pairs) for _, pairs in built["probes"]] == [
        ["attn_in", "attn_out", "idx", "in", "out", "selected"]] * 3
    assert [f for f, _ in built["state_pairs"]][-2:] == ["pos",
                                                         "rope_delta"]


def test_images_are_the_seeds_and_lag_the_position():
    import numpy as np

    cfg, workload, _, model = _toy()
    seen = model.images(cfg, workload, 4800000123)
    again = model.images(cfg, workload, 4800000123)
    other = model.images(cfg, workload, 4800000124)
    assert seen["spans"] == again["spans"] != other["spans"]
    np.testing.assert_array_equal(seen["vectors"], again["vectors"])
    assert seen["vectors"].shape == (2, 12, 64) and \
        seen["vectors"].dtype == np.float32
    assert seen["slots"].shape == (2, 12)
    assert seen["positions"].shape == (3, 2, 32)
    # two images of 2 x 3 tokens: each advances the position by 3 over 6
    assert seen["rope_delta"] == -6
    for d, spans in enumerate(seen["spans"]):
        assert [tuple(span[1:]) for span in spans] == [(2, 3), (2, 3)]
        (first, _, _), (second, _, _) = spans
        assert 0 <= first <= 10 and 16 <= second <= 26
        p = seen["positions"][:, d]
        assert (p[:, :first] == np.arange(first)).all()
        # the image's rows and columns from its first position on
        assert p[:, first:first + 6].tolist() == [
            [first] * 6, [first, first, first, first + 1, first + 1,
                          first + 1],
            [first, first + 1, first + 2] * 2]
        assert (p[:, first + 6] == first + 3).all()
        assert (p[:, -1] == 31 - 6).all()
    # the cell's: 12 spans of 32 x 32 in 64,512 slots
    cell = LOOKUP.json("workloads", CELL)
    assert -cell["image_spans"] * (32 * 32 - 32) == -11904
    assert cell["session_len"] // cell["image_spans"] >= 32 * 32


@pytest.fixture(scope="module")
def toy_forward():
    """The program's own reference over the toy documents with their
    images, and the seeded parameters."""
    import jax
    from paddle_tpu.models.reference import keye_vl2 as whole

    cfg, workload, spec, model = _toy()
    key = jax.random.PRNGKey(3)
    params = model.weights(cfg, spec, key)
    tokens = model.documents(cfg, workload, 7)
    seen = model.images(cfg, workload, 7)
    held = (cfg["first_expert"], cfg["num_experts"])
    want = whole.forward(cfg, params, tokens, positions=seen["positions"],
                         vectors=seen["vectors"],
                         image_slots=seen["slots"], held=held)
    return cfg, spec, model, key, params, tokens, seen, want, whole, held


def test_the_cells_copy_is_the_programs_reference():
    here = os.path.join(CHECKOUT, "benchmark", "reference", "keye_vl2.py")
    there = os.path.join(CHECKOUT, "paddle_tpu", "models", "reference",
                         "keye_vl2.py")
    with open(here) as a, open(there) as b:
        assert a.read() == b.read()


def test_the_session_is_what_the_programs_reference_caches(toy_forward):
    """In blocks of queries, a layer's parameters at a time, the session
    holds the keys, values and index keys the plain forward makes, and
    the kept document's layer inputs are the plain forward's."""
    import numpy as np

    cfg, spec, model, key, _, tokens, seen, want, _, _ = toy_forward
    reference = LOOKUP.module("reference", "keye_vl2")
    root = model.root(key)
    made, inputs = reference.session(
        cfg, model.ends(cfg, spec, root),
        lambda i: model.block(cfg, spec, root, i), tokens,
        seen["positions"], seen["vectors"], seen["slots"], 8, keep={1})
    assert len(made) == cfg["num_hidden_layers"]
    for i, (keys, values, index_keys) in enumerate(made):
        assert keys.shape == values.shape == (2, 2, 32, 16)
        assert index_keys.shape == (2, 32, 8)
        np.testing.assert_allclose(
            keys, np.asarray(want["keys"][i]).transpose(0, 2, 1, 3),
            atol=2e-5)
        np.testing.assert_allclose(
            values, np.asarray(want["values"][i]).transpose(0, 2, 1, 3),
            atol=2e-5)
        np.testing.assert_allclose(index_keys, want["index_keys"][i],
                                   atol=2e-5)
    assert sorted(inputs) == [1] and len(inputs[1]) == len(made)
    for i, x in enumerate(inputs[1][1:]):
        np.testing.assert_allclose(x, want["hidden"][i][1], atol=2e-5)


def test_the_reference_reads_no_gap_for_its_own_first_tokens(toy_forward):
    """`gaps` continued from the session's kept inputs reads 0 for the
    plain forward's own greedy continuation of a text turn, one altered
    token opens a gap at its position alone, and the last step's numbers
    of the reference's own choices read 1 and 0."""
    import jax.numpy as jnp
    import numpy as np

    cfg, spec, model, key, params, tokens, seen, _, whole, held = \
        toy_forward
    reference = LOOKUP.module("reference", "keye_vl2")
    root = model.root(key)
    ends = model.ends(cfg, spec, root)

    def block_of(i):
        return model.block(cfg, spec, root, i)

    _, inputs = reference.session(
        cfg, ends, block_of, tokens, seen["positions"], seen["vectors"],
        seen["slots"], 8, keep={0, 1})
    question = np.random.default_rng(1).integers(0, 97, (2, 8),
                                                 dtype=np.int32)

    def positions_of(length):
        turn = 32 + seen["rope_delta"] + np.arange(length - 32)
        return np.concatenate([seen["positions"], np.broadcast_to(
            turn, (3, 2, turn.size))], axis=2)

    def forward(fed):
        return whole.forward(
            cfg, params, fed, positions=positions_of(fed.shape[1]),
            vectors=seen["vectors"], image_slots=seen["slots"], held=held)

    served = np.zeros((2, 0), np.int32)
    for _ in range(8):
        fed = np.concatenate([tokens, question, served], axis=1)
        z = forward(fed)["logits"]
        served = np.concatenate(
            [served, np.asarray(jnp.argmax(z[:, -1], -1))[:, None].astype(
                np.int32)], axis=1)
    fed = np.concatenate([tokens, question, served], axis=1)    # 48 slots
    out = forward(fed)
    at = fed.shape[1] - 2
    layers = cfg["num_hidden_layers"]
    top_k = cfg["sa_config"]["topk"]
    own = [np.stack([np.flatnonzero(np.asarray(out["selection"][i][b, at]))
                     for b in range(2)]) for i in range(layers)]
    assert all(o.shape == (2, top_k) for o in own)
    embedded = [whole.embed(whole._f32(params), fed[b], seen["vectors"][b],
                            seen["slots"][b]) for b in range(2)]
    attn_in = [np.asarray(whole.rms_norm(
        out["hidden"][i - 1][:, at] if i
        else jnp.stack([e[at] for e in embedded]),
        params["blocks"][i]["input_norm"], cfg["rms_norm_eps"]))
        for i in range(layers)]
    last = {"at": at, "live": top_k, "selected": own, "attn_in": attn_in}

    def gaps(served, last=None):
        whole_seq = np.concatenate([tokens, question, served], axis=1)
        return reference.gaps(
            cfg, ends, block_of, whole_seq,
            positions_of(whole_seq.shape[1]), 32 + 8 - 1, served, 8, last,
            prefix=[inputs[0], inputs[1]])

    found, step = gaps(served, last)
    assert np.asarray(found).shape == (2, 8)
    assert float(np.asarray(found).max()) <= 1e-5
    for i in range(layers):
        assert step["shared"][i] == [1.0, 1.0]
        want = np.asarray(out["attn"][i][:, at])
        np.testing.assert_allclose(np.stack(step["attn"][i]), want,
                                   atol=2e-5 * np.abs(want).max())
    wrong = served.copy()
    wrong[1, 2] = (wrong[1, 2] + 1) % 97
    opened = np.asarray(gaps(wrong)[0])
    assert opened[1, 2] > 1e-3 and opened[0].max() <= 1e-5 and \
        opened[1, :2].max() <= 1e-5
    # half of the chosen slots swapped for others: the share says so
    rest = [np.stack([np.setdiff1d(np.arange(at + 1), o[b])[:top_k // 2]
                      for b in range(2)]) for o in own]
    swapped = [np.concatenate([o[:, :top_k // 2], r], axis=1)
               for o, r in zip(own, rest)]
    shared = gaps(served, dict(last, selected=swapped))[1]["shared"]
    assert all(0.3 <= s <= 0.8 for per in shared for s in per)


# -- the bytes and operations a step requires -----------------------------------

def test_step_bytes_by_hand():
    cfg = {"hidden_size": 8, "num_attention_heads": 4,
           "num_key_value_heads": 2, "head_dim": 2,
           "moe_intermediate_size": 4, "scored_experts": 8,
           "num_experts": 2, "num_experts_per_tok": 2,
           "num_hidden_layers": 3, "vocab_size": 10,
           "sa_config": {"indexer_num_heads": 2, "indexer_head_dim": 4,
                         "topk": 4}}
    # attention: input norm 8, q and k norms 2 + 2, W_q 8 x 8 = 64, W_k and
    # W_v 8 x 4 each, W_o 8 x 8 = 64; the chooser: W_iq 8 x 8 = 64, W_ik
    # 8 x 4 = 32, LayerNorm 8, W_w 8 x 2 = 16
    assert sparse_kv.attention_parameters(cfg) == 204 + 120
    # every layer: that, the norm before the experts (8) and the router
    # 8 x 8; the head: a norm 8 and 8 x 10; looked up: 3 token rows
    fixed = 3 * (324 + 8 + 64) + 88 + 24
    assert sparse_kv.fixed_weight_bytes(cfg, 3, 2) == fixed * 2
    # slot 5: 6 live keys of 4 values, 3 layers, 3 rows; 2 heads
    assert sparse_kv.index_step(cfg, 3, 5, 2) == {
        "flops": 3 * 2 * 3 * 2 * 4 * 6, "bytes": 3 * 3 * 6 * 4 * 2}
    # 4 chosen of the 6 live: a key and a value of 2 heads of 2; scores
    # and values 4 heads x 2 a slot each
    assert sparse_kv.attend_step(cfg, 3, 5, 2) == {
        "flops": 3 * 2 * 2 * 3 * 4 * 2 * 4, "bytes": 3 * 3 * 4 * 8 * 2}
    # fewer live than asked for: all of them
    assert sparse_kv.attend_step(cfg, 3, 1, 2)["bytes"] == 3 * 3 * 2 * 8 * 2
    assert sparse_kv.step_bytes(cfg, 3, 5, 2, 2, 2) == \
        fixed * 2 + 432 + 576


def test_step_bytes_of_the_cell():
    """The issue's arithmetic: a token's caches 2176 B a layer; 67 MB of
    live index keys and 34 MB of chosen keys and values a layer and
    step."""
    cfg = LOOKUP.json("configs", CONFIG)
    workload = LOOKUP.json("workloads", CELL)
    rows = workload["batch"]
    assert sparse_kv.attention_parameters(cfg) == pytest.approx(
        18.87e6 + 2.26e6, rel=2e-3)
    assert (sparse_kv.entry_width(cfg)
            + cfg["sa_config"]["indexer_head_dim"]) * 2 == 2176
    at = workload["session_len"] + workload["prompt_len"] \
        + (workload["gen_len"] - 2) / 2.0
    index = sparse_kv.index_step(cfg, rows, at, 2)
    assert index["bytes"] / 5 == pytest.approx(67e6, rel=0.01)
    attend = sparse_kv.attend_step(cfg, rows, at, 2)
    assert attend["bytes"] == 5 * 8 * 2048 * 1024 * 2
    assert attend["bytes"] / 5 == pytest.approx(34e6, rel=0.02)
    # both are memory-bound on the v5e
    for cost in (index, attend):
        assert cost["bytes"] / 819e9 > cost["flops"] / 197e12
    assert sparse_kv.fixed_weight_bytes(cfg, rows, 2) == pytest.approx(
        0.292e9, rel=0.02)
    assert sparse_kv.step_bytes(cfg, rows, at, 2, 2, 2) == pytest.approx(
        0.797e9, rel=0.02)
    # the configuration's own arithmetic: one chip's weights
    held = 16 * 3 * 2048 * 768
    layer = sparse_kv.attention_parameters(cfg) + 2048 + 2048 * 128 + held
    total = 5 * layer + 2 * 18992 * 2048 + 2048
    assert total * 2 == pytest.approx(1.13e9, rel=0.01)


# -- the readers ------------------------------------------------------------------

MARK = "~"
PATH = "jit(<lambda>)/while/body/closed_call/%s/~%s/%s"
FACTS = {"sparse_call_ms": 13700.0, "sparse_prefill_ms": 2500.0,
         "sparse_restore_ms": 900.0, "sparse_gen_len": 896,
         "sparse_prompt_len": 128, "sparse_session_len": 64512,
         "sparse_batch": 8, "sparse_calls": 2,
         "sparse_traced_call_ms": 13700.0,
         "sparse_step_applications": 1023, "decode_trace_lower_s": 4.3,
         "setup_compile_s": 75.0, "setup_cache_misses": 39,
         "compiles_in_window": 0, "memory_peak_bytes": 12_900_000_000,
         "decode_tok_per_s": 523.0}


class Written(types.SimpleNamespace):
    """Hashable, as harness.Run is: some readers keep what they reduced
    by the run."""
    __hash__ = object.__hash__


def written_run(facts=FACTS, peaks=PEAKS):
    """A run whose traced call spans 14 s: a prefill scan busy 1.8 of
    its 2 s, a decoding scan busy 11 of its 11.5: 1 s under `dsa_index`,
    4 under `dsa_select`, 1 under `kv_gather`, 0.5 under `attn_sparse`,
    0.5 under `kv_write`, 2 in a grouped-product kernel under
    `moe_experts`, 1 in the router, 1 in another `mul`."""
    def op(start, end, name, category):
        return xplane.Op(start, end, name, category)

    ops = [op(0.5, 2.5, "while.3", "while"),
           op(0.6, 2.4, "fusion.1", "loop fusion"),
           op(2.5, 14.0, "while.4", "while"),
           op(2.5, 3.5, "fusion.2", "output fusion"),
           op(3.5, 7.5, "sort.1", "sort"),
           op(7.5, 8.5, "fusion.3", "custom fusion"),
           op(8.5, 9.0, "gqa_decode_k2048.1", "custom-call"),
           op(9.0, 9.5, "fusion.4", "loop fusion"),
           op(9.5, 11.5, "moe_gmm_fwd_m128_n768_k64.1", "custom-call"),
           op(11.5, 12.5, "fusion.6", "output fusion"),
           op(12.5, 13.5, "fusion.9", "output fusion")]
    trace = xplane.Trace({0: xplane.Device(ops, [(0.5, 14.0, "jit_fn")])},
                         [(0.0, 14.0, xplane.WINDOW_SPAN)])
    return Written(
        facts=dict(facts), peaks=peaks, reduced=trace, trace_dir=None,
        config=LOOKUP.json("configs", CONFIG),
        workload=LOOKUP.json("workloads", CELL), lookup=LOOKUP, seed=5,
        trace=True, devices=[None])


def scoped_of(run):
    paths = {
        "fusion.1": PATH % ("mla_index_select", "i.tmp_0", "dsa_index/x"),
        "fusion.2": PATH % ("mla_index_select", "i.tmp_0",
                            "dsa_index/dot_general"),
        "sort.1": PATH % ("mla_index_select", "i.tmp_0", "dsa_select/sort"),
        "fusion.3": PATH % ("cached_attention", "a.tmp_0",
                            "kv_gather/gather"),
        "gqa_decode_k2048.1": PATH % ("cached_attention", "a.tmp_0",
                                      "attn_sparse/pallas_call"),
        "fusion.4": PATH % ("cached_attention", "a.tmp_0",
                            "kv_write/dynamic_update_slice"),
        "moe_gmm_fwd_m128_n768_k64.1": PATH % (
            "moe_experts", "m.tmp_0", "moe_experts/pallas_call"),
        "fusion.6": PATH % ("moe_router", "r.tmp_0", "dot_general"),
        "fusion.9": PATH % ("mul", "fc_9.tmp_0", "dot_general"),
    }
    device = run.reduced.devices[0]
    return op_scopes.Scoped(
        [(o.start, o.end, o.name, paths.get(o.name, ""))
         for o in device.work], run.reduced.window)


def test_the_new_readers_on_a_written_trace(monkeypatch, capsys):
    run = written_run()
    reader = {name: LOOKUP.module("layer_metrics", name)
              for name in NEW_READERS}
    monkeypatch.setattr(sparse_ops, "operations",
                        lambda r: (scoped_of(r), MARK))
    read = {name: r.read(run) for name, r in reader.items()}
    assert read["sparse_prefill_ms_per_call"] == 2500.0
    assert read["sparse_restore_ms_per_call"] == 900.0
    assert read["sparse_decode_step_ms"] == pytest.approx(11200.0 / 895)
    # inside the decoding scan alone: 1 + 4 + 1 + 0.5 + 0.5 s, not the
    # prefill's 1.8 under `dsa_index`
    assert read["sparse_kv_ms_per_step"] == pytest.approx(7000.0 / 895)
    assert read["sparse_kv_select_ms_per_step"] == pytest.approx(
        5000.0 / 895)
    assert read["sparse_moe_ms_per_step"] == pytest.approx(3000.0 / 895)
    cfg = run.config
    # the decode steps write slots 64640 .. 65534: mean 65087
    index = sparse_kv.index_step(cfg, 8, 65087.0, 2)
    # the multiply-adds alone: the keys come into fast memory under
    # other operations, outside the scope's time (the reader's docstring)
    assert read["sparse_kv_index_roofline"] == pytest.approx(
        100.0 * index["flops"] / 197e12 / (1.0 / 895))
    attend = sparse_kv.attend_step(cfg, 8, 65087.0, 2)
    assert read["sparse_kv_attend_roofline"] == pytest.approx(
        100.0 * attend["bytes"] / 819e9 / (1.5 / 895))
    must = sparse_kv.step_bytes(cfg, 8, 65087.0, 2, 2, 2)
    assert read["sparse_decode_hbm_roofline"] == pytest.approx(
        100.0 * must / 819e9 / (11.0 / 895))
    assert all(0 < read[n] < 100 for n in NEW_READERS if "roofline" in n)
    printed = capsys.readouterr().out
    assert "kv_gather %.4f" % (1000.0 / 895) in printed
    assert "dsa_select %.4f" % (4000.0 / 895) in printed
    assert "attn_sparse %.4f" % (500.0 / 895) in printed


def test_the_readers_of_the_other_cells_find_nothing_here():
    """The facts have names of their own: a `session_*`, `long_*` or
    `share_*` reader reads None on this cell's run, and this cell's read
    None on a run without its facts."""
    run = written_run()
    for name in ("session_decode_step_ms", "session_prefill_ms_per_call",
                 "long_decode_step_ms", "share_decode_step_ms",
                 "dsa_ms_per_step", "kv_attn_ms_per_step",
                 "session_decode_hbm_roofline"):
        assert LOOKUP.module("layer_metrics", name).read(run) is None, name
    bare = written_run(facts={})
    for name in NEW_READERS:
        assert LOOKUP.module("layer_metrics", name).read(bare) is None, name


# -- BENCHMARK.json ---------------------------------------------------------------

def test_benchmark_json_names_the_cell_and_its_metrics():
    with open(os.path.join(CHECKOUT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = [w for w in bench["workloads"] if w["name"] == CELL]
    assert len(cell) == 1 and cell[0]["chips"] == 1
    assert cell[0]["config"] == CONFIG and cell[0] is bench["workloads"][-1]
    config = bench["configs"][-1]
    assert config["name"] == CONFIG
    assert config["reduced"] == LOOKUP.json("configs", CONFIG)["reduced"] \
        == ["num_hidden_layers", "num_experts", "vocab_size"]
    rate = [m for m in bench["end_to_end"]
            if m["name"] == "decode_tok_per_s"][0]
    assert rate["workloads"][-1] == CELL
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW_READERS:
        assert by_name[name]["workloads"] == [CELL]
        assert by_name[name]["moves"] == "decode_tok_per_s"
        reader = LOOKUP.module("layer_metrics", name)
        assert (reader.UNIT, reader.SOURCE, reader.LAYER) == (
            by_name[name]["unit"], by_name[name]["source"],
            by_name[name]["layer"])
    for name in JOINED:
        assert by_name[name]["workloads"][-1] == CELL
    assert [m["name"] for m in bench["per_layer"]][-9:] == list(NEW_READERS)


def test_the_configuration_keeps_every_published_width():
    """Every number of the catalog's row under its key, but the three
    that `reduced` lists."""
    cfg = LOOKUP.json("configs", CONFIG)
    published = {
        "head_dim": 128, "hidden_size": 2048, "intermediate_size": 6144,
        "max_position_embeddings": 262144, "max_window_layers": 48,
        "moe_intermediate_size": 768, "num_attention_heads": 32,
        "num_experts_per_tok": 8, "num_key_value_heads": 4,
        "num_local_experts": 128, "rms_norm_eps": 1e-06,
        "rope_theta": 10000000, "decoder_sparse_step": 1}
    for key, value in published.items():
        assert cfg[key] == value, key
    assert cfg["sa_config"] == {
        "indexer_head_dim": 64, "indexer_num_heads": 16,
        "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
        "q_chunk_size": 512, "topk": 2048}
    assert cfg["rope_scaling"]["mrope_section"] == [16, 24, 24]
    assert cfg["published"] == {"num_hidden_layers": 48,
                                "num_experts": 128, "vocab_size": 151936}
    assert (cfg["num_hidden_layers"], cfg["num_experts"],
            cfg["vocab_size"]) == (5, 16, 18992)
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"]
    assert cfg["first_expert"] + cfg["num_experts"] <= \
        cfg["scored_experts"] == 128
    for key in ("stands_for", "arithmetic", "assumed", "departures",
                "reduced_why", "source_part"):
        assert cfg[key], key
