"""The session cell `dsv32-turn-16k-ep16`: its driver end to end as a CPU
rehearsal at a toy size (fixture `dsv32-tiny-turn`, found through
`--search-path`), the four controls that `correct` has to refuse, the
cell's copy of the reference against the program's own, the session it
makes, the bytes and operations of a decode step against counts made by
hand, the new readers on a written trace, every reader the benchmark
already had on this cell's facts with a chip's peaks set, and
BENCHMARK.json's entries for the cell.
"""

import json
import os
import types

import pytest

from benchmark.harness import CHECKOUT, Lookup
from benchmark.reduce import op_scopes, session_ops, share_ops, xplane
from benchmark.tests import session_control
from benchmark.tests.test_run import FIXTURE, last_line, run_cell

CELL = "dsv32-turn-16k-ep16"
CONFIG = "deepseek-v3.2"
TOY, TOY_CONFIG = "dsv32-tiny-turn", "dsv32-tiny"
NEW_READERS = ("dsa_ms_per_step", "dsa_select_ms_per_step",
               "dsa_index_roofline", "dsa_attend_roofline",
               "session_decode_step_ms", "session_prefill_ms_per_call",
               "session_restore_ms_per_call", "session_moe_ms_per_step",
               "session_decode_hbm_roofline")
CONTROLS = ("serve_dtype=float8_e4m3fn", "index_dtype=float8_e4m3fn",
            "index_topk=4", session_control.RECENT)
FLOORS = ("selected_share",)
PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
LOOKUP = Lookup([FIXTURE])
sparse_latent = LOOKUP.module("flops", "sparse_latent")


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
    assert metrics["decode_trace_lower_s"]["value"] > 0
    # what only a chip can say: this cell's, the share cell's, GPT-2's
    assert not (set(NEW_READERS) | {
        "share_decode_step_ms", "mla_ms_per_step", "mla_decode_roofline",
        "moe_share_roofline", "share_decode_hbm_roofline",
        "decode_step_ms", "decode_hbm_roofline"}) & set(metrics)
    for stream in (proc.stdout, proc.stderr):
        for name in ("gap_mean", "selected_share", "attn_off",
                     "attn_off_first", "held_part_off"):
            assert "check ok  : %s" % name in stream
        assert "check ok  : no compile inside the windows (0), limit 0" \
            in stream


def test_a_session_that_does_not_fit_is_refused_before_the_first_call(
        tmp_path):
    """`ProgramDecoder` cannot see a `pos` inside `init_state`: the
    driver answers for session + prompt + generated <= serve_positions."""
    workload = dict(LOOKUP.json("workloads", TOY), gen_len=26)
    os.makedirs(tmp_path / "workloads")
    with open(tmp_path / "workloads" / "too-long.json", "w") as f:
        json.dump(workload, f)
    import subprocess
    import sys

    proc = subprocess.run(
        [sys.executable, os.path.join(CHECKOUT, "benchmark", "run.py"),
         "--workload", "too-long", "--seed", "5", "--seconds", "1",
         "--search-path", str(tmp_path), "--search-path", FIXTURE],
        cwd=CHECKOUT, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=600)
    assert proc.returncode != 0
    assert "do not fit 64 cache positions" in proc.stderr


# -- what `correct` has to refuse -----------------------------------------------

def _limits(workload):
    limits = workload["correct"]
    return limits, sorted(set(limits) - {"why"})


def _kept(got, limits, name):
    return got[name] >= limits[name] if name in FLOORS \
        else got[name] <= limits[name]


@pytest.mark.parametrize("seed", [5, 6, 7])
def test_the_sound_path_keeps_the_limits(seed):
    import jax

    workload = dict(LOOKUP.json("workloads", TOY), name=TOY)
    limits, names = _limits(workload)
    sound = session_control.read(LOOKUP, workload, seed, jax.devices()[:1],
                                 None)
    assert all(_kept(sound, limits, n) for n in names), sound
    assert sound["rows"] == workload["checked_rows"]
    assert sound["tokens"] == workload["checked_rows"] * workload["gen_len"]
    assert len(sound["selected_share_by_layer"]) == 3 == \
        len(sound["attn_off_by_layer"])
    assert len(sound["held_part_off_by_layer"]) == 2


@pytest.mark.parametrize("control", CONTROLS)
@pytest.mark.parametrize("seed", [5, 6])
def test_the_control_is_not_correct(seed, control):
    """The program's own path with a float8 latent cache, with the index
    keys cached in float8 (three mantissa bits), with half as many slots
    chosen, and with the most recent slots in place of the chosen, each
    fail a limit that the cell as stated keeps."""
    import jax

    workload = dict(LOOKUP.json("workloads", TOY), name=TOY)
    limits, names = _limits(workload)
    got = session_control.read(LOOKUP, workload, seed, jax.devices()[:1],
                               None, control)
    assert not all(_kept(got, limits, n) for n in names), got
    if control != "serve_dtype=float8_e4m3fn":
        # the three that choose otherwise are seen by the chooser's own
        # number
        assert got["selected_share"] < limits["selected_share"]
    else:
        assert got["attn_off_first"] > limits["attn_off_first"]
        assert got["selected_share"] >= limits["selected_share"]


def test_the_recent_control_names_the_slots_before_the_position():
    import jax.numpy as jnp
    import numpy as np
    from paddle_tpu.ops import registry

    rs = np.random.RandomState(0)
    ins = {"Q": [jnp.asarray(rs.randn(2, 1, 8 * 16), jnp.float32)],
           "W": [jnp.asarray(rs.rand(2, 1, 8), jnp.float32)],
           "KNew": [jnp.asarray(rs.randn(2, 1, 16), jnp.float32)],
           "Cache": [jnp.asarray(rs.randn(2, 12, 16), jnp.float32)],
           "Position": [jnp.full((2,), 2, jnp.int32)]}
    real = registry.get_op_info("mla_index_select").kernel
    with session_control.most_recent_slots_chosen():
        out = registry.get_op_info("mla_index_select").kernel(
            None, ins, {"num_heads": 8, "top_k": 4})
    # position 2: slots 2, 1, 0 and one dead entry, masked by Live = 3
    assert np.asarray(out["Selected"][0]).tolist() == [[2, 1, 0, 11]] * 2
    assert np.asarray(out["Live"][0]).tolist() == [3, 3]
    assert registry.get_op_info("mla_index_select").kernel is real


# -- the seeded weights and the session -------------------------------------------

def _toy(dtype="float32"):
    cfg = LOOKUP.json("configs", TOY_CONFIG)
    spec = dict(LOOKUP.json("workloads", TOY)["weights"], dtype=dtype)
    return cfg, spec, LOOKUP.module("models", "dsv32_decode")


def test_the_weights_draw():
    """A block made alone is the block of the whole tree (the reference
    asks for one layer at a time); the spec's keys do what they say."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    cfg, spec, model = _toy("bfloat16")
    key = jax.random.PRNGKey(3000000019)
    tree = jax.jit(lambda k: model.weights(cfg, spec, k))(key)
    alone = jax.jit(lambda k: model.block(cfg, spec, model.root(k), 2))(key)
    for name, value in alone.items():
        np.testing.assert_array_equal(value, tree["blocks"][2][name])
    block = tree["blocks"][1]
    # biases are float32 around 0, whatever the weights' type
    for name in ("router_bias", "ik_norm_b"):
        assert block[name].dtype == jnp.float32
        assert abs(float(np.mean(block[name]))) < 3 * spec["bias_std"]
    assert "router_bias" not in tree["blocks"][0]
    assert block["router_bias"].shape == (cfg["scored_experts"],)
    assert block["w_iq"].dtype == jnp.bfloat16
    assert block["w_iq"].shape == (
        cfg["q_lora_rank"], cfg["index_n_heads"] * cfg["index_head_dim"])
    plain = model.weights(cfg, dict(spec, dtype="float32"), key)
    gained = model.weights(cfg, dict(spec, dtype="float32", qi_gain=3.0),
                           key)
    for was, now in zip(plain["blocks"], gained["blocks"]):
        np.testing.assert_allclose(now["w_iq"], 3.0 * was["w_iq"],
                                   rtol=1e-6)
        np.testing.assert_array_equal(now["w_ik"], was["w_ik"])
    built = model.build(cfg, 4)
    assert set(jax.tree_util.tree_leaves(built["param_names"])) == {
        p.name for p in built["main"].global_block().all_parameters()}
    assert sorted(built["cache_shapes"]) == sorted(
        "%s_cache_%d" % (kind, i) for kind in ("latent", "index")
        for i in range(3))
    assert [sorted(pairs) for _, pairs in built["probes"]] == [
        ["attn_in", "attn_out", "selected"],
        ["attn_in", "attn_out", "idx", "in", "out", "selected"],
        ["attn_in", "attn_out", "idx", "in", "out", "selected"]]


def test_documents_and_questions_are_the_seeds():
    import numpy as np

    cfg, _, model = _toy()
    workload = LOOKUP.json("workloads", TOY)
    docs = model.documents(cfg, workload, 3000000019)
    assert docs.shape == (2, 32) and docs.dtype == np.int32
    assert 0 <= docs.min() and docs.max() < cfg["vocab_size"]
    np.testing.assert_array_equal(docs, model.documents(cfg, workload,
                                                        3000000019))
    assert (docs != model.documents(cfg, workload, 3000000020)).any()
    assert model.prompts(cfg, workload, 7).shape == (2, 4, 8)


def _a_run(seed, model_seed=None):
    """A run of the toy cell on `--seed`, its file stating `weights.seed`
    where `model_seed` is given (the fixture's file states none)."""
    import time

    import jax
    from benchmark import harness

    workload = LOOKUP.json("workloads", TOY)
    if model_seed is not None:
        workload["weights"]["seed"] = model_seed
    return harness.Run(dict(workload, name=TOY),
                       LOOKUP.json("configs", TOY_CONFIG), seed, 0.0, False,
                       LOOKUP, jax.devices()[:1], None,
                       harness.SetupClock(time.perf_counter()),
                       harness.CompileClock())


def _same(a, b):
    import jax
    import numpy as np

    flat_a, flat_b = (jax.tree_util.tree_leaves(t) for t in (a, b))
    return len(flat_a) == len(flat_b) and all(
        x.dtype == y.dtype and np.array_equal(x, y)
        for x, y in zip(flat_a, flat_b))


def test_the_model_is_the_files_draw_and_the_traffic_is_the_seeds():
    """Under two `--seed`s a file that states `weights.seed` hands the
    reference the same `ends` and blocks bit for bit, under two
    `weights.seed`s different ones; the documents, the questions and the
    checked rows follow `--seed`."""
    import numpy as np

    driver = LOOKUP.module("drivers", "decode_session")
    share = LOOKUP.module("drivers", "decode_share")
    model = LOOKUP.module("models", "dsv32_decode")
    one, other, drawn_otherwise = \
        _a_run(11, 4000000501), _a_run(13, 4000000501), _a_run(11, 4000000503)

    def handed(run):
        ends, block_of = driver.seeded(run, model)
        return ends, [block_of(i)
                      for i in range(run.config["num_hidden_layers"])]

    (ends, blocks), (others_ends, others_blocks) = \
        handed(one), handed(drawn_otherwise)
    assert _same((ends, blocks), handed(other))
    assert not _same(ends, others_ends)
    for mine, theirs in zip(blocks, others_blocks):
        assert not _same(mine, theirs)
    cfg, workload = one.config, one.workload
    assert (model.documents(cfg, workload, one.seed)
            != model.documents(cfg, workload, other.seed)).any()
    assert (model.prompts(cfg, workload, one.seed)
            != model.prompts(cfg, workload, other.seed)).any()
    assert share.checked_rows(one).tolist() != \
        share.checked_rows(other).tolist()
    np.testing.assert_array_equal(share.checked_rows(one),
                                  share.checked_rows(drawn_otherwise))


@pytest.mark.parametrize("model_seed", [None, 4000000501])
def test_the_served_weights_are_the_blocks_the_reference_is_handed(
        model_seed):
    """`make_weights` (what is served) and `seeded` (what the reference
    gets, a block at a time) draw from one key, with and without
    `weights.seed` in the file; with it, the model is the one a file
    without the key drew on that `--seed` (PR 40's readings of seed
    4000000501 are this model's)."""
    shared = LOOKUP.module("drivers", "decode_program")
    driver = LOOKUP.module("drivers", "decode_session")
    model = LOOKUP.module("models", "dsv32_decode")
    run = _a_run(17, model_seed)
    served = shared.make_weights(run, model)
    ends, block_of = driver.seeded(run, model)
    blocks = served.pop("blocks")
    assert _same(served, ends)
    assert len(blocks) == run.config["num_hidden_layers"]
    for i, block in enumerate(blocks):
        assert _same(block, block_of(i))
    as_the_parent = shared.make_weights(
        _a_run(17 if model_seed is None else model_seed), model)
    assert _same(blocks, as_the_parent["blocks"])
    on_another_seed = shared.make_weights(_a_run(19, model_seed), model)
    assert _same(blocks, on_another_seed["blocks"]) \
        == (model_seed is not None)


@pytest.fixture(scope="module")
def toy_forward():
    """The program's own reference over 2 sequences of 32 tokens, and
    the seeded parameters."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from paddle_tpu.models.reference import deepseek_v32 as whole

    cfg, spec, model = _toy()
    key = jax.random.PRNGKey(3)
    params = model.weights(cfg, spec, key)
    tokens = np.random.default_rng(0).integers(0, 97, (2, 32),
                                               dtype=np.int32)
    held = (cfg["first_expert"], cfg["n_routed_experts"])
    return cfg, spec, model, key, params, tokens, whole.forward(
        cfg, params, jnp.asarray(tokens), held=held), whole, held


def test_the_session_is_what_the_programs_reference_caches(toy_forward):
    """The cell's copy, in blocks of queries, a layer's parameters at a
    time, makes the `c | r` and `k^I` that
    paddle_tpu/models/reference/deepseek_v32.py's plain forward makes."""
    import numpy as np

    cfg, spec, model, key, _, tokens, want, _, _ = toy_forward
    reference = LOOKUP.module("reference", "deepseek_v32")
    root = model.root(key)
    made, inputs = reference.session(
        cfg, model.ends(cfg, spec, root),
        lambda i: model.block(cfg, spec, root, i), tokens, 8, keep={1})
    assert len(made) == cfg["num_hidden_layers"]
    for i, (latents, keys) in enumerate(made):
        assert latents.shape == (2, 32, 24) and keys.shape == (2, 32, 16)
        np.testing.assert_allclose(latents, want["latents"][i], atol=2e-5)
        np.testing.assert_allclose(keys, want["index_keys"][i], atol=2e-5)
    # and the kept document's layer inputs are the plain forward's
    assert sorted(inputs) == [1] and len(inputs[1]) == len(made)
    for i, x in enumerate(inputs[1][1:]):
        np.testing.assert_allclose(x, want["hidden"][i][1], atol=2e-5)


def test_the_reference_reads_no_gap_for_its_own_first_tokens(toy_forward):
    """`gaps` of the plain forward's own greedy tokens is 0 everywhere
    (the two copies agree), one altered token opens a gap at its position
    alone, and the last step's numbers of the reference's own choices
    read 1 and 0."""
    import jax.numpy as jnp
    import numpy as np

    cfg, spec, model, key, params, tokens, _, whole, held = toy_forward
    reference = LOOKUP.module("reference", "deepseek_v32")
    root = model.root(key)
    prompt = jnp.asarray(tokens[:, :16])
    served = jnp.zeros((2, 0), jnp.int32)
    for _ in range(8):
        z = whole.forward(cfg, params, jnp.concatenate([prompt, served], 1),
                          held=held)["logits"]
        served = jnp.concatenate(
            [served, jnp.argmax(z[:, -1], -1)[:, None].astype(jnp.int32)],
            axis=1)
    fed = jnp.concatenate([prompt, served], axis=1)     # 24 tokens
    out = whole.forward(cfg, params, fed, held=held)
    at = fed.shape[1] - 2
    layers = cfg["num_hidden_layers"]
    own = [np.stack([np.flatnonzero(np.asarray(out["selection"][i][b, at]))
                     for b in range(2)]) for i in range(layers)]
    attn_in = [np.asarray(whole.rms_norm(
        (out["hidden"][i - 1] if i else params["embed"][fed])[:, at],
        params["blocks"][i]["input_norm"], cfg["rms_norm_eps"]))
        for i in range(layers)]
    last = {"at": at, "live": cfg["index_topk"], "selected": own,
            "attn_in": attn_in}

    def gaps(served, last=None, prefix=None):
        whole_seq = np.concatenate([np.asarray(prompt), np.asarray(served)],
                                   axis=1)
        return reference.gaps(
            cfg, model.ends(cfg, spec, root),
            lambda i: model.block(cfg, spec, root, i), whole_seq, 15,
            np.asarray(served), 8, last, prefix=prefix)

    found, step = gaps(served, last)
    # continued from the layers' inputs over the first 8 positions as
    # `session` keeps them, the same numbers: a position reads nothing
    # after it
    _, inputs = reference.session(
        cfg, model.ends(cfg, spec, root),
        lambda i: model.block(cfg, spec, root, i), tokens[:, :8], 8,
        keep={0, 1})
    again, step_again = gaps(served, last, [inputs[0], inputs[1]])
    np.testing.assert_allclose(np.asarray(again), np.asarray(found),
                               atol=1e-5)
    assert step_again["shared"] == step["shared"]
    for i in range(layers):
        np.testing.assert_allclose(np.stack(step_again["attn"][i]),
                                   np.stack(step["attn"][i]), atol=1e-5)
    assert np.asarray(found).shape == (2, 8)
    assert float(np.asarray(found).max()) <= 1e-5
    for i in range(layers):
        assert step["shared"][i] == [1.0, 1.0]
        want = np.asarray(out["attn_out"][i][:, at])
        np.testing.assert_allclose(np.stack(step["attn"][i]), want,
                                   atol=2e-5 * np.abs(want).max())
    wrong = served.at[1, 2].set((served[1, 2] + 1) % 97)
    opened = np.asarray(gaps(wrong)[0])
    assert opened[1, 2] > 1e-3 and opened[0].max() <= 1e-5 and \
        opened[1, :2].max() <= 1e-5
    # half of the chosen slots swapped for others: the share says so
    swapped = [np.where(np.arange(8) < 4, o, 23 - o) for o in own]
    shared = gaps(served, dict(last, selected=swapped))[1]["shared"]
    assert all(0.3 <= s <= 0.8 for per in shared for s in per)


def test_the_held_part_of_a_step_whose_rows_chose_no_held_expert():
    """16 rows now and then choose none of the 16 held experts in a layer
    (two rows in three choose none): the held part is zero on both sides
    and reads 0, not 0 / 0; something served where nothing belongs reads
    past every limit."""
    import jax
    import numpy as np

    cfg, spec, model = _toy()
    reference = LOOKUP.module("reference", "deepseek_v32")
    layer = cfg["first_k_dense_replace"]
    block = jax.tree_util.tree_map(
        lambda a: np.asarray(a, np.float32),
        model.block(cfg, spec, model.root(jax.random.PRNGKey(1)), layer))
    first, held = cfg["first_expert"], cfg["n_routed_experts"]
    absent = [e for e in range(cfg["scored_experts"])
              if not first <= e < first + held][:cfg["num_experts_per_tok"]]
    rows, hidden = 3, cfg["hidden_size"]
    probe = {"in": np.random.default_rng(0).normal(
                 size=(rows, 1, hidden)).astype(np.float32),
             "idx": np.tile(np.asarray(absent, np.int32), (rows, 1)),
             "out": np.zeros((rows, 1, hidden), np.float32)}
    assert reference.held_part_off(cfg, block, probe) == 0.0
    probe["out"][0, 0, 0] = 1.0
    assert reference.held_part_off(cfg, block, probe) == float("inf")


# -- the bytes and operations a step requires -----------------------------------

def test_step_bytes_by_hand():
    cfg = {"hidden_size": 8, "num_attention_heads": 2, "q_lora_rank": 4,
           "kv_lora_rank": 3, "qk_nope_head_dim": 2, "qk_rope_head_dim": 2,
           "v_head_dim": 2, "intermediate_size": 16,
           "moe_intermediate_size": 4, "scored_experts": 8,
           "n_routed_experts": 2, "num_experts_per_tok": 2,
           "num_hidden_layers": 3, "first_k_dense_replace": 1,
           "vocab_size": 10, "index_n_heads": 2, "index_head_dim": 4,
           "index_topk": 4}
    # attention: input norm 8, W_dq 32, q norm 4, W_uq 4 x 2 x 4 = 32,
    # W_dkv 8 x 5 = 40, kv norm 3, W_uk + W_uv 3 x 2 x 4 = 24, W_o 4 x 8
    # = 32; the chooser: W_iq 4 x 8 = 32, W_ik 8 x 4 = 32, LayerNorm 8,
    # W_w 8 x 2 = 16
    assert sparse_latent.attention_parameters(cfg) == 175 + 88
    # every layer: that and the norm before the feed-forward (8); dense
    # feed-forward 3 x 8 x 16; shared expert 3 x 8 x 4, router 8 x 8 and
    # its bias 8; the head: a norm 8 and 8 x 10; looked up: 3 token rows
    fixed = 3 * (263 + 8) + 384 + 2 * (96 + 72) + 88 + 24
    assert sparse_latent.fixed_weight_bytes(cfg, 3, 2) == fixed * 2
    # slot 5: 6 live keys of 4 values, 3 layers, 3 rows; 2 heads
    assert sparse_latent.index_step(cfg, 3, 5, 2) == {
        "flops": 3 * 2 * 3 * 2 * 4 * 6, "bytes": 3 * 3 * 6 * 4 * 2}
    # 4 chosen of the 6 live: scores 5 wide, values 3 wide
    assert sparse_latent.attend_step(cfg, 3, 5, 2) == {
        "flops": 3 * (2 * 3 * 2 * 5 * 4 + 2 * 3 * 2 * 3 * 4),
        "bytes": 3 * 3 * 4 * 5 * 2}
    # fewer live than asked for: all of them
    assert sparse_latent.attend_step(cfg, 3, 1, 2)["bytes"] == \
        3 * 3 * 2 * 5 * 2
    assert sparse_latent.step_bytes(cfg, 3, 5, 2, 2, 2) == \
        fixed * 2 + 432 + 360


def test_step_bytes_of_the_cell():
    """The issue's arithmetic: 3.40 GB of weights outside the routed
    experts, 0.33 GB of live index keys and 0.19 GB of chosen latents a
    decode step; 21 GFLOP of index scores, 46 of attention."""
    cfg = LOOKUP.json("configs", CONFIG)
    workload = LOOKUP.json("workloads", CELL)
    rows = workload["batch"]
    assert sparse_latent.attention_parameters(cfg) == pytest.approx(
        187.1e6 + 13.96e6, rel=1e-3)
    assert sparse_latent.fixed_weight_bytes(cfg, rows, 2) == \
        pytest.approx(3.40e9, rel=3e-3)
    at = workload["session_len"] + workload["prompt_len"] \
        + (workload["gen_len"] - 2) / 2.0
    index = sparse_latent.index_step(cfg, rows, at, 2)
    assert index["bytes"] == pytest.approx(0.33e9, rel=0.02)
    assert index["flops"] == pytest.approx(20.9e9, rel=0.01)
    attend = sparse_latent.attend_step(cfg, rows, at, 2)
    assert attend["bytes"] == 5 * 16 * 2048 * 1152 == 188_743_680
    assert attend["flops"] == 5 * 2 * 16 * 128 * (576 + 512) * 2048
    assert attend["flops"] == pytest.approx(45.6e9, rel=0.01)
    # the chooser's scores are memory-bound on the v5e, the attention
    # compute-bound by a hair: 0.2316 against 0.2305 ms
    assert index["bytes"] / 819e9 > index["flops"] / 197e12
    assert 1.0 < (attend["flops"] / 197e12) / (attend["bytes"] / 819e9) \
        < 1.01
    assert sparse_latent.step_bytes(cfg, rows, at, 2, 2, 2) == \
        pytest.approx(3.92e9, rel=0.01)
    # a token's caches: (576 + 128) values x 5 layers x 2 B
    assert (sparse_latent.latent_width(cfg) + cfg["index_head_dim"]) \
        * 5 * 2 == 7040


# -- the readers ------------------------------------------------------------------

MARK = "~"
PATH = "jit(<lambda>)/while/body/closed_call/%s/~%s/%s"
FACTS = {"session_call_ms": 17700.0, "session_prefill_ms": 2400.0,
         "session_restore_ms": 180.0, "session_gen_len": 896,
         "session_prompt_len": 128, "session_len": 15360,
         "session_batch": 16, "session_calls": 2,
         "session_traced_call_ms": 17700.0,
         "session_step_applications": 1023, "decode_trace_lower_s": 4.3,
         "setup_compile_s": 75.0, "setup_cache_misses": 39,
         "compiles_in_window": 0, "memory_peak_bytes": 14_900_000_000,
         "decode_tok_per_s": 809.0}


class Written(types.SimpleNamespace):
    """Hashable, as harness.Run is: some readers keep what they reduced
    by the run."""
    __hash__ = object.__hash__


def written_run(facts=FACTS, peaks=PEAKS, cell=CELL, config=CONFIG):
    """A run whose traced call spans 18 s: a prefill scan busy 1.8 of
    its 2 s, a decoding scan busy 15 of its 15.5: 1 s under `dsa_index`,
    0.5 under `dsa_select`, 0.5 under `dsa_gather`, 2 under `mla_scores`,
    1 under `mla_values`, 6 in a grouped-product kernel under
    `moe_experts`, 1 in the router of which 0.25 under `moe_groups`, 1 in
    the shared expert's product, 2 in another `mul`."""
    def op(start, end, name, category):
        return xplane.Op(start, end, name, category)

    ops = [op(0.5, 2.5, "while.3", "while"),
           op(0.6, 2.4, "fusion.1", "loop fusion"),
           op(2.5, 18.0, "while.4", "while"),
           op(2.5, 3.5, "fusion.2", "output fusion"),
           op(3.5, 4.0, "sort.1", "sort"),
           op(4.0, 4.5, "fusion.3", "custom fusion"),
           op(4.5, 6.5, "fusion.4", "output fusion"),
           op(6.5, 7.5, "fusion.5", "output fusion"),
           op(7.5, 13.5, "moe_gmm_fwd_m128_n1024_k64.1", "custom-call"),
           op(13.5, 14.25, "fusion.6", "output fusion"),
           op(14.25, 14.5, "fusion.7", "loop fusion"),
           op(14.5, 15.5, "fusion.8", "output fusion"),
           op(15.5, 17.5, "fusion.9", "output fusion")]
    trace = xplane.Trace({0: xplane.Device(ops, [(0.5, 18.0, "jit_fn")])},
                         [(0.0, 18.0, xplane.WINDOW_SPAN)])
    return Written(
        facts=dict(facts), peaks=peaks, reduced=trace, trace_dir=None,
        config=LOOKUP.json("configs", config),
        workload=LOOKUP.json("workloads", cell), lookup=LOOKUP, seed=5,
        trace=True, devices=[None])


def scoped_of(run, shared_instance):
    paths = {
        "fusion.1": PATH % ("mla_index_select", "i.tmp_0", "dsa_index/x"),
        "fusion.2": PATH % ("mla_index_select", "i.tmp_0",
                            "dsa_index/dot_general"),
        "sort.1": PATH % ("mla_index_select", "i.tmp_0", "dsa_select/sort"),
        "fusion.3": PATH % ("mla_cached_attention", "a.tmp_0",
                            "dsa_gather/gather"),
        "fusion.4": PATH % ("mla_cached_attention", "a.tmp_0",
                            "mla_scores/dot_general"),
        "fusion.5": PATH % ("mla_cached_attention", "a.tmp_0",
                            "mla_values/y"),
        "moe_gmm_fwd_m128_n1024_k64.1": PATH % (
            "moe_experts", "m.tmp_0", "moe_experts/pallas_call"),
        "fusion.6": PATH % ("moe_router", "r.tmp_0", "dot_general"),
        "fusion.7": PATH % ("moe_router", "r.tmp_0", "moe_groups/top_k"),
        "fusion.8": PATH % ("mul", shared_instance[1:], "dot_general"),
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
    shared = sorted(reader["session_moe_ms_per_step"].shared_products(run))
    # two products a shared expert, four expert layers
    assert len(shared) == 8 and all(s.startswith(MARK) for s in shared)
    monkeypatch.setattr(session_ops, "operations",
                        lambda r: (scoped_of(r, shared[0]), MARK))
    read = {name: r.read(run) for name, r in reader.items()}
    assert read["session_prefill_ms_per_call"] == 2400.0
    assert read["session_restore_ms_per_call"] == 180.0
    assert read["session_decode_step_ms"] == pytest.approx(15300.0 / 895)
    # inside the decoding scan alone: 1 + 0.5 + 0.5 + 2 + 1 s, not the
    # prefill's 1.8 under `dsa_index`
    assert read["dsa_ms_per_step"] == pytest.approx(5000.0 / 895)
    assert read["dsa_select_ms_per_step"] == pytest.approx(1000.0 / 895)
    # router 1 + experts 6 + the shared expert's product 1, not the other
    assert read["session_moe_ms_per_step"] == pytest.approx(8000.0 / 895)
    cfg = run.config
    # the decode steps write slots 15488 .. 16382: mean 15935
    index = sparse_latent.index_step(cfg, 16, 15935.0, 2)
    # the multiply-adds alone: the keys come into fast memory under
    # other operations, outside the scope's time (the reader's docstring)
    assert read["dsa_index_roofline"] == pytest.approx(
        100.0 * index["flops"] / 197e12 / (1.0 / 895))
    attend = sparse_latent.attend_step(cfg, 16, 15935.0, 2)
    assert read["dsa_attend_roofline"] == pytest.approx(
        100.0 * attend["flops"] / 197e12 / (3.0 / 895))
    must = sparse_latent.step_bytes(cfg, 16, 15935.0, 2, 2, 2)
    assert read["session_decode_hbm_roofline"] == pytest.approx(
        100.0 * must / 819e9 / (15.0 / 895))
    assert all(0 < read[n] < 100 for n in NEW_READERS if "roofline" in n)
    printed = capsys.readouterr().out
    assert "dsa_gather %.4f" % (500.0 / 895) in printed
    assert "mla_scores %.4f" % (2000.0 / 895) in printed
    assert "moe_router (moe_groups) %.4f" % (250.0 / 895) in printed
    assert printed.count("(compute-bound)") == 1
    assert "are not in this time" in printed
    assert "decode step: %.4f ms on the device (a prefill step %.4f)" \
        % (15000.0 / 895, 1800.0 / 127) in printed


def test_the_scopes_of_dsa_ms_per_step_add_up(monkeypatch, capsys):
    run = written_run()
    monkeypatch.setattr(session_ops, "operations",
                        lambda r: (scoped_of(r, "~none"), MARK))
    total = LOOKUP.module("layer_metrics", "dsa_ms_per_step").read(run)
    line = [x for x in capsys.readouterr().out.splitlines()
            if x.startswith("sparse latent attention")][0]
    parts = [float(x.rsplit(" ", 1)[1])
             for x in line.split(": ", 1)[1].split(", ")]
    assert sum(parts) == pytest.approx(total, abs=1e-3)


def test_the_new_readers_find_nothing_to_read_without_a_chip():
    run = written_run(peaks=None)
    for name in NEW_READERS:
        assert LOOKUP.module("layer_metrics", name).read(run) is None
    run = written_run({"session_call_ms": 17700.0})
    run.reduced = None
    for name in NEW_READERS:
        assert LOOKUP.module("layer_metrics", name).read(run) is None


@pytest.mark.parametrize("facts, cell, config", [
    ({"call_ms": 9000.0, "prefill_ms": 700.0, "gen_len": 512,
      "prompt_len": 512, "batch": 48, "traced_call_ms": 10000.0,
      "traced_step_applications": 1023, "decode_trace_lower_s": 2.5},
     "gpt2m-decode", "gpt2-medium"),
    ({"share_call_ms": 30000.0, "share_prefill_ms": 3900.0,
      "share_gen_len": 896, "share_prompt_len": 128, "share_batch": 256,
      "share_step_applications": 1023, "decode_trace_lower_s": 3.4},
     "pangu-decode-ep16", "openpangu-ultra-moe-718b")],
    ids=["gpt2m-decode", "pangu-decode-ep16"])
def test_the_new_readers_find_nothing_on_the_other_generation_cells(
        facts, cell, config):
    """On the chip, traced, with the other drivers' facts (the parent's
    checkout with these files laid over it runs so): nothing, and no
    raise."""
    run = written_run(facts, cell=cell, config=config)
    for name in NEW_READERS:
        assert LOOKUP.module("layer_metrics", name).read(run) is None


def test_no_reader_of_the_benchmark_raises_on_this_cells_facts(monkeypatch):
    """Every reader under layer_metrics/, the other generation cells' and
    the training cells' among them, gives None or a number on the session
    driver's facts with a chip's peaks set; the share cell's readers,
    whose counts would overstate this cell, find nothing to read."""
    run = written_run()
    run.trace_dir = os.path.join(CHECKOUT, "benchmark", "tests", "data")
    monkeypatch.setattr(session_ops, "operations", lambda r: None)
    found = {}
    for name in LOOKUP.names("layer_metrics"):
        if name in NEW_READERS:
            continue
        found[name] = LOOKUP.module("layer_metrics", name).read(run)
    assert all(v is None or isinstance(v, (int, float))
               for v in found.values()), found
    for name in ("share_decode_step_ms", "share_prefill_ms_per_call",
                 "mla_ms_per_step", "mla_decode_roofline",
                 "moe_share_ms_per_step", "moe_share_roofline",
                 "share_decode_hbm_roofline", "decode_step_ms",
                 "prefill_ms_per_call", "decode_hbm_roofline",
                 "decode_attention_ms_per_step", "moe_expert_roofline",
                 "moe_ms_per_step", "mfu", "setup_trace_lower_s"):
        assert found[name] is None, name
    assert found["decode_trace_lower_s"] == 4.3
    assert found["setup_compile_s"] == 75.0
    assert found["setup_cache_misses"] == 39
    assert found["compiles_in_window"] == 0
    assert share_ops.operations(run) is None


# -- BENCHMARK.json ---------------------------------------------------------------

def test_benchmark_json_has_the_cell_and_its_metrics():
    with open(os.path.join(CHECKOUT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    cell, workload = cells[CELL], LOOKUP.json("workloads", CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (CONFIG, CELL, 1)
    assert cell["why"] == workload["why"] and len(cell["why"]) <= 200
    assert 9 <= len(cells) <= 24
    # one model under varying traffic: the file states the draw (PR 42)
    assert workload["weights"]["seed"] == 4000000501
    assert "4000000501" in workload["weights"]["why"]
    configs = {c["name"]: c for c in bench["configs"]}
    entry, config = configs[CONFIG], LOOKUP.json("configs", CONFIG)
    assert entry["file"] == "benchmark/configs/%s.json" % CONFIG
    assert entry["source"] == config["source"]
    assert entry["reduced"] == config["reduced"] == [
        "num_hidden_layers", "first_k_dense_replace", "n_routed_experts",
        "vocab_size", "num_nextn_predict_layers"]
    assert len(entry["why"]) <= 200
    end_to_end = {m["name"]: m for m in bench["end_to_end"]}
    assert end_to_end["decode_tok_per_s"]["workloads"][:3] == \
        ["gpt2m-decode", "pangu-decode-ep16", CELL]
    assert CELL not in end_to_end["train_items_per_s"]["workloads"]
    listed = {m["name"]: m for m in bench["per_layer"]}
    assert listed["decode_trace_lower_s"]["workloads"][:3] == \
        ["gpt2m-decode", "pangu-decode-ep16", CELL]
    # the share cell's counts would overstate this cell: not listed there
    for name in ("mla_ms_per_step", "mla_decode_roofline",
                 "moe_share_ms_per_step", "moe_share_roofline",
                 "share_decode_hbm_roofline", "share_decode_step_ms"):
        assert CELL not in listed[name]["workloads"]
    for name in NEW_READERS:
        reader = LOOKUP.module("layer_metrics", name)
        assert CELL in listed[name]["workloads"]
        assert (listed[name]["moves"], listed[name]["layer"],
                listed[name]["unit"], listed[name]["source"]) == \
            (reader.MOVES, reader.LAYER, reader.UNIT, reader.SOURCE)
        assert set(listed[name]) == {"name", "unit", "better", "source",
                                     "layer", "moves", "workloads"}
        assert listed[name]["better"] == (
            "higher" if name.endswith("roofline") else "lower")
    # (not asserted: that these entries are the last of their lists.  The
    # next PR appends its own: ROADMAP Design 1(g))


def test_the_configuration_keeps_every_published_width():
    """Every number of the catalog's entry under its own key; only the
    five reduced keys differ, and none of them is a width."""
    config = LOOKUP.json("configs", CONFIG)
    published = {
        "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 3,
        "hidden_act": "silu", "hidden_size": 7168, "index_head_dim": 128,
        "index_n_heads": 64, "index_topk": 2048, "intermediate_size": 18432,
        "kv_lora_rank": 512, "max_position_embeddings": 163840,
        "model_type": "deepseek_v32", "moe_intermediate_size": 2048,
        "moe_layer_freq": 1, "n_group": 8, "n_routed_experts": 256,
        "n_shared_experts": 1, "norm_topk_prob": True,
        "num_attention_heads": 128, "num_experts_per_tok": 8,
        "num_hidden_layers": 61, "num_key_value_heads": 128,
        "num_nextn_predict_layers": 1, "q_lora_rank": 1536,
        "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
        "rms_norm_eps": 1e-06,
        "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40,
                         "mscale": 1, "mscale_all_dim": 1,
                         "original_max_position_embeddings": 4096,
                         "type": "yarn"},
        "rope_theta": 10000, "routed_scaling_factor": 2.5,
        "scoring_func": "sigmoid", "tie_word_embeddings": False,
        "topk_group": 4, "topk_method": "noaux_tc", "v_head_dim": 128,
        "vocab_size": 129280}
    differs = sorted(k for k, v in published.items() if config[k] != v)
    assert differs == sorted(config["reduced"])
    assert config["published"] == {k: published[k] for k in differs}
    assert (config["num_hidden_layers"], config["first_k_dense_replace"],
            config["n_routed_experts"], config["vocab_size"],
            config["num_nextn_predict_layers"]) == (5, 1, 16, 16160, 0)
    assert config["scored_experts"] == 256
    # the held range lies inside one group of 32
    first = config["first_expert"]
    assert first // 32 == (first + 15) // 32 == 2
    assert config["vocab_size"] * 8 == published["vocab_size"]
    for key in ("stands_for", "assumed", "departures", "arithmetic",
                "reduced_why"):
        assert config[key]
    workload = LOOKUP.json("workloads", CELL)
    assert workload["session_len"] + workload["prompt_len"] \
        + workload["gen_len"] == config["serve_positions"] == 16384
    assert (workload["batch"], workload["documents"],
            workload["questions_a_document"], workload["session_len"],
            workload["prompt_len"], workload["gen_len"], workload["pool"],
            workload["checked_rows"]) == (16, 4, 4, 15360, 128, 896, 4, 2)
    assert (workload["serve_dtype"], workload["index_dtype"],
            workload["weights"]["dtype"]) == ("bfloat16",) * 3
    limits = workload["correct"]
    assert set(limits) == {"gap_mean", "not_first_share", "selected_share",
                           "attn_off", "attn_off_first", "held_part_off",
                           "why"}
    model = LOOKUP.module("models", "dsv32_decode")
    sizes = model.sizes(config)
    assert sizes["indexer"] == (64, 128, 2048)
    assert (sizes["n_group"], sizes["topk_group"], sizes["held"]) == \
        (8, 4, (80, 16))
    assert sizes["yarn"] == {"factor": 40, "original_positions": 4096,
                             "beta_fast": 32, "beta_slow": 1, "mscale": 1}
