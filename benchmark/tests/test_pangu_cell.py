"""The share cell `pangu-decode-ep16`: its driver end to end as a CPU
rehearsal at a toy size (fixture `pangu-tiny-decode`, found through
`--search-path`), the three controls that `correct` has to refuse, the
bytes and operations of a decode step against counts made by hand, the
new readers on a written trace, every reader the benchmark already had
on this cell's facts with a chip's peaks set, and BENCHMARK.json's
entries for the cell.
"""

import json
import os
import types

import pytest

from benchmark.harness import CHECKOUT, Lookup
from benchmark.reduce import op_scopes, share_ops, xplane
from benchmark.tests import share_control
from benchmark.tests.test_run import FIXTURE, last_line, run_cell

CELL = "pangu-decode-ep16"
CONFIG = "openpangu-ultra-moe-718b"
TOY = "pangu-tiny-decode"
NEW_READERS = ("share_decode_step_ms", "share_prefill_ms_per_call",
               "mla_ms_per_step", "mla_decode_roofline",
               "moe_share_ms_per_step", "moe_share_roofline",
               "share_decode_hbm_roofline")
CONTROLS = ("serve_dtype=float8_e4m3fn", "weights.routed_mantissa_bits=3",
            share_control.DROP)
PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
LOOKUP = Lookup([FIXTURE])
latent_moe = LOOKUP.module("flops", "latent_moe")


# -- the driver, end to end -----------------------------------------------------

def test_untraced_rehearsal_has_exactly_the_two_metrics():
    result = last_line(run_cell(TOY, 0))
    assert result["correct"] is True and result["failed"] == 0
    assert set(result["metrics"]) == {"decode_tok_per_s", "setup_s"}
    assert result["metrics"]["decode_tok_per_s"]["unit"] == "tok/s"
    assert result["attempted"] % 8 == 0 and result["attempted"] >= 16
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
    assert "setup_trace_lower_s" not in metrics
    # what only a chip can say, this cell's and the GPT-2 cell's
    assert not (set(NEW_READERS) | {
        "prefill_ms_per_call", "decode_step_ms", "decode_hbm_roofline",
        "decode_attention_ms_per_step"}) & set(metrics)
    for stream in (proc.stdout, proc.stderr):
        assert "check ok  : gap_mean" in stream
        assert "check ok  : held_part_off" in stream
        assert "check ok  : no compile inside the windows (0), limit 0" \
            in stream


# -- what `correct` has to refuse -----------------------------------------------

def _limits(workload):
    limits = workload["correct"]
    return limits, sorted(set(limits) - {"why"})


@pytest.mark.parametrize("seed", [5, 6, 7])
def test_the_sound_path_keeps_the_limits(seed):
    import jax

    workload = dict(LOOKUP.json("workloads", TOY), name=TOY)
    limits, names = _limits(workload)
    sound = share_control.read(LOOKUP, workload, seed, jax.devices()[:1],
                               None)
    assert all(sound[n] <= limits[n] for n in names), sound
    assert sound["rows"] == workload["checked_rows"]
    assert sound["tokens"] == workload["checked_rows"] * workload["gen_len"]


@pytest.mark.parametrize("control", CONTROLS)
@pytest.mark.parametrize("seed", [5, 6, 7])
def test_the_control_is_not_correct(seed, control):
    """The program's own path with a float8 latent cache, with the held
    experts' weights rounded to float8's three mantissa bits, and with a
    token's last held expert dropped, each fail a limit that the cell as
    stated keeps."""
    import jax

    workload = dict(LOOKUP.json("workloads", TOY), name=TOY)
    limits, names = _limits(workload)
    got = share_control.read(LOOKUP, workload, seed, jax.devices()[:1],
                             None, control)
    assert any(got[n] > limits[n] for n in names), got


@pytest.mark.parametrize("seed", [5, 6, 7])
def test_rounded_expert_weights_fail_by_the_held_part_alone(seed):
    """The held experts' weights rounded to float8's three mantissa bits:
    the served tokens do not tell them from the sound ones (on the chip
    1.04-1.09 times the sound runs' `gap_mean`: a near-tied expert that
    changes places costs a token more than every weight's rounding does;
    PERF.md section 6).  `held_part_off` does: the held experts' part of
    the call's last step against the reference's routed sum of the same
    rows under the same choice of experts."""
    import jax

    workload = dict(LOOKUP.json("workloads", TOY), name=TOY)
    limits, _ = _limits(workload)
    got = share_control.read(LOOKUP, workload, seed, jax.devices()[:1],
                             None, "weights.routed_mantissa_bits=3")
    assert got["held_part_off"] > 10 * limits["held_part_off"]
    assert len(got["held_part_off_by_layer"]) == 2
    assert min(got["held_part_off_by_layer"]) > limits["held_part_off"]


def test_held_part_off_reads_the_weights_and_not_the_choice():
    """`held_part_off` of a held part computed by the expert op in
    bfloat16, for a choice of experts that is not the reference's own (the
    reference is handed it): a few thousandths with the weights as
    drawn, ten times that with them rounded to three mantissa bits
    (scripts/pangu_check.py (e) reads the same on the chip)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from paddle_tpu.ops import registry

    cfg = LOOKUP.json("configs", "pangu-tiny")
    spec = dict(LOOKUP.json("workloads", TOY)["weights"], dtype="bfloat16")
    model = LOOKUP.module("models", "pangu_decode")
    reference = LOOKUP.module("reference", "pangu_moe")
    root = model.root(jax.random.PRNGKey(11))
    rng = np.random.default_rng(3)
    u = jnp.asarray(rng.standard_normal((64, 1, cfg["hidden_size"])),
                    jnp.bfloat16)
    # any two distinct experts a row, held or not
    idx = jnp.asarray(np.argsort(rng.random((64, cfg["scored_experts"])),
                                 axis=1)[:, :2], jnp.int32)

    def probe(served):
        block = model.block(cfg, served, root, 1)
        scores = jax.nn.sigmoid(u[:, 0].astype(jnp.float32)
                                @ block["router"].astype(jnp.float32))
        top = jnp.take_along_axis(scores, idx, axis=1)
        top = cfg["routed_scaling_factor"] * top / top.sum(1, keepdims=True)
        out = registry.get_op_info("moe_experts").kernel(
            None, {"X": [u], "TopW": [top], "TopIdx": [idx],
                   "WGate": [block["w_gate"]], "WUp": [block["w_up"]],
                   "WDown": [block["w_down"]]},
            {"first_expert": cfg["first_expert"],
             "scored": cfg["scored_experts"]})
        return {"in": u, "idx": idx, "out": out["Out"][0]}

    exact = jax.tree_util.tree_map(
        lambda a: jnp.asarray(a, jnp.float32), model.block(cfg, spec, root,
                                                           1))
    sound = reference.held_part_off(cfg, exact, probe(spec))
    rounded = reference.held_part_off(
        cfg, exact, probe(dict(spec, routed_mantissa_bits=3)))
    assert sound < 0.006 and rounded > 0.03 and rounded > 6 * sound


def test_the_dropped_expert_is_a_held_one():
    """The control replaces, for every token with a held expert among
    its chosen, the last such index by one nobody holds, and touches no
    other."""
    import jax.numpy as jnp
    import numpy as np
    from paddle_tpu.ops import registry

    seen = {}
    info = registry.get_op_info("moe_experts")
    real = info.kernel

    def spy(ctx, ins, attrs):
        seen["idx"] = np.asarray(ins["TopIdx"][0])
        return real(ctx, ins, attrs)

    info.kernel = spy
    try:
        with share_control.last_held_expert_dropped():
            idx = jnp.asarray([[1, 3, 4], [0, 6, 7], [2, 5, 9], [3, 1, 4]],
                              jnp.int32)
            w = jnp.zeros((3, 4, 2))
            registry.get_op_info("moe_experts").kernel(
                None, {"X": [jnp.zeros((4, 4))],
                       "TopW": [jnp.ones((4, 3))], "TopIdx": [idx],
                       "WGate": [w], "WUp": [w],
                       "WDown": [jnp.zeros((3, 2, 4))]},
                {"first_expert": 2, "scored": 10})
    finally:
        info.kernel = real
    # held: 2, 3, 4
    assert seen["idx"].tolist() == [[1, 3, -1], [0, 6, 7], [-1, 5, 9],
                                    [3, 1, -1]]
    assert registry.get_op_info("moe_experts").kernel is real


def test_the_weights_draw():
    """A block made alone is the block of the whole tree (the reference
    asks for one layer at a time); the spec's keys do what they say."""
    import jax
    import numpy as np

    cfg = LOOKUP.json("configs", "pangu-tiny")
    spec = dict(LOOKUP.json("workloads", TOY)["weights"], dtype="float32")
    model = LOOKUP.module("models", "pangu_decode")
    key = jax.random.PRNGKey(3000000019)
    tree = jax.jit(lambda k: model.weights(cfg, spec, k))(key)
    alone = jax.jit(lambda k: model.block(cfg, spec, model.root(k), 2))(key)
    for name, value in alone.items():
        np.testing.assert_array_equal(value, tree["blocks"][2][name])
    plain = model.weights(cfg, dict(spec, q_gain=1.0, embed_std=spec["std"]),
                          key)
    gain = spec["q_gain"]
    for block, was in zip(tree["blocks"], plain["blocks"]):
        for name in ("w_uq_nope", "w_uq_rope"):
            np.testing.assert_allclose(block[name], gain * was[name],
                                       rtol=1e-6)
        np.testing.assert_array_equal(block["w_uk"], was["w_uk"])
    assert float(np.std(tree["embed"])) == pytest.approx(
        spec["embed_std"], rel=0.05)
    eighth = model.weights(cfg, dict(spec, routed_mantissa_bits=3),
                           key)
    b, e = tree["blocks"][1], eighth["blocks"][1]
    np.testing.assert_array_equal(b["shared_in"], e["shared_in"])
    off = np.abs(np.asarray(e["w_gate"]) - np.asarray(b["w_gate"]))
    assert 0 < off.max() <= 2.0 ** -4 * np.abs(np.asarray(b["w_gate"])).max()
    assert "ffn_in" in tree["blocks"][0] and "router" in tree["blocks"][1]
    assert tree["blocks"][1]["w_gate"].shape == (
        cfg["n_routed_experts"], cfg["hidden_size"],
        cfg["moe_intermediate_size"])
    assert tree["blocks"][1]["router"].shape == (cfg["hidden_size"],
                                                 cfg["scored_experts"])


def test_the_reference_reads_no_gap_for_its_own_first_tokens():
    """`gaps` of the reference's own greedy tokens is 0 everywhere (the
    plain full forward of paddle_tpu/models/reference/pangu_moe.py picks
    them: the two copies agree), and one altered token opens a gap at its
    position alone."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from paddle_tpu.models.reference import pangu_moe as whole

    cfg = LOOKUP.json("configs", "pangu-tiny")
    spec = dict(LOOKUP.json("workloads", TOY)["weights"], dtype="float32")
    model = LOOKUP.module("models", "pangu_decode")
    reference = LOOKUP.module("reference", "pangu_moe")
    root = model.root(jax.random.PRNGKey(3))
    params = model.weights(cfg, spec, jax.random.PRNGKey(3))
    held = (cfg["first_expert"], cfg["n_routed_experts"])
    prompt = jnp.asarray(np.arange(12).reshape(2, 6) % 97, jnp.int32)
    served = jnp.zeros((2, 0), jnp.int32)
    for _ in range(5):
        tokens = jnp.concatenate([prompt, served], axis=1)
        z = whole.forward(cfg, params, tokens, held=held)["logits"]
        served = jnp.concatenate(
            [served, jnp.argmax(z[:, -1], axis=-1)[:, None].astype(
                jnp.int32)], axis=1)

    def gaps(served):
        return np.asarray(reference.gaps(
            cfg, model.ends(cfg, spec, root),
            lambda i: model.block(cfg, spec, root, i), prompt, served, 1))

    assert gaps(served).shape == (2, 5)
    assert float(gaps(served).max()) <= 1e-5
    wrong = served.at[1, 2].set((served[1, 2] + 1) % 97)
    opened = gaps(wrong)
    assert opened[1, 2] > 1e-3 and opened[0].max() <= 1e-5 and \
        opened[1, :2].max() <= 1e-5


# -- the bytes and operations a step requires -----------------------------------

def test_step_bytes_by_hand():
    cfg = {"hidden_size": 8, "num_attention_heads": 2, "q_lora_rank": 4,
           "kv_lora_rank": 3, "qk_nope_head_dim": 2, "qk_rope_head_dim": 2,
           "v_head_dim": 2, "intermediate_size": 16,
           "moe_intermediate_size": 4, "scored_experts": 8,
           "n_routed_experts": 2, "num_experts_per_tok": 2,
           "num_hidden_layers": 3, "first_k_dense_replace": 1,
           "vocab_size": 10}
    # attention: input norm 8, W_dq 32, q norm 4, W_uq 4 x 2 x 4 = 32,
    # W_dkv 8 x 5 = 40, kv norm 3, W_uk + W_uv 3 x 2 x 4 = 24, W_o 4 x 8
    # = 32, three more norms 24
    assert latent_moe.attention_parameters(cfg) == 199
    assert latent_moe.expert_parameters(cfg) == 96
    # 3 rows x 2 assignments over 8 experts miss one with (7/8)^6
    reached = 2 * (1 - (7 / 8) ** 6)
    assert latent_moe.experts_with_a_row(cfg, 3) == pytest.approx(reached)
    assert latent_moe.layer_parameters(cfg, 0, 3) == 199 + 3 * 8 * 16
    expert_layer = 199 + 96 + 8 * 8 + reached * 96
    assert latent_moe.layer_parameters(cfg, 1, 3) == \
        pytest.approx(expert_layer)
    # the head: a norm 8 and 8 x 10; looked up: 3 token rows
    assert latent_moe.weight_bytes(cfg, 3, 2) == pytest.approx(
        (583 + 2 * expert_layer + 88 + 24) * 2)
    # slot 5: 6 slots of 5 values, 3 layers, 3 rows
    assert latent_moe.cache_bytes(cfg, 3, 5, 2) == 3 * 3 * 5 * 6 * 2 == 540
    assert latent_moe.mean_step_bytes(cfg, 3, 4, 6, 2, 2) == \
        latent_moe.step_bytes(cfg, 3, 5, 2, 2) == \
        latent_moe.weight_bytes(cfg, 3, 2) + 540
    # scores 2 x 3 rows x 2 heads x 5 wide x 6 slots, values 3 wide
    assert latent_moe.mla_step(cfg, 3, 5, 2) == {
        "flops": 3 * (360 + 216), "bytes": 540}
    assert latent_moe.held_expert_bytes(cfg, 3, 2) == \
        pytest.approx(2 * reached * 96 * 2)


def test_step_bytes_of_the_cell():
    """The issue's arithmetic: 9.54 GB of weights and 0.85 GB of live
    latents a decode step, 0.21 TFLOP of contractions."""
    cfg = LOOKUP.json("configs", CONFIG)
    workload = LOOKUP.json("workloads", CELL)
    rows, prompt, gen = (workload[k] for k in ("batch", "prompt_len",
                                               "gen_len"))
    assert latent_moe.attention_parameters(cfg) == 196_608_000 + 0 \
        or abs(latent_moe.attention_parameters(cfg) - 196.6e6) < 0.1e6
    assert latent_moe.expert_parameters(cfg) == 47_185_920
    assert latent_moe.experts_with_a_row(cfg, rows) == \
        pytest.approx(16, abs=0.01)
    weights = latent_moe.weight_bytes(cfg, rows, 2)
    assert weights == pytest.approx(9.545e9, rel=1e-3)
    # a token's latents: 576 values x 5 layers x 2 B
    assert latent_moe.cache_bytes(cfg, 1, 0, 2) == 5760
    mean = latent_moe.mean_step_bytes(cfg, rows, prompt, prompt + gen - 2,
                                      2, 2)
    assert mean - weights == rows * 5760 * 576
    assert mean == pytest.approx(10.39e9, rel=1e-3)
    step = latent_moe.mla_step(cfg, rows, 575, 2)
    assert step["flops"] == 5 * 2 * rows * 128 * (576 + 512) * 576
    assert step["flops"] == pytest.approx(0.205e12, rel=2e-3)
    # compute-bound at the live length, by a hair: 1.042 against 1.037 ms
    assert step["flops"] / 197e12 > step["bytes"] / 819e9
    assert latent_moe.held_expert_bytes(cfg, rows, 2) == \
        pytest.approx(6.04e9, rel=1e-3)


# -- the readers ------------------------------------------------------------------

MARK = "~"
PATH = "jit(<lambda>)/while/body/closed_call/%s/~%s/%s"
FACTS = {"share_call_ms": 30000.0, "share_prefill_ms": 3900.0,
         "share_gen_len": 896, "share_prompt_len": 128, "share_batch": 256,
         "share_calls": 1, "share_traced_call_ms": 30000.0,
         "share_step_applications": 1023, "decode_trace_lower_s": 3.4,
         "setup_compile_s": 25.0, "setup_cache_misses": 1,
         "compiles_in_window": 0, "memory_peak_bytes": 13_400_000_000,
         "decode_tok_per_s": 7600.0}


class Written(types.SimpleNamespace):
    """Hashable, as harness.Run is: some readers keep what they reduced
    by the run."""
    __hash__ = object.__hash__


def written_run(facts=FACTS, peaks=PEAKS, cell=CELL, config=CONFIG):
    """A run whose traced call spans 31 s: a short `while` of the
    prompt's first position, a prefill scan busy 3.5 of its 4 s, a
    decoding scan busy 25 of its 26: 10 s under `mla_cached_attention`
    (7 of them `mla_scores`), 9 in a grouped-product kernel under
    `moe_experts`, 1 in the router, 2 in the shared expert's product, 3
    in another `mul`."""
    def op(start, end, name, category):
        return xplane.Op(start, end, name, category)

    ops = [op(0.5, 0.6, "while.9", "while"),
           op(1.0, 5.0, "while.3", "while"),
           op(1.0, 4.5, "fusion.1", "loop fusion"),
           op(5.0, 31.0, "while.4", "while"),
           op(5.0, 12.0, "fusion.2", "output fusion"),
           op(12.0, 15.0, "fusion.3", "loop fusion"),
           op(15.0, 24.0, "moe_gmm_fwd_m256_n512_k64.1", "custom-call"),
           op(24.0, 25.0, "fusion.4", "output fusion"),
           op(25.0, 27.0, "fusion.5", "output fusion"),
           op(27.0, 30.0, "fusion.6", "output fusion")]
    trace = xplane.Trace({0: xplane.Device(ops, [(0.5, 31.0, "jit_fn")])},
                         [(0.0, 31.0, xplane.WINDOW_SPAN)])
    return Written(
        facts=dict(facts), peaks=peaks, reduced=trace, trace_dir=None,
        config=LOOKUP.json("configs", config),
        workload=LOOKUP.json("workloads", cell), lookup=LOOKUP, seed=5,
        trace=True, devices=[None])


def scoped_of(run, shared_instance):
    paths = {
        "fusion.1": PATH % ("mla_cached_attention", "a.tmp_0", "mla_scores/x"),
        "fusion.2": PATH % ("mla_cached_attention", "a.tmp_0",
                            "mla_scores/dot_general"),
        "fusion.3": PATH % ("mla_cached_attention", "a.tmp_0", "mla_values/y"),
        "moe_gmm_fwd_m256_n512_k64.1": PATH % (
            "moe_experts", "m.tmp_0", "moe_experts/pallas_call"),
        "fusion.4": PATH % ("moe_router", "r.tmp_0", "dot_general"),
        "fusion.5": PATH % ("mul", shared_instance[1:], "dot_general"),
        "fusion.6": PATH % ("mul", "fc_9.tmp_0", "dot_general"),
    }
    device = run.reduced.devices[0]
    return op_scopes.Scoped(
        [(o.start, o.end, o.name, paths.get(o.name, ""))
         for o in device.work], run.reduced.window)


def test_the_new_readers_on_a_written_trace(monkeypatch, capsys):
    run = written_run()
    reader = {name: LOOKUP.module("layer_metrics", name)
              for name in NEW_READERS}
    shared = sorted(reader["moe_share_ms_per_step"].shared_products(run))
    # two products a shared expert, four expert layers
    assert len(shared) == 8 and all(s.startswith(MARK) for s in shared)
    monkeypatch.setattr(share_ops, "operations",
                        lambda r: (scoped_of(r, shared[0]), MARK))
    read = {name: r.read(run) for name, r in reader.items()}
    assert read["share_prefill_ms_per_call"] == 3900.0
    assert read["share_decode_step_ms"] == pytest.approx(26100.0 / 895)
    # 3.5 + 7 + 3 s under the op over the call's 1023 step applications
    assert read["mla_ms_per_step"] == pytest.approx(13500.0 / 1023)
    # router 1 + experts 9 + the shared expert's product 2, not the other
    assert read["moe_share_ms_per_step"] == pytest.approx(12000.0 / 1023)
    cfg = run.config
    # the decode steps write slots 128 .. 1022: mean live length 576
    step = latent_moe.mla_step(cfg, 256, 575, 2)
    least = max(step["flops"] / 197e12, step["bytes"] / 819e9)
    assert read["mla_decode_roofline"] == pytest.approx(
        100.0 * least / (10.0 / 895))
    must = latent_moe.held_expert_bytes(cfg, 256, 2)
    assert read["moe_share_roofline"] == pytest.approx(
        100.0 * must / 819e9 * 1023 / 9.0)
    mean = latent_moe.mean_step_bytes(cfg, 256, 128, 1022, 2, 2)
    assert read["share_decode_hbm_roofline"] == pytest.approx(
        100.0 * mean / 819e9 / (25.0 / 895))
    printed = capsys.readouterr().out
    assert "mla_scores 10.264 ms" in printed        # 10.5 s / 1023
    assert "(compute-bound)" in printed
    assert "decode step: %.4f ms on the device (a prefill step %.4f)" \
        % (25000.0 / 895, 3500.0 / 127) in printed
    assert "moe_gmm_fwd_m256_n512_k64 1.0 calls" not in printed  # per step


def test_the_scans_are_the_two_longest_whiles():
    run = written_run()
    assert share_ops.call_scans(run) == ((1.0, 5.0), (5.0, 31.0))
    assert share_ops.decoding_steps(run) == ((5.0, 31.0), 895)
    run.reduced.devices[0].ops[:] = [
        o for o in run.reduced.devices[0].ops if o.name != "while.4"]
    # a short while is taken for a scan only where there are but two
    assert share_ops.call_scans(run) == ((0.5, 0.6), (1.0, 5.0))
    run.reduced.devices[0].ops[:] = [
        o for o in run.reduced.devices[0].ops if o.category != "while"]
    assert share_ops.call_scans(run) is None
    assert LOOKUP.module("layer_metrics",
                         "share_decode_hbm_roofline").read(run) is None


def test_a_path_under_the_scans_own_scopes():
    parts = share_ops.parts
    assert parts(PATH % ("mla_cached_attention", "a.tmp_0",
                         "mla_scores/dot_general:"), MARK) == \
        ("mla_cached_attention", "~a.tmp_0", ("mla_scores", "dot_general"))
    assert parts("jit(<lambda>)/while/body/dynamic_slice", MARK) is None
    assert parts("~alone", MARK) is None


def test_the_new_readers_find_nothing_to_read_without_a_chip():
    run = written_run(peaks=None)
    for name in NEW_READERS:
        assert LOOKUP.module("layer_metrics", name).read(run) is None
    run = written_run({"share_call_ms": 30000.0})
    run.reduced = None
    for name in NEW_READERS:
        assert LOOKUP.module("layer_metrics", name).read(run) is None


def test_the_new_readers_find_nothing_on_the_gpt2_cell():
    """On the chip, traced, with the GPT-2 driver's facts (the parent's
    checkout with these files laid over it runs so): nothing, and no
    raise."""
    run = written_run(
        {"call_ms": 9000.0, "prefill_ms": 4400.0, "gen_len": 512,
         "prompt_len": 512, "batch": 48, "traced_call_ms": 10000.0,
         "traced_step_applications": 1023, "decode_trace_lower_s": 2.5},
        cell="gpt2m-decode", config="gpt2-medium")
    for name in NEW_READERS:
        assert LOOKUP.module("layer_metrics", name).read(run) is None


def test_no_reader_of_the_benchmark_raises_on_this_cells_facts(monkeypatch):
    """Every reader under layer_metrics/, the GPT-2 cell's and the
    training cells' among them, gives None or a number on the share
    driver's facts with a chip's peaks set: on the chip a reader that
    reaches for `n_embd` or `traced_steps` would end the traced run."""
    run = written_run()
    run.trace_dir = os.path.join(CHECKOUT, "benchmark", "tests", "data")
    monkeypatch.setattr(share_ops, "operations", lambda r: None)
    found = {}
    for name in LOOKUP.names("layer_metrics"):
        if name in NEW_READERS:
            continue
        found[name] = LOOKUP.module("layer_metrics", name).read(run)
    assert all(v is None or isinstance(v, (int, float))
               for v in found.values()), found
    for name in ("decode_step_ms", "prefill_ms_per_call",
                 "decode_hbm_roofline", "decode_attention_ms_per_step",
                 "moe_expert_roofline", "moe_ms_per_step", "mfu",
                 "setup_trace_lower_s"):
        assert found[name] is None, name
    assert found["decode_trace_lower_s"] == 3.4
    assert found["setup_compile_s"] == 25.0
    assert found["setup_cache_misses"] == 1
    assert found["compiles_in_window"] == 0


# -- where the model comes from ---------------------------------------------------

@pytest.mark.parametrize("cell", [CELL, "gpt2m-decode", TOY,
                                  "gpt2-tiny-decode", "dsv32-tiny-turn"])
def test_a_file_that_states_no_weights_seed_draws_its_model_from_the_seed(
        cell):
    """`decode_program.model_key` is the one place a decode cell's model
    gets its key: `--seed`'s, bit for bit what the drivers made before
    PR 42, where the workload file states no `weights.seed` (these
    cells: their runs spread by 0.1%, their numbers stay), the file's
    where it states one, whatever `--seed` is."""
    import jax
    import numpy as np

    shared = LOOKUP.module("drivers", "decode_program")
    workload = LOOKUP.json("workloads", cell)
    assert "seed" not in workload["weights"]
    for seed in (7, 3900000301, 2 ** 31 + 11):
        run = types.SimpleNamespace(workload=workload, seed=seed)
        np.testing.assert_array_equal(
            jax.random.key_data(shared.model_key(run)),
            jax.random.key_data(jax.random.PRNGKey(seed)))
        stated = types.SimpleNamespace(
            workload=dict(workload, weights=dict(workload["weights"],
                                                 seed=4000000501)),
            seed=seed)
        np.testing.assert_array_equal(
            jax.random.key_data(shared.model_key(stated)),
            jax.random.key_data(jax.random.PRNGKey(4000000501)))


# -- BENCHMARK.json ---------------------------------------------------------------

def test_benchmark_json_has_the_cell_and_its_metrics():
    with open(os.path.join(CHECKOUT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    cell, workload = cells[CELL], LOOKUP.json("workloads", CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (CONFIG, CELL, 1)
    assert cell["why"] == workload["why"] and len(cell["why"]) <= 200
    assert 8 <= len(cells) <= 24
    assert [w["name"] for w in bench["workloads"] if w["chips"] == 4] == \
        ["resnet50-train-dp4"]
    configs = {c["name"]: c for c in bench["configs"]}
    entry, config = configs[CONFIG], LOOKUP.json("configs", CONFIG)
    assert entry["file"] == "benchmark/configs/%s.json" % CONFIG
    assert entry["source"] == config["source"]
    assert entry["reduced"] == config["reduced"] == [
        "num_hidden_layers", "first_k_dense_replace", "n_routed_experts",
        "vocab_size", "num_nextn_predict_layers"]
    assert len(entry["why"]) <= 200
    end_to_end = {m["name"]: m for m in bench["end_to_end"]}
    # (the lists' first entries, not the whole: later cells append)
    assert end_to_end["decode_tok_per_s"]["workloads"][:2] == \
        ["gpt2m-decode", CELL]
    assert CELL not in end_to_end["train_items_per_s"]["workloads"]
    listed = {m["name"]: m for m in bench["per_layer"]}
    assert listed["decode_trace_lower_s"]["workloads"][:2] == \
        ["gpt2m-decode", CELL]
    for name in ("prefill_ms_per_call", "decode_step_ms",
                 "decode_attention_ms_per_step", "decode_hbm_roofline"):
        assert listed[name]["workloads"] == ["gpt2m-decode"]
    for name in NEW_READERS:
        reader = LOOKUP.module("layer_metrics", name)
        assert listed[name]["workloads"] == [CELL]
        assert (listed[name]["moves"], listed[name]["layer"],
                listed[name]["unit"], listed[name]["source"]) == \
            (reader.MOVES, reader.LAYER, reader.UNIT, reader.SOURCE)
        assert set(listed[name]) == {"name", "unit", "better", "source",
                                     "layer", "moves", "workloads"}


def test_the_configuration_keeps_every_published_width():
    """Every number of the catalog's entry under its own key; only the
    five reduced keys differ, and none of them is a width."""
    config = LOOKUP.json("configs", CONFIG)
    published = {
        "attention_bias": False, "first_k_dense_replace": 3,
        "hidden_act": "silu", "hidden_size": 7680,
        "intermediate_size": 18432, "kv_lora_rank": 512,
        "max_position_embeddings": 131072, "model_type": "pangu_ultra_moe",
        "moe_intermediate_size": 2048, "n_routed_experts": 256,
        "n_shared_experts": 1, "norm_topk_prob": True,
        "num_attention_heads": 128, "num_experts_per_tok": 8,
        "num_hidden_layers": 61, "num_key_value_heads": 128,
        "num_nextn_predict_layers": 1, "q_lora_rank": 1536,
        "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
        "rms_norm_eps": 1e-05, "rope_theta": 25600000,
        "routed_scaling_factor": 2.5, "sandwich_norm": True,
        "tie_word_embeddings": False, "v_head_dim": 128,
        "vocab_size": 153600}
    differs = sorted(k for k, v in published.items() if config[k] != v)
    assert differs == sorted(config["reduced"])
    assert config["published"] == {k: published[k] for k in differs}
    assert (config["num_hidden_layers"], config["first_k_dense_replace"],
            config["n_routed_experts"], config["vocab_size"],
            config["num_nextn_predict_layers"]) == (5, 1, 16, 19200, 0)
    assert config["scored_experts"] == 256
    assert 0 <= config["first_expert"] <= 256 - 16
    assert config["vocab_size"] * 8 == published["vocab_size"]
    for key in ("stands_for", "assumed", "departures", "arithmetic",
                "reduced_why"):
        assert config[key]
    workload = LOOKUP.json("workloads", CELL)
    assert workload["prompt_len"] + workload["gen_len"] == \
        config["serve_positions"] == 1024
    assert (workload["batch"] % 32, workload["pool"],
            workload["serve_dtype"], workload["weights"]["dtype"]) == \
        (0, 4, "bfloat16", "bfloat16")
    assert workload["checked_rows"] >= 32
    assert workload["checked_rows"] * workload["gen_len"] >= 28672
