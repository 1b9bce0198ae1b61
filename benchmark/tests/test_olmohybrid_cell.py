"""The dense state cell `olmohybrid-decode-pp4`: its files found by
name, its driver end to end as a CPU rehearsal at a toy size (fixture
`olmohybrid-tiny-decode`, found through `--search-path`), the controls
that `correct` has to refuse, the cell's copy of the reference against
the program's, the model's draw, the configuration's arithmetic and the
bytes and operations of a decode step against hand counts, the new
readers on a written account of a traced call, on a cut recording of
the cell from the chip and on the other cells' facts, the workload
file's keys against the issue's traffic, and BENCHMARK.json's entries
for the cell.
"""

import collections
import json
import os
import shutil
import types

import numpy as np
import pytest

from benchmark.harness import CHECKOUT, Lookup
from benchmark.reduce import decoder_trace, state_ops, xplane
from benchmark.tests import dense_state_control, state_control
from benchmark.tests.test_run import FIXTURE, last_line, run_cell

CELL = "olmohybrid-decode-pp4"
CONFIG = "olmo-hybrid-7b"
TOY, TOY_CONFIG = "olmohybrid-tiny-decode", "olmohybrid-tiny"
NEW_READERS = ("dense_gdn_ms_per_step", "dense_gdn_step_roofline",
               "dense_gdn_prefill_ms_per_call", "mha_attn_ms_per_step",
               "mha_decode_roofline", "dense_ffn_ms_per_step",
               "dense_state_decode_hbm_roofline")
SHARED_READERS = ("decode_trace_lower_s", "decoder_prep_ms_per_call",
                  "decoder_idle_ms_per_call", "prefill_device_ms_per_call",
                  "decode_device_step_ms", "decode_unscoped_ms_per_step")
LIMITED = ("gap_mean", "not_first_share", "state_off", "state_off_first")
PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
LOOKUP = Lookup([FIXTURE])
olmo = LOOKUP.module("flops", "olmo_hybrid")
gated_delta = LOOKUP.module("flops", "gated_delta")
dense_state_ops = LOOKUP.module("reduce", "dense_state_ops")


# -- the cell's files, by name --------------------------------------------------

def test_the_cells_files_are_found_by_name():
    workload = LOOKUP.json("workloads", CELL)
    config = LOOKUP.json("configs", workload["config"])
    assert config["name"] == CONFIG
    for kind, name in (("drivers", workload["driver"]),
                       ("models", workload["builder"]),
                       ("reference", workload["reference"]),
                       ("flops", "olmo_hybrid"),
                       ("reduce", "dense_state_ops"),
                       ("tests", "dense_state_control")):
        assert os.path.dirname(LOOKUP.path(kind, name + ".py")).endswith(kind)
    assert set(NEW_READERS) <= set(LOOKUP.names("layer_metrics"))
    assert (config["builder"], config["reference"]) == \
        (workload["builder"], workload["reference"])


def test_the_cells_reference_is_the_programs_to_the_letter():
    with open(LOOKUP.path("reference", "olmo_hybrid.py")) as copy, \
            open(os.path.join(CHECKOUT, "paddle_tpu", "models", "reference",
                              "olmo_hybrid.py")) as own:
        assert copy.read() == own.read()


# -- the driver, end to end -----------------------------------------------------

def test_untraced_rehearsal_has_exactly_the_two_metrics():
    result = last_line(run_cell(TOY, 0))
    assert result["correct"] is True and result["failed"] == 0
    assert set(result["metrics"]) == {"decode_tok_per_s", "setup_s"}
    assert result["attempted"] % 8 == 0 and result["attempted"] >= 8
    assert result["device"]["platform"] == "cpu"


def test_traced_rehearsal_reads_counters_and_no_device_metric():
    proc = run_cell(TOY, 1)
    result = last_line(proc)
    metrics = result["metrics"]
    assert result["correct"] is True
    assert metrics["compiles_in_window"] == {"value": 0, "unit": "count"}
    assert {"setup_compile_s", "setup_cache_misses",
            "decode_trace_lower_s"} <= set(metrics)
    # what only a chip can say: this cell's and the state cell's
    assert not (set(NEW_READERS) | {
        "gdn_ms_per_step", "gdn_step_roofline", "gated_attn_ms_per_step",
        "state_decode_hbm_roofline", "decode_device_step_ms"}) & set(metrics)
    for stream in (proc.stdout, proc.stderr):
        for name in LIMITED:
            assert "check ok  : %s" % name in stream
        assert "check ok  : no compile inside the windows (0), limit 0" \
            in stream


# -- what `correct` has to refuse -----------------------------------------------

def test_every_control_is_refused_and_the_sound_path_is_not():
    import jax

    workload = dict(LOOKUP.json("workloads", TOY), name=TOY)
    config = LOOKUP.json("configs", TOY_CONFIG)
    controls = dense_state_control.controls_of(config, workload)
    assert len(controls) == 10
    found = dict(state_control.read(LOOKUP, workload, 7, jax.devices()[:1],
                                    None, controls))
    limits = workload["correct"]
    assert state_control.refused(found[None], limits) == []
    for spelling in controls:
        assert state_control.refused(found[spelling], limits), spelling
    # a state the step rounds is seen in the state alone at this size;
    # what is wrong in the full layer alone (the toy's last) leaves the
    # linear layers' states before it sound
    assert state_control.refused(found["state=bfloat16"], limits) \
        == ["state_off", "state_off_first"]
    for spelling in ("rotary=500000", "qk_norm=head", "qk_norm=none"):
        assert not {"state_off", "state_off_first"} & set(
            state_control.refused(found[spelling], limits))


def test_the_controls_are_the_references():
    """Every control `--all` switches is one the reference reads."""
    config = LOOKUP.json("configs", CONFIG)
    workload = LOOKUP.json("workloads", CELL)
    with open(LOOKUP.path("reference", "olmo_hybrid.py")) as f:
        text = f.read()
    for control in dense_state_control.controls_of(config,
                                                   workload).values():
        for key in control:
            assert '_control(cfg, "%s"' % key in text, key
    assert "tail_cut=128" in dense_state_control.controls_of(config, workload)


def test_the_checked_rows_begin_with_the_rows_whose_state_is_carried():
    driver = LOOKUP.module("drivers", "decode_state")
    workload = LOOKUP.json("workloads", CELL)
    rows = driver.checked_rows(types.SimpleNamespace(
        workload=workload, seed=6_700_000_201))
    assert rows.shape == (workload["checked_rows"],)
    assert list(rows[:workload["state_rows"]]) \
        == list(range(workload["state_rows"]))
    assert len(set(rows)) == len(rows) and rows.max() < workload["batch"]


# -- the model's draw -------------------------------------------------------------

def test_the_weights_draw_and_the_state_the_builder_declares():
    """A block made alone is the block served; the gates' parameters
    are float32 and lie where the configuration's `assumed` says; the
    state is declared two heads side by side where a head's values fill
    no lane block, and a head a unit at the toy's 24."""
    import jax

    model = LOOKUP.module("models", "olmohybrid_decode")
    config = LOOKUP.json("configs", TOY_CONFIG)
    spec = LOOKUP.json("workloads", TOY)["weights"]
    key = jax.random.PRNGKey(11)
    whole = jax.jit(lambda k: model.weights(config, spec, k))(key)
    for layer in (0, 3):
        alone = jax.jit(lambda k: model.block(config, spec, model.root(k),
                                              layer))(key)
        for name, value in alone.items():
            np.testing.assert_array_equal(
                np.asarray(value, np.float32),
                np.asarray(whole["blocks"][layer][name], np.float32))
    linear, full = whole["blocks"][0], whole["blocks"][3]
    assert set(full) - set(linear) == {"wq", "wk", "wv", "q_norm", "k_norm"}
    assert set(linear) & set(full) == {"post_attn_norm", "post_ffn_norm",
                                       "ffn_in", "ffn_out", "wo"}
    assert full["q_norm"].shape == full["k_norm"].shape == (64,)
    assert linear["a_log"].dtype == linear["dt_bias"].dtype == np.float32
    rate = np.exp(np.asarray(linear["a_log"]))
    assert (rate > 0).all() and (rate <= 16).all()
    step = np.log1p(np.exp(np.asarray(linear["dt_bias"], np.float64)))
    assert (step >= 0.999e-3).all() and (step <= 0.1001).all()
    assert model.layer_types(config) == (
        "linear_attention", "linear_attention", "linear_attention",
        "full_attention")
    real = LOOKUP.json("configs", CONFIG)
    assert model.layer_types(real) == 2 * model.layer_types(config)
    assert model.state_pack(real) == 2 and model.state_pack(config) == 1
    shapes = model.state_shapes(real, 128)
    assert shapes["delta_state_0"] == ((128, 15, 96, 384), "state")
    assert shapes["conv_tail_0"] == ((128, 3, 11520), "tail")
    assert shapes["k_cache_3"] == ((128, 30, 512, 128), "cache")
    assert sorted(shapes) == sorted(
        ["conv_tail_%d" % i for i in (0, 1, 2, 4, 5, 6)]
        + ["delta_state_%d" % i for i in (0, 1, 2, 4, 5, 6)]
        + ["%s_cache_%d" % (w, i) for w in "kv" for i in (3, 7)])
    sizes = model.sizes(real)
    assert (sizes["norm_order"], sizes["qk_norm"], sizes["attn_gate"],
            sizes["rope_theta"], sizes["beta_scale"], sizes["n_dense"],
            sizes["d_ff"]) == ("post", "whole", False, None, 2.0, 8, 11008)


# -- arithmetic, bytes and operations ---------------------------------------------

def test_parameters_and_bytes_are_the_issues():
    config = LOOKUP.json("configs", CONFIG)
    assert olmo.count(config, olmo.LINEAR) == 6
    assert olmo.count(config, olmo.FULL) == 2
    d = 3840
    # by hand, to the parameter: W_qkvz, W_ba, the filter, A_log and
    # dt_bias, the output norm, W_o
    assert olmo.linear_parameters(config) == (
        d * (2 * 2880 + 2 * 5760) + d * 60 + 11520 * 4 + 60 + 192
        + 5760 * d) == 88_750_332
    assert olmo.full_parameters(config) == 4 * d * d + 2 * d == 58_990_080
    assert olmo.layer_parameters(config) == 3 * d * 11008 + 2 * d \
        == 126_819_840
    assert olmo.chip_parameters(config) == 2_435_748_072
    assert round(olmo.chip_parameters(config) / 1e6, 1) == 2435.7
    # the whole model's count, the catalog's to four digits
    whole = 24 * (88_750_332 + 126_819_840) \
        + 8 * (58_990_080 + 126_819_840) + 2 * 100_352 * d + d
    assert round(whole / 1e9, 2) == 7.43
    assert olmo.state_row_bytes(config) == 30 * 96 * 192 * 4 == 2_211_840
    assert gated_delta.tail_row_bytes(config, 2) == 3 * 11520 * 2 == 69_120
    # a token a full layer: keys and values of 30 heads of 128, bfloat16
    one = olmo.kv_step(config, 1, 0, 2)["bytes"] / olmo.count(config,
                                                              olmo.FULL)
    assert one == 2 * 30 * 128 * 2 == 15_360
    # a step: 6 layers x 128 rows x 2.21 MB read and written
    rule = olmo.rule_step(config, 128)
    assert rule["bytes"] == 6 * 128 * (2 * 2_211_840
                                       + (2 * 2880 + 2 * 5760 + 60) * 4)
    assert 3.39e9 < rule["bytes"] < 3.46e9
    assert rule["flops"] == 6 * 128 * 30 * 96 * 192 * 7
    assert round(olmo.state_bytes(config, 128, 2) / 1e9, 2) == 3.50
    assert olmo.weight_bytes(config, 128, 2) == 2 * (
        2_435_748_072 - 100_352 * d + 128 * d)
    assert round(olmo.weight_bytes(config, 128, 2) / 1e9, 2) == 4.10
    at = 128 + (384 - 2) / 2.0
    kv = olmo.kv_step(config, 128, at, 2)
    assert kv["bytes"] == 128 * 2 * (at + 1) * 15_360
    assert round(kv["bytes"] / 1e9, 2) == 1.26
    assert kv["flops"] == 2 * 2 * 128 * 30 * 128 * 2 * (at + 1)
    must = olmo.step_bytes(config, 128, at, 2, 2)
    assert must == olmo.weight_bytes(config, 128, 2) \
        + olmo.state_bytes(config, 128, 2) + kv["bytes"]
    assert round(must / 1e9, 2) == 8.86
    assert 0.38 < olmo.state_bytes(config, 128, 2) / must < 0.40


# -- the readers ---------------------------------------------------------------------

MARK = "~"
Op = collections.namedtuple("Op", "start end name category path text")
FACTS = dict(dense_state_gen_len=5, dense_state_prompt_len=128,
             dense_state_batch=128, setup_compile_s=60.0,
             setup_cache_misses=30, decode_trace_lower_s=5.5,
             compiles_in_window=0)


class Written:
    """What a reader asks of a run (hashable: some readers keep what
    they made of one)."""

    def __init__(self, **fields):
        self.__dict__.update(fields)


def written_run(facts=None, cell=CELL, config=CONFIG, peaks=PEAKS):
    workload = dict(LOOKUP.json("workloads", cell), name=cell)
    return Written(
        workload=workload, config=LOOKUP.json("configs", config),
        facts=dict(FACTS) if facts is None else facts, peaks=peaks,
        lookup=LOOKUP, reduced=None, trace_dir=None, trace=True, seed=1,
        devices=[None], window_start=None, host_spans=[])


class WrittenCall:
    """A traced call of 4 steps, a few ms an op, written by hand: what
    decoder_trace.Parts gives the readers."""

    def __init__(self, names):
        def path(kind, instance, *inner):
            return "/".join(("jit(f)", "decode_steps", "while", "body", kind,
                             MARK + instance) + inner + ("fusion",))

        self.call = decoder_trace.Call(None, {"max_len": 5, "prompt_len": 128,
                                              "block": 128})
        self.steps, self.prefill = (10.0, 20.0), (0.0, 5.0)
        rule, conv = "gated_delta_rule", "causal_conv1d"
        self.ops = [
            Op(10.0, 10.004, "gdn_step_r8_h4_k8_v24_b8", "custom-call",
               path(rule, "gated_delta_rule_0.tmp_0", "gdn_state"), ""),
            Op(11.0, 11.001, "fusion.1", "loop fusion",
               path(rule, "gated_delta_rule_0.tmp_0", "gdn_gates"), ""),
            Op(12.0, 12.002, "fusion.2", "loop fusion",
               path(conv, "causal_conv1d_0.tmp_0"), ""),
            Op(13.0, 13.001, "fusion.3", "loop fusion",
               path("scale", "gdn_gates_3.tmp_0"), ""),
            Op(14.0, 14.001, "fusion.4", "loop fusion",
               path("rms_norm", "gdn_out_norm_0.tmp_0"), ""),
            Op(15.0, 15.003, "fusion.5", "output fusion",
               path("mul", names["w_qkvz"]), ""),
            # the full layer's mixer: a projection in, the norms, the
            # cache's write and walk, the projection out
            Op(15.5, 15.502, "fusion.6", "output fusion",
               path("mul", names["wq"]), ""),
            Op(15.6, 15.601, "fusion.6b", "loop fusion",
               path("rms_norm", "mha_attn_0.tmp_0"), ""),
            Op(16.0, 16.008, "gqa_decode_k512_d128", "custom-call",
               path("cached_attention", "cached_attention_0.tmp_0",
                    "attn_full"), ""),
            Op(16.5, 16.501, "fusion.7", "loop fusion",
               path("cached_attention", "cached_attention_0.tmp_0",
                    "kv_write"), ""),
            Op(17.0, 17.002, "fusion.8", "output fusion",
               path("mul", names["full_wo"]), ""),
            # a feed-forward: two products and the gate between them
            Op(18.0, 18.006, "fusion.9", "output fusion",
               path("mul", names["ffn_in"]), ""),
            Op(18.5, 18.501, "fusion.10", "loop fusion",
               path("swish", "dense_ffn_1.tmp_0"), ""),
            Op(19.0, 19.005, "fusion.11", "output fusion",
               path("mul", names["ffn_out"]), ""),
            # a block's norm: nobody's
            Op(19.5, 19.501, "fusion.12", "loop fusion",
               path("rms_norm", "rms_norm_0.tmp_0"), ""),
            # the block form, inside the prefill
            Op(1.0, 1.200, "fusion.13", "loop fusion",
               "/".join(("jit(f)", "decode_prefill", rule,
                         MARK + "gated_delta_rule_0.tmp_0", "gdn_chunks",
                         "dot_general")), ""),
        ]

    def work(self, interval):
        return [op for op in self.ops
                if interval[0] <= op.start and op.end <= interval[1]]

    def busy(self, interval):
        return sum(op.end - op.start for op in self.work(interval))


@pytest.fixture()
def written(monkeypatch):
    """A run whose traced call is `WrittenCall`, with the instances of
    the toy cell's own step Program."""
    from paddle_tpu.fluid import executor

    run = written_run(cell=TOY, config=TOY_CONFIG)
    run.facts["dense_state_batch"] = run.workload["batch"]
    ops = state_ops._step_ops.__wrapped__(run)
    by_weight = {od.input("Y")[0].split(".", 1)[1]
                 + ("@" + od.input("Y")[0].split(".")[0]): executor
                 .op_instance(od)[1:] for od in ops if od.type == "mul"}
    names = {"w_qkvz": by_weight["w_qkvz@block_0"],
             "wq": by_weight["wq@block_3"],
             "full_wo": by_weight["wo@block_3"],
             "ffn_in": by_weight["ffn_in@block_1"],
             "ffn_out": by_weight["ffn_out@block_1"]}
    monkeypatch.setattr(state_ops, "_step_ops", lambda r: ops)
    monkeypatch.setattr(state_ops.op_instances, "sigil", lambda: MARK)
    monkeypatch.setattr(decoder_trace, "parts",
                        lambda r: [WrittenCall(names)])
    return run


def test_the_new_readers_on_a_written_call(written, capsys):
    read = lambda name: LOOKUP.module("layer_metrics", name).read(written)
    # 4 steps: the kernel 4 ms, the gates 1 + 1, the convolution 2, the
    # norm 1, the linear layer's projection 3 (a full layer's `wo` is not
    # the linear mixers')
    assert read("dense_gdn_ms_per_step") == pytest.approx(12.0 / 4)
    # the walk 8, the write 1, the norm 1, the two projections 2 + 2
    assert read("mha_attn_ms_per_step") == pytest.approx(14.0 / 4)
    assert read("dense_ffn_ms_per_step") == pytest.approx(12.0 / 4)
    assert read("dense_gdn_prefill_ms_per_call") == pytest.approx(200.0)
    batch = written.workload["batch"]
    cost = olmo.rule_step(written.config, batch)
    assert read("dense_gdn_step_roofline") == pytest.approx(
        100 * cost["bytes"] / PEAKS["hbm_bytes_per_s"] / 1e-3)
    kv = olmo.kv_step(written.config, batch, 128 + 1.5, 4)
    assert read("mha_decode_roofline") == pytest.approx(
        100 * kv["bytes"] / PEAKS["hbm_bytes_per_s"] / 2e-3)
    must = olmo.step_bytes(written.config, batch, 128 + 1.5, 4, 4)
    busy = 0.039 / 4
    assert read("dense_state_decode_hbm_roofline") == pytest.approx(
        100 * must / PEAKS["hbm_bytes_per_s"] / busy)
    said = capsys.readouterr().out
    assert "gdn_state 1.0000" in said and "projections 0.7500" in said
    assert "memory-bound" in said and "gqa_decode_* 2.0000" in said
    assert "q and k norms 0.2500" in said
    assert "gate (elementwise) 0.2500, products 2.7500" in said
    # the state cell's own readers see none of it
    for name in ("gdn_ms_per_step", "gdn_step_roofline",
                 "gated_attn_ms_per_step", "state_decode_hbm_roofline",
                 "state_moe_ms_per_step"):
        assert LOOKUP.module("layer_metrics", name).read(written) is None


# `data/olmohybrid-decode-pp4-steps.xplane.pb` is a recording from the
# chip (TPU v5 lite, this cell traced on --seed 6700000204, my chip run,
# PR 67, call 4) cut by benchmark/tests/cut_scan_recording.py to device
# 0's step 191 of the decoding scan's 383 under its `while` (755
# operations with their paths as the chip wrote them, `jit(<lambda>)/
# decode_steps/while/body/closed_call/gated_delta_rule/
# ~gated_delta_rule_0.tmp_0/gdn_state/...gdn_step_r128_h30_k96_v192_b4/
# pallas_call`) and, as its other scan, the first of the two chunks the
# rule's block form walks in the prefill (the prompt is one application,
# so the call's second longest `while` is that walk: the cutter's last
# whole step of a scan of two, kept through a wrapper that takes the
# step before the end where the middle one has no next).  That decoding
# step wrote slot 128 + 191 = 319, the mean of the call's decoding
# steps, so the facts below say one decoding step there.  The recording
# holds no `decode/call` span (the cutter keeps the device's side): the
# test stands one over the recording's window, as the decoder's spans
# stood over the call on the chip.  Of the whole scan the run itself
# printed, a decoding step: the linear mixers 6.5513 ms (gdn_state
# 4.7990, the kernels 4.6901 at 89.83% of their roofline), the full
# layers' attention 5.3930 (gqa_decode_* 4.8238, 31.85%), the
# feed-forward 2.3487, the step 16.4059 ms on the device, 65.97%.
RECORDED_FACTS = dict(dense_state_gen_len=2, dense_state_prompt_len=318,
                      dense_state_batch=128)
RECORDED_MS = {"dense_gdn_ms_per_step": 6.552954,
               "mha_attn_ms_per_step": 5.394496,
               "dense_ffn_ms_per_step": 2.349100,
               "dense_gdn_prefill_ms_per_call": 7.711474}


def test_the_new_readers_on_a_recording_from_the_chip(tmp_path, capsys,
                                                       monkeypatch):
    from benchmark.reduce import program_spans

    shutil.copy(os.path.join(os.path.dirname(__file__), "data",
                             "olmohybrid-decode-pp4-steps.xplane.pb"),
                str(tmp_path))
    run = written_run(dict(FACTS, **RECORDED_FACTS))
    run.reduced, run.trace_dir = xplane.load(str(tmp_path)), str(tmp_path)
    lo, hi = run.reduced.window
    call = decoder_trace.Call(
        program_spans.Span(lo, hi, decoder_trace.CALL, ("/host:CPU", 0)),
        {"max_len": 2, "prompt_len": 318, "block": 128})
    monkeypatch.setattr(decoder_trace, "_traced",
                        lambda trace, trace_dir: ([call.span], [call]))
    read = {name: LOOKUP.module("layer_metrics", name).read(run)
            for name in NEW_READERS}
    for name, ms in RECORDED_MS.items():
        assert read[name] == pytest.approx(ms, abs=1e-6), name
    printed = capsys.readouterr().out
    assert "gdn_conv 0.3178, gdn_gates 0.0604, gdn_gates (elementwise) " \
        "0.0028, gdn_out_norm (elementwise) 0.0626, gdn_state 4.8006, " \
        "projections 1.3087" in printed
    assert "(no scope) 0.0056, attn_full 4.8294, kv_write 0.2476, " \
        "projections 0.2809, q and k norms 0.0309; gqa_decode_* 4.8250 ms " \
        "(x2.0)" in printed
    assert "products 2.3491" in printed
    # the six kernels of the step (what the prefix found says the state's
    # shape: `gdn_step_r128_h30_k96_v192_b4`, section 5's `device_ops`)
    assert "gdn_step_*: 4.6918 ms a decoding step (x6.0)" in printed
    assert "gdn_chunks 7.711" in printed
    config = run.config
    rule = olmo.rule_step(config, 128)
    assert read["dense_gdn_step_roofline"] == pytest.approx(
        100.0 * rule["bytes"] / 819e9 / 4.6918e-3, rel=1e-4)
    # the step that wrote slot 319: one decoding step after 318 tokens
    kv = olmo.kv_step(config, 128, 318.0, 2)
    assert read["mha_decode_roofline"] == pytest.approx(
        100.0 * kv["bytes"] / 819e9 / 4.8250e-3, rel=1e-4)
    assert "decode step: 16.4090 ms on the device" in printed
    must = olmo.step_bytes(config, 128, 318.0, 2, 2)
    assert read["dense_state_decode_hbm_roofline"] == pytest.approx(
        100.0 * must / 819e9 / 16.4090e-3, rel=1e-4)
    assert all(0 < read[n] < 100 for n in read if "roofline" in n)


@pytest.mark.parametrize("facts", [
    {"share_gen_len": 896, "share_step_applications": 1023},
    {"state_gen_len": 896, "state_prompt_len": 128, "state_batch": 128},
    {"hybrid_gen_len": 896}, {}])
def test_the_new_readers_find_nothing_without_the_cells_facts(written,
                                                              facts):
    """On another generation cell's facts (the parent's checkout with
    these files laid over it runs so): nothing, and no raise."""
    written.facts = facts
    for name in NEW_READERS:
        assert LOOKUP.module("layer_metrics", name).read(written) is None


def test_no_reader_of_the_benchmark_raises_on_this_cells_facts():
    """Every reader under layer_metrics/ gives None or a number on the
    dense state driver's facts with a chip's peaks set and no trace; the
    other generation cells' readers find nothing to read."""
    run = written_run()
    found = {name: LOOKUP.module("layer_metrics", name).read(run)
             for name in LOOKUP.names("layer_metrics")}
    assert all(v is None or isinstance(v, (int, float))
               for v in found.values()), found
    for name in NEW_READERS + (
            "gdn_ms_per_step", "gdn_step_roofline",
            "gdn_prefill_ms_per_call", "gated_attn_ms_per_step",
            "state_moe_ms_per_step", "state_decode_hbm_roofline",
            "share_decode_step_ms", "decode_step_ms",
            "long_decode_step_ms", "mfu", "setup_trace_lower_s"):
        assert found[name] is None, name
    assert found["decode_trace_lower_s"] == 5.5
    assert found["setup_compile_s"] == 60.0
    assert found["compiles_in_window"] == 0


# -- the workload file and BENCHMARK.json ----------------------------------------

def test_the_workload_is_the_issues_traffic():
    workload = LOOKUP.json("workloads", CELL)
    config = LOOKUP.json("configs", CONFIG)
    assert (workload["batch"], workload["prompt_len"], workload["gen_len"],
            workload["pool"], workload["checked_rows"],
            workload["state_rows"], workload["reference_rows"]) == \
        (128, 128, 384, 4, 32, 4, 2)
    assert workload["prompt_len"] + workload["gen_len"] \
        == config["serve_positions"] == 512
    assert (workload["serve_dtype"], workload["weights"]["dtype"]) == \
        ("bfloat16", "bfloat16")
    draw = workload["weights"]
    assert (draw["seed"], draw["std"], draw["embed_std"], draw["qk_gain"],
            draw["conv_std"], draw["dt_min"], draw["dt_max"]) == \
        (6700000101, 0.02, 1.0, 2.5, 0.3, 0.001, 0.1)
    assert (workload["driver"], workload["chips"]) == \
        ("decode_dense_state", 1)
    assert set(workload["correct"]) == set(LIMITED) | {"why"}
    for key in ("why", "who", "sizing"):
        assert workload[key]


def test_benchmark_json_has_the_cell_and_its_metrics():
    with open(os.path.join(CHECKOUT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    cell, workload = cells[CELL], LOOKUP.json("workloads", CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (CONFIG, CELL, 1)
    assert cell["why"] == workload["why"] and len(cell["why"]) <= 200
    assert 17 <= len(cells) <= 24
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    configs = {c["name"]: c for c in bench["configs"]}
    entry, config = configs[CONFIG], LOOKUP.json("configs", CONFIG)
    assert entry["file"] == "benchmark/configs/%s.json" % CONFIG
    assert entry["source"] == config["source"]
    assert entry["reduced"] == config["reduced"] == ["num_hidden_layers"]
    assert len(entry["why"]) <= 200
    end_to_end = {m["name"]: m for m in bench["end_to_end"]}
    assert CELL in end_to_end["decode_tok_per_s"]["workloads"]
    listed = {m["name"]: m for m in bench["per_layer"]}
    assert len(listed) <= 128
    for name in SHARED_READERS:
        assert CELL in listed[name]["workloads"]
    for name, m in listed.items():
        if name not in NEW_READERS + SHARED_READERS:
            assert CELL not in m.get("workloads", []), name
    for name in NEW_READERS:
        reader = LOOKUP.module("layer_metrics", name)
        assert listed[name]["workloads"] == [CELL]
        assert (listed[name]["moves"], listed[name]["layer"],
                listed[name]["unit"], listed[name]["source"]) == \
            (reader.MOVES, reader.LAYER, reader.UNIT, reader.SOURCE)
        assert set(listed[name]) == {"name", "unit", "better", "source",
                                     "layer", "moves", "workloads"}
        assert listed[name]["better"] == (
            "higher" if name.endswith("roofline") else "lower")


def test_the_configuration_keeps_every_published_key():
    """Every key of the catalog's entry under its own name and value
    but the depth; `layer_types` whole; what is derived says so."""
    config = LOOKUP.json("configs", CONFIG)
    published = {
        "model_type": "olmo_hybrid", "vocab_size": 100352,
        "hidden_size": 3840, "intermediate_size": 11008,
        "num_hidden_layers": 32, "num_attention_heads": 30,
        "num_key_value_heads": 30, "hidden_act": "silu",
        "max_position_embeddings": 65536, "attention_bias": False,
        "rms_norm_eps": 1e-06, "tie_word_embeddings": False,
        "layer_types": 8 * ["linear_attention", "linear_attention",
                            "linear_attention", "full_attention"],
        "linear_num_key_heads": 30, "linear_num_value_heads": 30,
        "linear_key_head_dim": 96, "linear_value_head_dim": 192,
        "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
        "rope_parameters": {"rope_theta": None}}
    differs = sorted(k for k, v in published.items() if config[k] != v)
    assert differs == config["reduced"] == ["num_hidden_layers"]
    assert config["published"] == {"num_hidden_layers": 32}
    assert config["num_hidden_layers"] == 8 \
        == 2 * config["full_attention_interval"]
    assert set(config["derived"]) == {"head_dim", "full_attention_interval"}
    assert config["head_dim"] * config["num_attention_heads"] \
        == config["hidden_size"]
    for key in ("stands_for", "assumed", "departures", "arithmetic",
                "reduced_why"):
        assert config[key]
    assert {"norm_order", "positions", "qk_norm", "state_dtype",
            "delta_rule", "a_log_dt_bias"} <= set(config["assumed"])
