"""The Mamba-2 state cell `granite-decode-ep4`: its files found by name,
its driver end to end as a CPU rehearsal at a toy size (fixture
`granite-small-tiny-decode`, found through `--search-path`), the
controls that `correct` has to refuse, the cell's copy of the reference
against the program's, the model's draw, the configuration's arithmetic
and the bytes and operations of a decode step against hand counts, the
new readers on a written account of a traced call, on a cut recording of
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
from benchmark.tests import ssd_state_control, state_control
from benchmark.tests.test_run import FIXTURE, last_line, run_cell

CELL = "granite-decode-ep4"
CONFIG = "granite-4.0-h-small"
TOY, TOY_CONFIG = "granite-small-tiny-decode", "granite-small-tiny"
NEW_READERS = ("ssd_step_roofline", "ssd_decode_hbm_roofline")
SHARED_READERS = ("decode_trace_lower_s", "decoder_prep_ms_per_call",
                  "decoder_idle_ms_per_call", "prefill_device_ms_per_call",
                  "decode_device_step_ms", "decode_unscoped_ms_per_step")
LIMITED = ("gap_mean", "not_first_share", "held_part_off", "state_off",
           "state_off_first", "state_step_off")
PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
LOOKUP = Lookup([FIXTURE])
ssd_step = LOOKUP.module("flops", "ssd_step")
ssd_state_ops = LOOKUP.module("reduce", "ssd_state_ops")


# -- the cell's files, by name --------------------------------------------------

def test_the_cells_files_are_found_by_name():
    workload = LOOKUP.json("workloads", CELL)
    config = LOOKUP.json("configs", workload["config"])
    assert config["name"] == CONFIG
    for kind, name in (("drivers", workload["driver"]),
                       ("models", workload["builder"]),
                       ("reference", workload["reference"]),
                       ("flops", "ssd_step"), ("reduce", "ssd_state_ops"),
                       ("tests", "ssd_state_control")):
        assert os.path.dirname(LOOKUP.path(kind, name + ".py")).endswith(kind)
    assert set(NEW_READERS) <= set(LOOKUP.names("layer_metrics"))
    assert (config["builder"], config["reference"]) == \
        (workload["builder"], workload["reference"])


def test_the_cells_reference_is_the_programs_to_the_letter():
    with open(LOOKUP.path("reference", "granite_moe_hybrid.py")) as copy, \
            open(os.path.join(CHECKOUT, "paddle_tpu", "models", "reference",
                              "granite_moe_hybrid.py")) as own:
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
    # what only a chip can say: this cell's and the delta-rule cells'
    assert not (set(NEW_READERS) | {
        "gdn_ms_per_step", "gdn_step_roofline", "state_moe_ms_per_step",
        "state_decode_hbm_roofline", "dense_gdn_ms_per_step",
        "decode_device_step_ms"}) & set(metrics)
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
    controls = ssd_state_control.controls_of(config, workload)
    assert len(controls) == 9
    found = dict(state_control.read(LOOKUP, workload, 7, jax.devices()[:1],
                                    None, controls))
    limits = workload["correct"]
    assert state_control.refused(found[None], limits) == []
    for spelling in controls:
        assert state_control.refused(found[spelling], limits), spelling
    # a state the step rounds is seen in the state alone at this size;
    # what is wrong past the first layer's scan (its `D x`, the attention
    # layer, the residual, the experts) leaves the first layer's state
    # sound; a dropped expert is seen in the held part alone
    assert state_control.refused(found["state=bfloat16"], limits) \
        == ["state_off", "state_off_first", "state_step_off"]
    # the last step alone reads the rounding in every mamba layer, and
    # a decay left out; nothing else of the controls is in one update
    assert min(found["state=bfloat16"]["state_step_off_by_layer"]) \
        > limits["state_step_off"]
    assert [spelling for spelling in controls if "state_step_off"
            in state_control.refused(found[spelling], limits)] \
        == ["state=bfloat16", "decay=false"]
    for spelling in ("skip=false", "attention_multiplier=head_dim**-0.5",
                     "residual_multiplier=1", "shared_width=16",
                     "drop=true"):
        assert "state_off_first" not in state_control.refused(
            found[spelling], limits), spelling
    assert "held_part_off" in state_control.refused(found["drop=true"],
                                                    limits)


def test_the_controls_are_the_references():
    """Every control `--all` switches is one the reference reads."""
    config = LOOKUP.json("configs", CONFIG)
    workload = LOOKUP.json("workloads", CELL)
    with open(LOOKUP.path("reference", "granite_moe_hybrid.py")) as f:
        text = f.read()
    controls = ssd_state_control.controls_of(config, workload)
    for control in controls.values():
        for key in control:
            assert '_control(cfg, "%s"' % key in text.replace(
                "_control(\n        cfg, ", "_control(cfg, "), key
    assert {"tail_cut=256", "state_cut=256", "shared_width=768"} \
        <= set(controls)
    assert controls["attention_multiplier=head_dim**-0.5"] \
        == {"attention_multiplier": 128 ** -0.5}


def test_the_checked_rows_begin_with_the_rows_whose_state_is_carried():
    driver = LOOKUP.module("drivers", "decode_state")
    workload = LOOKUP.json("workloads", CELL)
    rows = driver.checked_rows(types.SimpleNamespace(
        workload=workload, seed=7_100_000_201))
    assert rows.shape == (workload["checked_rows"],)
    assert list(rows[:workload["state_rows"]]) \
        == list(range(workload["state_rows"]))
    assert len(set(rows)) == len(rows) and rows.max() < workload["batch"]


# -- the model's draw -------------------------------------------------------------

def test_the_weights_draw_and_the_state_the_builder_declares():
    """A block made alone is the block served; the scan's parameters are
    float32 and lie where the configuration's `assumed` says; the state
    is declared in the layout the program carries it, state entries by
    head lanes."""
    import jax

    model = LOOKUP.module("models", "granite_small_decode")
    config = LOOKUP.json("configs", TOY_CONFIG)
    spec = LOOKUP.json("workloads", TOY)["weights"]
    key = jax.random.PRNGKey(11)
    whole = jax.jit(lambda k: model.weights(config, spec, k))(key)
    for layer in (0, 2):
        alone = jax.jit(lambda k: model.block(config, spec, model.root(k),
                                              layer))(key)
        for name, value in alone.items():
            np.testing.assert_array_equal(
                np.asarray(value, np.float32),
                np.asarray(whole["blocks"][layer][name], np.float32))
    mamba, attention = whole["blocks"][0], whole["blocks"][2]
    assert set(attention) - set(mamba) == {"wq", "wk", "wv", "wo"}
    assert set(mamba) & set(attention) == {
        "norm_1", "norm_2", "shared_in", "shared_out", "router", "w_gate",
        "w_up", "w_down"}
    assert set(whole) == {"embed", "norm_f", "blocks"}   # a tied head
    assert mamba["shared_in"].shape == (64, 48)          # twice 24
    assert mamba["w_gate"].shape == (4, 64, 16)
    assert mamba["router"].shape == (64, 8)
    for name in ("a_log", "dt_bias", "d"):
        assert mamba[name].dtype == np.float32, name
    rate = np.exp(np.asarray(mamba["a_log"]))
    assert (rate >= 1).all() and (rate <= 16).all()
    step = np.log1p(np.exp(np.asarray(mamba["dt_bias"], np.float64)))
    assert (step >= 0.999e-3).all() and (step <= 0.1001).all()
    assert abs(float(np.mean(np.asarray(mamba["d"]))) - 1.0) < 0.2
    real = LOOKUP.json("configs", CONFIG)
    assert model.layer_types(real) == 5 * ("mamba",) + ("attention",) \
        + 4 * ("mamba",)
    shapes = model.state_shapes(real, 64)
    assert shapes["ssd_state_0"] == ((64, 128, 8192), "state")
    assert shapes["conv_tail_0"] == ((64, 3, 8448), "tail")
    assert shapes["k_cache_5"] == ((64, 8, 640, 128), "cache")
    assert sorted(shapes) == sorted(
        ["conv_tail_%d" % i for i in range(10) if i != 5]
        + ["ssd_state_%d" % i for i in range(10) if i != 5]
        + ["k_cache_5", "v_cache_5"])
    assert model.probe_shapes(real, 2) == {
        "state": ((2, 128, 64, 128), "state"),
        "state_in": ((2, 16, 64, 128), "state"),
        "step_in": ((2, 1, 8192 + 256 + 128), "tail")}
    sizes = model.sizes(real)
    assert (sizes["d_expert"], sizes["d_shared"], sizes["n_experts"],
            sizes["held"], sizes["top_k"], sizes["sm_scale"],
            sizes["chunk"], sizes["vocab_size"]) == \
        (768, 1536, 72, (0, 18), 10, 0.0078125, 256, 25088)
    assert (sizes["embedding_multiplier"], sizes["residual_multiplier"],
            sizes["logits_scaling"]) == (12, 0.22, 16)


# -- arithmetic, bytes and operations ---------------------------------------------

def test_parameters_and_bytes_are_the_issues():
    config = LOOKUP.json("configs", CONFIG)
    assert ssd_step.count(config, ssd_step.MAMBA) == 9
    assert ssd_step.count(config, ssd_step.ATTENTION) == 1
    d = 4096
    # by hand, to the parameter: W_in, the filter and its bias, the
    # gated norm, A_log, D and dt_bias, W_out
    assert ssd_step.mamba_parameters(config) == (
        d * 16_768 + 8448 * 5 + 8192 + 384 + 8192 * d) == 102_286_976
    assert ssd_step.attention_parameters(config) \
        == 2 * d * d + 2 * d * 1024 == 41_943_040
    assert ssd_step.shared_parameters(config) \
        == 3 * d * 1536 + d * 72 + 2 * d == 18_874_368 + 294_912 + 8_192
    assert ssd_step.held_expert_parameters(config) \
        == 18 * 3 * d * 768 == 169_869_312
    assert ssd_step.chip_parameters(config) == 2_955_758_208
    assert round(2 * ssd_step.chip_parameters(config) / 1e9, 2) == 5.91
    # the whole model's count, the catalog's 32B-A9B
    beside = (36 * (102_286_976 + 19_177_472)
              + 4 * (41_943_040 + 19_177_472))
    assert round(beside / 40 / 1e6, 1) == 115.4
    whole = beside + 40 * 72 * 9_437_184 + 100_352 * d + d
    assert round(whole / 1e9, 1) == 32.2
    active = beside + 40 * 10 * 9_437_184 + 100_352 * d
    assert round(active / 1e9, 1) == 8.8
    assert ssd_step.state_row_bytes(config) == 128 * 64 * 128 * 4 \
        == 4_194_304
    assert ssd_step.tail_row_bytes(config, 2) == 3 * 8448 * 2 == 50_688
    # a step: 9 layers x 64 rows x 4.19 MB read and written
    step = ssd_step.step(config, 64)
    assert step["bytes"] == 9 * 64 * (2 * 4_194_304 + (2 * 8192 + 256 + 256) * 4)
    assert 4.83e9 < step["bytes"] < 4.92e9
    assert step["flops"] == 9 * 64 * 128 * 64 * 128 * 6
    assert round(ssd_step.state_bytes(config, 64, 2) / 1e9, 2) == 4.89
    at = 256 + (384 - 2) / 2.0
    assert ssd_step.kv_step(config, 64, at, 2) \
        == 64 * (at + 1) * 2 * 8 * 128 * 2
    assert round(ssd_step.kv_step(config, 64, at, 2) / 1e9, 2) == 0.12
    must = ssd_step.step_bytes(config, 64, at, 2, 2)
    assert must == 2 * 2_955_758_208 + ssd_step.state_bytes(config, 64, 2) \
        + ssd_step.kv_step(config, 64, at, 2)
    assert round(must / 1e9, 1) == 10.9
    assert 0.44 < ssd_step.state_bytes(config, 64, 2) / must < 0.46


# -- the readers ---------------------------------------------------------------------

MARK = "~"
Op = collections.namedtuple("Op", "start end name category path text")
FACTS = dict(ssd_state_gen_len=5, ssd_state_prompt_len=256,
             ssd_state_batch=64, setup_compile_s=60.0,
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

        self.call = decoder_trace.Call(None, {"max_len": 5, "prompt_len": 256,
                                              "block": 256})
        self.steps, self.prefill = (10.0, 20.0), (0.0, 5.0)
        scan, conv = "ssd_scan", "causal_conv1d"
        self.ops = [
            Op(10.0, 10.004, "ssd_step_r8_h16_p8_n16_b8x128", "custom-call",
               path(scan, "ssd_scan_0.tmp_0", "ssd_step"), ""),
            Op(11.0, 11.001, "fusion.1", "loop fusion",
               path(scan, "ssd_scan_0.tmp_0", "ssd_decay"), ""),
            Op(12.0, 12.002, "fusion.2", "loop fusion",
               path(conv, "causal_conv1d_0.tmp_0"), ""),
            Op(13.0, 13.001, "fusion.3", "loop fusion",
               path("rms_norm", "ssd_gated_norm_2.tmp_0"), ""),
            Op(14.0, 14.003, "fusion.4", "output fusion",
               path("mul", names["in_proj"]), ""),
            # the attention layer's mixer
            Op(15.0, 15.002, "fusion.5", "output fusion",
               path("mul", names["wq"]), ""),
            Op(16.0, 16.008, "gqa_decode_k640_d128", "custom-call",
               path("cached_attention", "cached_attention_0.tmp_0",
                    "attn_full"), ""),
            # a feed-forward half: the router, the held experts, the
            # shared expert's first product
            Op(17.0, 17.001, "fusion.6", "loop fusion",
               path("moe_router", "moe_router_0.tmp_0"), ""),
            Op(17.5, 17.506, "fusion.7", "output fusion",
               path("moe_experts", "moe_experts_0.tmp_0"), ""),
            Op(18.0, 18.002, "fusion.8", "output fusion",
               path("mul", names["shared_in"]), ""),
            # a block's norm: nobody's
            Op(19.5, 19.501, "fusion.9", "loop fusion",
               path("rms_norm", "rms_norm_0.tmp_0"), ""),
            # the block form, inside the prefill
            Op(1.0, 1.200, "ssd_block_c8_h16", "custom-call",
               "/".join(("jit(f)", "decode_prefill", scan,
                         MARK + "ssd_scan_0.tmp_0", "ssd_chunks",
                         "pallas_call")), ""),
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
    run.facts["ssd_state_batch"] = run.workload["batch"]
    ops = state_ops._step_ops.__wrapped__(ssd_state_ops.view(run))
    by_weight = {od.input("Y")[0]: executor.op_instance(od)[1:]
                 for od in ops if od.type == "mul"}
    names = {"in_proj": by_weight["block_0.in_proj"],
             "wq": by_weight["block_2.wq"],
             "shared_in": by_weight["block_1.shared_in"]}
    monkeypatch.setattr(state_ops, "_step_ops", lambda r: ops)
    monkeypatch.setattr(state_ops.op_instances, "sigil", lambda: MARK)
    monkeypatch.setattr(decoder_trace, "parts",
                        lambda r: [WrittenCall(names)])
    return run


def test_the_new_readers_on_a_written_call(written, capsys):
    read = lambda name: LOOKUP.module("layer_metrics", name).read(written)
    batch = written.workload["batch"]
    cost = ssd_step.step(written.config, batch)
    # 4 steps: the kernel 4 ms in all, 1 ms a step
    assert read("ssd_step_roofline") == pytest.approx(
        100 * cost["bytes"] / PEAKS["hbm_bytes_per_s"] / 1e-3)
    must = ssd_step.step_bytes(written.config, batch, 256 + 1.5, 4, 4)
    busy = 0.031 / 4
    assert read("ssd_decode_hbm_roofline") == pytest.approx(
        100 * must / PEAKS["hbm_bytes_per_s"] / busy)
    said = capsys.readouterr().out
    assert "ssd_decay 0.2500, ssd_step 1.0000; in all 1.2500" in said
    assert "ssd_chunks 200.0000" in said
    assert "ssd_step: 1.0000 ms a decoding step" in said \
        and "memory-bound" in said
    assert "attention projections 0.5000, cached_attention 2.0000, " \
        "convolution with its tail 0.5000, gated norm 0.2500, mamba " \
        "projections 0.7500, moe_experts 1.5000, moe_router 0.2500, scan " \
        "1.2500, shared expert 0.5000; in all 7.5000" in said
    assert "a call's prefill by the Program's ops, device ms: scan " \
        "200.0000" in said
    # the delta-rule cells' own readers see none of it
    for name in ("gdn_ms_per_step", "gdn_step_roofline",
                 "state_decode_hbm_roofline", "state_moe_ms_per_step",
                 "dense_gdn_ms_per_step", "dense_state_decode_hbm_roofline"):
        assert LOOKUP.module("layer_metrics", name).read(written) is None


# `data/granite-decode-ep4-steps.xplane.pb` is a recording from the chip
# (TPU v5 lite, this cell traced on --seed 7100001400 from the committed
# files, my chip run, PR 71, the second session's call C: the step is the
# plain `ssd_update`) cut by benchmark/tests/cut_scan_recording.py to
# device 0's step 191 of the decoding scan's 383 under its `while` (1762
# operations with their paths as the chip wrote them,
# `jit(<lambda>)/decode_steps/while/body/closed_call/ssd_scan/
# ~ssd_scan_0.tmp_0/ssd_step/...`) and, as its other scan, a step of the
# six the held experts' grouped products walk in the prefill (the prompt
# is one application and the block kernel one call, so the call's second
# longest `while` is `moe_experts`').  That decoding step wrote slot 256 +
# 191 = 447, the mean of the call's decoding steps, so the facts below
# say one decoding step there.  The recording holds no `decode/call` span
# (the cutter keeps the device's side): the test stands one over the
# recording's window.  Of the whole scan the run itself printed, a
# decoding step: the scan 7.3967 ms at 80.40% of its roofline, the step
# 16.9696 ms on the device, 78.57%.
RECORDED_FACTS = dict(ssd_state_gen_len=2, ssd_state_prompt_len=446,
                      ssd_state_batch=64)


def test_the_new_readers_on_a_recording_from_the_chip(tmp_path, capsys,
                                                       monkeypatch):
    from benchmark.reduce import program_spans

    shutil.copy(os.path.join(os.path.dirname(__file__), "data",
                             "granite-decode-ep4-steps.xplane.pb"),
                str(tmp_path))
    run = written_run(dict(FACTS, **RECORDED_FACTS))
    run.reduced, run.trace_dir = xplane.load(str(tmp_path)), str(tmp_path)
    lo, hi = run.reduced.window
    call = decoder_trace.Call(
        program_spans.Span(lo, hi, decoder_trace.CALL, ("/host:CPU", 0)),
        {"max_len": 2, "prompt_len": 446, "block": 256})
    monkeypatch.setattr(decoder_trace, "_traced",
                        lambda trace, trace_dir: ([call.span], [call]))
    read = {name: LOOKUP.module("layer_metrics", name).read(run)
            for name in NEW_READERS}
    printed = capsys.readouterr().out
    # the plain step: the decays' fusions lie under its scope too
    assert "ssd_scan, device ms a decoding step: ssd_step 7.3971; in all " \
        "7.3971" in printed
    assert "ssd_step: 7.3971 ms a decoding step" in printed
    assert "memory-bound" in printed
    assert "attention projections 0.0426, cached_attention 0.3057, " \
        "convolution with its tail 0.1016, gated norm 0.0194, mamba " \
        "projections 1.9017, moe_experts 5.4835, moe_router 0.0557, scan " \
        "7.3971, shared expert 0.1892; in all 15.4965" in printed
    config = run.config
    cost = ssd_step.step(config, 64)
    assert read["ssd_step_roofline"] == pytest.approx(
        100.0 * cost["bytes"] / 819e9 / 7.3971e-3, rel=1e-4)
    # the step that wrote slot 447: one decoding step after 446 tokens
    assert "decode step: 16.9653 ms on the device" in printed
    must = ssd_step.step_bytes(config, 64, 446.0, 2, 2)
    assert read["ssd_decode_hbm_roofline"] == pytest.approx(
        100.0 * must / 819e9 / 16.9653e-3, rel=1e-4)
    assert all(0 < read[n] < 100 for n in read)


@pytest.mark.parametrize("facts", [
    {"share_gen_len": 896, "share_step_applications": 1023},
    {"state_gen_len": 896, "state_prompt_len": 128, "state_batch": 128},
    {"dense_state_gen_len": 384, "dense_state_prompt_len": 128,
     "dense_state_batch": 128}, {"hybrid_gen_len": 896}, {}])
def test_the_new_readers_find_nothing_without_the_cells_facts(written,
                                                              facts):
    """On another generation cell's facts (the parent's checkout with
    these files laid over it runs so): nothing, and no raise."""
    written.facts = facts
    for name in NEW_READERS:
        assert LOOKUP.module("layer_metrics", name).read(written) is None


def test_no_reader_of_the_benchmark_raises_on_this_cells_facts():
    """Every reader under layer_metrics/ gives None or a number on this
    driver's facts with a chip's peaks set and no trace; the other
    generation cells' readers find nothing to read."""
    run = written_run()
    found = {name: LOOKUP.module("layer_metrics", name).read(run)
             for name in LOOKUP.names("layer_metrics")}
    assert all(v is None or isinstance(v, (int, float))
               for v in found.values()), found
    for name in NEW_READERS + (
            "gdn_ms_per_step", "gdn_step_roofline",
            "gdn_prefill_ms_per_call", "gated_attn_ms_per_step",
            "state_moe_ms_per_step", "state_decode_hbm_roofline",
            "dense_gdn_ms_per_step", "dense_state_decode_hbm_roofline",
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
            workload["pool"], workload["reference_rows"]) == \
        (64, 256, 384, 4, 2)
    assert workload["prompt_len"] + workload["gen_len"] \
        == config["serve_positions"] == 640
    assert workload["prompt_len"] == config["mamba_chunk_size"]
    assert workload["state_rows"] <= workload["checked_rows"] \
        <= workload["batch"]
    assert (workload["serve_dtype"], workload["weights"]["dtype"]) == \
        ("bfloat16", "bfloat16")
    draw = workload["weights"]
    assert (draw["seed"], draw["std"], draw["conv_std"], draw["dt_min"],
            draw["dt_max"]) == (7100000101, 0.02, 0.3, 0.001, 0.1)
    assert (workload["driver"], workload["chips"]) == \
        ("decode_ssd_state", 1)
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
    assert 18 <= len(cells) <= 24
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    configs = {c["name"]: c for c in bench["configs"]}
    entry, config = configs[CONFIG], LOOKUP.json("configs", CONFIG)
    assert entry["file"] == "benchmark/configs/%s.json" % CONFIG
    assert entry["source"] == config["source"]
    assert entry["reduced"] == config["reduced"] == [
        "num_hidden_layers", "num_local_experts", "vocab_size"]
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
        assert listed[name]["better"] == "higher"


def test_the_configuration_keeps_every_published_key():
    """Every key of the catalog's entry under its own name and value
    but the depth, the experts held and the vocabulary; `layer_types`
    whole; what is derived says so."""
    config = LOOKUP.json("configs", CONFIG)
    period = 5 * ["mamba"] + ["attention"] + 4 * ["mamba"]
    published = {
        "attention_bias": False, "attention_multiplier": 0.0078125,
        "embedding_multiplier": 12, "hidden_act": "silu",
        "hidden_size": 4096, "intermediate_size": 768,
        "layer_types": 4 * period, "logits_scaling": 16,
        "mamba_chunk_size": 256, "mamba_conv_bias": True,
        "mamba_d_conv": 4, "mamba_d_head": 64, "mamba_d_state": 128,
        "mamba_expand": 2, "mamba_n_groups": 1, "mamba_n_heads": 128,
        "mamba_proj_bias": False, "max_position_embeddings": 131072,
        "model_type": "granitemoehybrid",
        "normalization_function": "rmsnorm", "num_attention_heads": 32,
        "num_experts_per_tok": 10, "num_hidden_layers": 40,
        "num_key_value_heads": 8, "num_local_experts": 72,
        "position_embedding_type": "nope", "residual_multiplier": 0.22,
        "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
        "shared_intermediate_size": 1536, "tie_word_embeddings": True,
        "vocab_size": 100352}
    differs = sorted(k for k, v in published.items() if config[k] != v)
    assert differs == config["reduced"] == [
        "num_hidden_layers", "num_local_experts", "vocab_size"]
    assert config["published"] == {
        "num_hidden_layers": 40, "num_local_experts": 72,
        "vocab_size": 100352}
    assert (config["num_hidden_layers"], config["num_local_experts"],
            config["vocab_size"]) == (10, 18, 25088)
    assert (config["scored_experts"], config["first_expert"]) == (72, 0)
    assert set(config["derived"]) == {"head_dim", "scored_experts"}
    assert config["head_dim"] * config["num_attention_heads"] \
        == config["hidden_size"]
    for key in ("stands_for", "assumed", "departures", "arithmetic",
                "reduced_why"):
        assert config[key]
    assert {"state_dtype", "ssm_init", "head_dim", "intermediate_size",
            "routing"} <= set(config["assumed"])
