"""The hybrid cell `ling3-decode-ep16`: its files found by name, its
driver end to end as a CPU rehearsal at a toy size (fixture
`ling3-tiny-decode`, found through `--search-path`), the controls that
`correct` has to refuse, the cell's copy of the reference against the
program's, the model's draw, the bytes and operations of a decode step
against the issue's arithmetic, the new readers on a written account of
a traced call and on the other cells' facts, and BENCHMARK.json's
entries for the cell.  (No recording from the chip is under data/ for
this cell: its trace was read on the chip, PERF.md section 5; the
readers' reduction is held here to a call written by hand, as
test_qwen3next_cell.py's, whose `Written`, `Op` and `MARK` this uses.)
"""

import json
import os

import numpy as np
import pytest

from benchmark.harness import CHECKOUT, Lookup
from benchmark.reduce import decoder_trace, hybrid_ops
from benchmark.tests import hybrid_control, state_control
from benchmark.tests.test_qwen3next_cell import MARK, PEAKS, Op, Written
from benchmark.tests.test_run import FIXTURE, last_line, run_cell

CELL = "ling3-decode-ep16"
CONFIG = "ling-3.0-flash"
TOY, TOY_CONFIG = "ling3-tiny-decode", "ling3-tiny"
NEW_READERS = ("kda_ms_per_step", "kda_prefill_ms_per_call",
               "kda_step_roofline", "hybrid_mla_ms_per_step",
               "hybrid_moe_ms_per_step", "hybrid_decode_hbm_roofline")
SHARED_READERS = ("decode_trace_lower_s", "decoder_prep_ms_per_call",
                  "decoder_idle_ms_per_call", "prefill_device_ms_per_call",
                  "decode_device_step_ms", "decode_unscoped_ms_per_step")
LIMITED = ("gap_mean", "not_first_share", "held_part_off", "state_off",
           "state_off_first")
LOOKUP = Lookup([FIXTURE])
kimi_delta = LOOKUP.module("flops", "kimi_delta")


# -- the cell's files, by name --------------------------------------------------

def test_the_cells_files_are_found_by_name():
    workload = LOOKUP.json("workloads", CELL)
    config = LOOKUP.json("configs", workload["config"])
    assert config["name"] == CONFIG
    for kind, name in (("drivers", workload["driver"]),
                       ("models", workload["builder"]),
                       ("reference", workload["reference"]),
                       ("flops", "kimi_delta"), ("reduce", "hybrid_ops"),
                       ("tests", "hybrid_control")):
        assert os.path.dirname(LOOKUP.path(kind, name + ".py")).endswith(kind)
    assert set(NEW_READERS) <= set(LOOKUP.names("layer_metrics"))
    assert (config["builder"], config["reference"], workload["driver"]) == \
        (workload["builder"], workload["reference"], "decode_hybrid")


def test_the_cells_reference_is_the_programs_to_the_letter():
    with open(LOOKUP.path("reference", "ling3_flash.py")) as copy, \
            open(os.path.join(CHECKOUT, "paddle_tpu", "models", "reference",
                              "ling3_flash.py")) as own:
        assert copy.read() == own.read()


# -- the driver, end to end -----------------------------------------------------

def test_untraced_rehearsal_has_exactly_the_two_metrics():
    result = last_line(run_cell(TOY, 0))
    assert result["correct"] is True and result["failed"] == 0
    assert set(result["metrics"]) == {"decode_tok_per_s", "setup_s"}
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
    # what only a chip can say: this cell's and the other generation cells'
    assert not (set(NEW_READERS) | {
        "share_decode_step_ms", "mla_ms_per_step", "decode_step_ms",
        "state_moe_ms_per_step", "state_decode_hbm_roofline",
        "gdn_ms_per_step", "decode_device_step_ms"}) & set(metrics)
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
    controls = hybrid_control.controls_of(config, workload)
    assert len(controls) == 10 and {"gate=head", "state=bfloat16"} \
        <= set(controls)
    found = dict(state_control.read(LOOKUP, workload, 7, jax.devices()[:1],
                                    None, controls))
    limits = workload["correct"]
    assert state_control.refused(found[None], limits) == []
    for spelling in controls:
        assert state_control.refused(found[spelling], limits), spelling
    # a state the step rounds is seen in the state alone at this size, a
    # dropped expert in the held part, the gate a head in the first
    # layer's own state
    assert state_control.refused(found["state=bfloat16"], limits) \
        == ["state_off", "state_off_first"]
    assert "held_part_off" in state_control.refused(found["drop=true"],
                                                    limits)
    assert "state_off_first" in state_control.refused(found["gate=head"],
                                                      limits)


def test_a_dense_layer_carries_its_state_out_and_no_expert_probe():
    model = LOOKUP.module("models", "ling3_decode")
    config = LOOKUP.json("configs", TOY_CONFIG)
    built = model.build(config, 8, 2)
    probes = dict(built["probes"])
    assert model.layer_types(config) == (
        "linear_attention", "linear_attention", "latent_attention",
        "linear_attention")
    assert sorted(probes[0]) == ["state"]           # dense, KDA
    assert sorted(probes[1]) == ["idx", "in", "out", "state"]
    assert sorted(probes[2]) == ["idx", "in", "out"]        # latent
    kinds = {feed.rsplit("_", 1)[0]: kind for feed, (_, kind)
             in built["state_shapes"].items()}
    assert kinds == {"conv_tail": "tail", "delta_state": "state",
                     "latent_cache": "cache"}


# -- the model's draw -------------------------------------------------------------

def test_the_weights_draw():
    """A block made alone is the block served; the gate's parameters are
    float32 and lie where the configuration's `assumed` says; the
    latent's down-projection is drawn wider than the others."""
    import jax

    model = LOOKUP.module("models", "ling3_decode")
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
    dense, kda, latent = (whole["blocks"][i] for i in (0, 1, 2))
    assert {"ffn_in", "ffn_out"} <= set(dense) and "router" not in dense
    assert set(latent) - set(kda) == {"wq_nope", "wq_rope", "w_dkv",
                                      "kv_norm", "w_uk", "w_uv", "w_z"}
    assert kda["a_log"].dtype == kda["dt_bias"].dtype == np.float32
    assert kda["a_log"].shape == (4,) and kda["dt_bias"].shape == (32,)
    rate = np.exp(np.asarray(kda["a_log"]))
    assert (rate >= spec["rate_min"] * 0.999).all() \
        and (rate <= spec["rate_max"] * 1.001).all()
    bias = np.asarray(kda["dt_bias"])
    assert (bias >= spec["bias_min"]).all() \
        and (bias <= spec["bias_max"]).all()
    ratio = float(np.asarray(latent["w_dkv"], np.float32).std()
                  / np.asarray(latent["w_uk"], np.float32).std())
    assert abs(ratio - spec["kv_gain"]) < 0.3


# -- bytes and operations -----------------------------------------------------------

def test_parameters_and_bytes_are_the_issues():
    config = LOOKUP.json("configs", CONFIG)
    assert kimi_delta.count(config, kimi_delta.KDA) == 7
    assert kimi_delta.count(config, kimi_delta.LATENT) == 1
    assert kimi_delta.layer_types(config)[5] == kimi_delta.LATENT
    # the issue's arithmetic, to the parameter
    assert kimi_delta.kda_parameters(config) == \
        4 * 10_485_760 + 163_840 + 49_152 + 10_485_760 + 32 + 4096 + 128
    assert kimi_delta.latent_parameters(config) == \
        15_728_640 + 1_474_560 + 512 + 4_194_304 + 81_920 + 10_485_760
    assert kimi_delta.expert_parameters(config) == 5_898_240
    assert round(kimi_delta.chip_parameters(config) / 1e6) == 1771
    assert kimi_delta.state_row_bytes(config) == 2_097_152
    assert kimi_delta.tail_row_bytes(config, 2) == 73_728
    # a step: 7 layers x 128 rows x 2.1 MB read and written
    rule = kimi_delta.rule_step(config, 128)
    assert 3.76e9 < rule["bytes"] < 3.85e9
    assert rule["flops"] == 7 * 128 * 32 * 128 * 128 * 7
    assert round(kimi_delta.state_bytes(config, 128, 2) / 1e9, 2) == 3.89
    assert round(kimi_delta.fixed_weight_bytes(config, 128, 2) / 1e9, 2) \
        == 1.18
    assert round(kimi_delta.held_expert_bytes(config, 128, 2) / 1e9, 2) \
        == 1.96
    at = 128 + (896 - 2) / 2.0
    assert round(kimi_delta.latent_step(config, 128, at, 2)["bytes"] / 1e9,
                 2) == 0.08
    whole = kimi_delta.step_bytes(config, 128, at, 2, 2)
    assert whole == kimi_delta.fixed_weight_bytes(config, 128, 2) \
        + kimi_delta.state_bytes(config, 128, 2) \
        + kimi_delta.latent_step(config, 128, at, 2)["bytes"]
    # with an even router's experts: the issue's 7.1 GB, 55% the rule's
    total = whole + kimi_delta.held_expert_bytes(config, 128, 2)
    assert round(total / 1e9, 1) == 7.1
    assert 0.53 < kimi_delta.state_bytes(config, 128, 2) / total < 0.56


def test_a_small_share_counted_by_hand():
    """benchmark/tests/test_flops.py's manner: a share small enough to
    count on paper.  3 layers, group size 3 (K K L), 1 dense; 2 heads of
    4; hidden 8; latent 4 + 2 rotated, 4 + 2 query values and 4 values a
    head; experts of 3, 4 scored, 2 held; dense width 5; vocabulary
    10."""
    cfg = {"num_hidden_layers": 3, "layer_group_size": 3,
           "first_k_dense_replace": 1, "num_attention_heads": 2,
           "head_dim": 4, "hidden_size": 8, "short_conv_kernel_size": 4,
           "kv_lora_rank": 4, "qk_nope_head_dim": 4, "qk_rope_head_dim": 2,
           "v_head_dim": 4, "moe_intermediate_size": 3,
           "intermediate_size": 5, "scored_experts": 4, "num_experts": 2,
           "num_experts_per_tok": 1, "vocab_size": 10}
    assert kimi_delta.layer_types(cfg) == [kimi_delta.KDA, kimi_delta.KDA,
                                           kimi_delta.LATENT]
    # W_qkvf 8 x 32, W_bz 8 x 4, the filter 24 x 4, A_log 2, dt_bias 8,
    # the norm 4, W_o 8 x 8
    assert kimi_delta.kda_parameters(cfg) == 256 + 32 + 96 + 2 + 8 + 4 + 64
    # W_q 8 x 2 x 6, W_dkv 8 x 6 and its norm 4, W_uk and W_uv 4 x 2 x 8,
    # W_z 8 x 2, W_o 8 x 8
    assert kimi_delta.latent_parameters(cfg) == 96 + 48 + 4 + 64 + 16 + 64
    assert kimi_delta.expert_parameters(cfg) == 72
    assert kimi_delta.feed_forward_parameters(cfg, 0) == 16 + 120
    assert kimi_delta.feed_forward_parameters(cfg, 1) == 16 + 36 + 72
    assert kimi_delta.chip_parameters(cfg) == \
        2 * 80 + 8 + 2 * 462 + 292 + 136 + 2 * 124 + 2 * 2 * 72
    # a row: a state of 2 heads x 4 x 4 float32, a tail of 3 x 24
    assert kimi_delta.state_row_bytes(cfg) == 128
    assert kimi_delta.tail_row_bytes(cfg, 2) == 144
    # 3 rows, 2 KDA layers: the state each way, and q, k, v, the decay a
    # channel and the output [2, 4] and beta [2], float32
    rule = kimi_delta.rule_step(cfg, 3)
    assert rule["bytes"] == 2 * 3 * (2 * 128 + (5 * 8 + 2) * 4)
    assert rule["flops"] == 2 * 3 * 7 * 32
    assert kimi_delta.state_bytes(cfg, 3, 2) == 2 * 3 * 2 * (128 + 144)
    # the step that writes slot 4 reads 5 live latents of 6 values a row
    assert kimi_delta.latent_step(cfg, 3, 4, 2) == {
        "flops": 2 * 3 * 2 * 5 * (2 * 4 + 2), "bytes": 3 * 5 * 6 * 2}
    fixed = 2 * (8 + 80 + 3 * 8 + 2 * 462 + 292 + 136 + 2 * 124)
    assert kimi_delta.fixed_weight_bytes(cfg, 3, 2) == fixed
    assert kimi_delta.step_bytes(cfg, 3, 4, 2, 2) == fixed + 3264 + 180
    # 3 assignments over 4 scored: a held expert is missed with (3/4)^3
    assert kimi_delta.held_expert_bytes(cfg, 3, 2) == pytest.approx(
        2 * 2 * (1 - 0.75 ** 3) * 72 * 2)


# -- the readers ---------------------------------------------------------------------

def written_run(facts=None, cell=CELL, config=CONFIG, peaks=PEAKS):
    workload = dict(LOOKUP.json("workloads", cell), name=cell)
    found = dict(hybrid_gen_len=5, hybrid_prompt_len=128, hybrid_batch=128,
                 setup_compile_s=60.0, setup_cache_misses=30,
                 decode_trace_lower_s=5.5, compiles_in_window=0)
    return Written(
        workload=workload, config=LOOKUP.json("configs", config),
        facts=found if facts is None else facts, peaks=peaks, lookup=LOOKUP,
        reduced=None, trace_dir=None, trace=True, seed=1, devices=[None])


class WrittenCall:
    """A traced call of 4 steps, a few ms an op, written by hand: what
    decoder_trace.Parts gives the readers."""

    def __init__(self, names):
        def path(kind, instance, *inner):
            return "/".join(("jit(f)", "decode_steps", "while", "body", kind,
                             MARK + instance) + inner + ("fusion",))

        self.call = decoder_trace.Call(None, {"max_len": 5, "prompt_len": 128,
                                              "block": 64})
        self.steps, self.prefill = (10.0, 20.0), (0.0, 5.0)
        rule, conv, mla = ("gated_delta_rule", "causal_conv1d",
                           "mla_cached_attention")
        self.ops = [
            Op(10.0, 10.004, "kda_step_r128_h16", "custom-call",
               path(rule, "gated_delta_rule_0.tmp_0", "kda_state"), ""),
            Op(11.0, 11.001, "fusion.1", "loop fusion",
               path(rule, "gated_delta_rule_0.tmp_0", "gdn_gates"), ""),
            Op(12.0, 12.002, "fusion.2", "loop fusion",
               path(conv, "causal_conv1d_0.tmp_0"), ""),
            Op(13.0, 13.001, "fusion.3", "loop fusion",
               path("sigmoid", "kda_gates_3.tmp_0"), ""),
            Op(14.0, 14.001, "fusion.4", "loop fusion",
               path("rms_norm", "kda_out_norm_0.tmp_0"), ""),
            Op(15.0, 15.003, "fusion.5", "output fusion",
               path("mul", names["w_qkvf"]), ""),
            Op(15.5, 15.502, "fusion.6", "output fusion",
               path("mul", names["latent_wo"]), ""),
            Op(16.0, 16.008, "mla_decode_k512", "custom-call",
               path(mla, "mla_cached_attention_0.tmp_0", "mla_scores"), ""),
            Op(16.5, 16.501, "fusion.7", "loop fusion",
               path(mla, "mla_cached_attention_0.tmp_0", "mla_absorb"), ""),
            Op(17.0, 17.001, "fusion.8", "loop fusion",
               path("elementwise_mul", "latent_gate_1.tmp_0"), ""),
            Op(18.0, 18.006, "moe_gmm_fwd", "custom-call",
               path("moe_experts", "moe_0.tmp_3", "moe_experts"), ""),
            Op(18.5, 18.501, "fusion.9", "loop fusion",
               path("moe_router", "moe_0.tmp_0"), ""),
            Op(19.0, 19.002, "fusion.10", "output fusion",
               path("mul", names["shared_in"]), ""),
            Op(19.5, 19.503, "fusion.11", "output fusion",
               path("mul", names["ffn_in"]), ""),
            # the block form, inside the prefill
            Op(1.0, 1.200, "fusion.12", "loop fusion",
               "/".join(("jit(f)", "decode_prefill", rule,
                         MARK + "gated_delta_rule_0.tmp_0", "kda_chunks",
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
    the cell's own step Program."""
    from paddle_tpu.fluid import executor

    run = written_run()
    run.workload = dict(LOOKUP.json("workloads", TOY), name=TOY)
    run.config = LOOKUP.json("configs", TOY_CONFIG)
    run.facts["hybrid_batch"] = run.workload["batch"]
    ops = hybrid_ops._step_ops.__wrapped__(run)
    by_weight = {od.input("Y")[0].split(".", 1)[1]
                 + ("@" + od.input("Y")[0].split(".")[0]): executor
                 .op_instance(od)[1:] for od in ops if od.type == "mul"}
    names = {"w_qkvf": by_weight["w_qkvf@block_0"],
             "latent_wo": by_weight["wo@block_2"],
             "shared_in": by_weight["shared_in@block_1"],
             "ffn_in": by_weight["ffn_in@block_0"]}
    monkeypatch.setattr(hybrid_ops, "_step_ops", lambda r: ops)
    monkeypatch.setattr(hybrid_ops.op_instances, "sigil", lambda: MARK)
    monkeypatch.setattr(decoder_trace, "parts",
                        lambda r: [WrittenCall(names)])
    return run


def test_the_new_readers_on_a_written_call(written, capsys):
    read = lambda name: LOOKUP.module("layer_metrics", name).read(written)
    # 4 steps: the kernel 4 ms, the norms 1, the convolution 2, the
    # gates 1, the output norm 1, the KDA layer's projection 3 (the
    # latent layer's `wo`, 2, is the latent mixer's)
    assert read("kda_ms_per_step") == pytest.approx(12.0 / 4)
    assert read("hybrid_mla_ms_per_step") == pytest.approx(12.0 / 4)
    assert read("hybrid_moe_ms_per_step") == pytest.approx(12.0 / 4)
    assert read("kda_prefill_ms_per_call") == pytest.approx(200.0)
    cost = kimi_delta.rule_step(written.config, written.workload["batch"])
    assert read("kda_step_roofline") == pytest.approx(
        100 * cost["bytes"] / PEAKS["hbm_bytes_per_s"] / 1e-3)
    must = kimi_delta.step_bytes(written.config, written.workload["batch"],
                                 128 + 1.5, 4, 4)
    busy = 0.036 / 4
    assert read("hybrid_decode_hbm_roofline") == pytest.approx(
        100 * must / PEAKS["hbm_bytes_per_s"] / busy)
    said = capsys.readouterr().out
    assert "kda_state 1.0000" in said and "projections 0.7500" in said
    assert "memory-bound" in said and "mla_decode_k* 2.0000" in said
    assert "dense layers 0.7500" in said


def test_the_new_readers_find_nothing_in_a_program_without_the_scopes(
        written, monkeypatch):
    """A program that lowers the rule under `gdn_*` scopes and a
    `gdn_step_*` kernel (no per-channel gate: the parent's) gives the KDA
    readers nothing to read, and they do not raise."""
    call = decoder_trace.parts(written)[0]
    call.ops = [op._replace(name=op.name.replace("kda_", "gdn_"),
                            path=op.path.replace("kda_", "gdn_"))
                for op in call.ops]
    monkeypatch.setattr(decoder_trace, "parts", lambda r: [call])
    for name in ("kda_ms_per_step", "kda_prefill_ms_per_call",
                 "kda_step_roofline"):
        assert LOOKUP.module("layer_metrics", name).read(written) is None


def test_the_new_readers_find_nothing_without_the_cells_facts(written):
    """On another generation cell's facts (another cell's run with these
    files in place runs so): nothing, and no raise."""
    written.facts = {"state_gen_len": 896, "state_prompt_len": 128,
                     "state_batch": 128}
    for name in NEW_READERS:
        assert LOOKUP.module("layer_metrics", name).read(written) is None


def test_no_reader_of_the_benchmark_raises_on_this_cells_facts():
    """Every reader under layer_metrics/ gives None or a number on the
    hybrid driver's facts with a chip's peaks set and no trace; the
    other generation cells' readers find nothing to read."""
    run = written_run()
    found = {name: LOOKUP.module("layer_metrics", name).read(run)
             for name in LOOKUP.names("layer_metrics")}
    assert all(v is None or isinstance(v, (int, float))
               for v in found.values()), found
    for name in NEW_READERS + (
            "share_decode_step_ms", "share_prefill_ms_per_call",
            "share_decode_hbm_roofline", "moe_share_roofline",
            "decode_step_ms", "prefill_ms_per_call",
            "session_decode_step_ms", "long_decode_step_ms",
            "long_decode_hbm_roofline", "state_decode_hbm_roofline",
            "state_moe_ms_per_step", "gdn_ms_per_step", "gdn_step_roofline",
            "mfu"):
        # (setup_trace_lower_s reads the process's counters, not the
        # run: what it finds depends on which tests ran before)
        assert found[name] is None, name
    assert found["decode_trace_lower_s"] == 5.5
    assert found["setup_compile_s"] == 60.0
    assert found["setup_cache_misses"] == 30
    assert found["compiles_in_window"] == 0


# -- BENCHMARK.json ---------------------------------------------------------------

def test_benchmark_json_has_the_cell_and_its_metrics():
    with open(os.path.join(CHECKOUT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    cell, workload = cells[CELL], LOOKUP.json("workloads", CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (CONFIG, CELL, 1)
    assert cell["why"] == workload["why"] and len(cell["why"]) <= 200
    assert 13 <= len(cells) <= 24
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    configs = {c["name"]: c for c in bench["configs"]}
    entry, config = configs[CONFIG], LOOKUP.json("configs", CONFIG)
    assert entry["file"] == "benchmark/configs/%s.json" % CONFIG
    assert entry["source"] == config["source"]
    assert entry["reduced"] == config["reduced"]
    assert len(entry["why"]) <= 200
    end_to_end = {m["name"]: m for m in bench["end_to_end"]}
    assert CELL in end_to_end["decode_tok_per_s"]["workloads"]
    listed = {m["name"]: m for m in bench["per_layer"]}
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


def test_the_configuration_keeps_every_published_width():
    """Every key of the catalog's entry under its own name; only the
    four reduced keys differ, and none of them is a width."""
    config = LOOKUP.json("configs", CONFIG)
    published = {
        "first_k_dense_replace": 2, "head_dim": 128, "hidden_size": 2560,
        "gated_attention_proj_granularity_type": "head_wise",
        "group_norm_size": 1, "hidden_act": "silu",
        "intermediate_size": 6144, "kda_lower_bound": -5,
        "kda_safe_gate": True, "kv_lora_rank": 512, "layer_group_size": 6,
        "linear_silu": True, "max_position_embeddings": 262144,
        "moe_intermediate_size": 768, "moe_router_enable_expert_bias": True,
        "moe_shared_expert_intermediate_size": 768, "n_group": 8,
        "no_kda_lora": True, "norm_topk_prob": True,
        "num_attention_heads": 32, "num_experts": 512,
        "num_experts_per_tok": 8, "num_hidden_layers": 42,
        "num_key_value_heads": 32, "num_kv_heads_for_linear_attn": 0,
        "num_nextn_predict_layers": 1, "num_shared_experts": 1,
        "partial_rotary_factor": 0.5, "q_lora_rank": None,
        "qk_head_dim": 192, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
        "rms_norm_eps": 1e-06, "rope_interleave": True, "rope_scaling": None,
        "rope_theta": 6000000, "rotary_dim": 64,
        "routed_scaling_factor": 2.5, "score_function": "sigmoid",
        "scoring_func": "sigmoid", "short_conv_kernel_size": 4,
        "tie_word_embeddings": False, "topk_group": 4,
        "topk_method": "noaux_tc", "use_qk_norm": True, "v_head_dim": 128,
        "vocab_size": 157184, "model_type": "bailing_hybrid"}
    differs = sorted(k for k, v in published.items() if config[k] != v)
    assert differs == sorted(config["reduced"]) == [
        "num_experts", "num_hidden_layers", "num_nextn_predict_layers",
        "vocab_size"]
    assert config["published"] == {k: published[k] for k in differs}
    assert (config["num_hidden_layers"], config["num_experts"],
            config["vocab_size"], config["num_nextn_predict_layers"]) \
        == (8, 32, 19648, 0)
    # the dense layers, then a whole period and the model-configs floor
    # of four layers after the dense ones; the held range inside the
    # scored one
    assert config["num_hidden_layers"] - config["first_k_dense_replace"] \
        >= config["layer_group_size"]
    assert len(config["expert_swiglu_limit_list"]) == 42 \
        and not any(config["expert_swiglu_limit_list"][:8]) \
        and not any(config["share_expert_swiglu_limit_list"][:8])
    assert config["scored_experts"] == 512
    assert 0 <= config["first_expert"] <= 512 - 32
    assert config["num_experts"] >= config["num_experts_per_tok"]
    assert config["vocab_size"] * 8 == published["vocab_size"]
    assert "sixteen chips" in config["stands_for"]
    for key in ("stands_for", "assumed", "departures", "arithmetic",
                "reduced_why"):
        assert config[key]
    assert {"kda_heads", "kda_positions", "kda_gate", "kda_output",
            "qk_norm", "a_log_dt_bias", "state_dtype", "norm_order",
            "swiglu_limits", "mtp"} <= set(config["assumed"])
    workload = LOOKUP.json("workloads", CELL)
    assert workload["prompt_len"] + workload["gen_len"] \
        == config["serve_positions"] == 1024
    assert (workload["batch"], workload["checked_rows"],
            workload["reference_rows"], workload["pool"]) == (128, 32, 2, 4)
    assert set(workload["correct"]) == set(LIMITED) | {"why"}
