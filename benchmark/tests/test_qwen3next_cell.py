"""The state cell `qwen3next-decode-ep16`: its files found by name, its
driver end to end as a CPU rehearsal at a toy size (fixture
`qwen3next-tiny-decode`, found through `--search-path`), the controls
that `correct` has to refuse, the cell's copy of the reference against
the program's, the model's draw, the bytes and operations of a decode
step against the issue's arithmetic, the new readers on a written
account of a traced call and on the other cells' facts, and
BENCHMARK.json's entries for the cell.  (No recording from the chip is
under data/ for this cell: its trace was read on the chip, PERF.md
section 5; the readers' reduction is held here to a call written by
hand.)
"""

import collections
import json
import os
import types

import numpy as np
import pytest

from benchmark.harness import CHECKOUT, Lookup
from benchmark.reduce import decoder_trace, state_ops
from benchmark.tests import state_control
from benchmark.tests.test_run import FIXTURE, last_line, run_cell

CELL = "qwen3next-decode-ep16"
CONFIG = "qwen3-next-80b-a3b"
TOY, TOY_CONFIG = "qwen3next-tiny-decode", "qwen3next-tiny"
NEW_READERS = ("gdn_ms_per_step", "gdn_step_roofline",
               "gdn_prefill_ms_per_call", "gated_attn_ms_per_step",
               "state_moe_ms_per_step", "state_decode_hbm_roofline")
SHARED_READERS = ("decode_trace_lower_s", "decoder_prep_ms_per_call",
                  "decoder_idle_ms_per_call", "prefill_device_ms_per_call",
                  "decode_device_step_ms", "decode_unscoped_ms_per_step")
LIMITED = ("gap_mean", "not_first_share", "held_part_off", "state_off",
           "state_off_first")
PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
LOOKUP = Lookup([FIXTURE])
gated_delta = LOOKUP.module("flops", "gated_delta")


# -- the cell's files, by name --------------------------------------------------

def test_the_cells_files_are_found_by_name():
    workload = LOOKUP.json("workloads", CELL)
    config = LOOKUP.json("configs", workload["config"])
    assert config["name"] == CONFIG
    for kind, name in (("drivers", workload["driver"]),
                       ("models", workload["builder"]),
                       ("reference", workload["reference"]),
                       ("flops", "gated_delta"), ("reduce", "state_ops"),
                       ("tests", "state_control")):
        assert os.path.dirname(LOOKUP.path(kind, name + ".py")).endswith(kind)
    assert set(NEW_READERS) <= set(LOOKUP.names("layer_metrics"))
    assert (config["builder"], config["reference"]) == \
        (workload["builder"], workload["reference"])


def test_the_cells_reference_is_the_programs_to_the_letter():
    with open(LOOKUP.path("reference", "qwen3_next.py")) as copy, \
            open(os.path.join(CHECKOUT, "paddle_tpu", "models", "reference",
                              "qwen3_next.py")) as own:
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
        "long_decode_step_ms", "decode_device_step_ms"}) & set(metrics)
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
    controls = state_control.controls_of(config, workload)
    assert len(controls) == 10
    found = dict(state_control.read(LOOKUP, workload, 7, jax.devices()[:1],
                                    None, controls))
    limits = workload["correct"]
    assert state_control.refused(found[None], limits) == []
    for spelling in controls:
        assert state_control.refused(found[spelling], limits), spelling
    # a state the step rounds is seen in the state alone at this size,
    # a dropped expert in the held part
    assert state_control.refused(found["state=bfloat16"], limits) \
        == ["state_off", "state_off_first"]
    assert "held_part_off" in state_control.refused(found["drop=true"],
                                                    limits)


def test_the_checked_rows_begin_with_the_rows_whose_state_is_carried():
    driver = LOOKUP.module("drivers", "decode_state")
    workload = LOOKUP.json("workloads", CELL)
    seen = set()
    for seed in (1, 2, 3_000_000_017):
        rows = driver.checked_rows(types.SimpleNamespace(
            workload=workload, seed=seed))
        assert rows.shape == (workload["checked_rows"],)
        assert list(rows[:workload["state_rows"]]) \
            == list(range(workload["state_rows"]))
        assert len(set(rows)) == len(rows) and rows.max() < workload["batch"]
        assert list(rows) == sorted(rows)
        seen.add(tuple(rows))
    assert len(seen) == 3


# -- the model's draw -------------------------------------------------------------

def test_the_weights_draw():
    """A block made alone is the block served; the gates' parameters
    are float32 and lie where the configuration's `assumed` says."""
    import jax

    model = LOOKUP.module("models", "qwen3next_decode")
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
    linear, full = whole["blocks"][0], whole["blocks"][2]
    assert set(full) - set(linear) == {"wq", "wk", "wv", "q_norm", "k_norm"}
    assert linear["a_log"].dtype == linear["dt_bias"].dtype == np.float32
    rate = np.exp(np.asarray(linear["a_log"]))
    assert (rate > 0).all() and (rate <= 16).all()
    step = np.log1p(np.exp(np.asarray(linear["dt_bias"], np.float64)))
    assert (step >= 0.999e-3).all() and (step <= 0.1001).all()
    assert abs(float(np.asarray(linear["conv"], np.float32).std())
               - spec["conv_std"]) < 0.05
    assert model.layer_types(config) == (
        "linear_attention", "linear_attention", "full_attention",
        "linear_attention")


# -- bytes and operations -----------------------------------------------------------

def test_parameters_and_bytes_are_the_issues():
    config = LOOKUP.json("configs", CONFIG)
    assert gated_delta.count(config, gated_delta.LINEAR) == 9
    assert gated_delta.count(config, gated_delta.FULL) == 3
    # the issue's arithmetic, to the parameter
    assert gated_delta.linear_parameters(config) == \
        25_165_824 + 131_072 + 32_768 + 8_388_608 + 2 * 32 + 128
    assert gated_delta.full_parameters(config) == \
        16_777_216 + 2 * 1_048_576 + 8_388_608 + 2 * 256
    assert gated_delta.expert_parameters(config) == 3_145_728
    assert round(gated_delta.chip_parameters(config) / 1e6) == 1721
    assert gated_delta.state_row_bytes(config) == 2_097_152
    assert gated_delta.tail_row_bytes(config, 2) == 49_152
    # a step: 9 layers x 128 rows x 2.1 MB read and written
    rule = gated_delta.rule_step(config, 128)
    assert 4.83e9 < rule["bytes"] < 4.90e9
    assert rule["flops"] == 9 * 128 * 32 * 128 * 128 * 7
    assert round(gated_delta.state_bytes(config, 128, 2) / 1e9, 2) == 4.95
    assert round(gated_delta.fixed_weight_bytes(config, 128, 2) / 1e9, 2) \
        == 0.95
    assert round(gated_delta.held_expert_bytes(config, 128, 2) / 1e9, 1) \
        == 2.2
    at = 128 + (896 - 2) / 2.0
    assert round(gated_delta.kv_step(config, 128, at, 2)["bytes"] / 1e9, 2) \
        == 0.45
    whole = gated_delta.step_bytes(config, 128, at, 2, 2)
    assert whole == gated_delta.fixed_weight_bytes(config, 128, 2) \
        + gated_delta.state_bytes(config, 128, 2) \
        + gated_delta.kv_step(config, 128, at, 2)["bytes"]
    assert 0.75 < gated_delta.state_bytes(config, 128, 2) / whole < 0.8


# -- the readers ---------------------------------------------------------------------

MARK = "~"
Op = collections.namedtuple("Op", "start end name category path text")


class Written:
    """What a reader asks of a run (hashable: some readers keep what
    they made of one)."""

    def __init__(self, **fields):
        self.__dict__.update(fields)


def written_run(facts=None, cell=CELL, config=CONFIG, peaks=PEAKS):
    workload = dict(LOOKUP.json("workloads", cell), name=cell)
    found = dict(state_gen_len=5, state_prompt_len=128, state_batch=128,
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
                                              "block": 128})
        self.steps, self.prefill = (10.0, 20.0), (0.0, 5.0)
        rule, conv = "gated_delta_rule", "causal_conv1d"
        self.ops = [
            Op(10.0, 10.004, "gdn_step_r128_h16", "custom-call",
               path(rule, "gated_delta_rule_0.tmp_0", "gdn_state"), ""),
            Op(11.0, 11.001, "fusion.1", "loop fusion",
               path(rule, "gated_delta_rule_0.tmp_0", "gdn_gates"), ""),
            Op(12.0, 12.002, "fusion.2", "loop fusion",
               path(conv, "causal_conv1d_0.tmp_0"), ""),
            Op(13.0, 13.001, "fusion.3", "loop fusion",
               path("sigmoid", "gdn_gates_3.tmp_0"), ""),
            Op(14.0, 14.001, "fusion.4", "loop fusion",
               path("rms_norm", "gdn_out_norm_0.tmp_0"), ""),
            Op(15.0, 15.003, "fusion.5", "output fusion",
               path("mul", names["w_qkvz"]), ""),
            Op(15.5, 15.502, "fusion.6", "output fusion",
               path("mul", names["full_wo"]), ""),
            Op(16.0, 16.008, "gqa_decode_k1024_d256", "custom-call",
               path("cached_attention", "cached_attention_0.tmp_0",
                    "attn_full"), ""),
            Op(16.5, 16.501, "fusion.7", "loop fusion",
               path("cached_attention", "cached_attention_0.tmp_0",
                    "kv_write"), ""),
            Op(17.0, 17.001, "fusion.8", "loop fusion",
               path("elementwise_mul", "attn_gate_1.tmp_0"), ""),
            Op(18.0, 18.006, "moe_gmm_fwd", "custom-call",
               path("moe_experts", "moe_0.tmp_3", "moe_experts"), ""),
            Op(18.5, 18.501, "fusion.9", "loop fusion",
               path("moe_router", "moe_0.tmp_0"), ""),
            Op(19.0, 19.002, "fusion.10", "output fusion",
               path("mul", names["shared_in"]), ""),
            Op(19.5, 19.501, "fusion.11", "loop fusion",
               path("sigmoid", "shared_gate_0.tmp_0"), ""),
            # the block form, inside the prefill
            Op(1.0, 1.200, "fusion.12", "loop fusion",
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
    the cell's own step Program."""
    from paddle_tpu.fluid import executor

    run = written_run()
    run.workload = dict(LOOKUP.json("workloads", TOY), name=TOY)
    run.config = LOOKUP.json("configs", TOY_CONFIG)
    run.facts["state_batch"] = run.workload["batch"]
    ops = state_ops._step_ops.__wrapped__(run)
    by_weight = {od.input("Y")[0].split(".", 1)[1]
                 + ("@" + od.input("Y")[0].split(".")[0]): executor
                 .op_instance(od)[1:] for od in ops if od.type == "mul"}
    names = {"w_qkvz": by_weight["w_qkvz@block_0"],
             "full_wo": by_weight["wo@block_2"],
             "shared_in": by_weight["shared_in@block_0"]}
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
    assert read("gdn_ms_per_step") == pytest.approx(12.0 / 4)
    assert read("gated_attn_ms_per_step") == pytest.approx(10.0 / 4)
    assert read("state_moe_ms_per_step") == pytest.approx(10.0 / 4)
    assert read("gdn_prefill_ms_per_call") == pytest.approx(200.0)
    cost = gated_delta.rule_step(written.config, written.workload["batch"])
    assert read("gdn_step_roofline") == pytest.approx(
        100 * cost["bytes"] / PEAKS["hbm_bytes_per_s"] / 1e-3)
    must = gated_delta.step_bytes(written.config, written.workload["batch"],
                                  128 + 1.5, 4, 4)
    busy = 0.034 / 4
    assert read("state_decode_hbm_roofline") == pytest.approx(
        100 * must / PEAKS["hbm_bytes_per_s"] / busy)
    said = capsys.readouterr().out
    assert "gdn_state 1.0000" in said and "projections 0.7500" in said
    assert "memory-bound" in said and "gqa_decode_k* 2.0000" in said


def test_the_new_readers_find_nothing_without_the_cells_facts(written):
    """On another generation cell's facts (the parent's checkout with
    these files laid over it runs so): nothing, and no raise."""
    written.facts = {"share_gen_len": 896, "share_step_applications": 1023}
    for name in NEW_READERS:
        assert LOOKUP.module("layer_metrics", name).read(written) is None


def test_no_reader_of_the_benchmark_raises_on_this_cells_facts():
    """Every reader under layer_metrics/ gives None or a number on the
    state driver's facts with a chip's peaks set and no trace; the other
    generation cells' host-clock readers find nothing to read."""
    run = written_run()
    found = {name: LOOKUP.module("layer_metrics", name).read(run)
             for name in LOOKUP.names("layer_metrics")}
    assert all(v is None or isinstance(v, (int, float))
               for v in found.values()), found
    for name in NEW_READERS + ("share_decode_step_ms",
                               "share_prefill_ms_per_call",
                 "share_decode_hbm_roofline", "moe_share_roofline",
                 "decode_step_ms", "prefill_ms_per_call",
                 "session_decode_step_ms", "long_decode_step_ms",
                 "long_decode_hbm_roofline", "mfu", "setup_trace_lower_s"):
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
    assert bench["workloads"][-1] is cell and 12 <= len(cells) <= 24
    configs = {c["name"]: c for c in bench["configs"]}
    entry, config = configs[CONFIG], LOOKUP.json("configs", CONFIG)
    assert bench["configs"][-1] is entry
    assert entry["file"] == "benchmark/configs/%s.json" % CONFIG
    assert entry["source"] == config["source"]
    assert entry["reduced"] == config["reduced"]
    assert len(entry["why"]) <= 200
    end_to_end = {m["name"]: m for m in bench["end_to_end"]}
    assert end_to_end["decode_tok_per_s"]["workloads"][-1] == CELL
    listed = {m["name"]: m for m in bench["per_layer"]}
    for name in SHARED_READERS:
        assert listed[name]["workloads"][-1] == CELL
    for name, m in listed.items():
        if name not in NEW_READERS + SHARED_READERS:
            assert CELL not in m.get("workloads", []), name
    assert [m["name"] for m in bench["per_layer"][-len(NEW_READERS):]] \
        == list(NEW_READERS)
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
    three reduced keys differ, and none of them is a width."""
    config = LOOKUP.json("configs", CONFIG)
    published = {
        "decoder_sparse_step": 1, "full_attention_interval": 4,
        "head_dim": 256, "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 5120, "linear_conv_kernel_dim": 4,
        "linear_key_head_dim": 128, "linear_num_key_heads": 16,
        "linear_num_value_heads": 32, "linear_value_head_dim": 128,
        "max_position_embeddings": 262144, "mlp_only_layers": [],
        "model_type": "qwen3_next", "moe_intermediate_size": 512,
        "norm_topk_prob": True, "num_attention_heads": 16,
        "num_experts": 512, "num_experts_per_tok": 10,
        "num_hidden_layers": 48, "num_key_value_heads": 2,
        "partial_rotary_factor": 0.25, "rms_norm_eps": 1e-06,
        "rope_scaling": None, "rope_theta": 10000000,
        "shared_expert_intermediate_size": 512,
        "tie_word_embeddings": False, "use_sliding_window": False,
        "vocab_size": 151936}
    differs = sorted(k for k, v in published.items() if config[k] != v)
    assert differs == sorted(config["reduced"]) == [
        "num_experts", "num_hidden_layers", "vocab_size"]
    assert config["published"] == {k: published[k] for k in differs}
    assert (config["num_hidden_layers"], config["num_experts"],
            config["vocab_size"]) == (12, 32, 18992)
    # three whole periods; the held range inside the scored one
    assert config["num_hidden_layers"] % config["full_attention_interval"] \
        == 0
    assert config["scored_experts"] == 512
    assert 0 <= config["first_expert"] <= 512 - 32
    assert config["num_experts"] >= config["num_experts_per_tok"]
    assert config["vocab_size"] * 8 == published["vocab_size"]
    for key in ("stands_for", "assumed", "departures", "arithmetic",
                "reduced_why"):
        assert config[key]
    assert {"state_dtype", "rope_layout", "norm_order", "router",
            "delta_rule", "a_log_dt_bias", "mtp"} <= set(config["assumed"])
    workload = LOOKUP.json("workloads", CELL)
    assert workload["prompt_len"] + workload["gen_len"] \
        == config["serve_positions"] == 1024
    assert (workload["batch"], workload["checked_rows"],
            workload["reference_rows"], workload["pool"]) == (128, 32, 2, 4)
    assert set(workload["correct"]) == set(LIMITED) | {"why"}
