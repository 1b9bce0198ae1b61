"""The block-diffusion cell `sdar-diffuse-pp8`: its files found by name,
its driver end to end as a CPU rehearsal at a toy size (fixture
`sdar-tiny-diffuse`, found through `--search-path`), the controls that
`correct` has to refuse, the cell's copy of the reference against the
program's, the model's draw, the configuration's arithmetic and the
bytes and operations of a pass against hand counts, the new readers on a
written account of a traced call and on the other cells' facts (no cut
recording of the cell from the chip: cut_scan_recording.py cannot cut a
scan whose body holds a `while`), the workload file's keys against the
issue's traffic, and BENCHMARK.json's entries for the cell.
"""

import collections
import json
import os

import numpy as np
import pytest

from benchmark.harness import CHECKOUT, Lookup
from benchmark.reduce import decoder_trace, diffusion_ops
from benchmark.tests import diffusion_control, state_control
from benchmark.tests.test_run import FIXTURE, last_line, run_cell

CELL = "sdar-diffuse-pp8"
CONFIG = "sdar-30b-a3b-chat"
TOY, TOY_CONFIG = "sdar-tiny-diffuse", "sdar-tiny"
NEW_READERS = ("diffusion_pass_ms", "diffusion_tokens_per_pass",
               "diffusion_commit_share", "diffusion_unmask_ms_per_pass",
               "diffusion_attn_ms_per_pass", "diffusion_moe_ms_per_pass",
               "diffusion_head_ms_per_pass", "diffusion_pass_hbm_roofline",
               "diffusion_attn_roofline")
COUNTED = ("diffusion_tokens_per_pass", "diffusion_commit_share")
SHARED_READERS = ("decode_trace_lower_s", "decoder_prep_ms_per_call",
                  "decoder_idle_ms_per_call", "prefill_device_ms_per_call")
LIMITED = ("gap_mean", "not_first_share", "conf_off",
           "other_position_share", "kv_off")
PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
LOOKUP = Lookup([FIXTURE])
flops = LOOKUP.module("flops", "block_diffusion")
MARK = "~"
Op = collections.namedtuple("Op", "start end name category path text")


# -- the cell's files, by name --------------------------------------------------

def test_the_cells_files_are_found_by_name():
    workload = LOOKUP.json("workloads", CELL)
    config = LOOKUP.json("configs", workload["config"])
    assert config["name"] == CONFIG
    for kind, name in (("drivers", workload["driver"]),
                       ("models", workload["builder"]),
                       ("reference", workload["reference"]),
                       ("flops", "block_diffusion"),
                       ("reduce", "diffusion_ops"),
                       ("tests", "diffusion_control")):
        assert os.path.dirname(LOOKUP.path(kind, name + ".py")).endswith(kind)
    assert set(NEW_READERS) <= set(LOOKUP.names("layer_metrics"))
    assert (config["builder"], config["reference"]) == \
        (workload["builder"], workload["reference"])


def test_the_cells_reference_is_the_programs_to_the_letter():
    with open(LOOKUP.path("reference", "sdar_moe.py")) as copy, \
            open(os.path.join(CHECKOUT, "paddle_tpu", "models", "reference",
                              "sdar_moe.py")) as own:
        assert copy.read() == own.read()


# -- the driver, end to end -----------------------------------------------------

def test_untraced_rehearsal_has_exactly_the_two_metrics():
    result = last_line(run_cell(TOY, 0))
    assert result["correct"] is True and result["failed"] == 0
    assert set(result["metrics"]) == {"decode_tok_per_s", "setup_s"}
    assert result["attempted"] % 4 == 0 and result["attempted"] >= 4
    assert result["device"]["platform"] == "cpu"


def test_traced_rehearsal_reads_counters_and_no_device_metric():
    proc = run_cell(TOY, 1)
    result = last_line(proc)
    metrics = result["metrics"]
    assert result["correct"] is True
    assert metrics["compiles_in_window"] == {"value": 0, "unit": "count"}
    assert {"setup_compile_s", "setup_cache_misses",
            "decode_trace_lower_s"} <= set(metrics)
    # what the program counts, a CPU run can say: 4 denoising passes and
    # a commit a block of 4
    assert metrics["diffusion_tokens_per_pass"] == {"value": 0.8,
                                                    "unit": "tok/pass"}
    assert metrics["diffusion_commit_share"] == {"value": 20.0, "unit": "%"}
    # what only a chip can say
    assert not (set(NEW_READERS) - set(COUNTED) | {
        "decode_device_step_ms", "prefill_device_ms_per_call"}) \
        & set(metrics)
    assert "24 that denoise, 6 that commit" in proc.stdout
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
    controls = diffusion_control.controls_of(config, workload)
    assert sorted(controls) == ["causal_in_block=true", "causal_prefill=16",
                                "kv_dtype=float8_e4m3fn", "no_commit=true"]
    found = dict(state_control.read(LOOKUP, workload, 3, jax.devices()[:1],
                                    None, controls))
    limits = workload["correct"]
    assert state_control.refused(found[None], limits) == []
    for spelling in controls:
        assert state_control.refused(found[spelling], limits), spelling
    # the cache of a system without a commit pass, or in a narrower
    # type, is seen in the cache; a mask is not
    for spelling in ("no_commit=true", "kv_dtype=float8_e4m3fn"):
        assert "kv_off" in state_control.refused(found[spelling], limits)
    for spelling in ("causal_in_block=true", "causal_prefill=16"):
        assert "kv_off" not in state_control.refused(found[spelling],
                                                     limits)


def test_the_controls_are_the_references():
    """Every control `--all` switches is one the reference or the
    driver's comparison reads."""
    config = LOOKUP.json("configs", CONFIG)
    workload = LOOKUP.json("workloads", CELL)
    with open(LOOKUP.path("reference", "sdar_moe.py")) as f:
        text = f.read()
    with open(LOOKUP.path("drivers", "decode_diffusion.py")) as f:
        text += f.read()
    controls = diffusion_control.controls_of(config, workload)
    for control in controls.values():
        for key in control:
            assert '_control(cfg, "%s"' % key in text \
                or 'control.get("%s")' % key in text, key
    assert "causal_prefill=256" in controls


def test_the_checked_blocks_are_the_seeds():
    driver = LOOKUP.module("drivers", "decode_diffusion")
    seen = set()
    for seed in (1, 2, 2 ** 31 + 5):
        run = Written(workload=LOOKUP.json("workloads", CELL),
                      config=LOOKUP.json("configs", CONFIG), seed=seed)
        blocks = driver.checked_blocks(run)
        assert blocks.shape == (16,) and (np.diff(blocks) > 0).all()
        assert 0 <= blocks.min() and blocks.max() < 192
        seen.add(tuple(blocks))
    assert len(seen) == 3


def test_the_weights_draw():
    """A block made alone for the reference is the block served; the
    head's gain scales the head alone."""
    import jax

    model = LOOKUP.module("models", "sdar_decode")
    config = LOOKUP.json("configs", TOY_CONFIG)
    spec = LOOKUP.json("workloads", TOY)["weights"]
    key = jax.random.PRNGKey(7)
    whole = model.weights(config, spec, key)
    alone = model.block(config, spec, model.root(key), 1)
    for name, value in alone.items():
        np.testing.assert_array_equal(value, whole["blocks"][1][name])
    assert whole["blocks"][0]["w_gate"].shape == (8, 64, 32)
    assert whole["head"].shape == (64, 97)
    doubled = model.ends(config, dict(spec, head_gain=2.0), model.root(key))
    np.testing.assert_allclose(doubled["head"], 2 * np.asarray(whole["head"]),
                               rtol=1e-6)
    np.testing.assert_array_equal(doubled["embed"], whole["embed"])
    built = model.build(config, 4, 2)
    assert set(built["probes"]) == {"keys", "values"}
    assert built["cache_shapes"]["k_cache_1"] == (4, 2, 48, 16)


def test_parameters_and_bytes_are_the_issues():
    """The catalog's count: attention 18.87M, router 0.26M, an expert
    4.72M, 128 of them 604.0M; six layers and the ends 4.36B, 8.72 GB in
    bfloat16; a pass at the cell's size reads every expert."""
    config = LOOKUP.json("configs", CONFIG)
    outside, expert = flops.layer_parameters(config)
    assert expert == 3 * 2048 * 768 == 4718592
    assert outside == 18874368 + 256 + 4096 + 262144
    assert flops.chip_parameters(config) == 4361055744
    assert 2 * flops.chip_parameters(config) == pytest.approx(8.72e9,
                                                              rel=1e-3)
    assert flops.experts_read(config, 512) == pytest.approx(128, abs=1e-9)
    assert flops.experts_read(config, 4) == pytest.approx(
        128 * (1 - (15 / 16) ** 4))
    # the mean block of a call: 256 stored + 191 blocks before the last
    assert flops.live_slots(config, 256, 768) == 256 + 4 * 191 / 2 + 4
    assert flops.live_slots(config, 10, 9) == 8 + 4 * 2 / 2 + 4
    weights = flops.pass_weight_bytes(config, 128, 2, 0.8)
    assert weights == pytest.approx(
        2 * (6 * 623120640 + 2048 + 512 * 2048 + 0.8 * 151936 * 2048))
    attention = flops.attention_pass(config, 128, 642, 2)
    assert attention["bytes"] == 6 * 128 * 2 * (
        2 * 4 * 646 * 128 + 2 * 32 * 4 * 128)
    assert attention["flops"] == 6 * 128 * 4 * 32 * 4 * 642 * 128
    cost = flops.pass_cost(config, 128, 642, 2, 2, 0.8)
    assert cost["bytes"] == pytest.approx(weights + attention["bytes"])
    assert 8.9e9 < cost["bytes"] < 9.2e9
    arithmetic = config["arithmetic"]
    assert "623,120,640" in arithmetic["parameters_a_layer"]
    assert "4,361,055,744" in arithmetic["this_chip"]


# -- the readers ----------------------------------------------------------------

FACTS = dict(diffusion_gen_len=8, diffusion_prompt_len=16,
             diffusion_batch=4, diffusion_denoise_passes=8,
             diffusion_commit_passes=2, setup_compile_s=60.0,
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
    """A traced call of 2 blocks (8 denoising passes, 2 commits), a few
    ms an op, written by hand: what decoder_trace.Parts gives the
    readers."""

    def __init__(self, head):
        def path(scope, kind=None, instance=None, *inner):
            parts = ("jit(f)", "decode_steps", "while", "body", scope)
            if kind is not None:
                parts += (kind, MARK + instance) + inner
            return "/".join(parts + ("fusion",))

        self.call = decoder_trace.Call(None, {"max_len": 8, "prompt_len": 16,
                                              "block": 128})
        self.steps, self.prefill = (10.0, 20.0), (0.0, 5.0)
        denoise, commit = "diffusion_denoise", "diffusion_commit"
        attention = ("cached_attention", "cached_attention_0.tmp_0")
        self.ops = [
            Op(10.0, 10.020, "gqa_decode_k1024_t4_b4", "custom-call",
               path(denoise, *attention, "attn_block_causal"), ""),
            Op(10.1, 10.102, "fusion.1", "loop fusion",
               path(denoise, *attention, "kv_write"), ""),
            Op(11.0, 11.005, "gqa_decode_k1024_t4_b4", "custom-call",
               path(commit, *attention, "attn_block_causal"), ""),
            Op(12.0, 12.040, "fusion.2", "output fusion",
               path(denoise, "moe_experts", "moe_experts_0.tmp_0"), ""),
            Op(12.5, 12.510, "fusion.3", "loop fusion",
               path(commit, "moe_router", "moe_router_0.tmp_0"), ""),
            Op(13.0, 13.008, "fusion.4", "output fusion",
               path(denoise, "mul", head), ""),
            Op(14.0, 14.003, "fusion.5", "output fusion",
               path(denoise, "mul", "fc_0.tmp_0"), ""),
            Op(15.0, 15.012, "fusion.6", "loop fusion",
               "/".join(("jit(f)", "decode_steps", "while", "body",
                         "diffusion_unmask", "reduce_max", "fusion")), ""),
            # a copy the compiler added: under no op and not the rule's
            Op(16.0, 16.001, "copy.1", "copy",
               "/".join(("jit(f)", "decode_steps", "while", "body")), ""),
            # the walk of a prompt's block, inside the prefill
            Op(1.0, 1.200, "gqa_decode_k1024_t128_b4", "custom-call",
               "/".join(("jit(f)", "decode_prefill", "cached_attention",
                         MARK + "cached_attention_0.tmp_0",
                         "attn_block_causal")), ""),
        ]

    def work(self, interval):
        return [op for op in self.ops
                if interval[0] <= op.start and op.end <= interval[1]]

    def busy(self, interval):
        return sum(op.end - op.start for op in self.work(interval))


@pytest.fixture()
def written(monkeypatch):
    """A run whose traced call is `WrittenCall`, with the head's
    instance of the toy cell's own step Program."""
    run = written_run(cell=TOY, config=TOY_CONFIG)
    run.workload["reference_rows"] = 2
    head, = diffusion_ops._head_instance.__wrapped__(run)
    monkeypatch.setattr(diffusion_ops, "_head_instance", lambda r: {head})
    monkeypatch.setattr(diffusion_ops.op_instances, "sigil", lambda: MARK)
    monkeypatch.setattr(decoder_trace, "parts",
                        lambda r: [WrittenCall(head[len(MARK):])])
    return run


def test_the_new_readers_on_a_written_call(written, capsys, monkeypatch):
    read = lambda name: LOOKUP.module("layer_metrics", name).read(written)
    # 10 passes: everything inside the scan of blocks is 101 ms
    assert read("diffusion_pass_ms") == pytest.approx(101.0 / 10)
    assert read("diffusion_attn_ms_per_pass") == pytest.approx(27.0 / 10)
    assert read("diffusion_moe_ms_per_pass") == pytest.approx(50.0 / 10)
    assert read("diffusion_head_ms_per_pass") == pytest.approx(8.0 / 10)
    assert read("diffusion_unmask_ms_per_pass") == pytest.approx(12.0 / 10)
    found = diffusion_ops.by_part(written)
    assert found["other ops"][0] == pytest.approx(3e-4)
    assert found["unscoped"][0] == pytest.approx(1e-4)
    assert sum(s for s, _ in found.values()) == pytest.approx(101e-4)
    config, batch = written.config, written.workload["batch"]
    slots = flops.live_slots(config, 16, 8)
    cost = flops.pass_cost(config, batch, slots, 4, 4, 0.8)
    assert read("diffusion_pass_hbm_roofline") == pytest.approx(
        100 * cost["bytes"] / PEAKS["hbm_bytes_per_s"] / 10.1e-3)
    walk = flops.attention_pass(config, batch, slots, 4)
    assert read("diffusion_attn_roofline") == pytest.approx(
        100 * walk["bytes"] / PEAKS["hbm_bytes_per_s"] / 2.5e-3)
    said = capsys.readouterr().out
    assert "gqa_decode_*_b4: 2.5000 ms a pass (x0.2)" in said
    assert "memory-bound" in said
    assert "experts 5.0000" in said and "unmask 1.2000" in said
    # the counters' two read the process's registry, not the trace
    counters = {"decoder_diffusion_tokens_total": 64,
                "decoder_diffusion_passes_total{kind=denoise}": 16,
                "decoder_diffusion_passes_total{kind=commit}": 4,
                "decoder_diffusion_blocks_total": 4}
    monkeypatch.setattr(decoder_trace, "counters", lambda: counters)
    assert read("diffusion_tokens_per_pass") == pytest.approx(0.8)
    assert read("diffusion_commit_share") == pytest.approx(20.0)
    # the other generation cells' readers see none of it
    for name in ("mha_attn_ms_per_step", "sparse_moe_ms_per_step",
                 "dense_state_decode_hbm_roofline", "gqa_decode_roofline",
                 "sparse_kv_attend_roofline"):
        assert LOOKUP.module("layer_metrics", name).read(written) is None


@pytest.mark.parametrize("facts", [
    {"share_gen_len": 896, "share_step_applications": 1023},
    {"state_gen_len": 896, "state_prompt_len": 128, "state_batch": 128},
    {"dense_state_gen_len": 384, "dense_state_prompt_len": 128,
     "dense_state_batch": 128},
    {"sparse_gen_len": 64}, {}])
def test_the_new_readers_find_nothing_without_the_cells_facts(written,
                                                              facts):
    """On another generation cell's facts (the parent's checkout with
    these files laid over it runs so): nothing, and no raise."""
    written.facts = facts
    for name in NEW_READERS:
        assert LOOKUP.module("layer_metrics", name).read(written) is None


def test_the_new_readers_find_nothing_in_a_program_without_the_scopes(
        written, monkeypatch):
    """A traced call none of whose operations lies under `decode_steps`
    (the parent's program has no such decoder): nothing, and no raise."""
    call = WrittenCall("fc_9.tmp_0")
    call.steps = None
    monkeypatch.setattr(decoder_trace, "parts", lambda r: [call])
    monkeypatch.setattr(decoder_trace, "counters", lambda: {})
    for name in NEW_READERS:
        assert LOOKUP.module("layer_metrics", name).read(written) is None


def test_no_reader_of_the_benchmark_raises_on_this_cells_facts():
    """Every reader under layer_metrics/ gives None or a number on the
    diffusion driver's facts with a chip's peaks set and no trace; the
    other generation cells' readers find nothing to read."""
    run = written_run()
    found = {name: LOOKUP.module("layer_metrics", name).read(run)
             for name in LOOKUP.names("layer_metrics")}
    assert all(v is None or isinstance(v, (int, float))
               for v in found.values()), found
    for name in tuple(set(NEW_READERS) - set(COUNTED)) + (
            "gdn_ms_per_step", "mha_attn_ms_per_step",
            "state_moe_ms_per_step", "state_decode_hbm_roofline",
            "dense_state_decode_hbm_roofline", "share_decode_step_ms",
            "decode_step_ms", "long_decode_step_ms", "mfu",
            "setup_trace_lower_s"):
        assert found[name] is None, name
    assert found["decode_trace_lower_s"] == 5.5
    assert found["setup_compile_s"] == 60.0
    assert found["compiles_in_window"] == 0


# -- the workload file and BENCHMARK.json ----------------------------------------

def test_the_workload_is_the_issues_traffic():
    workload = LOOKUP.json("workloads", CELL)
    config = LOOKUP.json("configs", CONFIG)
    assert (workload["batch"], workload["prompt_len"], workload["gen_len"],
            workload["pool"], workload["reference_rows"],
            workload["checked_blocks"]) == (128, 256, 768, 4, 2, 16)
    assert workload["prompt_len"] + workload["gen_len"] \
        == config["serve_positions"] == 1024
    assert (workload["serve_dtype"], workload["weights"]["dtype"]) == \
        ("bfloat16", "bfloat16")
    draw = workload["weights"]
    assert (draw["seed"], draw["std"], draw["embed_std"], draw["qk_gain"],
            draw["head_gain"]) == (7300000101, 0.02, 1.0, 2.5, 1.0)
    assert (workload["driver"], workload["chips"]) == ("decode_diffusion", 1)
    assert set(workload["correct"]) == set(LIMITED) | {"why"}
    how = config["generation"]
    assert (how["block_length"], how["denoising_steps"], how["remasking"],
            how["confidence_threshold"], how["temperature"]) == \
        (4, 4, "low_confidence_dynamic", 0.9, 0.0)
    assert 0 <= how["mask_token_id"] < config["vocab_size"]
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
    assert 19 <= len(cells) <= 24
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
    # the list is at its cap: the cell's nine readers are files that
    # report in a traced run's line, and none of them is listed
    assert len(listed) == 128
    assert not set(NEW_READERS) & set(listed)
    for name in SHARED_READERS:
        assert CELL in listed[name]["workloads"]
    for name, m in listed.items():
        if name not in SHARED_READERS:
            assert CELL not in m.get("workloads", []), name
    for name in NEW_READERS:
        reader = LOOKUP.module("layer_metrics", name)
        assert (reader.MOVES, reader.UNIT != "", reader.SOURCE in (
            "device_trace", "program_counter")) == \
            ("decode_tok_per_s", True, True)
        assert reader.LAYER in ("decoding", "ops", "kernels")


def test_the_configuration_keeps_every_published_key():
    """Every key of the catalog's entry under its own name and value but
    the depth."""
    config = LOOKUP.json("configs", CONFIG)
    published = {
        "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
        "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 6144, "max_position_embeddings": 32768,
        "max_window_layers": 48, "mlp_only_layers": [],
        "model_type": "sdar_moe", "moe_intermediate_size": 768,
        "norm_topk_prob": True, "num_attention_heads": 32,
        "num_experts": 128, "num_experts_per_tok": 8,
        "num_hidden_layers": 48, "num_key_value_heads": 4,
        "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 1000000,
        "sliding_window": None, "tie_word_embeddings": False,
        "use_sliding_window": False, "vocab_size": 151936}
    differs = sorted(k for k, v in published.items() if config[k] != v)
    assert differs == config["reduced"] == ["num_hidden_layers"]
    assert config["published"] == {"num_hidden_layers": 48}
    assert config["num_hidden_layers"] == 6 == 48 // 8
    for key in ("stands_for", "assumed", "departures", "arithmetic",
                "reduced_why"):
        assert config[key]
    assert {"block_length", "denoising_steps", "remasking",
            "mask_token_id", "shift", "block_order"} <= set(
                config["assumed"])
