"""The cell `granite-train-4k` rehearsed on the CPU at toy sizes (the
fixture's `granite-tiny-train`, found by name through `--search-path`),
the FLOPs and bytes benchmark/flops/ssd.py counts against counts made by
hand, and the three readers that came with the cell: on a written trace,
and on a recording from the chip (`data/granite-train-4k-ssm.xplane.pb`).
"""

import json
import math
import os

import pytest

from benchmark.flops import ssd
from benchmark.harness import CHECKOUT, Lookup
from benchmark.tests import test_run
from benchmark.tests.test_ouro_cell import (LOOKUP, US, Run, _event,
                                            _fusion, _metadata, _read)

NEW_READERS = ("ssm_ms_per_step", "ssm_conv_ms_per_step", "ssd_roofline")
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


# -- run.py end to end -------------------------------------------------------

def test_untraced_rehearsal_trains_and_agrees_with_the_reference():
    proc = test_run.run_cell("granite-tiny-train", 0)
    result = test_run.last_line(proc)
    assert set(result) == test_run.RESULT_KEYS
    assert result["correct"] is True and result["failed"] == 0
    assert set(result["metrics"]) == {"train_items_per_s", "setup_s"}
    assert "check ok  : loss" in proc.stdout
    assert "tokens/s per chip" in proc.stdout


def test_traced_rehearsal_prints_no_device_metric_under_the_new_names():
    result = test_run.last_line(test_run.run_cell("granite-tiny-train", 1))
    assert result["correct"] is True
    metrics = result["metrics"]
    assert metrics["compiles_in_window"] == {"value": 0, "unit": "count"}
    assert not (test_run.DEVICE_METRICS | set(NEW_READERS)) & set(metrics)


def _catalog():
    """The catalog's `config` of granite-4.0-h-micro, key for key."""
    pattern = ["mamba"] * 5 + ["attention"]
    layer_types = (pattern + ["mamba"] * 4) * 3 + pattern + ["mamba"] * 4
    return {
        "attention_bias": False, "attention_multiplier": 0.015625,
        "embedding_multiplier": 12, "hidden_act": "silu",
        "hidden_size": 2048, "intermediate_size": 8192,
        "layer_types": layer_types, "logits_scaling": 8,
        "mamba_chunk_size": 256, "mamba_conv_bias": True,
        "mamba_d_conv": 4, "mamba_d_head": 64, "mamba_d_state": 128,
        "mamba_expand": 2, "mamba_n_groups": 1, "mamba_n_heads": 64,
        "mamba_proj_bias": False, "max_position_embeddings": 131072,
        "model_type": "granitemoehybrid",
        "normalization_function": "rmsnorm", "num_attention_heads": 32,
        "num_experts_per_tok": 0, "num_hidden_layers": 40,
        "num_key_value_heads": 8, "num_local_experts": 0,
        "position_embedding_type": "nope", "residual_multiplier": 0.22,
        "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
        "shared_intermediate_size": 8192, "tie_word_embeddings": True,
        "vocab_size": 100352}


def test_the_cells_files_state_what_the_issue_fixes():
    lookup = Lookup()
    workload = lookup.json("workloads", "granite-train-4k")
    assert (workload["driver"], workload["batch"], workload["pool"],
            workload["loss_read_every"], workload["chips"],
            workload["trace_seconds"]) == ("train_executor", 1, 4, 10, 1, 8.0)
    cfg = lookup.json("configs", workload["config"])
    assert cfg["sequence_length"] == 4096
    catalog = _catalog()
    assert len(catalog["layer_types"]) == 40
    assert [i for i, k in enumerate(catalog["layer_types"])
            if k == "attention"] == [5, 15, 25, 35]
    # the catalog's config, key for key; the one cut is the depth
    changed = {k for k, v in catalog.items() if cfg[k] != v}
    assert changed == {"num_hidden_layers", "layer_types"} \
        == set(cfg["reduced"])
    assert cfg["num_hidden_layers"] == 6
    assert cfg["layer_types"] == catalog["layer_types"][:6] \
        == ["mamba"] * 5 + ["attention"]
    assert {"kept", "stands_for", "num_hidden_layers", "layer_types"} \
        <= set(cfg["reduced_why"])
    assert {"sequence_length", "optimizer", "ssm_init", "head_dim",
            "float32_islands"} <= set(cfg["assumed"])
    assert (cfg["compute_dtype"], cfg["master_dtype"]) == ("bfloat16",
                                                           "float32")
    assert cfg["optimizer"]["type"] == "adam"
    assert (cfg["optimizer"]["beta1"], cfg["optimizer"]["beta2"],
            cfg["optimizer"]["epsilon"]) == (0.9, 0.95, 1e-08)
    with open(os.path.join(CHECKOUT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    listed = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW_READERS:
        assert listed[name]["workloads"] == ["granite-train-4k"]
        assert listed[name]["moves"] == "train_items_per_s"
        reader = lookup.module("layer_metrics", name)
        assert (listed[name]["layer"], listed[name]["unit"],
                listed[name]["source"]) == (reader.LAYER, reader.UNIT,
                                            reader.SOURCE)
    # a run of names where it begins, not "the last three", and the cell
    # and its configuration by name: later PRs append their own
    first = list(listed).index(NEW_READERS[0])
    assert list(listed)[first:first + 3] == list(NEW_READERS)
    cell = {"name": "granite-train-4k", "config": "granite-4.0-h-micro",
            "traffic": "granite-train-4k", "chips": 1,
            "why": workload["why"]}
    assert [w for w in bench["workloads"] if w["name"] == cell["name"]] \
        == [cell] and len(cell["why"]) <= 200
    entry, = [c for c in bench["configs"] if c["name"] == cfg["name"]]
    assert (entry["name"], entry["source"], entry["reduced"]) == (
        cfg["name"], cfg["source"], cfg["reduced"])
    assert all(1 <= len(e["why"]) <= 200
               for e in bench["configs"] + bench["workloads"])
    # one four-chip cell: the quota is a quarter, rounded down
    four = [w["name"] for w in bench["workloads"] if w["chips"] == 4]
    assert four == ["resnet50-train-dp4"]
    assert 6 <= len(bench["workloads"]) <= 24
    assert 5 <= len(bench["configs"]) <= 24


def test_the_reference_copy_is_the_programs():
    def code(path):
        with open(os.path.join(CHECKOUT, path)) as f:
            return f.read()

    assert code("benchmark/reference/granite_hybrid.py") == \
        code("paddle_tpu/models/reference/granite_hybrid.py")
    assert "import paddle_tpu" not in code(
        "benchmark/reference/granite_hybrid.py")


def test_the_builder_builds_the_published_widths():
    """The cell's program, built (not run) from the configuration's file:
    five state-space layers and one attention layer at the published
    widths, one tied table, the reference's parameter layout."""
    lookup = Lookup()
    cfg = lookup.json("configs", "granite-4.0-h-micro")
    built = lookup.module("models", "granite_hybrid").build(cfg, 1,
                                                            train=True)
    block = built["main"].global_block()
    mamba, attention = (built["param_names"]["blocks"][i] for i in (0, 5))
    shapes = {w: tuple(block.var(mamba[w]).shape) for w in (
        "in_proj", "conv_w", "conv_b", "a_log", "d", "dt_bias", "norm_g",
        "out_proj", "w_in", "w_out")}
    assert shapes == {
        "in_proj": (2048, 8512), "conv_w": (4352, 4), "conv_b": (4352,),
        "a_log": (64,), "d": (64,), "dt_bias": (64,), "norm_g": (4096,),
        "out_proj": (4096, 2048), "w_in": (2048, 16384),
        "w_out": (8192, 2048)}
    assert {w: tuple(block.var(attention[w]).shape)
            for w in ("wq", "wk", "wv", "wo")} == {
        "wq": (2048, 2048), "wk": (2048, 512), "wv": (2048, 512),
        "wo": (2048, 2048)}
    assert tuple(block.var("embed.w").shape) == (100352, 2048)
    ops = [op.type for op in block.desc.ops]
    assert (ops.count("ssd_scan"), ops.count("causal_conv1d"),
            ops.count("flash_attention"), ops.count("rope")) == (5, 5, 1, 0)
    scan = [op for op in block.desc.ops if op.type == "ssd_scan"][0]
    assert scan.attrs["chunk_size"] == 256 and scan.attrs["num_heads"] == 64
    assert tuple(block.var(scan.output("States")[0]).shape) == \
        (1, 16, 128, 4096)
    flash = [op for op in block.desc.ops if op.type == "flash_attention"][0]
    assert flash.attrs["sm_scale"] == 0.015625
    assert flash.attrs["num_heads"] == 32
    assert built["feed_names"] == ["tokens", "targets"]
    assert built["items_per_step"] == 4096
    params = sum(math.prod(p.shape) for p in block.all_parameters())
    assert params == 647_259_328       # 647.3M: 10.4 GB at 16 B each
    with pytest.raises(ValueError, match="group"):
        lookup.module("models", "granite_hybrid").program_sizes(
            dict(cfg, mamba_n_groups=8))


# -- FLOPs and bytes from shapes -----------------------------------------------

def test_scan_cost_by_hand():
    """One scan of the cell: 16 chunks of 256, 64 heads of 64, state 128,
    bfloat16."""
    cost = ssd.scan_cost(1, 4096, 64, 64, 128, 256)
    half = 256 * 257 // 2                    # 32896 pairs i >= j
    state = 256 * 128 * 64
    fwd = 2 * 16 * (half * 128 + 64 * (half * 64 + 2 * state))
    bwd = 2 * 16 * (2 * half * 128 + 64 * (2 * half * 64 + 4 * state))
    assert cost["forward"]["flops"] == fwd == 13_036_421_120
    assert cost["backward"]["flops"] == bwd == 2 * fwd
    assert cost["forward"]["exps"] == 16 * 64 * half
    wide, narrow, steps = 4096 * 4096 * 2, 4096 * 128 * 2, 4096 * 64 * 4
    assert cost["forward"]["bytes"] == 2 * wide + 2 * narrow + steps
    assert cost["backward"]["bytes"] == (
        4 * wide + 2 * narrow + 16 * 128 * 4096 * 4
        + 2 * 4096 * 128 * 4 + 2 * steps)
    # a kernel that computes whole [256, 256] tiles does 256 / 128.5 of
    # the causal half: it reads lower, none can read over 100%
    peaks = LOOKUP.json("", "peaks")["devices"]["TPU v5 lite"]
    total = {k: cost["forward"][k] + cost["backward"][k]
             for k in ("flops", "bytes")}
    least, bound = ssd.roofline(total, peaks)
    assert bound == "memory"
    assert least == pytest.approx(total["bytes"] / 819e9)
    assert total["flops"] / 197e12 < least


def test_scan_cost_of_the_tiny_program():
    cfg = LOOKUP.json("configs", "granite-tiny")
    program = LOOKUP.module("models", "granite_hybrid").build(
        cfg, 2, train=True)["main"]
    cost = ssd.program_cost(program)
    assert cost["scans"] == 2
    one = ssd.scan_cost(2, 32, 8, 16, 16, 8)
    for key in ("flops", "bytes", "exps"):
        assert cost[key] == 2 * (one["forward"][key] + one["backward"][key])
    empty = ssd.program_cost(LOOKUP.module("models", "ouro").build(
        LOOKUP.json("configs", "ouro-tiny"), 2, train=True)["main"])
    assert empty == {"flops": 0, "bytes": 0, "exps": 0, "scans": 0}


# -- the readers on a written trace ------------------------------------------

def _kernel(name, i):
    return ('%%%s.%d = f32[8]{0} custom-call(f32[8]{0} %%p), '
            'custom_call_target=\\"tpu_custom_call\\"' % (name, i))


def _moving(i, elements):
    """A loop fusion that reads and writes `elements` bfloat16 values."""
    return ("%%fusion.%d = bf16[%d]{0} fusion(bf16[%d]{0} %%p), kind=kLoop, "
            "calls=%%c%d" % (i, elements, elements, i))


CONV = "jit(segment_fn)/causal_conv1d/"
CONV_G = "jit(segment_fn)/causal_conv1d_grad/"
SCAN = "jit(segment_fn)/ssd_scan/"
SCAN_G = "jit(segment_fn)/ssd_scan_grad/"
FWD_K, BWD_K = "ssd_fwd_c256_h2", "ssd_bwd_c256_h2"
# Device time in microseconds, one traced "step":
#   fusion 1     0 ..  4  mul (in_proj: not the layer's own ops)
#   fusion 2     4 ..  6  causal_conv1d            moves 2 x 819000 B
#   fusion 3     6 ..  7  ssd_scan/ssd_decay
#   kernel 4     7 .. 17  ssd_scan/ssd_chunks, the forward kernel
#   fusion 5    17 .. 19  ssd_scan, under none of its scopes
#   fusion 6    19 .. 29  mul_grad
#   fusion 7    29 .. 31  ssd_scan_grad/ssd_decay
#   kernel 8    31 .. 56  ssd_scan_grad/ssd_chunks, the backward kernel
#   fusion 9    56 .. 59  ssd_scan_grad/ssd_chunks (the reverse sums)
#   fusion 10   59 .. 63  causal_conv1d_grad       moves 2 x 819000 B
#   fusion 11   63 .. 64  adam
OPS = [
    (1, 0, 4, _fusion(1, "kOutput"), "jit(segment_fn)/mul/dot_general:"),
    (2, 4, 2, _moving(2, 409500), CONV + "mul:"),
    (3, 6, 1, _fusion(3), SCAN + "ssd_decay/softplus:"),
    (4, 7, 10, _kernel(FWD_K, 4), SCAN + "ssd_chunks/%s:" % FWD_K),
    (5, 17, 2, _fusion(5), SCAN + "convert_element_type:"),
    (6, 19, 10, _fusion(6, "kOutput"),
     "jit(segment_fn)/mul_grad/transpose(jvp())/dot_general:"),
    (7, 29, 2, _fusion(7), SCAN_G + "ssd_decay/exp:"),
    (8, 31, 25, _kernel(BWD_K, 8), SCAN_G + "ssd_chunks/%s:" % BWD_K),
    (9, 56, 3, _fusion(9), SCAN_G + "ssd_chunks/cumsum:"),
    (10, 59, 4, _moving(10, 409500), CONV_G + "mul:"),
    (11, 63, 1, _fusion(11), "jit(segment_fn)/adam/sub:"),
]
WRITTEN = """
planes {
  name: "/device:TPU:0"
  lines { name: "XLA Ops" timestamp_ns: 0
    %s
  }
  %s
  stat_metadata { key: 9 value { id: 9 name: "tf_op" } }
}
planes {
  name: "/host:CPU"
  lines { name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 70000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "bench/window" } }
}
""" % ("\n    ".join(_event(i, s, n) for i, s, n, _, _ in OPS),
       "\n  ".join(_metadata(i, text, path) for i, _, _, text, path in OPS))


class GraniteRun(Run):
    def __init__(self, trace_dir, peaks, steps=1):
        Run.__init__(self, trace_dir, peaks, steps)
        self.config = LOOKUP.json("configs", "granite-tiny")
        self.workload = dict(LOOKUP.json("workloads", "granite-tiny-train"),
                             name="granite-tiny-train")


def _trace_dir(tmp_path, text):
    from jax.profiler import ProfileData

    (tmp_path / "written.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace(text))
    return str(tmp_path)


def test_the_new_readers_on_a_written_trace(tmp_path, capsys):
    peaks = LOOKUP.json("", "peaks")["devices"]["TPU v5 lite"]
    run = GraniteRun(_trace_dir(tmp_path, WRITTEN), peaks)
    ms = 1e-3
    # everything but the two products and adam
    assert _read("ssm_ms_per_step", run) == pytest.approx(
        (2 + 1 + 10 + 2 + 2 + 25 + 3 + 4) * ms)
    printed = capsys.readouterr().out
    assert "ssd_scan 0.013 ms and 3.0 operations a step" in printed
    assert "ssd_scan_grad 0.030 ms and 3.0 operations a step" in printed
    assert "ssd_scan/ssd_chunks 0.010 ms" in printed
    assert "ssd_scan_grad/ssd_chunks 0.028 ms" in printed
    assert "ssd_scan/(no scope) 0.002 ms" in printed
    # the convolution, forward and backward: 2 x 1.638 MB in 6 us
    assert _read("ssm_conv_ms_per_step", run) == pytest.approx(6 * ms)
    printed = capsys.readouterr().out
    assert "causal_conv1d 0.002 ms and 1.0 operations a step" in printed
    assert "causal_conv1d_grad 0.004 ms" in printed
    share = 100 * 4 * 819000 / peaks["hbm_bytes_per_s"] / (6 * US)
    assert share == pytest.approx(66.67, abs=0.01)
    assert "%.1f%% of the HBM roofline" % share in printed
    # both scan ops' 43 us against what the tiny program's two scans need
    cfg = run.config
    one = ssd.scan_cost(2, cfg["sequence_length"], cfg["mamba_n_heads"],
                        cfg["mamba_d_head"], cfg["mamba_d_state"],
                        cfg["mamba_chunk_size"])
    need = {k: 2 * (one["forward"][k] + one["backward"][k])
            for k in ("flops", "bytes")}
    least = max(need["flops"] / peaks["bf16_flops_per_s"],
                need["bytes"] / peaks["hbm_bytes_per_s"])
    assert _read("ssd_roofline", run) == pytest.approx(
        100 * least / (43 * US))
    printed = capsys.readouterr().out
    assert "%s 1.0 calls and 0.010 ms a step" % FWD_K in printed
    assert "%s 1.0 calls and 0.025 ms a step" % BWD_K in printed
    assert "the program's 2 scan(s)" in printed
    assert "(memory-bound)" in printed and "took 0.043 ms" in printed
    # two steps in the same window: half of everything a step, the same
    # share of the roofline
    two = GraniteRun(run.trace_dir, peaks, steps=2)
    assert _read("ssm_ms_per_step", two) == pytest.approx(24.5 * ms)
    assert _read("ssm_conv_ms_per_step", two) == pytest.approx(3 * ms)
    assert _read("ssd_roofline", two) == pytest.approx(
        2 * 100 * least / (43 * US))


def test_the_new_readers_return_nothing_where_there_is_nothing(tmp_path):
    peaks = LOOKUP.json("", "peaks")["devices"]["TPU v5 lite"]
    written = _trace_dir(tmp_path, WRITTEN)
    # a CPU rehearsal (no peaks), an untraced run and a run without
    # steps read nothing
    for run in (GraniteRun(written, None), GraniteRun(None, peaks),
                GraniteRun(written, peaks, steps=0)):
        for name in NEW_READERS:
            assert _read(name, run) is None, name


def test_a_program_without_a_state_space_layer_gives_no_value(tmp_path):
    """The parent commit's programs, and the other five cells': no
    `ssd_scan` or `causal_conv1d` scope and no `ssd_` kernel."""
    text = WRITTEN.replace("ssd_fwd", "other_fwd").replace("ssd_bwd",
                                                           "other_bwd")
    for op in ("ssd_scan_grad", "causal_conv1d_grad", "ssd_scan",
               "causal_conv1d"):
        text = text.replace("jit(segment_fn)/%s/" % op,
                            "jit(segment_fn)/mul_grad/")
    peaks = LOOKUP.json("", "peaks")["devices"]["TPU v5 lite"]
    run = GraniteRun(_trace_dir(tmp_path, text), peaks)
    for name in NEW_READERS:
        assert _read(name, run) is None, name


# -- the readers on a recording from the chip ---------------------------------

# `data/granite-train-4k-ssm.xplane.pb` is a recording from the chip (TPU v5
# lite, granite-train-4k, PR 31's traced run on seed 3000000105), cut down
# to device 0's events under the `ssd_scan` and `causal_conv1d` op types and
# their gradients' of one step (142.983 ms from the first layer's
# convolution to the same instruction's next run; 195 events of 195
# instructions) with each instruction's `tf_op` path, and one `bench/window`
# span over the step.  What it holds, in microseconds (summed from the
# events when the recording was cut, by the scope each path lies under):
#
#   causal_conv1d                   15 events     925.796
#   causal_conv1d_grad              30 events    3240.403
#   ssd_scan/ssd_chunks             35 events    2152.971
#   ssd_scan/ssd_decay              15 events      25.209
#   ssd_scan_grad (no scope)         5 events      17.412
#   ssd_scan_grad/ssd_chunks        95 events    5432.816
#                                               11794.607
#   of them the kernels:
#   ssd_fwd_c256_h2                  5 calls     1933.145
#   ssd_bwd_c256_h2                  5 calls     5023.616
RECORDED_CONV_US = 925.796 + 3240.403
RECORDED_SCAN_US = 2152.971 + 25.209 + 17.412 + 5432.816


class RecordedRun(Run):
    def __init__(self, trace_dir, peaks):
        Run.__init__(self, trace_dir, peaks, 1)
        lookup = Lookup()
        self.lookup = lookup
        self.workload = dict(lookup.json("workloads", "granite-train-4k"),
                             name="granite-train-4k")
        self.config = lookup.json("configs", self.workload["config"])


def test_the_new_readers_on_a_recording_from_the_chip(tmp_path, capsys):
    import shutil

    shutil.copy(os.path.join(DATA, "granite-train-4k-ssm.xplane.pb"),
                str(tmp_path))
    peaks = LOOKUP.json("", "peaks")["devices"]["TPU v5 lite"]
    run = RecordedRun(str(tmp_path), peaks)
    assert _read("ssm_ms_per_step", run) == pytest.approx(
        (RECORDED_CONV_US + RECORDED_SCAN_US) * 1e-3, abs=1e-6)
    printed = capsys.readouterr().out
    assert "ssd_scan 2.178 ms and 50.0 operations a step" in printed
    assert "ssd_scan_grad 5.450 ms and 100.0 operations a step" in printed
    assert "ssd_scan_grad/ssd_chunks 5.433 ms" in printed
    assert "ssd_scan/ssd_decay 0.025 ms" in printed
    assert "ssd_intra" not in printed        # the kernels are the path
    assert _read("ssm_conv_ms_per_step", run) == pytest.approx(
        RECORDED_CONV_US * 1e-3, abs=1e-6)
    printed = capsys.readouterr().out
    assert "causal_conv1d_grad 3.240 ms and 30.0 operations a step" \
        in printed
    assert "% of the HBM roofline" in printed
    # five scans and their gradients: 1.232 GB at 819 GB/s, memory-bound
    one = ssd.scan_cost(1, 4096, 64, 64, 128, 256)
    moved = 5 * (one["forward"]["bytes"] + one["backward"]["bytes"])
    least = moved / peaks["hbm_bytes_per_s"]
    assert _read("ssd_roofline", run) == pytest.approx(
        100 * least / (RECORDED_SCAN_US * US), rel=1e-6)
    assert 0 < 100 * least / (RECORDED_SCAN_US * US) < 100
    printed = capsys.readouterr().out
    assert "ssd_bwd_c256_h2 5.0 calls and 5.024 ms a step" in printed
    assert "ssd_fwd_c256_h2 5.0 calls and 1.933 ms a step" in printed
    assert "the program's 5 scan(s)" in printed
    assert "(memory-bound)" in printed
