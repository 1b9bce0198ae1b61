"""The cell `olmoe-train-4k` rehearsed on the CPU at toy sizes (the
fixture's `olmoe-tiny-train`, found by name through `--search-path`), the
FLOPs and bytes benchmark/flops/grouped.py counts against counts made by
hand, and the three readers that came with the cell: on a written trace,
and on a recording from the chip (`data/olmoe-train-4k-experts.xplane.pb`).
"""

import json
import math
import os

import pytest

from benchmark.harness import CHECKOUT, Lookup
from benchmark.tests import test_run
from benchmark.tests.test_ouro_cell import (LOOKUP, US, Run, _event,
                                            _fusion, _metadata, _read)

NEW_READERS = ("moe_ms_per_step", "moe_route_ms_per_step",
               "moe_expert_roofline")
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


# -- run.py end to end -------------------------------------------------------

def test_untraced_rehearsal_trains_and_agrees_with_the_reference():
    proc = test_run.run_cell("olmoe-tiny-train", 0)
    result = test_run.last_line(proc)
    assert set(result) == test_run.RESULT_KEYS
    assert result["correct"] is True and result["failed"] == 0
    assert set(result["metrics"]) == {"train_items_per_s", "setup_s"}
    assert "check ok  : loss" in proc.stdout
    assert "tokens/s per chip" in proc.stdout


def test_traced_rehearsal_prints_no_device_metric_under_the_new_names():
    result = test_run.last_line(test_run.run_cell("olmoe-tiny-train", 1))
    assert result["correct"] is True
    metrics = result["metrics"]
    assert metrics["compiles_in_window"] == {"value": 0, "unit": "count"}
    assert not (test_run.DEVICE_METRICS | set(NEW_READERS)) & set(metrics)


def test_the_cells_files_state_what_the_issue_fixes():
    lookup = Lookup()
    workload = lookup.json("workloads", "olmoe-train-4k")
    assert (workload["driver"], workload["batch"], workload["pool"],
            workload["loss_read_every"], workload["chips"],
            workload["trace_seconds"]) == ("train_executor", 1, 4, 10, 1, 8.0)
    cfg = lookup.json("configs", workload["config"])
    assert cfg["sequence_length"] == 4096
    # the catalog's config, key for key; the one cut is the depth
    catalog = {"attention_bias": False, "clip_qkv": None,
               "hidden_act": "silu", "hidden_size": 2048,
               "intermediate_size": 1024, "max_position_embeddings": 4096,
               "model_type": "olmoe", "norm_topk_prob": False,
               "num_attention_heads": 16, "num_experts": 64,
               "num_experts_per_tok": 8, "num_hidden_layers": 16,
               "num_key_value_heads": 16, "rms_norm_eps": 1e-05,
               "rope_scaling": None, "rope_theta": 10000,
               "tie_word_embeddings": False, "vocab_size": 50304}
    changed = {k for k, v in catalog.items() if cfg[k] != v}
    assert changed == {"num_hidden_layers"} == set(cfg["reduced"])
    assert cfg["num_hidden_layers"] == 1
    assert {"aux_coef", "z_coef", "optimizer", "router_dtype", "qk_norm",
            "intermediate_size"} <= set(cfg["assumed"])
    assert (cfg["aux_coef"], cfg["z_coef"]) == (0.01, 0.001)
    assert cfg["optimizer"] == {"type": "adam", "learning_rate": 0.0004,
                                "beta1": 0.9, "beta2": 0.95,
                                "epsilon": 1e-08}
    with open(os.path.join(CHECKOUT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    listed = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW_READERS:
        assert listed[name]["workloads"] == ["olmoe-train-4k"]
    cell = [w for w in bench["workloads"] if w["name"] == "olmoe-train-4k"]
    assert cell == [{"name": "olmoe-train-4k", "config": "olmoe-1b-7b",
                     "traffic": "olmoe-train-4k", "chips": 1,
                     "why": workload["why"]}]
    # (by name, not "the last of its list": later PRs append their own)
    assert [c["name"] for c in bench["configs"]].count("olmoe-1b-7b") == 1
    # one four-chip cell: the quota is a quarter, rounded down
    four = [w["name"] for w in bench["workloads"] if w["chips"] == 4]
    assert four == ["resnet50-train-dp4"]
    assert len(four) <= max(1, len(bench["workloads"]) // 4)


def test_the_reference_copy_is_the_programs():
    """benchmark/reference/olmoe.py is paddle_tpu/models/reference/
    olmoe.py but for the docstring's last lines, which say whose copy it
    is."""
    def code(path):
        with open(os.path.join(CHECKOUT, path)) as f:
            text = f.read()
        return text[text.index('"""', 3):]

    assert code("benchmark/reference/olmoe.py") == \
        code("paddle_tpu/models/reference/olmoe.py")


def test_the_builder_builds_every_expert_at_the_published_widths():
    """The cell's program, built (not run) from the configuration's file:
    all 64 experts in three stacked parameters, 8 a token, 32768 routed
    rows, the reference's parameter layout."""
    lookup = Lookup()
    cfg = lookup.json("configs", "olmoe-1b-7b")
    built = lookup.module("models", "olmoe").build(cfg, 1, train=True)
    block = built["main"].global_block()
    names = built["param_names"]["blocks"][0]
    shapes = {w: tuple(block.var(names[w]).shape)
              for w in ("router", "w_gate", "w_up", "w_down", "q_norm")}
    assert shapes == {"router": (2048, 64), "w_gate": (64, 2048, 1024),
                      "w_up": (64, 2048, 1024), "w_down": (64, 1024, 2048),
                      "q_norm": (2048,)}
    experts = [op for op in block.desc.ops if op.type == "moe_experts"]
    assert len(experts) == 1
    assert tuple(block.var(experts[0].output("Xs")[0]).shape) == \
        (8 * 4096, 2048)
    assert built["items_per_step"] == 4096
    params = sum(math.prod(p.shape)
                 for p in block.all_parameters())
    assert params == 625_616_896       # 625.6M: 10.0 GB at 16 B each


# -- FLOPs and bytes from the IR ---------------------------------------------

def test_grouped_flops_by_hand():
    grouped = LOOKUP.module("flops", "grouped")
    assert grouped.product_flops(32768, 2048, 1024) == 2 * 32768 * 2048 * 1024
    cost = grouped.layer_cost(32768, 64, 2048, 1024)
    one = 2 * 32768 * 2048 * 1024
    assert cost["forward"]["flops"] == 3 * one
    assert cost["backward"]["flops"] == 6 * one
    # rows in and out and all 64 matrices, bfloat16; a dw in float32
    rows, weights = 32768 * (2048 + 1024) * 2, 64 * 2048 * 1024
    assert cost["forward"]["bytes"] == 3 * (rows + weights * 2)
    assert cost["backward"]["bytes"] == 3 * (rows + weights * 2) \
        + 3 * (rows + weights * 4)
    peaks = LOOKUP.json("", "peaks")["devices"]["TPU v5 lite"]
    total = {"flops": 9 * one, "bytes": cost["forward"]["bytes"]
             + cost["backward"]["bytes"]}
    seconds, bound = grouped.roofline(total, peaks)
    assert bound == "compute"
    assert seconds == pytest.approx(9 * one / peaks["bf16_flops_per_s"])


def test_grouped_cost_of_the_tiny_program():
    cfg = LOOKUP.json("configs", "olmoe-tiny")
    program = LOOKUP.module("models", "olmoe").build(cfg, 2, train=True)[
        "main"]
    grouped = LOOKUP.module("flops", "grouped")
    rows = 2 * cfg["sequence_length"] * cfg["num_experts_per_tok"]
    one = 2 * rows * cfg["hidden_size"] * cfg["intermediate_size"]
    cost = grouped.program_cost(program)
    assert cost["layers"] == cfg["num_hidden_layers"]
    assert cost["rows"] == cfg["num_hidden_layers"] * rows
    assert cost["products"] == 9 * cfg["num_hidden_layers"]
    assert cost["flops"] == 9 * cfg["num_hidden_layers"] * one
    # a program without an expert layer costs nothing here, and the
    # dense products' counter holds none of the experts' FLOPs
    gpt2 = LOOKUP.module("models", "gpt2").build(
        LOOKUP.json("configs", "gpt2-tiny"), 2, train=True)["main"]
    assert grouped.program_cost(gpt2)["flops"] == 0
    flops = LOOKUP.module("flops", "program").program_flops(program)
    tokens, d = 2 * cfg["sequence_length"], cfg["hidden_size"]
    dense = cfg["num_hidden_layers"] * 4 * d * d + d * cfg["vocab_size"]
    attention = flops["kernels"]["flash_attention_fwd"]["flops"]
    assert flops["mxu"] == 3 * 2 * tokens * dense + 2 * attention


# -- the readers on a written trace ------------------------------------------

def _kernel(name, i):
    return ('%%%s.%d = f32[8]{0} custom-call(f32[8]{0} %%p), '
            'custom_call_target=\\"tpu_custom_call\\"' % (name, i))


FWD = "jit(segment_fn)/moe_experts/"
BWD = "jit(segment_fn)/moe_experts_grad/"
UP, DOWN, DX, DW = ("moe_gmm_fwd_m256_n1024_k2048", "moe_gmm_fwd_m256_n2048_"
                    "k1024", "moe_gmm_dx_m256_n1024_k2048",
                    "moe_gmm_dw_m256_n1024_k2048")
# Device time in microseconds, one traced "step":
#   fusion 1     0 ..  3  moe_router (product, softmax, top-k)
#   fusion 2     3 ..  5  moe_experts/moe_route   (sort)
#   fusion 3     5 ..  9  moe_experts/moe_route   (gather)
#   kernel 4,5   9 .. 29  moe_experts/moe_experts gate, up: 10 us each
#   fusion 6    29 .. 31  moe_experts/moe_experts (silu * up)
#   kernel 7    31 .. 43  moe_experts/moe_experts down, 12 us
#   fusion 8    43 .. 48  moe_experts/moe_combine
#   fusion 9    48 .. 58  mul (the head: not the layer's)
#   fusion 10   58 .. 62  moe_experts_grad/moe_combine
#   kernel 11   62 .. 76  moe_experts_grad/moe_experts dx, 14 us
#   kernel 12   76 .. 96  moe_experts_grad/moe_experts dw, 20 us
#   fusion 13   96 .. 99  moe_experts_grad/moe_route
#   fusion 14   99 ..100  moe_router_grad
#   fusion 15  100 ..101  moe_experts_grad, under none of its scopes
OPS = [
    (1, 0, 3, _fusion(1, "kOutput"), "jit(segment_fn)/moe_router/dot_general:"),
    (2, 3, 2, _fusion(2), FWD + "moe_route/sort:"),
    (3, 5, 4, _fusion(3), FWD + "moe_route/gather:"),
    (4, 9, 10, _kernel(UP, 4), FWD + "moe_experts/%s:" % UP),
    (5, 19, 10, _kernel(UP, 5), FWD + "moe_experts/%s:" % UP),
    (6, 29, 2, _fusion(6), FWD + "moe_experts/mul:"),
    (7, 31, 12, _kernel(DOWN, 7), FWD + "moe_experts/%s:" % DOWN),
    (8, 43, 5, _fusion(8), FWD + "moe_combine/reduce_sum:"),
    (9, 48, 10, _fusion(9, "kOutput"), "jit(segment_fn)/mul/dot_general:"),
    (10, 58, 4, _fusion(10), BWD + "moe_combine/gather:"),
    (11, 62, 14, _kernel(DX, 11), BWD + "moe_experts/%s:" % DX),
    (12, 76, 20, _kernel(DW, 12), BWD + "moe_experts/%s:" % DW),
    (13, 96, 3, _fusion(13), BWD + "moe_route/reduce_sum:"),
    (14, 99, 1, _fusion(14),
     "jit(segment_fn)/moe_router_grad/transpose(jvp())/dot_general:"),
    (15, 100, 1, _fusion(15), BWD + "convert_element_type:"),
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
    events { metadata_id: 1 offset_ps: 0 duration_ps: 110000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "bench/window" } }
}
""" % ("\n    ".join(_event(i, s, n) for i, s, n, _, _ in OPS),
       "\n  ".join(_metadata(i, text, path) for i, _, _, text, path in OPS))


class MoeRun(Run):
    def __init__(self, trace_dir, peaks, steps=1):
        Run.__init__(self, trace_dir, peaks, steps)
        self.config = LOOKUP.json("configs", "olmoe-tiny")
        self.workload = dict(LOOKUP.json("workloads", "olmoe-tiny-train"),
                             name="olmoe-tiny-train")


def _trace_dir(tmp_path, text):
    from jax.profiler import ProfileData

    (tmp_path / "written.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace(text))
    return str(tmp_path)


def test_the_new_readers_on_a_written_trace(tmp_path, capsys):
    peaks = LOOKUP.json("", "peaks")["devices"]["TPU v5 lite"]
    run = MoeRun(_trace_dir(tmp_path, WRITTEN), peaks)
    ms = 1e-3
    # everything but the head's product
    assert _read("moe_ms_per_step", run) == pytest.approx((101 - 10) * ms)
    printed = capsys.readouterr().out
    assert "moe_router 0.003 ms and 1.0 operations" in printed
    assert "moe_experts_grad 0.042 ms and 5.0 operations" in printed
    assert "moe_experts/moe_experts 0.034 ms" in printed
    assert "moe_experts_grad/(no scope) 0.001 ms" in printed
    # route and combine, forward and backward
    assert _read("moe_route_ms_per_step", run) == \
        pytest.approx((2 + 4 + 5 + 4 + 3) * ms)
    assert "moe_experts_grad/moe_combine 0.004 ms" in capsys.readouterr().out
    # the five kernel calls, 66 us, against the tiny program's 18 products:
    # at toy widths the experts' matrices outweigh the rows and the bytes
    # bound (at the cell's the FLOPs do: test_grouped_flops_by_hand)
    cfg = run.config
    rows = 2 * cfg["sequence_length"] * cfg["num_experts_per_tok"]
    d, f, e = (cfg[k] for k in ("hidden_size", "intermediate_size",
                                "num_experts"))
    flops = 18 * 2 * rows * d * f
    moved = 2 * (6 * (rows * (d + f) * 2 + e * d * f * 2)
                 + 3 * (rows * (d + f) * 2 + e * d * f * 4))
    least = max(flops / peaks["bf16_flops_per_s"],
                moved / peaks["hbm_bytes_per_s"])
    assert least == moved / peaks["hbm_bytes_per_s"]
    assert _read("moe_expert_roofline", run) == pytest.approx(
        100 * least / (66 * US))
    printed = capsys.readouterr().out
    assert "%s 2.0 calls and 0.020 ms a step" % UP in printed
    assert "18 products of the program's 2 expert layer(s)" in printed
    assert "(memory-bound)" in printed
    # two steps in the same window: half of everything a step, the same
    # share of the roofline
    two = MoeRun(run.trace_dir, peaks, steps=2)
    assert _read("moe_ms_per_step", two) == pytest.approx(45.5 * ms)
    assert _read("moe_expert_roofline", two) == pytest.approx(
        2 * 100 * least / (66 * US))


def test_the_new_readers_return_nothing_where_there_is_nothing(tmp_path):
    peaks = LOOKUP.json("", "peaks")["devices"]["TPU v5 lite"]
    written = _trace_dir(tmp_path, WRITTEN)
    # a CPU rehearsal (no peaks) reads no roofline; an untraced run and a
    # run without steps read nothing
    assert _read("moe_expert_roofline", MoeRun(written, None)) is None
    for run in (MoeRun(None, peaks), MoeRun(written, peaks, steps=0)):
        for name in NEW_READERS:
            assert _read(name, run) is None, name


def test_a_program_without_an_expert_layer_gives_no_value(tmp_path):
    """The parent commit's programs, and the other four cells': no
    `moe_*` scope and no `moe_gmm` kernel."""
    text = WRITTEN.replace("moe_gmm", "other_kernel")
    for op in ("moe_experts_grad", "moe_router_grad", "moe_experts",
               "moe_router"):
        text = text.replace("jit(segment_fn)/%s/" % op,
                            "jit(segment_fn)/mul_grad/")
    peaks = LOOKUP.json("", "peaks")["devices"]["TPU v5 lite"]
    run = MoeRun(_trace_dir(tmp_path, text), peaks)
    for name in NEW_READERS:
        assert _read(name, run) is None, name


# -- the readers on a recording from the chip ---------------------------------

# `data/olmoe-train-4k-experts.xplane.pb` is a recording from the chip (TPU
# v5 lite, olmoe-train-4k, PR 29's first traced run, seed 2900000011), cut
# down to device 0's events under the `moe_*` op types of one step (75.759
# ms from one `moe_router` to the next; 90 events of 66 instructions) with
# each instruction's `tf_op` path, and one `bench/window` span over the
# step.  What it holds, in microseconds (summed from the events when the
# recording was cut, by the scope each path lies under):
#
#   moe_router                      12 events      46.130
#   moe_experts/moe_route            9 events     669.666
#   moe_experts/moe_experts         16 events    7458.860
#   moe_experts/moe_combine          4 events    1323.728
#   moe_experts_grad/moe_combine     4 events     491.230
#   moe_experts_grad/moe_experts    36 events   10074.649
#   moe_experts_grad/moe_route       4 events    2002.261
#   moe_router_grad                  5 events     105.751
#                                                22172.275
#   of them the kernels:
#   moe_gmm_fwd_m256_n1024_k2048     2 calls     2275.035
#   moe_gmm_fwd_m256_n2048_k1024     1 call      1225.373
#   moe_gmm_dx_m256_n1024_k2048      2 calls     2483.227
#   moe_gmm_dx_m256_n2048_k1024      1 call      1166.752
#   moe_gmm_dw_m256_n1024_k2048      2 calls     3380.172
#   moe_gmm_dw_m256_n2048_k1024      1 call      1716.135
#                                                12246.694
RECORDED_LAYER_US = 22172.275
RECORDED_MOVING_US = 669.666 + 1323.728 + 491.230 + 2002.261
RECORDED_KERNEL_US = 12246.694


class RecordedRun(Run):
    def __init__(self, trace_dir, peaks):
        Run.__init__(self, trace_dir, peaks, 1)
        lookup = Lookup()
        self.lookup = lookup
        self.workload = dict(lookup.json("workloads", "olmoe-train-4k"),
                             name="olmoe-train-4k")
        self.config = lookup.json("configs", self.workload["config"])


def test_the_new_readers_on_a_recording_from_the_chip(tmp_path, capsys):
    import shutil

    shutil.copy(os.path.join(DATA, "olmoe-train-4k-experts.xplane.pb"),
                str(tmp_path))
    peaks = LOOKUP.json("", "peaks")["devices"]["TPU v5 lite"]
    run = RecordedRun(str(tmp_path), peaks)
    assert _read("moe_ms_per_step", run) == pytest.approx(
        RECORDED_LAYER_US * 1e-3, abs=1e-6)
    printed = capsys.readouterr().out
    assert "moe_experts_grad/moe_experts 10.075 ms" in printed
    assert "moe_experts 9.452 ms and 29.0 operations a step" in printed
    assert "(no scope)" not in printed
    assert _read("moe_route_ms_per_step", run) == pytest.approx(
        RECORDED_MOVING_US * 1e-3, abs=1e-6)
    # nine products of 2 * 32768 * 2048 * 1024 FLOPs at 197 TFLOP/s
    least = 9 * 2 * 32768 * 2048 * 1024 / peaks["bf16_flops_per_s"]
    assert _read("moe_expert_roofline", run) == pytest.approx(
        100 * least / (RECORDED_KERNEL_US * US), rel=1e-6)
    printed = capsys.readouterr().out
    assert "moe_gmm_dw_m256_n1024_k2048 2.0 calls and 3.380 ms" in printed
    assert "on 32768 routed rows require 1237.0 GFLOP" in printed
    assert "(compute-bound)" in printed
