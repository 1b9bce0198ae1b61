"""The cell `smallthinker-train-16k-ep8` rehearsed on the CPU at toy
sizes (the fixture's `smallthinker-tiny-train`, found by name through
`--search-path`), its files against what the issue fixes and the
catalog's config key for key, the FLOPs and bytes
benchmark/flops/window_flash.py counts against counts made by hand, and
the four readers that came with the cell on a written trace.
"""

import json
import math
import os

import pytest

from benchmark.harness import CHECKOUT, Lookup
from benchmark.tests import test_run
from benchmark.tests.test_ouro_cell import (LOOKUP, US, Run, _event,
                                            _fusion, _metadata, _read)

CELL = "smallthinker-train-16k-ep8"
NEW_READERS = ("window_flash_fwd_roofline", "window_flash_bwd_roofline",
               "window_attn_ms_per_step", "full_attn_ms_per_step")


# -- run.py end to end -------------------------------------------------------

def test_untraced_rehearsal_trains_and_agrees_with_the_reference():
    proc = test_run.run_cell("smallthinker-tiny-train", 0)
    result = test_run.last_line(proc)
    assert set(result) == test_run.RESULT_KEYS
    assert result["correct"] is True and result["failed"] == 0
    assert set(result["metrics"]) == {"train_items_per_s", "setup_s"}
    assert "check ok  : loss" in proc.stdout
    assert "tokens/s per chip" in proc.stdout


def test_traced_rehearsal_prints_no_device_metric_under_the_new_names():
    result = test_run.last_line(
        test_run.run_cell("smallthinker-tiny-train", 1))
    assert result["correct"] is True
    metrics = result["metrics"]
    assert metrics["compiles_in_window"] == {"value": 0, "unit": "count"}
    assert not (test_run.DEVICE_METRICS | set(NEW_READERS)) & set(metrics)


def test_the_cells_files_state_what_the_issue_fixes():
    lookup = Lookup()
    workload = lookup.json("workloads", CELL)
    assert (workload["driver"], workload["batch"], workload["pool"],
            workload["loss_read_every"], workload["chips"],
            workload["trace_seconds"]) == ("train_executor", 1, 4, 10, 1,
                                           8.0)
    cfg = lookup.json("configs", workload["config"])
    assert cfg["sequence_length"] == cfg["max_position_embeddings"] == 16384
    # the catalog's config, key for key; the cuts are `reduced`
    catalog = {
        "head_dim": 128, "hidden_size": 2560,
        "max_position_embeddings": 16384,
        "model_name": "smallthinker_21b_instruct",
        "moe_ffn_hidden_size": 768, "moe_num_active_primary_experts": 6,
        "moe_num_primary_experts": 64,
        "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
        "num_attention_heads": 28, "num_hidden_layers": 52,
        "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
        "rope_layout": [0, 1, 1, 1] * 13, "rope_scaling": None,
        "rope_theta": 1500000, "sliding_window_layout": [0, 1, 1, 1] * 13,
        "sliding_window_size": 4096, "tie_word_embeddings": False,
        "vocab_size": 151936}
    changed = {k for k, v in catalog.items() if cfg[k] != v}
    assert changed == set(cfg["reduced"]) == {
        "num_hidden_layers", "rope_layout", "sliding_window_layout",
        "moe_num_primary_experts", "vocab_size"}
    assert (cfg["num_hidden_layers"], cfg["moe_num_primary_experts"],
            cfg["vocab_size"]) == (4, 8, 18992)
    assert cfg["rope_layout"] == cfg["sliding_window_layout"] == [0, 1, 1, 1]
    assert cfg["vocab_size"] * 8 == catalog["vocab_size"]
    assert (cfg["scored_experts"], cfg["first_expert"]) == (64, 24)
    assert cfg["published"] == {
        "num_hidden_layers": 52, "rope_layout": "[0, 1, 1, 1] x 13",
        "sliding_window_layout": "[0, 1, 1, 1] x 13",
        "moe_num_primary_experts": 64, "vocab_size": 151936}
    assert {"router_reads", "hidden_act", "bias", "residual", "rope",
            "aux_loss", "optimizer", "router_dtype"} <= set(cfg["assumed"])
    assert {"stands_for", "arithmetic", "expert_load"} \
        <= set(cfg["reduced_why"])
    assert cfg["optimizer"] == {"type": "adam", "learning_rate": 3e-04,
                                "beta1": 0.9, "beta2": 0.95,
                                "epsilon": 1e-08}
    with open(os.path.join(CHECKOUT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    listed = {m["name"]: m for m in bench["per_layer"] + bench["end_to_end"]}
    for name in NEW_READERS:
        assert listed[name]["workloads"] == [CELL]
        assert listed[name]["moves"] == "train_items_per_s"
    for name in ("train_items_per_s", "setup_trace_lower_s",
                 "matmul_roofline", "head_ms_per_step", "moe_ms_per_step",
                 "moe_route_ms_per_step", "norm_rope_ms_per_step",
                 "norm_rope_roofline", "executor_idle_ms_per_step",
                 "executor_run_host_ms", "functional_step_ms"):
        assert listed[name]["workloads"][-1] == CELL
    # counted as causal, or by every scored expert's rows: not this cell's
    for name in ("flash_fwd_roofline", "flash_bwd_roofline",
                 "attention_ms_per_step", "moe_expert_roofline",
                 "mxu_roofline"):
        assert CELL not in listed[name]["workloads"]
    assert listed["mxu_roofline"]["workloads"] == [
        "resnet50-train", "resnet50-train-dp4", "gpt2m-train",
        "ouro-train-4k", "olmoe-train-4k", "granite-train-4k"]
    cell = [w for w in bench["workloads"] if w["name"] == CELL]
    assert cell == [{"name": CELL, "config": "smallthinker-21b-a3b",
                     "traffic": CELL, "chips": 1, "why": workload["why"]}]
    assert len(workload["why"]) <= 200
    config = [c for c in bench["configs"]
              if c["name"] == "smallthinker-21b-a3b"]
    assert len(config) == 1 and config[0]["reduced"] == cfg["reduced"]
    assert config[0]["source"] == cfg["source"]
    four = [w["name"] for w in bench["workloads"] if w["chips"] == 4]
    assert four == ["resnet50-train-dp4"]


def test_the_reference_copy_is_the_programs():
    """benchmark/reference/smallthinker.py is paddle_tpu/models/
    reference/smallthinker.py but for the docstring's last lines, which
    say whose copy it is."""
    def code(path):
        with open(os.path.join(CHECKOUT, path)) as f:
            text = f.read()
        return text[text.index('"""', 3):]

    assert code("benchmark/reference/smallthinker.py") == \
        code("paddle_tpu/models/reference/smallthinker.py")


def test_the_builder_builds_the_share_at_the_published_widths():
    """The cell's program, built (not run) from the configuration's
    file: one full layer and three with a window of 4096, 28 query heads
    over 4 key/value heads of 128, 8 held experts of 64 scored in three
    stacked parameters, 6 a token, the reference's parameter layout."""
    lookup = Lookup()
    cfg = lookup.json("configs", "smallthinker-21b-a3b")
    built = lookup.module("models", "smallthinker").build(cfg, 1, train=True)
    block = built["main"].global_block()
    names = built["param_names"]["blocks"][0]
    shapes = {w: tuple(block.var(names[w]).shape)
              for w in ("wq", "wk", "wo", "router", "w_gate", "w_down")}
    assert shapes == {"wq": (2560, 3584), "wk": (2560, 512),
                      "wo": (3584, 2560), "router": (2560, 64),
                      "w_gate": (8, 2560, 768), "w_down": (8, 768, 2560)}
    flash = [op for op in block.desc.ops if op.type == "flash_attention"]
    assert [op.attrs.get("window", 0) for op in flash] == [0, 4096, 4096,
                                                           4096]
    experts = [op for op in block.desc.ops if op.type == "moe_experts"]
    assert len(experts) == 4
    assert all(op.attrs == {"first_expert": 24, "scored": 64,
                            "activation": "relu"} for op in experts)
    assert tuple(block.var(experts[0].output("Xs")[0]).shape) == \
        (6 * 16384, 2560)
    assert built["items_per_step"] == 16384
    params = sum(math.prod(p.shape) for p in block.all_parameters())
    assert params == 370_547_200        # 370.5M: 5.93 GB at 16 B each


def test_the_model_is_the_seeds_draw_and_the_embedding_the_files_law():
    """As every training cell's: the start-up program draws from its
    `random_seed` (benchmark/training.py sets it to `--seed`), so two
    seeds are two models and one seed one; the embedding alone is drawn
    N(0, `embedding_std`) and not by the stack's default."""
    import jax
    import numpy as np
    import paddle_tpu.fluid as fluid

    cfg = LOOKUP.json("configs", "smallthinker-tiny")
    model = LOOKUP.module("models", "smallthinker")

    def weights(cfg, seed):
        built = model.build(cfg, 1, train=True)
        assert all(od.attrs.get("seed", 0) == 0 for od in
                   built["startup"].global_block().desc.ops)
        built["startup"].random_seed = seed
        scope = fluid.Scope()
        fluid.Executor(fluid.CPUPlace()).run(built["startup"], scope=scope)
        return ({n: np.asarray(scope.get(n)) for n in
                 jax.tree_util.tree_leaves(built["param_names"])},
                built["param_names"])

    (one, names), (again, _), (other, _) = (weights(cfg, 5),
                                            weights(cfg, 5),
                                            weights(cfg, 6))
    assert all((one[n] == again[n]).all() for n in one)
    drawn = [n for n in one if one[n].std() > 0]
    assert len(drawn) > 20
    assert all((one[n] != other[n]).any() for n in drawn)
    # no two parameters of one shape are the same draw
    routers = [one[b["router"]] for b in names["blocks"]]
    assert len(routers) == 4 and (routers[0] != routers[1]).any()
    embedding = one[names["embed"]]
    assert embedding.std() == pytest.approx(cfg["embedding_std"], rel=0.05)
    plain, _ = weights(dict(cfg, embedding_std=None), 5)
    assert plain[names["embed"]].std() < 0.2
    for c in (cfg, Lookup().json("configs", "smallthinker-21b-a3b")):
        assert "weights_seed" not in c and c["embedding_std"] == 1.0


# -- FLOPs and bytes from shapes ---------------------------------------------

def test_window_flash_flops_by_hand():
    wf = LOOKUP.module("flops", "window_flash")
    assert wf.attended_pairs(16384, 4096) == 58_722_304
    assert wf.attended_pairs(16384, 0) == 134_225_920
    assert wf.attended_pairs(4096, 4096) == wf.attended_pairs(4096, 0)
    # by the mask itself, at a small size
    seen = sum(1 for i in range(300) for j in range(300)
               if i - 77 < j <= i)
    assert wf.attended_pairs(300, 77) == seen
    forward = wf.forward_cost(1, 28, 16384, 128, 4096)
    assert forward["flops"] == 4 * 28 * 58_722_304 * 128
    rows = 28 * 16384
    assert forward["bytes"] == 4 * rows * 128 * 2 + 2 * rows * 4
    backward = wf.backward_cost(1, 28, 16384, 128, 4096)
    assert backward["flops"] == 2 * forward["flops"]
    assert backward["bytes"] == 7 * rows * 128 * 2 + 2 * rows * 4
    peaks = LOOKUP.json("", "peaks")["devices"]["TPU v5 lite"]
    seconds, bound = wf.roofline(forward, peaks)
    assert bound == "compute"
    assert seconds == pytest.approx(
        forward["flops"] / peaks["bf16_flops_per_s"])
    assert wf.FWD_NAME.match("flash_attention_fwd_q1024_k1024_s256_w4096_h1")
    assert not wf.FWD_NAME.match("flash_attention_fwd_q1024_k1024_s256_h1")
    assert wf.BWD_NAME.match("flash_attention_bwd_dkv_q1024_k512_w4096_h1")
    assert not wf.BWD_NAME.match("flash_attention_bwd_dq_q1024_k512_h1")
    assert not wf.BWD_NAME.match("flash_attention_fwd_q128_k128_w64")


def test_window_flash_cost_of_the_tiny_program():
    cfg = LOOKUP.json("configs", "smallthinker-tiny")
    program = LOOKUP.module("models", "smallthinker").build(
        cfg, 1, train=True)["main"]
    wf = LOOKUP.module("flops", "window_flash")
    whole = wf.program_cost(program)
    cost = whole["window"]
    seq, heads, d = (cfg[k] for k in ("sequence_length",
                                      "num_attention_heads", "head_dim"))
    pairs = wf.attended_pairs(seq, cfg["sliding_window_size"])
    assert cost["forward"] == {
        "flops": 3 * 4 * heads * pairs * d, "calls": 3,
        "bytes": 3 * (4 * heads * seq * d * 2 + 2 * heads * seq * 4)}
    assert cost["backward"]["flops"] == 2 * cost["forward"]["flops"]
    assert cost["backward"]["calls"] == 3
    assert whole["full"]["forward"]["calls"] == 1
    assert whole["full"]["forward"]["flops"] \
        == 4 * heads * (seq * (seq + 1) // 2) * d
    # a program without a window costs nothing here
    gpt2 = LOOKUP.module("models", "gpt2").build(
        LOOKUP.json("configs", "gpt2-tiny"), 2, train=True)["main"]
    assert wf.program_cost(gpt2)["window"]["forward"]["calls"] == 0
    assert wf.program_cost(gpt2)["full"]["forward"]["calls"] == 2


# -- the readers on a written trace ------------------------------------------

def _kernel(name, i):
    return ('%%%s.%d = f32[8]{0} custom-call(f32[8]{0} %%p), '
            'custom_call_target=\\"tpu_custom_call\\"' % (name, i))


FWD = "jit(segment_fn)/flash_attention/"
BWD = "jit(segment_fn)/flash_attention_grad/flash_attention_bwd/"
FULL_F, WIN_F = ("flash_attention_fwd_q128_k128_kvres_s128_h7",
                 "flash_attention_fwd_q128_k128_kvres_s128_w48_h7")
FULL_B, WIN_DKV, WIN_DQ = ("flash_attention_bwd_q128_k128_s128_h7",
                           "flash_attention_bwd_dkv_q128_k128_w48_h7",
                           "flash_attention_bwd_dq_q128_k128_w48_h7")
# Device time in microseconds, one traced "step":
#   kernel 1     0 .. 10  flash_attention/attn_full, the full layer
#   kernel 2-4  10 .. 22  flash_attention/attn_window, 4 us each
#   fusion 5    22 .. 30  mul (a projection: nobody's)
#   fusion 6    30 .. 32  flash_attention_grad/.../attn_window row sums
#   kernel 7    32 .. 40  .../attn_window dkv, 8 us
#   kernel 8    40 .. 46  .../attn_window dq, 6 us
#   kernel 9    46 .. 66  .../attn_full, the one backward kernel, 20 us
OPS = [
    (1, 0, 10, _kernel(FULL_F, 1), FWD + "attn_full/%s:" % FULL_F),
    (2, 10, 4, _kernel(WIN_F, 2), FWD + "attn_window/%s:" % WIN_F),
    (3, 14, 4, _kernel(WIN_F, 3), FWD + "attn_window/%s:" % WIN_F),
    (4, 18, 4, _kernel(WIN_F, 4), FWD + "attn_window/%s:" % WIN_F),
    (5, 22, 8, _fusion(5, "kOutput"), "jit(segment_fn)/mul/dot_general:"),
    (6, 30, 2, _fusion(6), BWD + "attn_window/reduce_sum:"),
    (7, 32, 8, _kernel(WIN_DKV, 7), BWD + "attn_window/%s:" % WIN_DKV),
    (8, 40, 6, _kernel(WIN_DQ, 8), BWD + "attn_window/%s:" % WIN_DQ),
    (9, 46, 20, _kernel(FULL_B, 9), BWD + "attn_full/%s:" % FULL_B),
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


class WindowRun(Run):
    def __init__(self, trace_dir, peaks, steps=1):
        Run.__init__(self, trace_dir, peaks, steps)
        self.config = LOOKUP.json("configs", "smallthinker-tiny")
        self.workload = dict(
            LOOKUP.json("workloads", "smallthinker-tiny-train"),
            name="smallthinker-tiny-train")


def _trace_dir(tmp_path, text):
    from jax.profiler import ProfileData

    (tmp_path / "written.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace(text))
    return str(tmp_path)


def test_the_new_readers_on_a_written_trace(tmp_path, capsys):
    peaks = LOOKUP.json("", "peaks")["devices"]["TPU v5 lite"]
    run = WindowRun(_trace_dir(tmp_path, WRITTEN), peaks)
    ms = 1e-3
    assert _read("window_attn_ms_per_step", run) == pytest.approx(
        (12 + 2 + 8 + 6) * ms)
    printed = capsys.readouterr().out
    assert "attn_window: backward 0.016 ms a step, forward 0.012 ms" \
        in printed
    assert _read("full_attn_ms_per_step", run) == pytest.approx(30 * ms)
    assert "attn_full: backward 0.020 ms a step, forward 0.010 ms" \
        in capsys.readouterr().out
    wf = LOOKUP.module("flops", "window_flash")
    program = LOOKUP.module("models", "smallthinker").build(
        run.config, 1, train=True)["main"]
    cost = wf.program_cost(program)["window"]
    # the three window calls, 12 us, against the tiny program's: at toy
    # sizes the bytes bound
    least = max(cost["forward"]["flops"] / peaks["bf16_flops_per_s"],
                cost["forward"]["bytes"] / peaks["hbm_bytes_per_s"])
    assert _read("window_flash_fwd_roofline", run) == pytest.approx(
        100 * least / (12 * US))
    printed = capsys.readouterr().out
    assert "%s 3.0 calls and 0.012 ms a step" % WIN_F in printed
    assert FULL_F + " " not in printed
    least = max(cost["backward"]["flops"] / peaks["bf16_flops_per_s"],
                cost["backward"]["bytes"] / peaks["hbm_bytes_per_s"])
    assert _read("window_flash_bwd_roofline", run) == pytest.approx(
        100 * least / (14 * US))
    printed = capsys.readouterr().out
    assert "%s 1.0 calls and 0.008 ms a step" % WIN_DKV in printed
    assert "%s 1.0 calls and 0.006 ms a step" % WIN_DQ in printed
    # two steps in the same window: half the time a step, the same share
    two = WindowRun(run.trace_dir, peaks, steps=2)
    assert _read("window_attn_ms_per_step", two) == pytest.approx(14 * ms)
    assert _read("window_flash_bwd_roofline", two) == pytest.approx(
        2 * 100 * least / (14 * US))


def test_the_new_readers_return_nothing_where_there_is_nothing(tmp_path):
    peaks = LOOKUP.json("", "peaks")["devices"]["TPU v5 lite"]
    written = _trace_dir(tmp_path, WRITTEN)
    # a CPU rehearsal (no peaks) reads no roofline; an untraced run and a
    # run without steps read nothing
    for name in NEW_READERS[:2]:
        assert _read(name, WindowRun(written, None)) is None
    for run in (WindowRun(None, peaks), WindowRun(written, peaks, steps=0)):
        for name in NEW_READERS:
            assert _read(name, run) is None, name


def test_a_program_without_a_window_gives_no_value(tmp_path):
    """The parent commit's programs (no `attn_*` scope, no `_w` in a
    kernel's name) and this commit's other cells (every attention under
    `attn_full`): none of the four reads anything."""
    peaks = LOOKUP.json("", "peaks")["devices"]["TPU v5 lite"]
    parent = WRITTEN.replace("attn_full/", "").replace("attn_window/", "") \
        .replace("_w48", "")
    others = WRITTEN.replace("attn_window", "attn_full").replace("_w48", "")
    for i, text in enumerate((parent, others)):
        where = tmp_path / str(i)
        where.mkdir()
        run = WindowRun(_trace_dir(where, text), peaks)
        for name in NEW_READERS:
            assert _read(name, run) is None, (i, name)
