"""The cell `ouro-train-4k` rehearsed on the CPU at toy sizes (the
fixture's `ouro-tiny-train`, found by name through `--search-path`), the
bytes benchmark/flops/elementwise.py counts against counts made by hand,
and the four readers that came with the cell on a written trace.
"""

import json
import os

import pytest

from benchmark.harness import CHECKOUT, Lookup
from benchmark.reduce import xplane
from benchmark.tests import test_run

LOOKUP = Lookup([test_run.FIXTURE])
US = 1e-6
NEW_READERS = ("attention_ms_per_step", "norm_rope_ms_per_step",
               "norm_rope_roofline", "grad_accum_ms_per_step")


# -- run.py end to end -------------------------------------------------------

def test_untraced_rehearsal_trains_and_agrees_with_the_reference():
    proc = test_run.run_cell("ouro-tiny-train", 0)
    result = test_run.last_line(proc)
    assert set(result) == test_run.RESULT_KEYS
    assert result["correct"] is True and result["failed"] == 0
    assert set(result["metrics"]) == {"train_items_per_s", "setup_s"}
    assert "check ok  : loss" in proc.stdout
    assert "tokens/s per chip" in proc.stdout


def test_traced_rehearsal_prints_no_device_metric_under_the_new_names():
    result = test_run.last_line(test_run.run_cell("ouro-tiny-train", 1))
    assert result["correct"] is True
    metrics = result["metrics"]
    assert metrics["compiles_in_window"] == {"value": 0, "unit": "count"}
    assert not (test_run.DEVICE_METRICS | set(NEW_READERS)) & set(metrics)


def test_the_cells_files_state_what_the_issue_fixes():
    lookup = Lookup()
    workload = lookup.json("workloads", "ouro-train-4k")
    assert (workload["driver"], workload["batch"], workload["pool"],
            workload["loss_read_every"], workload["chips"]) == \
        ("train_executor", 1, 4, 10, 1)
    cfg = lookup.json("configs", workload["config"])
    assert cfg["sequence_length"] == 4096
    # the catalog's widths, unchanged; the one cut is the depth
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["head_dim"], cfg["num_key_value_heads"],
            cfg["intermediate_size"], cfg["vocab_size"],
            cfg["total_ut_steps"], cfg["tie_word_embeddings"]) == \
        (2048, 16, 128, 16, 5632, 49152, 4, False)
    assert cfg["reduced"] == ["num_hidden_layers"]
    assert cfg["num_hidden_layers"] in (2, 3, 4)
    assert {"norm_f", "exit_entropy_beta", "bias"} <= set(cfg["assumed"])
    with open(os.path.join(CHECKOUT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    listed = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW_READERS:
        assert "ouro-train-4k" in listed[name]["workloads"]
    assert listed["attention_ms_per_step"]["workloads"] == \
        ["gpt2m-train", "ouro-train-4k"]


# -- bytes from the IR -------------------------------------------------------

@pytest.fixture(scope="module")
def tiny_program():
    cfg = LOOKUP.json("configs", "ouro-tiny")
    built = LOOKUP.module("models", "ouro").build(cfg, 2, train=True)
    return cfg, built["main"]


def test_elementwise_bytes_by_hand(tiny_program):
    cfg, program = tiny_program
    elementwise = LOOKUP.module("flops", "elementwise")
    cost = elementwise.program_bytes(program, 2)
    # batch 2 x 32 tokens x 64 wide, two bytes each
    x = 2 * cfg["sequence_length"] * cfg["hidden_size"] * 2
    passes, layers = cfg["total_ut_steps"], cfg["num_hidden_layers"]
    norms = passes * (4 * layers + 1)       # a sandwich a block, norm_f
    ropes = passes * layers * 2             # q and k
    assert cost["ops"] == {
        "rms_norm": {"bytes": norms * 2 * x, "calls": norms},
        "rope": {"bytes": ropes * 2 * x, "calls": ropes},
        # a norm's gradient reads x and dy and writes dx; rope's turns dy
        "rms_norm_grad": {"bytes": norms * 3 * x, "calls": norms},
        "rope_grad": {"bytes": ropes * 2 * x, "calls": ropes},
    }
    assert cost["total"] == (5 * norms + 4 * ropes) * x
    # twice the item size, twice the bytes; a program with no such op, none
    assert elementwise.program_bytes(program, 4)["total"] == 2 * cost["total"]
    gpt2 = LOOKUP.module("models", "gpt2").build(
        LOOKUP.json("configs", "gpt2-tiny"), 2, train=True)["main"]
    assert elementwise.program_bytes(gpt2, 2) == {"total": 0, "ops": {}}


def test_instruction_bytes_by_hand():
    count = LOOKUP.module("flops", "elementwise").instruction_bytes
    tiled = "{2,1,0:T(8,128)(2,1)}"
    # a result and an operand of 4096 x 2048 bfloat16, a float32 scale
    assert count("%%fusion.2 = bf16[1,4096,2048]%s fusion(bf16[1,4096,2048]"
                 "%s %%p.1, f32[2048]{0:T(1024)} %%p.2), kind=kLoop, "
                 "calls=%%fused_computation.7" % (tiled, tiled)) == \
        2 * 4096 * 2048 * 2 + 2048 * 4
    # a tuple of results; the one the layout keeps on-chip moves nothing
    assert count("%fusion.1020 = (s32[128,1,1]{0,2,1:T(1,128)S(1)}, "
                 "s32[128]{0:T(128)}) fusion(s32[128,1]{0,1:T(1,128)} "
                 "%ro_ins__label__.1), kind=kLoop, calls=%fc.2089") == \
        128 * 4 + 128 * 4
    # a predicate is a byte, a scalar has no dimensions, a token no size
    assert count("%c = pred[16]{0} compare(f32[] %a, f32[16]{0} %b)") == \
        16 + 4 + 64
    assert count("%t = token[] after-all()") == 0


def test_program_flops_count_the_looped_products_without_an_edit(
        tiny_program):
    cfg, program = tiny_program
    flops = LOOKUP.module("flops", "program").program_flops(program)
    tokens = 2 * cfg["sequence_length"]
    d, f, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    passes, layers = cfg["total_ut_steps"], cfg["num_hidden_layers"]
    block = 4 * d * d + 3 * d * f           # q, k, v, o; gate, up, down
    forward = 2 * tokens * passes * (layers * block + d * v + d)
    # every product's gradient is two products, but the last pass's gate
    # has none (lambda_R is not in the loss)
    kernel = flops["kernels"]["flash_attention_fwd"]
    assert kernel["calls"] == passes * layers
    backward_attention = 2 * kernel["flops"]
    assert flops["mxu"] == 3 * forward - 2 * 2 * tokens * d \
        + backward_attention
    assert flops["total"] == flops["mxu"] + kernel["flops"]


# -- the readers on a written trace ------------------------------------------

# Device time in microseconds, one traced "step":
#   fusion.1   0 .. 10  mul               (a product, kOutput)
#   fusion.2  10 .. 14  rms_norm
#   fusion.3  14 .. 16  rope
#   kernel.4  16 .. 21  flash_attention   (the kernel)
#   kernel.5  21 .. 26  flash_attention_grad (the forward again)
#   fusion.6  26 .. 33  flash_attention_grad/flash_attention_bwd
#   fusion.7  33 .. 35  rope_grad
#   fusion.8  35 .. 41  rms_norm_grad
#   fusion.9  41 .. 44  sum
#   fusion.10 44 .. 45  adam
def _event(i, start, length):
    return ("events { metadata_id: %d offset_ps: %d duration_ps: %d }"
            % (i, start * 1000000, length * 1000000))


def _metadata(i, text, path):
    return ('event_metadata { key: %d value { id: %d name: "%s" stats { '
            'metadata_id: 9 str_value: "%s" } } }' % (i, i, text, path))


def _fusion(i, kind="kLoop"):
    return ("%%fusion.%d = f32[8]{0} fusion(f32[8]{0} %%p), kind=%s, "
            "calls=%%c%d" % (i, kind, i))


def _kernel(i):
    return ('%%flash_attention_fwd_q1024_k512_kvres.%d = f32[8]{0} '
            'custom-call(f32[8]{0} %%p), custom_call_target='
            '\\"tpu_custom_call\\"' % i)


OPS = [
    (1, 0, 10, _fusion(1, "kOutput"), "jit(segment_fn)/mul/dot_general:"),
    (2, 10, 4, _fusion(2), "jit(segment_fn)/rms_norm/mul:"),
    (3, 14, 2, _fusion(3), "jit(segment_fn)/rope/concatenate:"),
    (4, 16, 5, _kernel(4),
     "jit(segment_fn)/flash_attention/flash_attention_fwd:"),
    (5, 21, 5, _kernel(5), "jit(segment_fn)/flash_attention_grad/"
     "transpose(jvp())/flash_attention_fwd:"),
    (6, 26, 7, _fusion(6), "jit(segment_fn)/flash_attention_grad/transpose("
     "flash_attention_grad)/jvp(flash_attention_bwd)/while/body/mul:"),
    (7, 33, 2, _fusion(7), "jit(segment_fn)/rope_grad/transpose(jvp())/mul:"),
    (8, 35, 6, _fusion(8),
     "jit(segment_fn)/rms_norm_grad/transpose(jvp())/mul:"),
    (9, 41, 3, _fusion(9), "jit(segment_fn)/sum/add:"),
    (10, 44, 1, _fusion(10), "jit(segment_fn)/adam/sub:"),
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
    events { metadata_id: 1 offset_ps: 0 duration_ps: 50000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "bench/window" } }
}
""" % ("\n    ".join(_event(i, s, n) for i, s, n, _, _ in OPS),
       "\n  ".join(_metadata(i, text, path) for i, _, _, text, path in OPS))


class Run:
    """What a reader is given, as far as these readers look."""

    def __init__(self, trace_dir, peaks, steps=1):
        self.lookup = LOOKUP
        self.config = LOOKUP.json("configs", "ouro-tiny")
        self.workload = dict(LOOKUP.json("workloads", "ouro-tiny-train"),
                             name="ouro-tiny-train")
        self.trace_dir, self.peaks = trace_dir, peaks
        self.reduced = xplane.load(trace_dir) if trace_dir else None
        self.facts = {"traced_steps": steps} if steps else {}


@pytest.fixture(scope="module")
def written(tmp_path_factory):
    from jax.profiler import ProfileData

    trace_dir = tmp_path_factory.mktemp("ouro_trace")
    (trace_dir / "written.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace(WRITTEN))
    return str(trace_dir)


def _read(name, run):
    return run.lookup.module("layer_metrics", name).read(run)


def test_the_new_readers_on_a_written_trace(written, capsys):
    peaks = LOOKUP.json("", "peaks")["devices"]["TPU v5 lite"]
    run = Run(written, peaks)
    ms = 1e-3
    # the op, the forward run again under its gradient, the backward scan
    assert _read("attention_ms_per_step", run) == \
        pytest.approx((5 + 5 + 7) * ms)
    assert _read("norm_rope_ms_per_step", run) == \
        pytest.approx((4 + 2 + 2 + 6) * ms)
    assert _read("grad_accum_ms_per_step", run) == pytest.approx(3 * ms)
    printed = capsys.readouterr().out
    assert "flash_attention_grad 0.012 ms and 2.0 operations" in printed
    assert "rms_norm_grad 0.006 ms" in printed
    assert "sum: 1.0 operations and 0.003 ms a step" in printed
    # the four instructions under the norm and rope scopes: a result and
    # an operand of 8 float32 each, in 14 us
    assert _read("norm_rope_roofline", run) == pytest.approx(
        100 * (4 * 64 / peaks["hbm_bytes_per_s"]) / (14 * US))
    printed = capsys.readouterr().out
    assert "4.0 instructions a step moved" in printed
    assert "the program's 78 ops would move" in printed
    # two steps in the same window: half of everything a step
    assert _read("grad_accum_ms_per_step", Run(written, peaks, steps=2)) \
        == pytest.approx(1.5 * ms)
    # the existing readers find the kernel by the prefix of its name
    run.facts["flops"] = {"kernels": {"flash_attention_fwd": {
        "flops": 2e6, "bytes": 1e3, "calls": 1}}}
    assert _read("flash_fwd_roofline", run) == pytest.approx(
        100 * (2e6 / peaks["bf16_flops_per_s"]) * 2 / (10 * US))
    assert "flash_attention_fwd: 2.0 calls" in capsys.readouterr().out


def test_the_new_readers_return_nothing_where_there_is_nothing(written):
    peaks = LOOKUP.json("", "peaks")["devices"]["TPU v5 lite"]
    # a CPU rehearsal (no peaks), an untraced run, a run without steps
    for run in (Run(written, None), Run(None, peaks),
                Run(written, peaks, steps=0)):
        for name in NEW_READERS:
            assert _read(name, run) is None, name


def test_a_program_without_these_ops_gives_no_value(tmp_path):
    """The parent commit's program (and `gpt2m-train`'s) has attention
    but no `rms_norm`, `rope` or shared parameter's `sum`."""
    from jax.profiler import ProfileData

    text = WRITTEN
    for op in ("rms_norm_grad", "rope_grad", "rms_norm", "rope", "sum"):
        text = text.replace("jit(segment_fn)/%s/" % op,
                            "jit(segment_fn)/layer_norm/")
    (tmp_path / "written.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace(text))
    peaks = LOOKUP.json("", "peaks")["devices"]["TPU v5 lite"]
    run = Run(str(tmp_path), peaks)
    assert _read("attention_ms_per_step", run) == pytest.approx(17e-3)
    for name in NEW_READERS[1:]:
        assert _read(name, run) is None, name
