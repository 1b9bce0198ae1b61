"""The op's instance in a path, device time by instance, the FLOPs by
instance against `program_flops`, and the six readers that came with the
instance scope (PR 33): on a written trace, on two recordings from the
chip, and where there is nothing to read.
"""

import json
import os
import shutil

import pytest

from benchmark.flops import instances
from benchmark.flops import program as program_flops
from benchmark.harness import CHECKOUT, Lookup
from benchmark.reduce import op_instances, xplane
from benchmark.tests import test_run
from benchmark.tests.test_ouro_cell import _event, _fusion, _metadata

LOOKUP = Lookup([test_run.FIXTURE])
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
US = 1e-6
READERS = ("op_instance_named_share", "conv_fwd_roofline",
           "conv_bwd_roofline", "conv_top5_lost_ms_per_step",
           "matmul_roofline", "head_ms_per_step")
RESNET_CELLS = ["resnet50-train", "resnet50-train-dp4"]
LM_CELLS = ["gpt2m-train", "ouro-train-4k", "olmoe-train-4k",
            "granite-train-4k"]


# -- from a path to an instance ------------------------------------------------

@pytest.mark.parametrize("path,found", [
    ("jit(segment_fn)/conv2d/~conv2d_7.tmp_0/conv_general_dilated:",
     ("conv2d", "~conv2d_7.tmp_0")),
    # the gradient op has its forward's instance
    ("jit(segment_fn)/conv2d_grad/~conv2d_7.tmp_0/transpose(jvp())/"
     "conv_general_dilated:", ("conv2d_grad", "~conv2d_7.tmp_0")),
    # the trainers' step, an optimizer op named for its parameter
    ("jit(step)/momentum/~conv2d_43.w_0/sub:",
     ("momentum", "~conv2d_43.w_0")),
    # a nested jit right after the instance: nothing else need follow
    ("jit(segment_fn)/softmax_with_cross_entropy/~loss.tmp_0/"
     "jit(take_along_axis):", ("softmax_with_cross_entropy", "~loss.tmp_0")),
    # an op in a sub-block: the outer op and its instance
    ("jit(segment_fn)/while/~acc/while/body/scale/~t.tmp_0/mul:",
     ("while", "~acc")),
    # a scope opened under a transformation is wrapped in its name
    ("jit(step)/mul_grad/transpose(jvp(~fc_1.tmp_0))/dot_general",
     ("mul_grad", "~fc_1.tmp_0")),
    # a program from before the scope: the type, no instance
    ("jit(segment_fn)/conv2d/conv_general_dilated:", ("conv2d", None)),
    ("jit(segment_fn)/moe_experts/moe_route/sort:", ("moe_experts", None)),
    # under no op type
    ("jit(step)/mul", None),
    ("", None),
])
def test_type_and_instance(path, found):
    assert op_instances.type_and_instance(path, "~") == found


def test_the_sigil_and_the_rule_are_the_programs():
    from paddle_tpu.core.desc import OpDesc
    from paddle_tpu.fluid import executor

    assert op_instances.sigil() == executor.INSTANCE_SIGIL == "~"
    od = OpDesc("conv2d", {"Input": ["x"], "Filter": ["w"]},
                {"Output": ["conv2d_7.tmp_0"]})
    path = "jit(f)/%s/%s/conv_general_dilated:" % (
        od.type, executor.op_instance(od))
    assert op_instances.type_and_instance(path, op_instances.sigil()) == \
        ("conv2d", "~conv2d_7.tmp_0")


@pytest.mark.parametrize("text,shapes", [
    ("%convert_reduce_fusion.3 = (f32[256]{0:T(256)S(1)}, "
     "bf16[128,256,56,56]{1,0,3,2:T(8,128)(2,1)}) fusion(bf16[256,64,1,1]"
     "{0,3,2,1:T(2,128)(2,1)S(1)} %copy-done.154), kind=kOutput, "
     "calls=%fused_computation.11", [(256,), (128, 256, 56, 56)]),
    ("%fusion.7 = bf16[64,3,7,7]{0,1,3,2:T(4,128)(2,1)} fusion(bf16[8]{0} "
     "%p), kind=kOutput, calls=%c", [(64, 3, 7, 7)]),
    ("%copy.4 = f32[] copy(f32[] %p)", [()]),
    ("dot_general.1", []),
])
def test_written_shapes(text, shapes):
    assert op_instances.written_shapes(text) == shapes


@pytest.mark.parametrize("written,kind", [
    ("bf16[128,256,56,56]{1,0,3,2}", op_instances.INPUT),
    # XLA's own order of the filter's axes, and a 1 x 1 filter as a matrix
    ("f32[1,1,256,64]{3,2,1,0}", op_instances.WEIGHT),
    ("f32[256,64]{1,0}", op_instances.WEIGHT),
    ("(f32[64,256,1,1]{0,1,3,2}, bf16[128,256,56,56]{1,0,3,2})",
     op_instances.BOTH),
    ("bf16[64,256,1,1]{0,1,3,2:S(1)}", op_instances.WEIGHT),
    ("f32[64]{0}", op_instances.OTHER),
])
def test_gradient_kind_by_the_shape_written(written, kind):
    text = "%%fusion.1 = %s fusion(f32[8]{0} %%p), kind=kOutput, calls=%%c" \
        % written
    assert op_instances.gradient_kind(
        text, [64, 256, 1, 1], [128, 256, 56, 56]) == kind


# -- FLOPs by instance ---------------------------------------------------------

@pytest.mark.parametrize("config,batch,convs,products", [
    ("resnet50", 128, 53, 1), ("gpt2-medium", 8, 0, 97)])
def test_instance_flops_add_up_to_program_flops(config, batch, convs,
                                                products):
    lookup = Lookup()
    cfg = lookup.json("configs", config)
    program = lookup.module("models", cfg["builder"]).build(
        cfg, batch, train=True)["main"]
    found = instances.by_instance(program)
    kinds = [e["kind"] for e in found.values()]
    assert (kinds.count(instances.CONV), kinds.count(instances.MATMUL)) == \
        (convs, products)
    block = program.global_block()
    attention = sum(program_flops._attention(block, od, False)
                    for od in block.desc.ops
                    if od.type == "flash_attention_grad")
    assert (attention > 0) == (config == "gpt2-medium")
    # exactly: integers on both sides
    assert sum(e["forward"] + e["backward"] for e in found.values()) \
        == program_flops.program_flops(program)["mxu"] - attention
    # an op and its gradient are one entry; the stem has one gradient
    assert all(e["ops"] == 1 and e["gradients"] in (1, 2)
               for e in found.values())
    assert [e["gradients"] for e in found.values()].count(1) == \
        (1 if convs else 0)
    assert op_instances.shared(program) == {}


def test_ops_that_write_one_variable_share_an_instance_and_are_counted():
    import paddle_tpu.fluid as fluid

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[4, 4], dtype="float32",
                              append_batch_size=False)
        acc = fluid.layers.fill_constant(shape=[4, 4], dtype="float32",
                                         value=0.0)
        fluid.layers.sums(input=[acc, x], out=acc)
        fluid.layers.sums(input=[acc, x], out=acc)
    assert op_instances.shared(main) == {("sum", "~" + acc.name): 2}


# -- the readers on a written trace --------------------------------------------

class Run:
    """What a reader is given, as far as these readers look."""

    def __init__(self, cell, trace_dir, peaks, steps=1, lookup=LOOKUP):
        self.lookup = lookup
        self.workload = dict(lookup.json("workloads", cell), name=cell)
        self.config = lookup.json("configs", self.workload["config"])
        self.trace_dir, self.peaks = trace_dir, peaks
        self.reduced = xplane.load(trace_dir) if trace_dir else None
        self.facts = {"traced_steps": steps, "chips": 1} if steps else {}


def _read(name, run):
    return run.lookup.module("layer_metrics", name).read(run)


def _peaks():
    return LOOKUP.json("", "peaks")["devices"]["TPU v5 lite"]


def _conv_text(i, written, kind="kOutput"):
    return ("%%fusion.%d = %s fusion(bf16[8]{0} %%p), kind=%s, calls=%%c%d"
            % (i, written, kind, i))


CONV = "jit(step)/conv2d/"
CONV_G = "jit(step)/conv2d_grad/"
# The fixture's ResNet-50 at 64 x 64, batch 8 (`resnet50-tiny-train-dp4`).
# ~conv2d_0: filter 64x3x7x7, input 8x3x64x64, one gradient;
# ~conv2d_1: filter 256x64x1x1, input 8x64x16x16, two.
# Device time in microseconds, one traced "step":
#   fusion 1    0 ..  6  conv2d/~conv2d_0.tmp_0
#   fusion 2    6 ..  8  conv2d/~conv2d_1.tmp_0
#   fusion 3    8 ..  9  batch_norm/~batch_norm_0.tmp_2 (not a convolution)
#   fusion 4    9 .. 13  conv2d_grad/~conv2d_1.tmp_0, writes the input's shape
#   fusion 5   13 .. 16  conv2d_grad/~conv2d_1.tmp_0, writes the filter's
#   fusion 6   16 .. 17  conv2d_grad/~conv2d_1.tmp_0, writes neither: other
#   fusion 7   17 .. 27  conv2d_grad/~conv2d_0.tmp_0, the filter's
#   fusion 8   27 .. 28  momentum/~conv2d_0.w_0
#   fusion 9   28 .. 30  conv2d, no instance (an old cache entry's path)
CONV_OPS = [
    (1, 0, 6, _conv_text(1, "bf16[8,64,32,32]{3,2,1,0}"),
     CONV + "~conv2d_0.tmp_0/conv_general_dilated:"),
    (2, 6, 2, _conv_text(2, "bf16[8,256,16,16]{3,2,1,0}"),
     CONV + "~conv2d_1.tmp_0/conv_general_dilated:"),
    (3, 8, 1, _fusion(3), "jit(step)/batch_norm/~batch_norm_0.tmp_2/mul:"),
    (4, 9, 4, _conv_text(4, "bf16[8,64,16,16]{1,0,3,2}"),
     CONV_G + "~conv2d_1.tmp_0/transpose(jvp())/conv_general_dilated:"),
    (5, 13, 3, _conv_text(5, "(f32[64]{0}, f32[1,1,64,256]{3,2,1,0})"),
     CONV_G + "~conv2d_1.tmp_0/transpose(jvp())/conv_general_dilated:"),
    (6, 16, 1, _conv_text(6, "f32[256]{0:S(1)}", "kLoop"),
     CONV_G + "~conv2d_1.tmp_0/convert_element_type:"),
    (7, 17, 10, _conv_text(7, "f32[64,3,7,7]{0,1,3,2}"),
     CONV_G + "~conv2d_0.tmp_0/transpose(jvp())/conv_general_dilated:"),
    (8, 27, 1, _fusion(8), "jit(step)/momentum/~conv2d_0.w_0/sub:"),
    (9, 28, 2, _conv_text(9, "bf16[8,64,16,16]{3,2,1,0}"),
     CONV + "conv_general_dilated:"),
]
PRODUCT = "jit(segment_fn)/mul/"
PRODUCT_G = "jit(segment_fn)/mul_grad/"
# The fixture's GPT-2 (`gpt2-tiny-train`: 2 x 128 tokens, 64 wide, 97
# tokens): ~fc_0 is 256 x 64 x 192, ~fc_8 the head, 256 x 64 x 97.
#   fusion 1    0 ..  4  mul/~fc_0.tmp_0
#   fusion 2    4 ..  7  mul/~fc_8.tmp_0
#   fusion 3    7 .. 12  mul_grad/~fc_8.tmp_0
#   fusion 4   12 .. 20  mul_grad/~fc_0.tmp_0
#   fusion 5   20 .. 21  adam/~fc_0.w_0
PRODUCT_OPS = [
    (1, 0, 4, _fusion(1, "kOutput"), PRODUCT + "~fc_0.tmp_0/dot_general:"),
    (2, 4, 3, _fusion(2, "kOutput"), PRODUCT + "~fc_8.tmp_0/dot_general:"),
    (3, 7, 5, _fusion(3, "kOutput"),
     PRODUCT_G + "~fc_8.tmp_0/transpose(jvp())/dot_general:"),
    (4, 12, 8, _fusion(4, "kOutput"),
     PRODUCT_G + "~fc_0.tmp_0/transpose(jvp())/dot_general:"),
    (5, 20, 1, _fusion(5), "jit(segment_fn)/adam/~fc_0.w_0/sub:"),
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
    events { metadata_id: 1 offset_ps: 0 duration_ps: 40000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "bench/window" } }
}
"""


def _trace_dir(tmp_path, ops):
    from jax.profiler import ProfileData

    text = WRITTEN % (
        "\n    ".join(_event(i, s, n) for i, s, n, _, _ in ops),
        "\n  ".join(_metadata(i, text, path) for i, _, _, text, path in ops))
    tmp_path.mkdir(exist_ok=True)
    (tmp_path / "written.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace(text))
    return str(tmp_path)


def test_the_convolution_readers_on_a_written_trace(tmp_path, capsys):
    peaks = _peaks()
    run = Run("resnet50-tiny-train-dp4", _trace_dir(tmp_path, CONV_OPS),
              peaks)
    found = op_instances.seconds(run)
    assert found["conv2d", "~conv2d_0.tmp_0"][:4] == \
        [pytest.approx(6 * US), 1, pytest.approx(6 * US), 1]
    assert found["conv2d_grad", "~conv2d_1.tmp_0"][:4] == \
        [pytest.approx(8 * US), 3, pytest.approx(7 * US), 2]
    assert found["conv2d", None][0] == pytest.approx(2 * US)
    # 2 of the 30 us under an op type lie under no instance
    assert _read("op_instance_named_share", run) == \
        pytest.approx(100 * 28 / 30)
    printed = capsys.readouterr().out
    assert "4 instances of 3 op types" in printed
    assert "conv2d ~conv2d_0.tmp_0 0.006 / 0.010 / 0.000" in printed
    assert "momentum ~conv2d_0.w_0 0.000 / 0.000 / 0.001" in printed

    flops = instances.of_run(run)
    stem, second = (flops["conv2d", "~conv2d_%d.tmp_0" % i] for i in (0, 1))
    assert (stem["forward"], stem["backward"]) == (154140672, 154140672)
    assert (second["forward"], second["backward"]) == (67108864, 134217728)
    peak = peaks["bf16_flops_per_s"]
    assert _read("conv_fwd_roofline", run) == pytest.approx(
        100 * (stem["forward"] + second["forward"]) / peak / (8 * US))
    assert _read("conv_bwd_roofline", run) == pytest.approx(
        100 * (stem["backward"] + second["backward"]) / peak / (18 * US))
    capsys.readouterr()
    # two convolutions have time, so the worst five are these two
    lost = (16 + 10) * US - (2 * stem["forward"] + 3 * second["forward"]) \
        / peak
    assert _read("conv_top5_lost_ms_per_step", run) == \
        pytest.approx(lost * 1e3)
    printed = capsys.readouterr().out
    assert "convolutions: 2 with operations under them, 0.026 ms" in printed
    # forward / input / weight / other, the stem with no input gradient
    assert "~conv2d_0.tmp_0 64x3x7x7 -> 8x64x32x32 /2x2: 0.006 / 0.000 / " \
        "0.010 / 0.000" in printed
    assert "~conv2d_1.tmp_0 256x64x1x1 -> 8x256x16x16 /1x1: 0.002 / 0.004 " \
        "/ 0.003 / 0.001" in printed
    assert printed.index("~conv2d_0.tmp_0 64x3") < \
        printed.index("~conv2d_1.tmp_0 256x")        # the stem loses more
    # no product of this program has time, and it has no vocabulary
    assert _read("matmul_roofline", run) is None
    assert _read("head_ms_per_step", run) is None
    # two steps in the same window: half the time a step, twice the share
    two = Run("resnet50-tiny-train-dp4", run.trace_dir, peaks, steps=2)
    assert _read("conv_fwd_roofline", two) == pytest.approx(
        2 * 100 * (stem["forward"] + second["forward"]) / peak / (8 * US))


def test_the_product_readers_on_a_written_trace(tmp_path, capsys):
    peaks = _peaks()
    run = Run("gpt2-tiny-train", _trace_dir(tmp_path, PRODUCT_OPS), peaks)
    assert _read("op_instance_named_share", run) == pytest.approx(100.0)
    flops = instances.of_run(run)
    assert len(flops) == 9
    body, head = flops["mul", "~fc_0.tmp_0"], flops["mul", "~fc_8.tmp_0"]
    assert head["forward"] == 2 * 256 * 64 * 97
    # the two products with operations under them; the other seven are
    # left out on both sides
    need = sum(e["forward"] + e["backward"] for e in (body, head))
    assert _read("matmul_roofline", run) == pytest.approx(
        100 * need / peaks["bf16_flops_per_s"] / (20 * US))
    printed = capsys.readouterr().out
    assert "7 products of the program have no operation" in printed
    assert "matrix products: 2, 0.020 ms a step under them" in printed
    assert "256x64x192: 1, 0.004 / 0.008" in printed
    assert "256x64x97: 1, 0.003 / 0.005" in printed
    assert _read("head_ms_per_step", run) == pytest.approx(8e-3)
    printed = capsys.readouterr().out
    assert "~fc_8.tmp_0 256x64x97 forward 0.003 backward 0.005 ms" in printed
    assert body["forward"] == 2 * 256 * 64 * 192
    for name in ("conv_fwd_roofline", "conv_bwd_roofline",
                 "conv_top5_lost_ms_per_step"):
        assert _read(name, run) is None, name


def test_the_readers_return_nothing_where_there_is_nothing(tmp_path,
                                                           monkeypatch):
    from paddle_tpu.fluid import executor

    peaks = _peaks()
    conv = _trace_dir(tmp_path / "conv", CONV_OPS)
    product = _trace_dir(tmp_path / "product", PRODUCT_OPS)
    # a CPU rehearsal (no peaks), an untraced run, a run without steps
    for cell, written in (("resnet50-tiny-train-dp4", conv),
                          ("gpt2-tiny-train", product)):
        for run in (Run(cell, written, None), Run(cell, None, peaks),
                    Run(cell, written, peaks, steps=0)):
            for name in READERS:
                assert _read(name, run) is None, name
    # the parent commit's program: no `op_instance`, no sigil
    monkeypatch.delattr(executor, "op_instance")
    monkeypatch.delattr(executor, "INSTANCE_SIGIL")
    for cell, written in (("resnet50-tiny-train-dp4", conv),
                          ("gpt2-tiny-train", product)):
        run = Run(cell, written, peaks)
        assert instances.of_run(run) is None
        for name in READERS:
            assert _read(name, run) is None, name


def test_a_step_program_from_an_older_cache_entry_reads_zero(tmp_path,
                                                             capsys):
    """`op_name` is not in the compile cache's key: this program run on
    a step that a checkout from before the scope compiled has the old
    paths.  The share says so; the other readers have nothing."""
    ops = [(i, s, n, text, path.replace("/~conv2d_0.tmp_0", "")
            .replace("/~conv2d_1.tmp_0", "").replace("/~conv2d_0.w_0", "")
            .replace("/~batch_norm_0.tmp_2", ""))
           for i, s, n, text, path in CONV_OPS]
    run = Run("resnet50-tiny-train-dp4", _trace_dir(tmp_path, ops), _peaks())
    assert _read("op_instance_named_share", run) == 0.0
    assert "compile cache" in capsys.readouterr().out
    for name in READERS[1:]:
        assert _read(name, run) is None, name


# -- the readers on recordings from the chip -----------------------------------

# `data/resnet50-train-convs.xplane.pb` is a recording from the chip (TPU v5
# lite, resnet50-train, PR 33's traced run on seed 3300000101), cut down
# (benchmark/tests/cut_recording.py) to device 0's events under `conv2d`
# and `conv2d_grad` of one step for the stem, the ten convolutions of the
# first stage and the first block of the second (`~conv2d_0` .. `~conv2d_13`:
# 53 events of 53 instructions), each with its `tf_op` path, and one
# `bench/window` span over the step.  What it holds, in microseconds
# (summed from the events when the recording was cut):
#
#   instance     conv2d   conv2d_grad |  instance     conv2d   conv2d_grad
#   ~conv2d_0    603.896      928.973 |  ~conv2d_7    318.377     1175.606
#   ~conv2d_1    316.851     1158.540 |  ~conv2d_8    274.270     1572.931
#   ~conv2d_2     40.134      217.440 |  ~conv2d_9    223.892      579.877
#   ~conv2d_3    223.477      580.502 |  ~conv2d_10   317.311     1243.636
#   ~conv2d_4    372.097     1175.924 |  ~conv2d_11   154.448     1574.206
#   ~conv2d_5    276.180     1850.355 |  ~conv2d_12   136.396      600.564
#   ~conv2d_6    223.478      581.039 |  ~conv2d_13   153.076      320.921
RECORDED_CONV_US = {
    0: (603.896, 928.973), 1: (316.851, 1158.540), 2: (40.134, 217.440),
    3: (223.477, 580.502), 4: (372.097, 1175.924), 5: (276.180, 1850.355),
    6: (223.478, 581.039), 7: (318.377, 1175.606), 8: (274.270, 1572.931),
    9: (223.892, 579.877), 10: (317.311, 1243.636), 11: (154.448, 1574.206),
    12: (136.396, 600.564), 13: (153.076, 320.921)}
# `data/olmoe-train-4k-products.xplane.pb`: the same for olmoe-train-4k (seed
# 3300000104) under `mul` and `mul_grad`: 16 events of 16 instructions, the
# four attention projections and the head.
RECORDED_PRODUCT_US = {
    "~fc_0.tmp_0": (182.178, 380.089), "~fc_1.tmp_0": (193.604, 391.366),
    "~fc_2.tmp_0": (181.452, 380.072), "~fc_3.tmp_0": (198.022, 388.836),
    "~fc_4.tmp_0": (4647.160, 17286.584)}


def _recorded(tmp_path, name, cell):
    shutil.copy(os.path.join(DATA, name), str(tmp_path))
    return Run(cell, str(tmp_path), _peaks(), lookup=Lookup())


def test_the_convolution_readers_on_a_recording_from_the_chip(tmp_path,
                                                              capsys):
    run = _recorded(tmp_path, "resnet50-train-convs.xplane.pb",
                    "resnet50-train")
    assert _read("op_instance_named_share", run) == pytest.approx(100.0)
    rows = run.lookup.module(
        "layer_metrics", "conv_top5_lost_ms_per_step").table(run)
    assert len(rows) == 14          # of the program's 53
    by_name = {r["instance"]: r for r in rows}
    for i, (forward, backward) in RECORDED_CONV_US.items():
        row = by_name["~conv2d_%d.tmp_0" % i]
        assert row["forward"] == pytest.approx(forward * US, abs=1e-9)
        assert row["backward"] == pytest.approx(backward * US, abs=1e-9)
        # every operation of a gradient wrote the filter's shape or the
        # input's: no fusion holds both
        assert row["other"] == 0.0
        assert (row["input"] > 0) == (i != 0)       # the stem has none
    flops = instances.of_run(run)
    peak = run.peaks["bf16_flops_per_s"]
    fwd_need = sum(flops["conv2d", "~conv2d_%d.tmp_0" % i]["forward"]
                   for i in RECORDED_CONV_US)
    bwd_need = sum(flops["conv2d", "~conv2d_%d.tmp_0" % i]["backward"]
                   for i in RECORDED_CONV_US)
    fwd_us = sum(f for f, _ in RECORDED_CONV_US.values())
    bwd_us = sum(b for _, b in RECORDED_CONV_US.values())
    capsys.readouterr()
    assert _read("conv_fwd_roofline", run) == pytest.approx(
        100 * fwd_need / peak / (fwd_us * US), rel=1e-6)
    assert 0 < 100 * fwd_need / peak / (fwd_us * US) < 100
    assert "39 forward convolutions of the program have no operation" \
        in capsys.readouterr().out
    assert _read("conv_bwd_roofline", run) == pytest.approx(
        100 * bwd_need / peak / (bwd_us * US), rel=1e-6)
    capsys.readouterr()
    # the five furthest from their floor: four 1 x 1 layers of the first
    # stage, three of them at 56 x 56 x 256, and the stride-2 shortcut
    assert [r["instance"] for r in rows[:5]] == [
        "~conv2d_%d.tmp_0" % i for i in (5, 8, 10, 4, 11)]
    assert _read("conv_top5_lost_ms_per_step", run) == pytest.approx(
        sum(r["lost"] for r in rows[:5]) * 1e3)
    assert 7.5 < sum(r["lost"] for r in rows[:5]) * 1e3 < 7.7
    printed = capsys.readouterr().out
    assert "convolutions: 14 with operations under them" in printed
    assert "~conv2d_5.tmp_0 64x256x1x1 -> 128x64x56x56 /1x1: 0.276 / " \
        "1.509 / 0.342 / 0.000, 9.4%, 1.926, 1.882" in printed
    # its operations' bytes at the HBM peak are nearly its whole time
    assert 0.85 < by_name["~conv2d_5.tmp_0"]["at_hbm_peak"] / (
        (276.180 + 1850.355) * US) < 1.0
    assert _read("matmul_roofline", run) is None


def test_the_product_readers_on_a_recording_from_the_chip(tmp_path, capsys):
    run = _recorded(tmp_path, "olmoe-train-4k-products.xplane.pb",
                    "olmoe-train-4k")
    assert _read("op_instance_named_share", run) == pytest.approx(100.0)
    flops = instances.of_run(run)
    assert set(flops) == {("mul", name) for name in RECORDED_PRODUCT_US}
    need = sum(e["forward"] + e["backward"] for e in flops.values())
    took = sum(f + b for f, b in RECORDED_PRODUCT_US.values()) * US
    capsys.readouterr()
    assert _read("matmul_roofline", run) == pytest.approx(
        100 * need / run.peaks["bf16_flops_per_s"] / took, rel=1e-6)
    assert 61 < 100 * need / run.peaks["bf16_flops_per_s"] / took < 62
    printed = capsys.readouterr().out
    assert "matrix products: 5, 24.229 ms a step under them, 14.945 at " \
        "the bf16 peak" in printed
    assert "4096x2048x50304: 1, 4.647 / 17.287, 58.6%" in printed
    assert "4096x2048x2048: 4, 0.755 / 1.540, 91.2%" in printed
    assert _read("head_ms_per_step", run) == pytest.approx(
        (4647.160 + 17286.584) * 1e-3, abs=1e-6)
    assert "~fc_4.tmp_0 4096x2048x50304 forward 4.647 backward 17.287 ms " \
        "(58.6% of the roofline)" in capsys.readouterr().out
    assert _read("conv_top5_lost_ms_per_step", run) is None


# -- BENCHMARK.json --------------------------------------------------------------

def test_benchmark_json_lists_the_six_readers_as_the_issue_fixes():
    with open(os.path.join(CHECKOUT, "BENCHMARK.json")) as f:
        listed = {m["name"]: m for m in json.load(f)["per_layer"]}
    # appended in the issue's order (not "the last six": the next PR
    # appends too)
    first = list(listed).index(READERS[0])
    assert list(listed)[first:first + 6] == list(READERS)
    want = {"op_instance_named_share": ("%", "higher", "ops", None),
            "conv_fwd_roofline": ("%", "higher", "kernels", RESNET_CELLS),
            "conv_bwd_roofline": ("%", "higher", "kernels", RESNET_CELLS),
            "conv_top5_lost_ms_per_step": ("ms", "lower", "ops",
                                           RESNET_CELLS),
            "matmul_roofline": ("%", "higher", "kernels", LM_CELLS),
            "head_ms_per_step": ("ms", "lower", "ops", LM_CELLS)}
    for name, (unit, better, layer, cells) in want.items():
        entry = listed[name]
        assert (entry["unit"], entry["better"], entry["layer"],
                entry.get("workloads")) == (unit, better, layer, cells)
        assert (entry["source"], entry["moves"]) == \
            ("device_trace", "train_items_per_s")
