"""The `flash_bwd_roofline` reader on a written trace whose backward is
kernels (one op's the one kernel, the other's the two that walk), beside
the readers that were there: the forward's
roofline still finds its kernel by the prefix of its name and
`flash_bwd_ms_per_step` still reads the `flash_attention_bwd` scope; on
a trace whose backward is a scan (the parent's program) the new reader
gives nothing."""

import pytest

from benchmark.tests import test_ouro_cell as written_trace
from benchmark.tests.test_ouro_cell import (LOOKUP, US, Run, _event,
                                            _fusion, _metadata, _read)

GRAD = "jit(segment_fn)/flash_attention_grad/transpose(flash_attention_grad)/"
BWD = GRAD + "jvp(flash_attention_bwd)/"


def _kernel(name, i):
    return ('%%%s.%d = f32[8]{0} custom-call(f32[8]{0} %%p), '
            'custom_call_target=\\"tpu_custom_call\\"' % (name, i))


# Device time in microseconds, one traced "step" of two attention ops:
#   kernel 1,2    0 .. 8   the forward, twice (4 us each)
#   kernel 3,4    8 .. 16  the forward again under the gradient
#   fusion 5     16 .. 18  lse and delta under the backward's scope
#   kernel 6     18 .. 30  the first op's dK/dV, 12 us
#   kernel 7     30 .. 42  the second op's one kernel, 12 us
#   kernel 8     42 .. 52  the first op's dQ, 10 us
#   kernel 9     52 .. 62  the second op's one kernel again (another
#                          step's, say), 10 us
#   fusion 10    62 .. 65  a product that reads a kernel's result
OPS = [(i, 4 * (i - 1), 4, _kernel("flash_attention_fwd_q512_k512_kvres", i),
        "jit(segment_fn)/flash_attention/flash_attention_fwd:")
       for i in (1, 2)]
OPS += [(i, 4 * (i - 1), 4,
         _kernel("flash_attention_fwd_q512_k512_kvres", i),
         "jit(segment_fn)/flash_attention_grad/transpose(jvp())/"
         "flash_attention_fwd:") for i in (3, 4)]
OPS += [(5, 16, 2, _fusion(5), BWD + "mul:")]
DKV, DQ, ONE = ("flash_attention_bwd_dkv_q1024_k512",
                "flash_attention_bwd_dq_q1024_k512",
                "flash_attention_bwd_q512_k512")
OPS += [(i, start, n, _kernel(name, i),
         BWD + "jit(_bwd_kernels)/%s:" % name)
        for i, start, n, name in ((6, 18, 12, DKV), (7, 30, 12, ONE),
                                  (8, 42, 10, DQ), (9, 52, 10, ONE))]
OPS += [(10, 62, 3,
         "%%fusion.10 = f32[8]{0} fusion(f32[8]{0} %%%s.9), kind=kOutput, "
         "calls=%%c10" % ONE, "jit(segment_fn)/mul_grad/dot_general:")]
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
# what flops/program.py holds for two forward kernels of 1e6 FLOP each
FACTS = {"kernels": {"flash_attention_fwd": {
    "flops": 2e6, "bytes": 1e3, "calls": 2}}}


def _run(tmp_path, text, steps=1):
    from jax.profiler import ProfileData

    (tmp_path / "written.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace(text))
    peaks = LOOKUP.json("", "peaks")["devices"]["TPU v5 lite"]
    run = Run(str(tmp_path), peaks, steps=steps)
    run.facts["flops"] = FACTS
    return run, peaks


def test_the_backward_kernels_are_read_by_the_prefix_of_their_name(
        tmp_path, capsys):
    run, peaks = _run(tmp_path, WRITTEN)
    # twice the forward's FLOPs at the peak, over the four kernels' 44 us;
    # neither the scope's fusion nor a kernel's reader is a kernel
    assert _read("flash_bwd_roofline", run) == pytest.approx(
        100 * (2 * 2e6 / peaks["bf16_flops_per_s"]) / (44 * US))
    printed = capsys.readouterr().out
    assert DKV + " 1.0 calls and 0.012 ms a step" in printed
    assert DQ + " 1.0 calls and 0.010 ms a step" in printed
    assert ONE + " 2.0 calls and 0.022 ms a step" in printed
    assert "the program's 2 attention ops requires" in printed


def test_two_steps_in_the_window_do_not_change_a_share(tmp_path):
    one = _read("flash_bwd_roofline", _run(tmp_path, WRITTEN)[0])
    two = _read("flash_bwd_roofline", _run(tmp_path, WRITTEN, steps=2)[0])
    assert two == pytest.approx(2 * one)


def test_the_readers_that_were_there_read_the_fragment_as_before(
        tmp_path, capsys):
    run, peaks = _run(tmp_path, WRITTEN)
    # the four forward calls and nothing of the backward's
    assert _read("flash_fwd_roofline", run) == pytest.approx(
        100 * (1e6 / peaks["bf16_flops_per_s"]) * 4 / (16 * US))
    assert "flash_attention_fwd: 4.0 calls" in capsys.readouterr().out
    # everything under the scope: the fusion and the four kernels
    assert _read("flash_bwd_ms_per_step", run) == pytest.approx(46e-3)
    assert ("flash_attention_fwd: 2.0 of a step's 4.0 calls lie under a "
            "*_grad scope") in capsys.readouterr().out
    # the op and its gradient, whole
    assert _read("attention_ms_per_step", run) == pytest.approx(62e-3)


def test_a_backward_that_is_a_scan_gives_no_value(tmp_path):
    """The parent's program: operations under the scope, none a kernel."""
    run, _ = _run(tmp_path, written_trace.WRITTEN)
    assert _read("flash_bwd_roofline", run) is None
    assert _read("flash_bwd_ms_per_step", run) == pytest.approx(7e-3)


def test_nothing_to_read_gives_nothing(tmp_path):
    run, peaks = _run(tmp_path, WRITTEN)
    run.facts.pop("flops")
    assert _read("flash_bwd_roofline", run) is None
    for run in (Run(str(tmp_path), None), Run(None, peaks),
                Run(str(tmp_path), peaks, steps=0)):
        run.facts["flops"] = FACTS
        assert _read("flash_bwd_roofline", run) is None


def test_the_metric_is_listed_for_the_two_transformer_cells():
    import json
    import os

    from benchmark.harness import CHECKOUT

    with open(os.path.join(CHECKOUT, "BENCHMARK.json")) as f:
        listed = {m["name"]: m for m in json.load(f)["per_layer"]}
    entry = listed["flash_bwd_roofline"]
    assert entry["workloads"] == ["gpt2m-train", "ouro-train-4k"]
    reader = LOOKUP.module("layer_metrics", "flash_bwd_roofline")
    assert (entry["unit"], entry["layer"], entry["moves"], entry["source"]) \
        == (reader.UNIT, reader.LAYER, reader.MOVES, reader.SOURCE)
