"""The `flash_fwd_regrad_calls_per_step` reader on the written trace of
test_flash_bwd_roofline.py, whose step holds four forward-kernel calls,
two of them under `flash_attention_grad`: it counts those two, 0.0 once
they lie under the forward op (a gradient that reads saved statistics),
and nothing where the trace holds no forward call or names no op."""

import json
import os

import pytest

from benchmark.harness import CHECKOUT
from benchmark.tests.test_flash_bwd_roofline import WRITTEN, _run
from benchmark.tests.test_ouro_cell import LOOKUP, Run, _read

NAME = "flash_fwd_regrad_calls_per_step"
REGRAD = ("jit(segment_fn)/flash_attention_grad/transpose(jvp())/"
          "flash_attention_fwd:")
FORWARD = "jit(segment_fn)/flash_attention/flash_attention_fwd:"


def test_the_forward_calls_under_a_gradient_are_counted_a_step(tmp_path):
    assert REGRAD in WRITTEN
    assert _read(NAME, _run(tmp_path, WRITTEN)[0]) == 2.0
    assert _read(NAME, _run(tmp_path, WRITTEN, steps=2)[0]) == 1.0


def test_forward_calls_and_none_under_a_gradient_read_zero(tmp_path):
    run, _ = _run(tmp_path, WRITTEN.replace(REGRAD, FORWARD))
    value = _read(NAME, run)
    assert value == 0.0 and value is not None
    # the readers beside it still find their four calls and their scope
    assert _read("flash_fwd_roofline", run) is not None
    assert _read("flash_bwd_ms_per_step", run) == pytest.approx(46e-3)


def test_nothing_to_read_gives_nothing(tmp_path):
    no_forward = WRITTEN.replace("flash_attention_fwd_q512_k512_kvres",
                                 "another_kernel")
    assert _read(NAME, _run(tmp_path, no_forward)[0]) is None
    # a program that names no op: every path goes
    unnamed = WRITTEN.replace("jit(segment_fn)/", "")
    assert _read(NAME, _run(tmp_path, unnamed)[0]) is None
    peaks = LOOKUP.json("", "peaks")["devices"]["TPU v5 lite"]
    for run in (Run(str(tmp_path), None), Run(None, peaks),
                Run(str(tmp_path), peaks, steps=0)):
        assert _read(NAME, run) is None


def test_the_metric_is_listed_for_the_two_transformer_cells():
    # by name, not "the last of the list": the next PR appends its own
    with open(os.path.join(CHECKOUT, "BENCHMARK.json")) as f:
        entry, = [m for m in json.load(f)["per_layer"] if m["name"] == NAME]
    reader = LOOKUP.module("layer_metrics", NAME)
    assert entry == {
        "name": NAME, "unit": reader.UNIT, "better": "lower",
        "source": reader.SOURCE, "layer": reader.LAYER,
        "moves": reader.MOVES,
        "workloads": ["gpt2m-train", "ouro-train-4k"]}
