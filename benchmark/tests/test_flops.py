"""The functions that count a kernel's and a program's operations,
against counts made by hand."""

import json
import os

from benchmark.harness import Lookup

LOOKUP = Lookup()
flash = LOOKUP.module("flops", "flash")


def test_attended_pairs():
    assert flash.attended_pairs(4, 4, causal=False) == 16
    assert flash.attended_pairs(4, 4, causal=True) == 10      # 1+2+3+4
    # two queries at the end of six keys see 5 and 6 of them
    assert flash.attended_pairs(2, 6, causal=True) == 11


def test_flash_forward_cost_by_hand():
    # batch 2, 3 heads, 4 queries and keys, head size 8, causal, bf16:
    # 10 attended pairs per head, two products of 2*8 FLOPs per pair
    cost = flash.forward_cost(2, 3, 4, 4, 8, causal=True)
    assert cost["flops"] == 2 * 3 * 10 * (2 * 8 + 2 * 8) == 1920
    # q, k, v, o: 4 arrays of 2*3*4 rows of 8 two-byte values; m and l:
    # 2 arrays of 2*3*4 float32
    assert cost["bytes"] == 4 * 24 * 8 * 2 + 2 * 24 * 4 == 1728
    assert flash.backward_flops(2, 3, 4, 4, 8, causal=True) == 2 * 1920


def test_flash_cost_of_the_gpt2_medium_cell():
    # b8, 16 heads, 1024 tokens, head size 64, causal: 524800 pairs
    cost = flash.forward_cost(8, 16, 1024, 1024, 64, causal=True)
    assert cost["flops"] == 4 * 8 * 16 * 524800 * 64 == 17196646400
    assert cost["bytes"] == 4 * 131072 * 64 * 2 + 2 * 131072 * 4


def test_roofline_names_the_bound():
    peaks = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert flash.roofline({"flops": 1000, "bytes": 50}, peaks) == \
        (10.0, "compute")
    assert flash.roofline({"flops": 100, "bytes": 50}, peaks) == \
        (5.0, "memory")


def test_peaks_table_has_the_v5e_and_its_source():
    with open(LOOKUP.path("", "peaks.json")) as f:
        table = json.load(f)
    assert "cloud.google.com/tpu/docs/v5e" in table["source"]
    v5e = table["devices"]["TPU v5 lite"]
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9


def test_program_flops_of_a_small_program_by_hand():
    """conv 3->4 channels, 3x3, on 2 images of 8x8 (same padding), then
    fc 256->5, loss, backward, SGD.

    conv forward: 2*4*8*8 outputs x 2*(3*3*3) = 512 x 54 = 27648; its
    gradient op produces only Filter@GRAD (the image needs none): 27648.
    fc forward: 2*5 outputs x 2*256 = 5120; its gradient op produces both
    X@GRAD and Y@GRAD: 10240."""
    import paddle_tpu.fluid as fluid

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        image = fluid.layers.data(name="image", shape=[2, 3, 8, 8],
                                  dtype="float32", append_batch_size=False)
        label = fluid.layers.data(name="label", shape=[2, 1],
                                  dtype="int64", append_batch_size=False)
        conv = fluid.layers.conv2d(input=image, num_filters=4,
                                   filter_size=3, padding=1,
                                   bias_attr=False)
        logits = fluid.layers.fc(input=conv, size=5)
        loss = fluid.layers.mean(
            fluid.layers.softmax_with_cross_entropy(logits, label))
        fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    got = LOOKUP.module("flops", "program").program_flops(main)
    assert got["mxu"] == 27648 + 27648 + 5120 + 10240
    assert got["total"] == got["mxu"] and got["kernels"] == {}


def test_program_flops_counts_the_flash_kernel_apart():
    fixture = Lookup([os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "fixture")])
    cfg = fixture.json("configs", "gpt2-tiny")
    built = fixture.module("models", cfg["builder"]).build(cfg, 2, True)
    got = fixture.module("flops", "program").program_flops(built["main"])
    one = flash.forward_cost(2, cfg["n_head"], 128, 128, 16, causal=True)
    kernel = got["kernels"][flash.KERNEL_NAME]
    assert kernel == {"flops": 2 * one["flops"], "bytes": 2 * one["bytes"],
                      "calls": 2}
    # per layer: qkv 64->192, proj 64->64, fc 64->256->64; the head
    # 64->97; each forward once and backward twice, on 2*128 tokens; and
    # the attention backward at twice the forward's FLOPs
    per_token = 2 * (2 * 64 * (192 + 64 + 256 + 256)) + 2 * 64 * 97
    assert got["mxu"] == 3 * 256 * per_token + 2 * 2 * one["flops"]
    assert got["total"] == got["mxu"] + kernel["flops"]
