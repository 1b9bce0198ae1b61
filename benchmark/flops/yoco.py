"""The bytes and operations one decode step of a decoder-hybrid-decoder
served whole (benchmark/models/phi4flash_decode.py) must move and do,
from the configuration's sizes: what no implementation can avoid, not
what this one does.  No count holds bytes the step need not move: a slot
past the position, a lane of padding, a zero a query carries in the half
of a pair it does not use.

One layer writes the whole-extent cache and `readers` layers attend it
(itself and every cross layer): a step reads the live slots' keys and
values once a reader (`shared_kv_step`; a reader's two products run over
`head` values a key and `2 * head` a value, every query head).  A window
layer reads its ring's live slots (`window_step`).  A Mamba layer reads
its scan state and writes it back, float32, with the step's operands and
`A_log` once (`scan_step`), and its convolution's tail likewise
(`tail_bytes`).  And the step reads every weight once: the embedding is
the head, so no row of it is left out (`weight_bytes`).
"""

import math

MAMBA, WINDOW, FULL, GMU, CROSS = "mamba", "window", "full", "gmu", "cross"
F32 = 4
# a state element a position: dt A (1), exp (1), times the state (1),
# dt x B (1, `dt x` a channel's), add (1), times C and add (2)
SCAN_OPS = 7


def kinds(cfg):
    n = cfg["num_hidden_layers"]
    half = n // 2
    return tuple(
        (MAMBA if i <= half else GMU) if i % 2 == 0
        else WINDOW if i < half else FULL if i == half + 1 else CROSS
        for i in range(n))


def count(cfg, kind):
    return kinds(cfg).count(kind)


def widths(cfg):
    """(hidden, head_dim, d_inner, d_state, d_conv, dt_rank)."""
    d = cfg["hidden_size"]
    return (d, d // cfg["num_attention_heads"],
            cfg.get("mamba_expand", 2) * d, cfg.get("mamba_d_state", 16),
            cfg.get("mamba_d_conv", 4),
            cfg.get("mamba_dt_rank", math.ceil(d / 16)))


def readers(cfg):
    """The layers that attend the one whole-extent cache."""
    return 1 + count(cfg, CROSS)


def layer_parameters(cfg, kind):
    """(parameters served in the weights' type, parameters float32) of
    one layer of `kind`, its two LayerNorms and feed-forward with it."""
    d, dim, d_inner, n, conv, rank = widths(cfg)
    heads, kv_heads = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    served = 2 * d + 3 * d * cfg["intermediate_size"]   # LN scales, F
    single = 2 * d                                      # LN biases
    if kind == MAMBA:
        served += d * 2 * d_inner + d_inner * conv + d_inner * (rank + 2 * n) \
            + rank * d_inner + d_inner * d
        single += d_inner * (n + 3)     # A_log; conv bias, dt bias, D
    elif kind == GMU:
        served += 2 * d * d_inner
    else:
        served += 2 * d * heads * dim + 2 * dim         # W_q, W_o, subln
        single += 4 * dim                               # lambda's vectors
        if kind != CROSS:
            served += d * 2 * kv_heads * dim            # W_kv
    return served, single


def chip_parameters(cfg):
    """Every parameter the chip holds: the model, whole."""
    d = cfg["hidden_size"]
    return cfg["vocab_size"] * d + 2 * d + sum(
        sum(layer_parameters(cfg, kind)) for kind in kinds(cfg))


def weight_bytes(cfg, itemsize):
    """Bytes of weights a step reads: all of them, once (the tied head
    reads every row of the embedding)."""
    d = cfg["hidden_size"]
    total = (cfg["vocab_size"] * d + d) * itemsize + d * F32
    for kind in kinds(cfg):
        served, single = layer_parameters(cfg, kind)
        total += served * itemsize + single * F32
    return total


def slot_bytes(cfg, itemsize):
    """A key and a value of every key/value head: one slot of a row."""
    _, dim = widths(cfg)[:2]
    return 2 * cfg["num_key_value_heads"] * dim * itemsize


def _slot_flops(cfg):
    """The two products of every query head over one slot: a score over
    `head` values, a pair's values `2 * head` wide, 2 FLOPs a
    multiply-add."""
    _, dim = widths(cfg)[:2]
    return 2 * cfg["num_attention_heads"] * (dim + 2 * dim)


def shared_kv_step(cfg, batch, position, itemsize):
    """{"flops", "bytes"} of the attention over the one whole-extent
    cache in the step that writes `position`: slots 0 .. position, every
    row, once a reader."""
    live = readers(cfg) * batch * (position + 1)
    return {"flops": live * _slot_flops(cfg),
            "bytes": live * slot_bytes(cfg, itemsize)}


def window_step(cfg, batch, position, itemsize):
    """{"flops", "bytes"} of the window layers' attention in the step
    that writes `position`: a ring's live slots, every row and layer."""
    live = count(cfg, WINDOW) * batch \
        * min(position + 1, cfg["sliding_window"])
    return {"flops": live * _slot_flops(cfg),
            "bytes": live * slot_bytes(cfg, itemsize)}


def scan_step(cfg, batch, itemsize):
    """{"flops", "bytes"} of the selective scans' step, every Mamba
    layer: the float32 state read and written, a position's operands in
    (x in the served type, the step size float32, B and C float32) and y
    out, A_log, D and the step's bias once."""
    _, _, d_inner, n, _, _ = widths(cfg)
    layers = count(cfg, MAMBA)
    state = batch * d_inner * n
    operands = batch * (d_inner * (2 * itemsize + F32) + 2 * n * F32)
    return {"flops": layers * state * SCAN_OPS,
            "bytes": layers * (2 * state * F32 + operands
                               + d_inner * (n + 2) * F32)}


def tail_bytes(cfg, batch, itemsize):
    """The convolutions' tails read and written, every Mamba layer."""
    _, _, d_inner, _, conv, _ = widths(cfg)
    return count(cfg, MAMBA) * 2 * batch * (conv - 1) * d_inner * itemsize


def state_bytes(cfg, batch, itemsize):
    """{"state", "tail", "ring", "cache"}: bytes of the states a call is
    handed, as the step declares them."""
    _, _, d_inner, n, conv, _ = widths(cfg)
    slot = batch * slot_bytes(cfg, itemsize)
    return {"state": count(cfg, MAMBA) * batch * d_inner * n * F32,
            "tail": tail_bytes(cfg, batch, itemsize) // 2,
            "ring": count(cfg, WINDOW) * slot * cfg["sliding_window"],
            "cache": slot * cfg["serve_positions"]}


def step_bytes(cfg, batch, position, weight_itemsize, cache_itemsize):
    """The bytes the step that writes slot `position` must move: the
    weights once, the shared cache once a reader, the rings, the scan
    states and the tails read and written."""
    return (weight_bytes(cfg, weight_itemsize)
            + shared_kv_step(cfg, batch, position, cache_itemsize)["bytes"]
            + window_step(cfg, batch, position, cache_itemsize)["bytes"]
            + scan_step(cfg, batch, weight_itemsize)["bytes"]
            + tail_bytes(cfg, batch, weight_itemsize))
