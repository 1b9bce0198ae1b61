"""The bytes and operations one decode step of a share that mixes window
and full attention over grouped key/value heads
(benchmark/models/exaone_decode.py) must move and do, from the
configuration's sizes: what no implementation can avoid, not what this
one does.  No count holds bytes the step need not move: a slot past the
position, a ring's slot that holds nothing yet, a held expert with no
row.

A step's attention reads the *live* keys and values: `head_dim` values
of each of `num_key_value_heads` heads, twice (a key and a value), of
the positions up to the one it writes on a full layer and of the last
`sliding_window` of them on a window layer, every row; its two products
are over the same slots for all `num_attention_heads` query heads:
`kv_step`.  And the step reads, whatever the batch, every weight the
chip holds outside the routed experts (`fixed_weight_bytes`): which of
the held routed experts a step's few rows reach is the router's choice
at run time (8 rows x 8 choices over 128 experts put 4 assignments a
layer on the held 8) and is not in a trace, so `step_bytes` leaves the
routed experts out and is a floor of what the step moves.
"""

WINDOW = "sliding_attention"


def attention_parameters(cfg):
    """One layer's attention sub-layer with the block's two norms."""
    d, dim = cfg["hidden_size"], cfg["head_dim"]
    heads, kv_heads = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return (d + d * heads * dim         # input norm, W_q
            + 2 * d * kv_heads * dim    # W_k, W_v
            + 2 * dim                   # the q and k norms' scales
            + heads * dim * d + d)      # W_o, the pre-feed-forward norm


def expert_parameters(cfg):
    """One gated expert of the routed width (the shared one too)."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def dense_parameters(cfg):
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def router_parameters(cfg):
    """The router's matrix and its selection bias."""
    return (cfg["hidden_size"] + 1) * cfg["scored_experts"]


def chip_parameters(cfg):
    """Every parameter this chip holds."""
    d = cfg["hidden_size"]
    total = 2 * cfg["vocab_size"] * d + d
    for kind in cfg["mlp_layer_types"]:
        total += attention_parameters(cfg)
        if kind == "dense":
            total += dense_parameters(cfg)
        else:
            total += router_parameters(cfg) \
                + (1 + cfg["num_experts"]) * expert_parameters(cfg)
    return total


def fixed_weight_bytes(cfg, batch, itemsize):
    """Bytes of weights a step reads whatever its rows: every layer's
    attention and norms, the dense feed-forward or the shared expert
    with the router and its bias; the last norm and the head; of the
    embedding the rows looked up."""
    d = cfg["hidden_size"]
    total = d + d * cfg["vocab_size"] + batch * d
    for kind in cfg["mlp_layer_types"]:
        total += attention_parameters(cfg) + (
            dense_parameters(cfg) if kind == "dense"
            else expert_parameters(cfg) + router_parameters(cfg))
    return total * itemsize


def slot_bytes(cfg, itemsize):
    """A key and a value of every key/value head: one slot of a row."""
    return 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * itemsize


def live_slots(cfg, position, kinds=("window", "full")):
    """The slots the step that writes `position` attends, a row, added
    up over the layers of `kinds`."""
    total = 0
    for kind in cfg["layer_types"]:
        if kind == WINDOW and "window" in kinds:
            total += min(position + 1, cfg["sliding_window"])
        elif kind != WINDOW and "full" in kinds:
            total += position + 1
    return total


def kv_step(cfg, batch, position, itemsize, kinds=("window", "full")):
    """{"flops", "bytes"} of the attention's two products over the live
    slots in the step that writes `position`, the layers of `kinds`: 2
    FLOPs a multiply-add for scores and for values, every query head;
    the live keys and values read once."""
    live = live_slots(cfg, position, kinds)
    return {"flops": 2 * 2 * batch * cfg["num_attention_heads"]
            * cfg["head_dim"] * live,
            "bytes": batch * live * slot_bytes(cfg, itemsize)}


def session_bytes(cfg, batch, itemsize):
    """{"window", "full"}: bytes of the caches a call is handed, as the
    step declares them (a ring a window layer, `serve_positions` a full
    one)."""
    out = {"window": 0, "full": 0}
    for kind in cfg["layer_types"]:
        ring = kind == WINDOW
        out["window" if ring else "full"] += batch * slot_bytes(
            cfg, itemsize) * (cfg["sliding_window"] if ring
                              else cfg["serve_positions"])
    return out


def step_bytes(cfg, batch, position, weight_itemsize, cache_itemsize):
    """A floor of the bytes the step that writes slot `position` moves:
    the fixed weights and the live keys and values (the routed experts a
    row reached are left out: the module's docstring)."""
    return (fixed_weight_bytes(cfg, batch, weight_itemsize)
            + kv_step(cfg, batch, position, cache_itemsize)["bytes"])
