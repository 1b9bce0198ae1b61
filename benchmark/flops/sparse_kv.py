"""The bytes and operations one decode step of a share that attends a
chosen set of grouped key/value caches (benchmark/models/keye_decode.py)
must move and do, from the configuration's sizes: what no implementation
can avoid, not what this one does.  No count holds bytes the step need
not move: a slot past the position, a key or value that was not chosen,
a held expert with no row.

A step's chooser reads the *live* index keys (`indexer_head_dim` values
of the slots up to the one it writes, every layer, every row) and scores
them with `indexer_num_heads` heads: `index_step`.  Its attention reads
the *chosen* keys and values (`min(topk, live)` slots of `2 x kv heads x
head_dim` values, every layer, every row; one set for all key/value
heads) for two contractions a query head: `attend_step`.  And the step
reads, whatever the batch, every weight the chip holds outside the
routed experts (`fixed_weight_bytes`: this model has no shared expert):
which of the held routed experts a step's few rows reach is the router's
choice at run time (8 rows x 8 choices over 128 experts reach about 6 of
the held 16 a layer) and is not in a trace, so `step_bytes` leaves the
routed experts out and is a floor of what the step moves.
"""


def index_step(cfg, batch, position, itemsize):
    """{"flops", "bytes"} of the chooser's scores in the step that
    writes slot `position`, every layer: 2 FLOPs a multiply-add, the
    live slots' keys read once."""
    sa, live = cfg["sa_config"], position + 1
    layers = cfg["num_hidden_layers"]
    return {"flops": layers * 2 * batch * sa["indexer_num_heads"]
            * sa["indexer_head_dim"] * live,
            "bytes": layers * batch * live * sa["indexer_head_dim"]
            * itemsize}


def entry_width(cfg):
    """Values a slot holds for the attention: a key and a value a
    key/value head."""
    return 2 * cfg["num_key_value_heads"] * cfg["head_dim"]


def attend_step(cfg, batch, position, itemsize):
    """{"flops", "bytes"} of the gather and the attention over the
    chosen slots in the step that writes slot `position`, every layer:
    scores and values heads x head_dim a chosen slot each, the chosen
    keys and values read once (an implementation that gathers them into
    a copy reads and writes them once more: not counted)."""
    chosen = min(cfg["sa_config"]["topk"], position + 1)
    heads, layers = cfg["num_attention_heads"], cfg["num_hidden_layers"]
    products = 2 * 2 * batch * heads * cfg["head_dim"] * chosen
    return {"flops": layers * products,
            "bytes": layers * batch * chosen * entry_width(cfg) * itemsize}


def attention_parameters(cfg):
    """One layer's attention sub-layer, its chooser among it."""
    d, dim = cfg["hidden_size"], cfg["head_dim"]
    heads, kv_heads = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    sa = cfg["sa_config"]
    ih, idim = sa["indexer_num_heads"], sa["indexer_head_dim"]
    return (d + 2 * dim                             # input, q and k norms
            + d * dim * (heads + 2 * kv_heads)      # W_q, W_k, W_v
            + heads * dim * d                       # W_o
            + d * ih * idim + d * idim + 2 * idim + d * ih)  # the chooser


def fixed_weight_bytes(cfg, batch, itemsize):
    """Bytes of weights a step reads whatever its rows: every layer's
    attention, its pre-feed-forward norm and its router; the last norm
    and the head; of the embedding the rows looked up."""
    d = cfg["hidden_size"]
    a_layer = attention_parameters(cfg) + d + d * cfg["scored_experts"]
    return (cfg["num_hidden_layers"] * a_layer + d + d * cfg["vocab_size"]
            + batch * d) * itemsize


def step_bytes(cfg, batch, position, weight_itemsize, cache_itemsize,
               index_itemsize):
    """A floor of the bytes the step that writes slot `position` moves:
    the fixed weights, the live index keys, the chosen keys and values
    (the routed experts a row reached are left out: the module's
    docstring)."""
    return (fixed_weight_bytes(cfg, batch, weight_itemsize)
            + index_step(cfg, batch, position, index_itemsize)["bytes"]
            + attend_step(cfg, batch, position, cache_itemsize)["bytes"])
