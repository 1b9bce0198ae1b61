"""The bytes one decode step of a GPT-2-shaped decoder with a key/value
cache must move through the chip's memory, from the configuration's
sizes: what no implementation can avoid, not what this one does.

A step reads every weight once whatever the batch (the matrices, biases
and norms of every block, the last norm and the head; of the two
embedding tables only the rows it looks up), reads the *live* part of
the cache (the keys and values of the positions before the one it
writes, for every row of the batch), and writes this position's key and
value.  The logits and the activations between the products are three
orders of magnitude below and are not counted.  A cache slot past the
live length holds nothing and need not be read: an implementation that
attends the whole extent under a mask moves more than this.
"""

from benchmark.models.gpt2 import inner_width


def weight_bytes(cfg, batch, itemsize):
    """Bytes of weights one step reads."""
    width, inner = cfg["n_embd"], inner_width(cfg)
    block = (2 * width                      # ln_1
             + width * 3 * width + 3 * width
             + width * width + width
             + 2 * width                    # ln_2
             + width * inner + inner
             + inner * width + width)
    head = 2 * width + width * cfg["vocab_size"] + cfg["vocab_size"]
    looked_up = (batch + 1) * width        # token rows, one position row
    return (cfg["n_layer"] * block + head + looked_up) * itemsize


def cache_bytes(cfg, batch, position, itemsize):
    """Bytes of cache the step that writes slot `position` moves: the
    `position` slots before it read, its own written, keys and values,
    every layer, every row."""
    return (2 * cfg["n_layer"] * batch * cfg["n_embd"]
            * (position + 1) * itemsize)


def step_bytes(cfg, batch, position, weight_itemsize, cache_itemsize):
    return (weight_bytes(cfg, batch, weight_itemsize)
            + cache_bytes(cfg, batch, position, cache_itemsize))


def mean_step_bytes(cfg, batch, first, last, weight_itemsize,
                    cache_itemsize):
    """Mean of `step_bytes` over the steps that write slots `first` to
    `last`, both included (the cache term is linear in the slot)."""
    return step_bytes(cfg, batch, (first + last) / 2.0, weight_itemsize,
                      cache_itemsize)


def whole_extent_step_bytes(cfg, batch, weight_itemsize, cache_itemsize):
    """What a step moves that reads every slot of the cache whatever the
    live length."""
    return step_bytes(cfg, batch, cfg["n_positions"] - 1, weight_itemsize,
                      cache_itemsize)
