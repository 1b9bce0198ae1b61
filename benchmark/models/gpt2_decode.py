"""The GPT-2-shaped decoder's key/value-cached decode step as a fluid
Program, from a configuration file, with what a generation cell makes
from the seed beside it.

The step is the program's own
`paddle_tpu.models.transformer_program.build_transformer_cached_step_program`
(one token in, the next token's logits out, a key and a value cache a
layer through the `cached_attention` op) at the configuration's widths;
`fluid.ProgramDecoder` scans it.  The parameter names are read off the op
descs by benchmark/models/gpt2.py `param_names`, as for the training
program: the two programs give one architecture's parameters the same
names in the same order of ops.

`weights` and `prompts` are pure functions of the seed and import nothing
of the program: the driver hands their arrays to the program, and the
plain reference (benchmark/reference/gpt2_decode.py) makes its own copy
from the same seed.
"""

from benchmark.models.gpt2 import inner_width, param_names


def build(cfg, batch):
    """{"main", "logits", "state_pairs", "param_names", "cache_names",
    "cache_shape"} of the cached step at `batch` rows and the
    configuration's whole context."""
    from paddle_tpu.models.transformer_program import (
        build_transformer_cached_step_program)

    heads, width = cfg["n_head"], cfg["n_embd"]
    main, _, logits, pairs = build_transformer_cached_step_program(
        batch, cfg["n_positions"], cfg["vocab_size"],
        n_layer=cfg["n_layer"], n_head=heads, d_model=width,
        d_ff=inner_width(cfg))
    names = param_names(main)
    if len(names["blocks"]) != cfg["n_layer"]:
        raise ValueError("the program's cached step does not have the %d "
                         "blocks configuration %r states"
                         % (cfg["n_layer"], cfg["name"]))
    return {"main": main, "logits": logits,
            "state_pairs": pairs, "param_names": names,
            "cache_names": [feed for feed, _ in pairs if feed != "pos"],
            "cache_shape": (batch, heads, cfg["n_positions"],
                            width // heads)}


def weights(cfg, spec, key):
    """Every parameter from a seeded key in the type it is served in
    (`spec`: the workload's `weights`), as the tree
    benchmark/reference/gpt2.py documents: matrices and embeddings
    N(0, std), the source's `initializer_range`; biases N(0, std) and
    scales 1 + N(0, std) and not the source's 0 and 1, so that a bias or
    a scale left out is seen.  Pure jax: call it under one `jax.jit`.

    Two things a trained decoder has and independent draws do not, which
    `correct` needs (PERF.md section 4), each asked for by the spec:
    `qk_gain` multiplies the query and key columns of every `qkv`
    matrix, so that a query's scores spread by `qk_gain`**2 times as
    much and it attends a few keys, not the mean of all of them (a mean
    over hundreds of keys averages away whatever the cache's type does
    to one of them); `paired_fc_2` draws half of every `fc_2` and gives
    the other half its negative, so that the feed-forward's output has
    no part that every context shares (the mean of a ReLU, which after
    24 layers makes every context choose among the same few tokens)."""
    import jax
    import jax.numpy as jnp

    dtype, std = jnp.dtype(spec["dtype"]), spec["std"]
    width, inner, layers = cfg["n_embd"], inner_width(cfg), cfg["n_layer"]
    root = jax.random.fold_in(key, 0xD0DE)
    count = iter(range(1 << 20))

    def normal(shape, mean=0.0):
        drawn = jax.random.normal(jax.random.fold_in(root, next(count)),
                                  shape, jnp.float32)
        return (mean + std * drawn).astype(dtype)

    def norm(*lead):
        return normal(lead + (width,), 1.0), normal(lead + (width,))

    def dense(rows, cols, *lead):
        return normal(lead + (rows, cols)), normal(lead + (cols,))

    # one draw a kind of parameter for all the layers, then cut by layer:
    # a dozen random ops to compile and run where there were three hundred
    stacked = {"ln_1": norm(layers), "qkv": dense(width, 3 * width, layers),
               "proj": dense(width, width, layers), "ln_2": norm(layers),
               "fc_1": dense(width, inner, layers),
               "fc_2": dense(inner, width, layers)}
    gain = spec.get("qk_gain", 1.0)
    if gain != 1.0:
        w, b = stacked["qkv"]
        stacked["qkv"] = (w.at[..., :2 * width].multiply(gain), b)
    if spec.get("paired_fc_2"):
        w, b = stacked["fc_2"]
        half = w[:, :inner // 2]
        stacked["fc_2"] = (jnp.concatenate([half, -half], axis=1), b)
    return {
        "wte": normal((cfg["vocab_size"], width)),
        "wpe": normal((cfg["n_positions"], width)),
        "blocks": [{kind: (a[i], b[i]) for kind, (a, b) in stacked.items()}
                   for i in range(layers)],
        "ln_f": norm(),
        "head": dense(width, cfg["vocab_size"]),
    }


def prompts(cfg, workload, seed):
    """The pool of prompt batches, `[pool, batch, prompt_len]` int32 on
    the host: uniform ids over the whole vocabulary.  Every seed gives
    the same sizes."""
    import numpy as np

    rng = np.random.default_rng([seed, 0x9E3779B9])
    return rng.integers(
        0, cfg["vocab_size"],
        (workload["pool"], workload["batch"], workload["prompt_len"]),
        dtype=np.int32)
