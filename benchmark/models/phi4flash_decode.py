"""Phi-4-mini-flash-reasoning, whole, as a cached decode step Program,
from a configuration file, with what the turn cell makes from the seed
beside it.

The step is the program's own
`paddle_tpu.models.sambay_program.build_sambay_cached_step_program` (a
block of T >= 1 tokens in, the logits after its last out; nine Mamba-1
layers through `causal_conv1d` with its tail and `selective_scan` with
its state, eight differential-attention layers over rings of
`sliding_window` slots and one over the whole extent through
`cached_attention` and `diff_combine`, seven cross layers that read that
one cache and seven gated memory units that read layer 16's scan output)
at the configuration's widths; `fluid.ProgramDecoder` scans it.

The weights are drawn as benchmark/models/pangu_decode.py draws them
(its `_draw`, `root`: a parameter's stream is its name's, so a block can
be made alone for the reference bit for bit as it is served), with the
kinds this model adds: a bias N(0, `bias_std`) and lambda's vectors N(0,
`lambda_std`) in float32; the convolution's taps N(0, `conv_std`)
(Mamba's own start is uniform on +-0.5: taps of 0.02 would leave the
scan an input of nothing); the queries' projection times `q_gain` and
the scan's low-rank projection times `ssm_gain` (the workload's
`weights.why`); and Mamba's published start for the scan's own
parameters, which are no draw at all or a draw on a log scale: A = 1 ..
d_state along the state, D = 1, and a step bias such that softplus gives
steps log-uniform between 0.001 and 0.1.  `documents` and `prompts` (the
questions) are pure functions of the seed.
"""

import math
import zlib

from benchmark import harness

_pangu = harness.Lookup().module("models", "pangu_decode")
root = _pangu.root

MAMBA, WINDOW, FULL, GMU, CROSS = "mamba", "window", "full", "gmu", "cross"
DT_MIN, DT_MAX = 1e-3, 1e-1


def sizes(cfg):
    """The configuration's keys as the step builder's arguments."""
    heads = cfg["num_attention_heads"]
    if cfg["mb_per_layer"] != 2 or not cfg["tie_word_embeddings"] \
            or cfg["hidden_act"] != "silu" or cfg["mlp_bias"] \
            or cfg["lm_head_bias"] or cfg["hidden_size"] % heads:
        raise ValueError("phi4flash_decode builder: configuration %r asks "
                         "for what the step does not build" % cfg["name"])
    return dict(
        vocab_size=cfg["vocab_size"], n_layer=cfg["num_hidden_layers"],
        window=cfg["sliding_window"], n_head=heads,
        n_kv_head=cfg["num_key_value_heads"],
        d_head=cfg["hidden_size"] // heads, d_model=cfg["hidden_size"],
        d_ff=cfg["intermediate_size"], d_state=cfg["mamba_d_state"],
        d_conv=cfg["mamba_d_conv"], expand=cfg["mamba_expand"],
        dt_rank=cfg["mamba_dt_rank"], eps=cfg["layer_norm_eps"])


def kinds(cfg):
    from paddle_tpu.models.sambay_program import layer_kinds

    return layer_kinds(cfg["num_hidden_layers"])


def state_shapes(cfg, batch, window=None):
    """{feed: shape} of every state of the step: a scan state and a tail
    a Mamba layer, a ring a window layer, the one whole-extent cache."""
    args = sizes(cfg)
    window = window or args["window"]
    d_inner = args["expand"] * args["d_model"]
    pairs, width = args["n_kv_head"] // 2, 2 * args["d_head"]
    out = {}
    for i, kind in enumerate(kinds(cfg)):
        if kind == MAMBA:
            out["ssm_state_%d" % i] = (batch, args["d_state"], d_inner)
            out["conv_tail_%d" % i] = (batch, args["d_conv"] - 1, d_inner)
        elif kind in (WINDOW, FULL):
            stem = "%s_ring_%d" if kind == WINDOW else "%s_cache_%d"
            for which in "kv":
                out[stem % (which, i)] = (
                    batch, pairs,
                    window if kind == WINDOW else cfg["serve_positions"],
                    width)
    return out


def build(cfg, batch, **changed):
    """{"main", "logits", "state_pairs", "param_names", "state_shapes",
    "probes"} of the cached step at `batch` rows and the configuration's
    `serve_positions`; `changed` overrides builder arguments (a control
    of `correct` serves another `window`, drops the subtraction, takes
    the memory after the gate or reads the cache before its write).

    "probes" is, per layer, (layer, {what: a state pair}) of what the
    step only writes and a decoder carries out of a call's last step:
    "in" [batch, 1, hidden] the mixer's normed input and "out" what the
    mixer gave for it ([batch, 1, hidden]; on a Mamba layer the scan's
    output before the gate, [batch, 1, d_inner], and "xc" the convolved
    input the scan read, the same shape)."""
    import jax
    from paddle_tpu.models.sambay_program import (
        build_sambay_cached_step_program, sambay_param_names)

    args = dict(sizes(cfg), **changed)
    main, _, logits, pairs, parts = build_sambay_cached_step_program(
        batch, cfg["serve_positions"], **args)
    probes, scans = [], zip(parts["scan_in"], parts["scan_out"])
    for i, kind in enumerate(kinds(cfg)):
        found = {"in": parts["mixer_in"][i], "out": parts["mixer_out"][i]}
        if kind == MAMBA:
            found["xc"], found["out"] = next(scans)
        probes.append((i, {what: ("probe_%d.%s" % (i, what), var.name)
                           for what, var in found.items()}))
    names = sambay_param_names(cfg["num_hidden_layers"])
    built = {p.name for p in main.global_block().all_parameters()}
    if set(jax.tree_util.tree_leaves(names)) != built:
        raise ValueError("the program's parameters %s are not those "
                         "configuration %r names"
                         % (sorted(built), cfg["name"]))
    return {"main": main, "logits": logits, "state_pairs": pairs,
            "param_names": names, "probes": probes,
            "state_shapes": state_shapes(cfg, batch, args["window"])}


def _shapes(cfg, layer):
    """{name: (shape, kind)} of one block's parameters."""
    args = sizes(cfg)
    d, dim = args["d_model"], args["d_head"]
    heads, kv_heads = args["n_head"], args["n_kv_head"]
    d_inner, n = args["expand"] * d, args["d_state"]
    rank, f = args["dt_rank"], args["d_ff"]
    out = {"ln1.w": ((d,), "norm"), "ln1.b": ((d,), "bias"),
           "ln2.w": ((d,), "norm"), "ln2.b": ((d,), "bias"),
           "ffn_in": ((d, 2 * f), "matrix"), "ffn_out": ((f, d), "matrix")}
    kind = kinds(cfg)[layer]
    if kind == MAMBA:
        out.update({
            "in_proj": ((d, 2 * d_inner), "matrix"),
            "conv_w": ((d_inner, args["d_conv"]), "conv"),
            "conv_b": ((d_inner,), "bias"),
            "x_proj": ((d_inner, rank + 2 * n), "scan"),
            "dt_proj": ((rank, d_inner), "matrix"),
            "dt_bias": ((d_inner,), "dt_bias"),
            "a_log": ((d_inner, n), "a_log"), "d": ((d_inner,), "ones"),
            "out_proj": ((d_inner, d), "matrix")})
    elif kind == GMU:
        out.update(gmu_in=((d, d_inner), "matrix"),
                   gmu_out=((d_inner, d), "matrix"))
    else:
        out.update({
            "wq": ((d, heads * dim), "query"),
            "wo": ((heads * dim, d), "matrix"),
            "subln": ((2 * dim,), "norm"),
            **{w: ((dim,), "lambda") for w in ("lq1", "lk1", "lq2", "lk2")}})
        if kind != CROSS:
            out["wkv"] = ((d, 2 * kv_heads * dim), "matrix")
    return out


def _draw(spec, key, name, shape, kind):
    """pangu_decode's `_draw` for its kinds ("matrix", "query", "norm",
    "embed"), under another deviation and in float32 for "bias" and
    "lambda", under another deviation for "conv" and "scan"; and the
    scan's own parameters as Mamba starts them."""
    import jax
    import jax.numpy as jnp

    if kind in ("bias", "lambda"):
        return _pangu._draw(dict(spec, std=spec[kind + "_std"],
                                 dtype="float32"), key, name, shape,
                            "matrix")
    if kind == "conv":
        return _pangu._draw(dict(spec, std=spec["conv_std"]), key, name,
                            shape, "matrix")
    if kind == "scan":
        return _pangu._draw(dict(spec, std=spec["std"]
                                 * spec.get("ssm_gain", 1.0)), key, name,
                            shape, "matrix")
    if kind == "ones":
        return jnp.ones(shape, jnp.float32)
    if kind == "a_log":
        return jnp.broadcast_to(
            jnp.log(jnp.arange(1, shape[1] + 1, dtype=jnp.float32)), shape)
    if kind == "dt_bias":
        word = jax.random.bits(
            jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF),
            shape, jnp.uint32)
        u = (word >> 8).astype(jnp.float32) * jnp.float32(2.0 ** -24)
        dt = jnp.exp(u * jnp.float32(math.log(DT_MAX / DT_MIN))
                     + jnp.float32(math.log(DT_MIN)))
        # the x with softplus(x) = dt
        return dt + jnp.log(-jnp.expm1(-dt))
    return _pangu._draw(spec, key, name, shape, kind)


def block(cfg, spec, key, layer):
    """The parameters of block `layer` from the `root` key.  Pure jax."""
    return {name: _draw(spec, key, "block_%d.%s" % (layer, name), shape,
                        kind)
            for name, (shape, kind) in _shapes(cfg, layer).items()}


def ends(cfg, spec, key):
    """{"embed", "norm_f"} from the `root` key: the head is the
    embedding's."""
    d = cfg["hidden_size"]
    return {"embed": _draw(spec, key, "embed", (cfg["vocab_size"], d),
                           "embed"),
            "norm_f": {"w": _draw(spec, key, "norm_f.w", (d,), "norm"),
                       "b": _draw(spec, key, "norm_f.b", (d,), "bias")}}


def weights(cfg, spec, key):
    """Every parameter from a seeded key in the type it is served in, as
    the tree benchmark/reference/phi4_flash.py documents.  Pure jax:
    call it under one `jax.jit`."""
    key = root(key)
    tree = ends(cfg, spec, key)
    tree["blocks"] = [block(cfg, spec, key, i)
                      for i in range(cfg["num_hidden_layers"])]
    return tree


def documents(cfg, workload, seed):
    """The seeded documents whose sessions the rows continue,
    `[documents, session_len]` int32 on the host: uniform ids over the
    vocabulary."""
    import numpy as np

    rng = np.random.default_rng([seed, 0xD0C5])
    return rng.integers(0, cfg["vocab_size"],
                        (workload["documents"], workload["session_len"]),
                        dtype=np.int32)


def prompts(cfg, workload, seed):
    """The pool of question batches, `[pool, batch, prompt_len]` int32:
    row r of a batch asks of document r // questions_a_document."""
    return _pangu.prompts(cfg, workload, seed)
