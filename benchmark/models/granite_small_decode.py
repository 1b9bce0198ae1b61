"""One chip's share of granite-4.0-h-small as a cached decode step
Program, from a configuration file, with what the generation cell makes
from the seed beside it.

The step is the program's own
`paddle_tpu.models.hybrid_program.build_granite_hybrid_cached_step_program`
(a block of tokens in, the next token's logits over the held rows of the
tied table out; a convolution tail and a float32 Mamba-2 state a mamba
layer through `causal_conv1d` and `ssd_scan`, keys and values over the
whole extent an attention layer through `cached_attention`, grouped
heads, no positions, the model's own softmax scale; the held range of
the routed experts through `moe_experts` beside a shared expert twice
their width) at the configuration's widths; `fluid.ProgramDecoder` scans
it.

The weights are drawn as benchmark/models/qwen3next_decode.py draws them
(its `_draw`: pangu's integer sums, so that a block can be made alone
for the reference bit for bit as it is served; the convolution's filter
N(0, `conv_std`); `dt_bias` = softplus^-1 of a step drawn log-uniformly
from [`dt_min`, `dt_max`], float32) with three kinds more: `A_log` = log
U(1, 16), float32, as arXiv:2405.21060 and granite-4.0-h-micro's file
draw it; `D` = 1 + N(0, std), float32; and pangu's `query` kind on W_q
(`q_gain`: nothing norms q, so a gain on the matrix is not normed away).
`prompts` is a pure function of the seed.
"""

import zlib

from benchmark import harness

_lookup = harness.Lookup()
_qwen = _lookup.module("models", "qwen3next_decode")
_pangu = _lookup.module("models", "pangu_decode")
root = _qwen.root
prompts = _qwen.prompts

MAMBA, ATTENTION = "mamba", "attention"


def layer_types(cfg):
    """The layers this chip serves: the first `num_hidden_layers` of the
    source's `layer_types`."""
    return tuple(cfg["layer_types"][:cfg["num_hidden_layers"]])


def sizes(cfg):
    """The configuration's keys as the step builder's arguments."""
    if not cfg["tie_word_embeddings"] or cfg["attention_bias"] \
            or cfg["hidden_act"] != "silu" or cfg["mamba_proj_bias"] \
            or not cfg["mamba_conv_bias"] or cfg["mamba_n_groups"] != 1 \
            or cfg["position_embedding_type"] != "nope" \
            or cfg["normalization_function"] != "rmsnorm" \
            or cfg["mamba_n_heads"] * cfg["mamba_d_head"] \
            != cfg["mamba_expand"] * cfg["hidden_size"] \
            or set(layer_types(cfg)) - {MAMBA, ATTENTION}:
        raise ValueError("granite_small_decode builder: configuration %r "
                         "asks for what the step does not build"
                         % cfg["name"])
    return dict(
        vocab_size=cfg["vocab_size"], layer_types=layer_types(cfg),
        d_model=cfg["hidden_size"], n_head=cfg["num_attention_heads"],
        n_kv_head=cfg["num_key_value_heads"], d_head=cfg["head_dim"],
        mamba_heads=cfg["mamba_n_heads"], mamba_d_head=cfg["mamba_d_head"],
        d_state=cfg["mamba_d_state"], d_conv=cfg["mamba_d_conv"],
        chunk=cfg["mamba_chunk_size"], d_expert=cfg["intermediate_size"],
        d_shared=cfg["shared_intermediate_size"],
        n_experts=cfg["scored_experts"],
        held=(cfg["first_expert"], cfg["num_local_experts"]),
        top_k=cfg["num_experts_per_tok"], eps=cfg["rms_norm_eps"],
        sm_scale=cfg["attention_multiplier"],
        embedding_multiplier=cfg["embedding_multiplier"],
        residual_multiplier=cfg["residual_multiplier"],
        logits_scaling=cfg["logits_scaling"])


def state_shapes(cfg, batch):
    """{feed: (shape, "state" | "tail" | "cache")} of what a call hands
    over beside the position: a mamba layer's convolution tail (in the
    weights' type) and the scan's state (float32, in the layout the
    program carries it: state entries by head lanes), an attention
    layer's keys and values over `serve_positions` (in the serving
    type)."""
    inner = cfg["mamba_n_heads"] * cfg["mamba_d_head"]
    entries = cfg["mamba_d_state"]
    out = {}
    for i, kind in enumerate(layer_types(cfg)):
        if kind == MAMBA:
            out["conv_tail_%d" % i] = (
                (batch, cfg["mamba_d_conv"] - 1, inner + 2 * entries),
                "tail")
            out["ssd_state_%d" % i] = ((batch, entries, inner), "state")
        else:
            for which in "kv":
                out["%s_cache_%d" % (which, i)] = (
                    (batch, cfg["num_key_value_heads"],
                     cfg["serve_positions"], cfg["head_dim"]), "cache")
    return out


def probe_shapes(cfg, state_rows):
    """{what: (shape, "state" | "tail")} of what a mamba layer's probes
    hand out of the first `state_rows` rows: the state the step hands on
    and the first heads of the one it was handed (the program's
    `STATE_IN_HEADS`), a head at a time as the reference has them
    (float32), and what its scan read at the position, [x | B | C | dt]
    (in the weights' type)."""
    from paddle_tpu.models.hybrid_program import STATE_IN_HEADS

    heads, dim = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    entries = cfg["mamba_d_state"]
    apart = (state_rows, heads, dim, entries)
    return {"state": (apart, "state"),
            "state_in": ((state_rows, min(heads, STATE_IN_HEADS), dim,
                          entries), "state"),
            "step_in": ((state_rows, 1, heads * dim + 2 * entries + heads),
                        "tail")}


def build(cfg, batch, state_rows=0, **changed):
    """{"main", "logits", "state_pairs", "param_names", "state_shapes",
    "probes"} of the cached step at `batch` rows and the configuration's
    `serve_positions`; `changed` overrides builder arguments.

    "probes" is, per layer, (layer, {what: a state pair}) of what the
    step only writes and a decoder carries out of a call's last step:
    the expert layer's three, "in", "idx", "out", and for a mamba layer
    with `state_rows` > 0 `probe_shapes`' three."""
    import jax
    from paddle_tpu.models.hybrid_program import (
        build_granite_hybrid_cached_step_program,
        granite_moe_hybrid_param_names)

    args = dict(sizes(cfg), state_rows=state_rows, **changed)
    main, _, logits, pairs, parts = \
        build_granite_hybrid_cached_step_program(
            batch, cfg["serve_positions"], **args)
    probes, mamba = [], 0
    for i, kind in enumerate(layer_types(cfg)):
        found = {what: parts[part][i] for what, part in (
            ("in", "moe_in"), ("idx", "top_idx"), ("out", "moe_out"))}
        if kind == MAMBA:
            if state_rows:
                found.update({what: parts["ssd_" + what][mamba]
                              for what in probe_shapes(cfg, state_rows)})
            mamba += 1
        probes.append((i, {what: ("probe_%d.%s" % (i, what), var.name)
                           for what, var in found.items()}))
    names = granite_moe_hybrid_param_names(layer_types(cfg))
    built = {p.name for p in main.global_block().all_parameters()}
    if set(jax.tree_util.tree_leaves(names)) != built:
        raise ValueError("the program's parameters %s are not those "
                         "configuration %r names"
                         % (sorted(built), cfg["name"]))
    return {"main": main, "logits": logits, "state_pairs": pairs,
            "param_names": names, "probes": probes,
            "state_shapes": state_shapes(cfg, batch)}


def _shapes(cfg, layer):
    """{name: (shape, kind)} of one block's parameters; pangu's kinds
    and "conv", "a_log", "dt_bias", "skip"."""
    d = cfg["hidden_size"]
    f, shared = cfg["intermediate_size"], cfg["shared_intermediate_size"]
    held = cfg["num_local_experts"]
    out = {
        "norm_1": ((d,), "norm"), "norm_2": ((d,), "norm"),
        "shared_in": ((d, 2 * shared), "matrix"),
        "shared_out": ((shared, d), "matrix"),
        "router": ((d, cfg["scored_experts"]), "matrix"),
        "w_gate": ((held, d, f), "routed"), "w_up": ((held, d, f), "routed"),
        "w_down": ((held, f, d), "routed"),
    }
    if layer_types(cfg)[layer] == MAMBA:
        heads, entries = cfg["mamba_n_heads"], cfg["mamba_d_state"]
        inner = heads * cfg["mamba_d_head"]
        channels = inner + 2 * entries
        out.update(
            in_proj=((d, inner + channels + heads), "matrix"),
            conv_w=((channels, cfg["mamba_d_conv"]), "conv"),
            conv_b=((channels,), "matrix"),
            dt_bias=((heads,), "dt_bias"), a_log=((heads,), "a_log"),
            d=((heads,), "skip"), norm_g=((inner,), "norm"),
            out_proj=((inner, d), "matrix"))
    else:
        heads, kv_heads, dim = (cfg["num_attention_heads"],
                                cfg["num_key_value_heads"], cfg["head_dim"])
        out.update(
            wq=((d, heads * dim), "query"),
            wk=((d, kv_heads * dim), "matrix"),
            wv=((d, kv_heads * dim), "matrix"),
            wo=((heads * dim, d), "matrix"))
    return out


def _draw(spec, key, name, shape, kind):
    """qwen3next_decode's `_draw` for its kinds (pangu's among them);
    `A_log` = log U(1, 16) and `D` = 1 + N(0, std), float32 both."""
    import jax
    import jax.numpy as jnp

    if kind == "skip":
        return _pangu._draw(dict(spec, dtype="float32"), key, name, shape,
                            "norm")
    if kind != "a_log":
        return _qwen._draw(spec, key, name, shape, kind)
    drawn = jax.random.uniform(
        jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF),
        shape, jnp.float32)
    return jnp.log(1.0 + 15.0 * drawn)


def block(cfg, spec, key, layer):
    """The parameters of block `layer` from the `root` key.  Pure jax."""
    return {name: _draw(spec, key, "block_%d.%s" % (layer, name), shape,
                        kind)
            for name, (shape, kind) in _shapes(cfg, layer).items()}


def ends(cfg, spec, key):
    """{"embed", "norm_f"} from the `root` key: the table is also the
    head."""
    d, vocab = cfg["hidden_size"], cfg["vocab_size"]
    return {"embed": _draw(spec, key, "embed", (vocab, d), "embed"),
            "norm_f": _draw(spec, key, "norm_f", (d,), "norm")}


def weights(cfg, spec, key):
    """Every parameter from a seeded key in the type it is served in, as
    the tree benchmark/reference/granite_moe_hybrid.py documents.  Pure
    jax: call it under one `jax.jit`."""
    key = root(key)
    tree = ends(cfg, spec, key)
    tree["blocks"] = [block(cfg, spec, key, i)
                      for i in range(cfg["num_hidden_layers"])]
    return tree
