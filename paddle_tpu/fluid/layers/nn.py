"""Neural-network layers: op-builder DSL.

TPU-native equivalent of reference layers
(reference: python/paddle/v2/fluid/layers/nn.py — fc:69, embedding:190,
conv2d:912, pool2d, batch_norm:1250, dropout, cross_entropy, accuracy …).
Each function appends ops to the current block; nothing executes here.
"""

import numpy as np

from ..layer_helper import LayerHelper
from ..framework import Variable
from ..initializer import Constant, LogScale, Normal, Xavier
from ..param_attr import ParamAttr

__all__ = [
    "fc", "embedding", "dropout", "cross_entropy", "square_error_cost",
    "accuracy", "softmax", "conv2d", "pool2d", "batch_norm", "topk",
    "chunk_eval", "matmul", "l2_normalize", "one_hot",
    "softmax_with_cross_entropy", "sigmoid_cross_entropy_with_logits",
    "sequence_conv", "sequence_pool", "sequence_first_step",
    "sequence_last_step", "sequence_expand", "sequence_reshape", "lstm_unit",
    "reduce_sum", "reduce_mean", "reduce_max", "reduce_min", "transpose",
    "cos_sim", "clip", "clip_by_norm", "layer_norm", "split", "warpctc",
    "nce", "im2sequence", "row_conv", "multiplex", "smooth_l1",
    "linear_chain_crf", "crf_decoding", "lrn", "conv2d_transpose",
    "dynamic_lstm", "dynamic_gru", "gru_unit", "sequence_softmax",
    "sequence_slice", "lod_reset", "edit_distance", "ctc_greedy_decoder",
    "sequence_concat", "beam_search", "beam_search_decode",
    "sequence_reverse", "sequence_unnest", "sequence_renest",
    "flash_attention", "cached_attention", "mla_cached_attention",
    "mla_index_select", "rms_norm", "rope", "moe", "hc_maps", "hc_pre",
    "hc_post",
    "ssd_scan", "causal_conv1d", "gated_delta_rule", "expand", "gather", "slice", "cumsum",
    "selective_scan", "diff_combine",
]


def cached_attention(query, key, value, k_cache, v_cache, position,
                     num_heads=1, sm_scale=None, num_kv_heads=None,
                     window=0, name=None, selected=None, live=None,
                     reader=0, shared_readers=0, prefill_block=None,
                     diffusion_block=0):
    """Attention through a KV cache over a block of T >= 1 consecutive
    positions of every row (ops/attention.py cached_attention; T = 1 is
    a decode step): query [batch, T, num_heads * head_dim], key/value
    [batch, T, kv heads * head_dim], caches [batch, kv heads, slots,
    head_dim], position int [1] or [batch], the position of the block's
    first entry (query i attends slots 0 .. position + i).  T may be
    left open (-1) in the Program.  `num_kv_heads` fewer than
    `num_heads`: grouped-query attention, query head j reads key/value
    head j // (num_heads / num_kv_heads).  `window` > 0 (T = 1): the
    caches are rings of `window` slots written at position mod window,
    and a query sees itself and the window - 1 positions before it.
    With `selected` int32 [batch, top_k] and `live` int32 [batch]
    (`mla_index_select`'s two; whole-extent caches) a step attends the
    slots `selected` names, the first `live` of each row, one set for
    every key/value head; a block of T > 1 positions takes a set a
    position, `selected` [batch, T, top_k] and `live` [batch, T].
    `prefill_block`: the most positions a block of this op was sized
    for, which `fluid.ProgramDecoder` reads off the Program to prefill
    by (a longer block is refused).  `diffusion_block` B > 0: the
    block-causal mask of generation by diffusion over blocks, T a
    multiple of B and query i attends slots 0 .. position + B (i // B +
    1) - 1, to the end of its own block of B.
    Returns (out [batch, T, num_heads * head_dim], k_cache_out,
    v_cache_out) — thread the cache outputs back as decode state
    (`fluid.ProgramDecoder` state pairs).

    With `key` and `value` None the layer **reads caches it does not
    write** (whole-extent, no chosen set) and returns `out` alone:
    `k_cache` and `v_cache` are then another layer's `k_cache_out` and
    `v_cache_out` of the same step (query i attends slots 0 .. position
    + i of them, the step's own among them), `reader` which of that
    cache's readers this one is, numbered from 1; the layer that writes
    such a cache states `shared_readers`, how many attend it, itself
    among them.  Both are counters' labels and change no arithmetic."""
    helper = LayerHelper("cached_attention", name=name)
    out = helper.create_tmp_variable(query.dtype)
    attrs = {"num_heads": int(num_heads),
             "sm_scale": float(sm_scale or 0.0)}
    if key is None:
        if value is not None or window or selected is not None:
            raise ValueError(
                "cached_attention: a layer that reads caches it does not "
                "write has neither key nor value, window or chosen set")
        if num_kv_heads and int(num_kv_heads) != int(num_heads):
            attrs["num_kv_heads"] = int(num_kv_heads)
        if reader:
            attrs["reader"] = int(reader)
        helper.append_op(
            type="cached_attention",
            inputs={"Q": [query], "KCache": [k_cache], "VCache": [v_cache],
                    "Position": [position]},
            outputs={"Out": [out]}, attrs=attrs)
        return out
    kc_out = helper.create_tmp_variable(k_cache.dtype)
    vc_out = helper.create_tmp_variable(v_cache.dtype)
    # said only where asked for: a Program without them is, attr for
    # attr, the Program it was
    if num_kv_heads and int(num_kv_heads) != int(num_heads):
        attrs["num_kv_heads"] = int(num_kv_heads)
    if window:
        attrs["window"] = int(window)
    if shared_readers:
        attrs["shared_readers"] = int(shared_readers)
    if prefill_block:
        attrs["prefill_block"] = int(prefill_block)
    if diffusion_block:
        attrs["diffusion_block"] = int(diffusion_block)
    inputs = {"Q": [query], "KNew": [key], "VNew": [value],
              "KCache": [k_cache], "VCache": [v_cache],
              "Position": [position]}
    if selected is not None:
        inputs.update(Selected=[selected], Live=[live])
    helper.append_op(
        type="cached_attention", inputs=inputs,
        outputs={"Out": [out], "KCacheOut": [kc_out],
                 "VCacheOut": [vc_out]},
        attrs=attrs)
    return out, kc_out, vc_out


def mla_cached_attention(q_nope, q_rope, c_new, r_new, cache, position,
                         num_heads, v_head_dim, uk_attr=None, uv_attr=None,
                         name=None, selected=None, live=None,
                         sm_scale=None, prefill_block=None, sink_attr=None):
    """One decode step of latent attention over a cache of latents, or a
    block of T consecutive steps at once
    (ops/attention.py mla_cached_attention): `q_nope` [batch, T,
    num_heads * nope] and `q_rope` [batch, T, num_heads * rope] (rotated)
    the queries of T >= 1 consecutive tokens of every row, `c_new`
    [batch, T, latent] (normed) and `r_new` [batch, T,
    rope] (rotated) the tokens' cache entries, `cache` [batch, positions,
    latent + rope], `position` int [1] or [batch], the slot the block's
    first token writes (query t attends slots 0 .. position + t).  Creates the keys' and
    values' up-projections [latent, num_heads * nope] and [latent,
    num_heads * v_head_dim], which the op absorbs; the scores' scale is
    the op's own, (nope + rope) ** -0.5, unless `sm_scale` gives one.
    With `selected` int32 [batch, top_k] and `live` int32 [batch]
    (`mla_index_select`'s two; [batch, T, top_k] and [batch, T] of a
    block of T > 1 positions, a set a position) each position attends
    the slots its set names, the first `live` of them, and not every
    slot up to its own.  `prefill_block`: the most
    positions a block of this op is sized for; the op carries it as an
    attr, refuses a longer block, and `fluid.ProgramDecoder` prefills a
    prompt through the step by the smallest its ops state.  `sink_attr`
    creates a float32 parameter [num_heads] (zeros at the start), a
    learned sink: a logit a head that joins the softmax's denominator and
    has no value.  Returns (out
    [batch, T, num_heads * v_head_dim], cache_out): thread `cache_out`
    back as decode state (`fluid.ProgramDecoder` state pairs)."""
    if (selected is None) != (live is None):
        raise ValueError("mla_cached_attention: `selected` and `live` "
                         "come together")
    helper = LayerHelper("mla_cached_attention", name=name)
    latent = int(c_new.shape[-1])
    nope = int(q_nope.shape[-1]) // int(num_heads)
    w_uk = helper.create_parameter(
        uk_attr or ParamAttr(), shape=[latent, num_heads * nope],
        dtype=q_nope.dtype, default_initializer=Xavier())
    w_uv = helper.create_parameter(
        uv_attr or ParamAttr(), shape=[latent, num_heads * v_head_dim],
        dtype=q_nope.dtype, default_initializer=Xavier())
    out = helper.create_tmp_variable(q_nope.dtype)
    cache_out = helper.create_tmp_variable(cache.dtype)
    inputs = {"QNope": [q_nope], "QRope": [q_rope], "CNew": [c_new],
              "RNew": [r_new], "Cache": [cache], "WUk": [w_uk],
              "WUv": [w_uv], "Position": [position]}
    attrs = {"num_heads": int(num_heads)}
    # an op carries only what it was given: the Program of a step that
    # attends every slot at the op's own scale stays what it was
    if selected is not None:
        inputs.update(Selected=[selected], Live=[live])
    if sink_attr is not None:
        inputs["Sink"] = [helper.create_parameter(
            sink_attr, shape=[num_heads], dtype="float32",
            default_initializer=Constant(0.0))]
    if sm_scale:
        attrs["sm_scale"] = float(sm_scale)
    if prefill_block:
        attrs["prefill_block"] = int(prefill_block)
    helper.append_op(
        type="mla_cached_attention", inputs=inputs,
        outputs={"Out": [out], "CacheOut": [cache_out]}, attrs=attrs)
    return out, cache_out


def mla_index_select(q, w, k_new, cache, position, num_heads, top_k,
                     scale=1.0, name=None):
    """One decode step of a learned chooser of cache slots, or a block
    of T consecutive steps at once (ops/attention.py mla_index_select):
    `q` [batch, T, num_heads * dim] (rotated) and `w` [batch, T,
    num_heads] the index queries and their weights of T >= 1
    consecutive tokens of every row, `k_new` [batch, T, dim] their index
    keys, `cache` [batch, positions, dim] the chooser's own cache,
    `position` int [1] or [batch], the slot the block's first token
    writes.  For query t a slot s <= position + t scores scale * sum_j
    w_t,j relu(q_t,j . k_s).  Returns (selected, live, cache_out):
    `selected` is each query's `top_k` best-scoring slots as a set, in
    ascending slot order, of which the first `live` are slots to attend:
    int32 [batch, top_k] and [batch] where `q` is declared one position
    a call, [batch, T, top_k] and [batch, T] where it declares a block
    axis (T may be left open, -1; such a step fed T = 1 gives a step's
    [batch, top_k] and [batch]).  Hand the two to `mla_cached_attention`
    (a step's or a block's) or `cached_attention` (a step's), thread
    `cache_out` back as decode state."""
    helper = LayerHelper("mla_index_select", name=name)
    selected = helper.create_tmp_variable("int32", stop_gradient=True)
    live = helper.create_tmp_variable("int32", stop_gradient=True)
    cache_out = helper.create_tmp_variable(cache.dtype)
    attrs = {"num_heads": int(num_heads), "top_k": int(top_k)}
    if scale != 1.0:
        attrs["scale"] = float(scale)
    helper.append_op(
        type="mla_index_select",
        inputs={"Q": [q], "W": [w], "KNew": [k_new], "Cache": [cache],
                "Position": [position]},
        outputs={"CacheOut": [cache_out], "Selected": [selected],
                 "Live": [live]}, attrs=attrs)
    return selected, live, cache_out


def hc_maps(x, p_attr=None, alpha_attr=None, bias_attr=None, epsilon=1e-6,
            magnitude=2.0, iterations=20, name=None):
    """The three mappings of one sub-layer's hyper-connection
    (ops/hyper_connection.py hc_maps) from the residual's streams `x`
    [batch, seq, n, hidden]: creates the float32 projections [n *
    hidden, n * n + 2 * n] (`pre | post | res`; N(0, 1 / (n hidden)), so
    that a normed token's products are of size 1), their three scalars
    [3] (ones) and biases [n * n + 2 * n] (zeros).  Returns (pre [batch,
    seq, n], post [batch, seq, n], res [batch, seq, n, n]) in float32:
    sigmoid, `magnitude` x sigmoid, and exp brought to a doubly
    stochastic matrix by `iterations` Sinkhorn steps."""
    helper = LayerHelper("hc_maps", name=name)
    n, hidden = int(x.shape[-2]), int(x.shape[-1])
    maps = n * n + 2 * n
    p = helper.create_parameter(
        p_attr or ParamAttr(), shape=[n * hidden, maps], dtype="float32",
        default_initializer=Normal(0.0, (n * hidden) ** -0.5))
    alpha = helper.create_parameter(
        alpha_attr or ParamAttr(), shape=[3], dtype="float32",
        default_initializer=Constant(1.0))
    bias = helper.create_parameter(
        bias_attr or ParamAttr(), shape=[maps], dtype="float32",
        default_initializer=Constant(0.0))
    pre, post, res = (helper.create_tmp_variable("float32",
                                                 stop_gradient=True)
                      for _ in range(3))
    helper.append_op(
        type="hc_maps",
        inputs={"X": [x], "P": [p], "Alpha": [alpha], "Bias": [bias]},
        outputs={"Pre": [pre], "Post": [post], "Res": [res]},
        attrs={"epsilon": float(epsilon), "magnitude": float(magnitude),
               "iterations": int(iterations)})
    return pre, post, res


def hc_pre(x, pre, name=None):
    """The sub-layer's input read off the streams `x` [batch, seq, n,
    hidden] (ops/hyper_connection.py hc_pre): sum_j pre[j] x_j, [batch,
    seq, hidden] in x's type."""
    helper = LayerHelper("hc_pre", name=name)
    out = helper.create_tmp_variable(x.dtype)
    helper.append_op(type="hc_pre", inputs={"X": [x], "Pre": [pre]},
                     outputs={"U": [out]})
    return out


def hc_post(x, res, post, y, name=None):
    """The streams after a sub-layer (ops/hyper_connection.py hc_post):
    x'_i = sum_j res[i, j] x_j + post[i] y for the streams `x` [batch,
    seq, n, hidden] and the sub-layer's output `y` [batch, seq,
    hidden]."""
    helper = LayerHelper("hc_post", name=name)
    out = helper.create_tmp_variable(x.dtype)
    helper.append_op(
        type="hc_post",
        inputs={"X": [x], "Res": [res], "Post": [post], "Y": [y]},
        outputs={"XOut": [out]})
    return out


def flash_attention(queries, keys, values, num_heads=1, causal=False,
                    sm_scale=None, sequence_parallel_axis="",
                    sequence_parallel_mode="ring", block_size=None,
                    name=None, window=0):
    """Fused multi-head attention over dense [batch, seq, dim] tensors.

    Exceeds the reference surface (python/paddle/v2/fluid/nets.py:338
    materializes the [T,T] probability matrix from composed ops): this
    lowers to the single `flash_attention` op whose kernel is the
    pallas online-softmax kernel (kernels/flash_attention.py) — TPU
    MXU blocks, no T×T in HBM, blockwise-recompute VJP.  With
    `sequence_parallel_axis` set and the program compiled under a mesh
    carrying that axis, the op runs sequence-parallel attention:
    mode "ring" rotates K/V over ICI neighbors while q/k/v stay
    sequence-sharded; mode "ulysses" all-to-alls the shard axis from
    sequence to heads and attends full sequences locally
    (parallel/ring.py).  The kernel chooses its block sizes from the
    shapes unless `block_size` names one.  Beside the result the op
    writes `Lse`, each score row's log-sum-exp as float32 [batch,
    heads, seq] whatever the compute type: the one statistic its
    gradient reads, kept so that the backward pass does not run the
    forward kernel again.  With `window` W > 0 (and `causal`) a query
    attends its last W keys alone, its own among them; the op carries
    the attr only then.
    """
    helper = LayerHelper("flash_attention", name=name)
    out = helper.create_tmp_variable(queries.dtype)
    lse = helper.create_tmp_variable("float32", stop_gradient=True)
    attrs = {"num_heads": int(num_heads), "causal": bool(causal),
             "sm_scale": float(sm_scale or 0.0),
             "sequence_parallel_axis": sequence_parallel_axis,
             "sequence_parallel_mode": sequence_parallel_mode,
             "block_size": int(block_size or 0)}
    if window:
        if not causal:
            raise ValueError("flash_attention: a window of %d keys bounds "
                             "a causal query; causal is False" % window)
        attrs["window"] = int(window)
    helper.append_op(
        type="flash_attention",
        inputs={"Q": [queries], "K": [keys], "V": [values]},
        outputs={"Out": [out], "Lse": [lse]}, attrs=attrs)
    return out


def dynamic_lstm(input, size, param_attr=None, bias_attr=None,
                 use_peepholes=True, is_reverse=False,
                 gate_activation="sigmoid", cell_activation="tanh",
                 candidate_activation="tanh", dtype="float32", **kwargs):
    """Dynamic-length LSTM over ragged input (reference: layers/nn.py:249
    dynamic_lstm, lstm_op.cc).  `input` is the 4*hidden projection (from
    fc); this layer adds the recurrent weight/bias and the scan."""
    helper = LayerHelper("lstm", param_attr=param_attr,
                         bias_attr=bias_attr, **kwargs)
    size = size // 4
    weight = helper.create_parameter(
        helper.param_attr, shape=[size, 4 * size], dtype=dtype)
    bias_size = [1, 7 * size] if use_peepholes else [1, 4 * size]
    bias = helper.create_parameter(helper.bias_attr or ParamAttr(),
                                   shape=bias_size, dtype=dtype,
                                   is_bias=True)
    hidden = helper.create_tmp_variable(dtype, lod_level=input.lod_level)
    cell = helper.create_tmp_variable(dtype, lod_level=input.lod_level)
    batch_gate = helper.create_tmp_variable(dtype, stop_gradient=True,
                                            lod_level=input.lod_level)
    batch_cell_pre_act = helper.create_tmp_variable(
        dtype, stop_gradient=True, lod_level=input.lod_level)
    helper.append_op(
        type="lstm",
        inputs={"Input": [input], "Weight": [weight], "Bias": [bias]},
        outputs={"Hidden": [hidden], "Cell": [cell],
                 "BatchGate": [batch_gate],
                 "BatchCellPreAct": [batch_cell_pre_act]},
        attrs={"use_peepholes": use_peepholes, "is_reverse": is_reverse,
               "gate_activation": gate_activation,
               "cell_activation": cell_activation,
               "candidate_activation": candidate_activation})
    return hidden, cell


def dynamic_gru(input, size, param_attr=None, bias_attr=None,
                is_reverse=False, gate_activation="sigmoid",
                candidate_activation="tanh", h_0=None, dtype="float32",
                **kwargs):
    """Dynamic GRU over ragged input (reference: layers/nn.py dynamic_gru,
    gru_op.cc); `input` is the 3*hidden projection."""
    helper = LayerHelper("gru", param_attr=param_attr,
                         bias_attr=bias_attr, **kwargs)
    weight = helper.create_parameter(
        helper.param_attr, shape=[size, 3 * size], dtype=dtype)
    bias = helper.create_parameter(helper.bias_attr or ParamAttr(),
                                   shape=[1, 3 * size], dtype=dtype,
                                   is_bias=True)
    hidden = helper.create_tmp_variable(dtype, lod_level=input.lod_level)
    batch_gate = helper.create_tmp_variable(dtype, stop_gradient=True)
    batch_reset = helper.create_tmp_variable(dtype, stop_gradient=True)
    batch_hidden = helper.create_tmp_variable(dtype, stop_gradient=True)
    inputs = {"Input": [input], "Weight": [weight], "Bias": [bias]}
    if h_0 is not None:
        inputs["H0"] = [h_0]
    helper.append_op(
        type="gru", inputs=inputs,
        outputs={"Hidden": [hidden], "BatchGate": [batch_gate],
                 "BatchResetHiddenPrev": [batch_reset],
                 "BatchHidden": [batch_hidden]},
        attrs={"is_reverse": is_reverse,
               "gate_activation": gate_activation,
               "activation": candidate_activation})
    return hidden


def gru_unit(input, hidden, size, param_attr=None, bias_attr=None,
             activation="tanh", gate_activation="sigmoid", **kwargs):
    """reference: layers/nn.py gru_unit, gru_unit_op.cc."""
    helper = LayerHelper("gru_unit", param_attr=param_attr,
                         bias_attr=bias_attr, **kwargs)
    dtype = input.dtype
    size = size // 3
    weight = helper.create_parameter(
        helper.param_attr, shape=[size, 3 * size], dtype=dtype)
    bias = helper.create_parameter(helper.bias_attr or ParamAttr(),
                                   shape=[1, 3 * size], dtype=dtype,
                                   is_bias=True)
    gate = helper.create_tmp_variable(dtype)
    reset_hidden_pre = helper.create_tmp_variable(dtype)
    updated_hidden = helper.create_tmp_variable(dtype)
    helper.append_op(
        type="gru_unit",
        inputs={"Input": [input], "HiddenPrev": [hidden],
                "Weight": [weight], "Bias": [bias]},
        outputs={"Gate": [gate], "ResetHiddenPrev": [reset_hidden_pre],
                 "Hidden": [updated_hidden]},
        attrs={"activation": activation,
               "gate_activation": gate_activation})
    return updated_hidden, reset_hidden_pre, gate


def sequence_softmax(x=None, input=None, **kwargs):
    x = x if x is not None else input
    helper = LayerHelper("sequence_softmax", **kwargs)
    out = helper.create_tmp_variable(x.dtype, lod_level=x.lod_level)
    helper.append_op(type="sequence_softmax", inputs={"X": [x]},
                     outputs={"Out": [out]})
    return out


def beam_search(pre_ids, ids, scores, beam_size, end_id, level=0,
                **kwargs):
    """Per-source top-k beam step (reference: layers/nn.py:1578
    beam_search over beam_search_op.cc)."""
    helper = LayerHelper("beam_search", **kwargs)
    selected_ids = helper.create_tmp_variable(dtype="int64",
                                              stop_gradient=True,
                                              lod_level=2)
    selected_scores = helper.create_tmp_variable(dtype="float32",
                                                 stop_gradient=True,
                                                 lod_level=2)
    helper.append_op(
        type="beam_search",
        inputs={"pre_ids": [pre_ids], "ids": [ids], "scores": [scores]},
        outputs={"selected_ids": [selected_ids],
                 "selected_scores": [selected_scores]},
        attrs={"beam_size": beam_size, "end_id": end_id, "level": level},
        infer_shape=False)
    return selected_ids, selected_scores


def beam_search_decode(ids, scores, **kwargs):
    """Backtrack per-step beam selections into full hypotheses
    (reference: beam_search_decode_op.cc).  ids/scores: TensorArray-like
    lists of the per-step selected ids/scores."""
    helper = LayerHelper("beam_search_decode", **kwargs)
    sentence_ids = helper.create_tmp_variable(dtype="int64",
                                              stop_gradient=True,
                                              lod_level=2)
    sentence_scores = helper.create_tmp_variable(dtype="float32",
                                                 stop_gradient=True,
                                                 lod_level=2)
    ids_list = list(ids) if isinstance(ids, (list, tuple)) else [ids]
    scores_list = (list(scores) if isinstance(scores, (list, tuple))
                   else [scores])
    helper.append_op(
        type="beam_search_decode",
        inputs={"Ids": ids_list, "Scores": scores_list},
        outputs={"SentenceIds": [sentence_ids],
                 "SentenceScores": [sentence_scores]},
        infer_shape=False)
    return sentence_ids, sentence_scores


def sequence_concat(input, axis=0, **kwargs):
    """Per-example concatenation of ragged inputs along time (axis=0) or
    features (axis=1) (reference: sequence_concat_op.cc)."""
    helper = LayerHelper("sequence_concat", input=input, **kwargs)
    inputs = helper.multiple_input()
    out = helper.create_tmp_variable(dtype=inputs[0].dtype,
                                     lod_level=inputs[0].lod_level)
    helper.append_op(type="sequence_concat",
                     inputs={"X": inputs},
                     outputs={"Out": [out]},
                     attrs={"axis": axis})
    return out


def sequence_slice(input, offset, length, **kwargs):
    helper = LayerHelper("sequence_slice", **kwargs)
    out = helper.create_tmp_variable(input.dtype, lod_level=1)
    helper.append_op(type="sequence_slice",
                     inputs={"X": [input], "Offset": [offset],
                             "Length": [length]},
                     outputs={"Out": [out]})
    return out


def lod_reset(x, y=None, target_lod=None, **kwargs):
    helper = LayerHelper("lod_reset", **kwargs)
    out = helper.create_tmp_variable(x.dtype, lod_level=1)
    if y is not None:
        helper.append_op(type="lod_reset",
                         inputs={"X": [x], "TargetLoD": [y]},
                         outputs={"Out": [out]})
    else:
        helper.append_op(type="lod_reset", inputs={"X": [x]},
                         outputs={"Out": [out]},
                         attrs={"target_lod": list(target_lod)})
    return out


def edit_distance(input, label, normalized=False, ignored_tokens=None,
                  **kwargs):
    """reference: edit_distance_op.cc."""
    helper = LayerHelper("edit_distance", **kwargs)
    out = helper.create_tmp_variable(dtype="float32", stop_gradient=True,
                                     shape=[-1, 1])
    seq_num = helper.create_tmp_variable(dtype="int32",
                                         stop_gradient=True, shape=[1])
    helper.append_op(
        type="edit_distance",
        inputs={"Hyps": [input], "Refs": [label]},
        outputs={"Out": [out], "SequenceNum": [seq_num]},
        attrs={"normalized": normalized,
               "ignored_tokens": ignored_tokens or []})
    return out, seq_num


def ctc_greedy_decoder(input, blank, **kwargs):
    """Greedy CTC decode of per-step class scores: argmax each step,
    merge repeats, drop blanks (reference: the topk + ctc_align_op.cc
    pair).  `input` is the ragged [T, num_classes] probs/logits
    sequence; an int input is taken as already-argmaxed ids."""
    helper = LayerHelper("ctc_align", **kwargs)
    ids = input
    if not np.issubdtype(np.dtype(str(input.dtype)), np.integer):
        _, ids = topk(input, 1)
    out = helper.create_tmp_variable(dtype="int32", stop_gradient=True)
    helper.append_op(type="ctc_align", inputs={"Input": [ids]},
                     outputs={"Output": [out]},
                     attrs={"blank": blank, "merge_repeated": True})
    return out


def fc(input, size, num_flatten_dims=1, param_attr=None, bias_attr=None,
       act=None, name=None, **kwargs):
    """Fully-connected layer (reference: layers/nn.py:69).  Lowered as one
    or more `mul` ops (MXU matmuls) + `sum` + bias + activation; XLA fuses
    the chain."""
    helper = LayerHelper("fc", input=input, size=size, act=act,
                         param_attr=param_attr, bias_attr=bias_attr,
                         name=name, **kwargs)
    dtype = helper.input_dtype

    mul_results = []
    for input_var, p_attr in helper.iter_inputs_and_params():
        input_shape = input_var.shape
        param_num_flatten = num_flatten_dims
        param_shape = [
            _prod(input_shape[param_num_flatten:])
        ] + [size]
        w = helper.create_parameter(p_attr, shape=param_shape, dtype=dtype)
        tmp = helper.create_tmp_variable(dtype,
                                         lod_level=input_var.lod_level)
        helper.append_op(
            type="mul", inputs={"X": [input_var], "Y": [w]},
            outputs={"Out": [tmp]},
            attrs={"x_num_col_dims": num_flatten_dims,
                   "y_num_col_dims": 1})
        mul_results.append(tmp)

    if len(mul_results) == 1:
        pre_bias = mul_results[0]
    else:
        pre_bias = helper.create_tmp_variable(dtype)
        helper.append_op(type="sum", inputs={"X": mul_results},
                         outputs={"Out": [pre_bias]})
    pre_act = helper.append_bias_op(pre_bias, dim_start=num_flatten_dims)
    return helper.append_activation(pre_act)


def _prod(dims):
    r = 1
    for d in dims:
        r *= int(d)
    return r


def embedding(input, size, is_sparse=False, padding_idx=None,
              param_attr=None, dtype="float32", **kwargs):
    """Lookup-table layer (reference: layers/nn.py:190, lookup_table_op.cc).
    is_sparse selects the SelectedRows gradient path."""
    helper = LayerHelper("embedding", param_attr=param_attr, **kwargs)
    w = helper.create_parameter(helper.param_attr, shape=size, dtype=dtype,
                                is_bias=False)
    tmp = helper.create_tmp_variable(dtype, lod_level=input.lod_level)
    helper.append_op(
        type="lookup_table", inputs={"Ids": [input], "W": [w]},
        outputs={"Out": [tmp]},
        attrs={"is_sparse": is_sparse,
               "padding_idx": -1 if padding_idx is None else padding_idx})
    return tmp


def dropout(x, dropout_prob, is_test=False, seed=None, **kwargs):
    helper = LayerHelper("dropout", **kwargs)
    out = helper.create_tmp_variable(x.dtype, lod_level=x.lod_level)
    mask = helper.create_tmp_variable(x.dtype, stop_gradient=True)
    helper.append_op(
        type="dropout", inputs={"X": [x]},
        outputs={"Out": [out], "Mask": [mask]},
        attrs={"dropout_prob": dropout_prob, "is_test": is_test,
               "fix_seed": seed is not None, "seed": seed or 0})
    return out


def cross_entropy(input, label, soft_label=False, **kwargs):
    helper = LayerHelper("cross_entropy", **kwargs)
    out = helper.create_tmp_variable(input.dtype,
                                     lod_level=input.lod_level)
    helper.append_op(
        type="cross_entropy", inputs={"X": [input], "Label": [label]},
        outputs={"Y": [out]}, attrs={"soft_label": soft_label})
    return out


def square_error_cost(input, label, **kwargs):
    """(input - label)^2, elementwise (reference: layers/nn.py
    square_error_cost builds elementwise_sub + square)."""
    helper = LayerHelper("square_error_cost", **kwargs)
    minus_out = helper.create_tmp_variable(input.dtype)
    helper.append_op(type="elementwise_sub",
                     inputs={"X": [input], "Y": [label]},
                     outputs={"Out": [minus_out]})
    square_out = helper.create_tmp_variable(input.dtype)
    helper.append_op(type="square", inputs={"X": [minus_out]},
                     outputs={"Out": [square_out]})
    return square_out


def accuracy(input, label, k=1, correct=None, total=None, **kwargs):
    """top-k accuracy (reference: layers/nn.py accuracy → top_k +
    accuracy ops)."""
    helper = LayerHelper("accuracy", **kwargs)
    topk_out = helper.create_tmp_variable(dtype=input.dtype)
    topk_indices = helper.create_tmp_variable(dtype="int32",
                                              stop_gradient=True)
    helper.append_op(
        type="top_k", inputs={"X": [input]},
        outputs={"Out": [topk_out], "Indices": [topk_indices]},
        attrs={"k": k})
    acc_out = helper.create_tmp_variable(dtype="float32",
                                         stop_gradient=True)
    if correct is None:
        correct = helper.create_tmp_variable(dtype="int32",
                                             stop_gradient=True)
    if total is None:
        total = helper.create_tmp_variable(dtype="int32",
                                           stop_gradient=True)
    helper.append_op(
        type="accuracy",
        inputs={"Out": [topk_out], "Indices": [topk_indices],
                "Label": [label]},
        outputs={"Accuracy": [acc_out], "Correct": [correct],
                 "Total": [total]})
    return acc_out


def topk(input, k, **kwargs):
    helper = LayerHelper("top_k", **kwargs)
    values = helper.create_tmp_variable(dtype=input.dtype)
    indices = helper.create_tmp_variable(dtype="int32", stop_gradient=True)
    helper.append_op(type="top_k", inputs={"X": [input]},
                     outputs={"Out": [values], "Indices": [indices]},
                     attrs={"k": k})
    return values, indices


def softmax(input, **kwargs):
    helper = LayerHelper("softmax", **kwargs)
    out = helper.create_tmp_variable(input.dtype,
                                     lod_level=input.lod_level)
    helper.append_op(type="softmax", inputs={"X": [input]},
                     outputs={"Out": [out]})
    return out


def softmax_with_cross_entropy(logits, label, soft_label=False, **kwargs):
    helper = LayerHelper("softmax_with_cross_entropy", **kwargs)
    softmax_v = helper.create_tmp_variable(logits.dtype)
    loss = helper.create_tmp_variable(logits.dtype)
    helper.append_op(
        type="softmax_with_cross_entropy",
        inputs={"Logits": [logits], "Label": [label]},
        outputs={"Softmax": [softmax_v], "Loss": [loss]},
        attrs={"soft_label": soft_label})
    return loss


def sigmoid_cross_entropy_with_logits(x, label, **kwargs):
    helper = LayerHelper("sigmoid_cross_entropy_with_logits", **kwargs)
    out = helper.create_tmp_variable(x.dtype)
    helper.append_op(
        type="sigmoid_cross_entropy_with_logits",
        inputs={"X": [x], "Label": [label]}, outputs={"Out": [out]})
    return out


def conv2d(input, num_filters, filter_size, stride=None, padding=None,
           groups=None, param_attr=None, bias_attr=None, use_cudnn=True,
           act=None, name=None, **kwargs):
    """2-D convolution, NCHW (reference: layers/nn.py:912, conv_op.cc,
    conv_cudnn_op.cu.cc).  Lowers to XLA's fused convolution on the MXU —
    there is no separate cudnn variant to pick."""
    helper = LayerHelper("conv2d", input=input, act=act,
                         param_attr=param_attr, bias_attr=bias_attr,
                         name=name, **kwargs)
    dtype = input.dtype
    num_channels = input.shape[1]
    groups = groups or 1
    if num_channels % groups != 0:
        raise ValueError("num_channels must be divisible by groups")
    num_filter_channels = num_channels // groups
    filter_size = _pair(filter_size)
    stride = _pair(stride or 1)
    padding = _pair(padding or 0)

    filter_shape = [num_filters, num_filter_channels] + list(filter_size)
    std = (2.0 / (filter_size[0] * filter_size[1] * num_channels)) ** 0.5
    filter_param = helper.create_parameter(
        helper.param_attr, shape=filter_shape, dtype=dtype,
        default_initializer=Normal(0.0, std, 0))
    pre_bias = helper.create_tmp_variable(dtype)
    helper.append_op(
        type="conv2d",
        inputs={"Input": [input], "Filter": [filter_param]},
        outputs={"Output": [pre_bias]},
        attrs={"strides": list(stride), "paddings": list(padding),
               "groups": groups, "dilations": [1, 1]})
    pre_act = helper.append_bias_op(pre_bias, dim_start=1, dim_end=2)
    return helper.append_activation(pre_act)


def _pair(v):
    if isinstance(v, (list, tuple)):
        return list(v)
    return [v, v]


def conv2d_transpose(input, num_filters, output_size=None, filter_size=None,
                     padding=None, stride=None, dilation=None,
                     param_attr=None, use_cudnn=True, name=None, **kwargs):
    """reference: conv2d_transpose_op.cc."""
    helper = LayerHelper("conv2d_transpose", input=input,
                         param_attr=param_attr, name=name, **kwargs)
    dtype = input.dtype
    num_channels = input.shape[1]
    stride = _pair(stride or 1)
    padding = _pair(padding or 0)
    dilation = _pair(dilation or 1)
    if filter_size is None:
        if output_size is None:
            raise ValueError("need filter_size or output_size")
        output_size = _pair(output_size)
        h_in, w_in = input.shape[2], input.shape[3]
        filter_size = [
            (output_size[0] - (h_in - 1) * stride[0] + 2 * padding[0] - 1)
            // dilation[0] + 1,
            (output_size[1] - (w_in - 1) * stride[1] + 2 * padding[1] - 1)
            // dilation[1] + 1]
    else:
        filter_size = _pair(filter_size)
    filter_shape = [num_channels, num_filters] + list(filter_size)
    img_filter = helper.create_parameter(
        helper.param_attr, shape=filter_shape, dtype=dtype)
    out = helper.create_tmp_variable(dtype)
    helper.append_op(
        type="conv2d_transpose",
        inputs={"Input": [input], "Filter": [img_filter]},
        outputs={"Output": [out]},
        attrs={"strides": stride, "paddings": padding,
               "dilations": dilation})
    return out


def pool2d(input, pool_size, pool_type="max", pool_stride=None,
           pool_padding=None, global_pooling=False, use_cudnn=True,
           ceil_mode=False, name=None, **kwargs):
    """reference: layers/nn.py pool2d, pool_op.cc; lowers to XLA
    reduce-window."""
    if pool_type not in ("max", "avg"):
        raise ValueError("pool_type must be max|avg")
    helper = LayerHelper("pool2d", **kwargs)
    out = helper.create_tmp_variable(input.dtype)
    helper.append_op(
        type="pool2d", inputs={"X": [input]}, outputs={"Out": [out]},
        attrs={"pooling_type": pool_type,
               "ksize": _pair(pool_size),
               "global_pooling": global_pooling,
               "strides": _pair(pool_stride or 1),
               "paddings": _pair(pool_padding or 0),
               "ceil_mode": ceil_mode})
    return out


def batch_norm(input, act=None, is_test=False, momentum=0.9, epsilon=1e-5,
               param_attr=None, bias_attr=None, data_layout="NCHW",
               name=None, moving_mean_name=None, moving_variance_name=None,
               **kwargs):
    """Batch normalization (reference: layers/nn.py:1250,
    batch_norm_op.cc).  Lowers to fused normalize-and-scale; the moving
    stats are persistable state updated in-graph."""
    helper = LayerHelper("batch_norm", param_attr=param_attr,
                         bias_attr=bias_attr, act=act, name=name, **kwargs)
    dtype = input.dtype
    input_shape = input.shape
    if data_layout == "NCHW":
        channel_num = input_shape[1]
    elif data_layout == "NHWC":
        channel_num = input_shape[-1]
    else:
        raise ValueError("unsupported data_layout %r" % data_layout)
    param_shape = [channel_num]

    scale = helper.create_parameter(
        helper.param_attr or ParamAttr(), shape=param_shape, dtype=dtype,
        default_initializer=Constant(1.0))
    bias = helper.create_parameter(
        helper.bias_attr or ParamAttr(), shape=param_shape, dtype=dtype,
        is_bias=True)

    mean = helper.create_global_variable(
        name=moving_mean_name, dtype=dtype, shape=param_shape,
        persistable=True)
    helper.set_variable_initializer(mean, Constant(0.0))
    variance = helper.create_global_variable(
        name=moving_variance_name, dtype=dtype, shape=param_shape,
        persistable=True)
    helper.set_variable_initializer(variance, Constant(1.0))

    saved_mean = helper.create_tmp_variable(dtype, stop_gradient=True)
    saved_variance = helper.create_tmp_variable(dtype, stop_gradient=True)
    out = helper.create_tmp_variable(dtype)

    helper.append_op(
        type="batch_norm",
        inputs={"X": [input], "Scale": [scale], "Bias": [bias],
                "Mean": [mean], "Variance": [variance]},
        outputs={"Y": [out], "MeanOut": [mean], "VarianceOut": [variance],
                 "SavedMean": [saved_mean],
                 "SavedVariance": [saved_variance]},
        attrs={"momentum": momentum, "epsilon": epsilon,
               "is_test": is_test, "data_layout": data_layout})
    return helper.append_activation(out)


def layer_norm(input, scale=True, shift=True, begin_norm_axis=1,
               epsilon=1e-5, param_attr=None, bias_attr=None, act=None,
               **kwargs):
    helper = LayerHelper("layer_norm", param_attr=param_attr,
                         bias_attr=bias_attr, act=act, **kwargs)
    dtype = input.dtype
    param_shape = [_prod(input.shape[begin_norm_axis:])]
    inputs = {"X": [input]}
    if scale:
        s = helper.create_parameter(helper.param_attr or ParamAttr(),
                                    shape=param_shape, dtype=dtype,
                                    default_initializer=Constant(1.0))
        inputs["Scale"] = [s]
    if shift:
        b = helper.create_parameter(helper.bias_attr or ParamAttr(),
                                    shape=param_shape, dtype=dtype,
                                    is_bias=True)
        inputs["Bias"] = [b]
    out = helper.create_tmp_variable(dtype)
    mean_out = helper.create_tmp_variable(dtype, stop_gradient=True)
    var_out = helper.create_tmp_variable(dtype, stop_gradient=True)
    helper.append_op(
        type="layer_norm", inputs=inputs,
        outputs={"Y": [out], "Mean": [mean_out], "Variance": [var_out]},
        attrs={"epsilon": epsilon, "begin_norm_axis": begin_norm_axis})
    return helper.append_activation(out)


def rms_norm(input, epsilon=1e-6, param_attr=None, **kwargs):
    """Root-mean-square normalisation over the last axis with a learned
    scale (ops/norm.py rms_norm): no mean, no bias."""
    helper = LayerHelper("rms_norm", param_attr=param_attr, **kwargs)
    scale = helper.create_parameter(
        helper.param_attr, shape=[input.shape[-1]], dtype=input.dtype,
        default_initializer=Constant(1.0))
    out = helper.create_tmp_variable(input.dtype)
    helper.append_op(type="rms_norm",
                     inputs={"X": [input], "Scale": [scale]},
                     outputs={"Y": [out]}, attrs={"epsilon": epsilon})
    return out


def rope(input, positions, num_heads, theta=10000.0, inv_freq=None,
         rotary_dim=None, full_width=False, sections=None, **kwargs):
    """Rotary position embedding on each head of `input` [batch, seq,
    num_heads * head_dim] at `positions` [batch, seq] (ops/attention.py
    rope): rotate-half form, base `theta`, or the rates `inv_freq` (a
    list, one a pair: `ops.attention.yarn_inv_freq` makes YaRN's) in
    place of theta's powers; `rotary_dim` turns the first so many values
    of every head and hands on the rest.  `full_width`: a step that takes
    a block of positions has the op turn a block where it lies, the same
    numbers with no view of half heads (see the op).  `sections` (three
    pair counts that add up to the rotated pairs) with `positions` [3,
    batch, seq]: a token's temporal, height and width positions, pair i
    turned by the component its section names."""
    helper = LayerHelper("rope", **kwargs)
    out = helper.create_tmp_variable(input.dtype)
    attrs = {"num_heads": int(num_heads), "theta": float(theta)}
    if inv_freq is not None:
        attrs["inv_freq"] = [float(f) for f in inv_freq]
    if rotary_dim:
        attrs["rotary_dim"] = int(rotary_dim)
    if full_width:
        attrs["full_width"] = True
    if sections:
        attrs["sections"] = [int(n) for n in sections]
    helper.append_op(type="rope",
                     inputs={"X": [input], "Positions": [positions]},
                     outputs={"Out": [out]}, attrs=attrs)
    return out


def moe(input, num_experts, expert_size, top_k, router_attr=None,
        gate_attr=None, up_attr=None, down_attr=None, name=None,
        scoring="softmax", norm_topk=False, scale=1.0, held=None,
        bias_attr=None, n_group=0, topk_group=0, activation="silu",
        router_input=None, swiglu_limit=None):
    """A routed expert layer over `input` [..., hidden] (ops/moe.py): a
    float32 router sends every token to its `top_k` of `num_experts`
    gated-SiLU experts of width `expert_size`, each computed for it (no
    capacity, nothing dropped), and the token adds them up weighted by
    their router probabilities as they are.  The experts' weights are
    three parameters, [num_experts, hidden, expert_size] twice and
    [num_experts, expert_size, hidden], initialised with one expert's
    fans.  Returns (out, lb_loss, z_loss, routing): the layer's output,
    its load-balance and router z-loss (float32 [1] each, to be added
    to the objective with their coefficients) and a dict of the
    router's Variables "logits" [tokens, num_experts], "top_w" and
    "top_idx" [tokens, top_k] and the experts' "counts" [num_experts],
    the rows each expert was given.

    Under `scoring="sigmoid"`, `bias_attr` creates a selection bias
    [num_experts] (zeros at the start) that is added to the scores for
    the choice and not for the weights, and `n_group` > 1 limits the
    choice to the experts of a token's `topk_group` best groups of
    consecutive experts (ops/moe.py moe_router).

    `held` = (first, count) makes the layer one chip's share of an
    expert-parallel one: it holds `count` of the experts its router
    scores, and its output and every gradient are those experts' part.
    `activation` "relu" makes the experts ReGLU; `swiglu_limit` L clamps
    them, act(min(gate, L)) * clip(up, -L, L).  With `router_input`
    [..., hidden] the router scores that tensor and the experts still
    read `input` (a router placed before the attention sub-layer).
    """
    helper = LayerHelper("moe", name=name)
    hidden = int(input.shape[-1])
    dtype = input.dtype

    def param(attr, shape, init):
        return helper.create_parameter(
            attr or ParamAttr(), shape=shape, dtype=dtype,
            default_initializer=init)

    first, count = held or (0, num_experts)
    if not 0 <= first <= first + count <= num_experts:
        raise ValueError("moe: held experts %d..%d are not among the %d "
                         "scored" % (first, first + count, num_experts))
    w_router = param(router_attr, [hidden, num_experts], Xavier())
    stacked = Xavier(stacked=True)
    w_gate = param(gate_attr, [count, hidden, expert_size], stacked)
    w_up = param(up_attr, [count, hidden, expert_size], stacked)
    w_down = param(down_attr, [count, expert_size, hidden], stacked)

    def tmp(dtype, stop_gradient=False):
        return helper.create_tmp_variable(dtype, stop_gradient=stop_gradient)

    # an op carries only what differs from the softmax layer that holds
    # every expert: that layer's Program stays attr for attr what it was
    routing = {"scoring": scoring, "norm_topk": bool(norm_topk),
               "scale": float(scale)}
    if routing == {"scoring": "softmax", "norm_topk": False, "scale": 1.0}:
        routing = {}
    share = {} if (first, count) == (0, num_experts) else {
        "first_expert": int(first), "scored": int(num_experts)}
    router_ins = {"X": [input if router_input is None else router_input],
                  "W": [w_router]}
    if activation != "silu":
        share = dict(share, activation=str(activation))
    if swiglu_limit:
        share = dict(share, swiglu_limit=float(swiglu_limit))
    if bias_attr is not None:
        router_ins["Bias"] = [helper.create_parameter(
            bias_attr, shape=[num_experts], dtype="float32", is_bias=True)]
    if n_group and n_group > 1:
        routing.update(n_group=int(n_group), topk_group=int(topk_group))
    logits, top_w = tmp("float32"), tmp("float32")
    top_idx = tmp("int32", stop_gradient=True)
    lb_loss, z_loss = tmp("float32"), tmp("float32")
    helper.append_op(
        type="moe_router", inputs=router_ins,
        outputs={"Logits": [logits], "TopW": [top_w], "TopIdx": [top_idx],
                 "LbLoss": [lb_loss], "ZLoss": [z_loss]},
        attrs=dict({"top_k": int(top_k)}, **routing))
    out = tmp(dtype)
    kept = {slot: tmp("int32" if slot in ("RowSlot", "TokenRow", "Counts")
                      else dtype, stop_gradient=True)
            for slot in ("Xs", "Gate", "Up", "RowSlot", "TokenRow",
                         "Counts")}
    helper.append_op(
        type="moe_experts",
        inputs={"X": [input], "TopW": [top_w], "TopIdx": [top_idx],
                "WGate": [w_gate], "WUp": [w_up], "WDown": [w_down]},
        outputs=dict({"Out": [out]}, **{s: [v] for s, v in kept.items()}),
        attrs=share)
    return out, lb_loss, z_loss, {"logits": logits, "top_w": top_w,
                                  "top_idx": top_idx,
                                  "counts": kept["Counts"]}


def ssd_scan(x, dt, b, c, num_heads, chunk_size=256, a_log_attr=None,
             d_attr=None, dt_bias_attr=None, name=None, state=None):
    """Mamba-2's selective state-space scan (ops/ssm.py ssd_scan) over
    `x` [batch, seq, num_heads * head_dim] with the steps `dt` [batch,
    seq, num_heads] as the projection gives them (the op adds its bias
    and takes the softplus, in float32) and the shared `b`, `c` [batch,
    seq, d_state]: per head S_t = exp(dt_t A) S_{t-1} + dt_t x_t b_t^T,
    y_t = S_t c_t + D x_t, computed `chunk_size` positions at a time.
    Three float32 parameters of [num_heads]: `ALog` (A = -exp(ALog);
    log of a uniform [1, 16]), `D` (ones) and `DtBias` (the inverse
    softplus of a step drawn log-uniformly from [1e-3, 1e-1]), as
    arXiv:2405.21060 initialises them, so that decays lie between 0.2
    and 0.999 a step.  `seq` must be a multiple of `chunk_size`.

    With `state` [batch, d_state, num_heads * head_dim] float32, what
    the positions before the block left (zeros at a sequence's start;
    `ops.ssm.heads_apart` gives it a head at a time), the scan starts
    from it and the layer returns (out, state_out): thread `state_out`
    back as decode state (`fluid.ProgramDecoder` state pairs).  `seq`
    is then one position (a decode step: one update of the state) or a
    multiple of `chunk_size`, and may be left open (-1); the op says
    `prefill_block` = `chunk_size`, the positions `fluid.ProgramDecoder`
    prefills a prompt by.  Forward only."""
    helper = LayerHelper("ssd_scan", name=name)

    def param(attr, init):
        return helper.create_parameter(
            attr or ParamAttr(), shape=[num_heads], dtype="float32",
            default_initializer=init)

    a_log = param(a_log_attr, LogScale(1.0, 16.0, "log_uniform"))
    d_skip = param(d_attr, Constant(1.0))
    dt_bias = param(dt_bias_attr,
                    LogScale(1e-3, 1e-1, "inverse_softplus_log_uniform"))
    out = helper.create_tmp_variable(x.dtype)
    inputs = {"X": [x], "Dt": [dt], "DtBias": [dt_bias],
              "ALog": [a_log], "B": [b], "C": [c], "D": [d_skip]}
    attrs = {"num_heads": int(num_heads), "chunk_size": int(chunk_size)}
    if state is not None:
        state_out = helper.create_tmp_variable("float32",
                                               stop_gradient=True)
        helper.append_op(
            type="ssd_scan", inputs=dict(inputs, State=[state]),
            outputs={"Y": [out], "StateOut": [state_out]},
            attrs=dict(attrs, prefill_block=int(chunk_size)))
        return out, state_out
    states = helper.create_tmp_variable("float32", stop_gradient=True)
    helper.append_op(
        type="ssd_scan", inputs=inputs,
        outputs={"Y": [out], "States": [states]}, attrs=attrs)
    return out


def causal_conv1d(input, filter_size=4, activation="silu", param_attr=None,
                  bias_attr=None, name=None, tail=None):
    """Causal depthwise convolution over the sequence axis of `input`
    [batch, seq, channels] (ops/ssm.py causal_conv1d): out_t =
    act(bias + sum_j filter[:, j] x_{t-(filter_size-1)+j}), zeros before
    position 0, each channel by itself; `activation` "silu" or None.

    With `tail` [batch, filter_size - 1, channels], the positions before
    the block, position 0 reads them in place of zeros and the layer
    returns (out, tail_out): thread `tail_out` back as decode state
    (`fluid.ProgramDecoder` state pairs).  `bias_attr=False`, beside a
    tail alone: no bias."""
    helper = LayerHelper("causal_conv1d", name=name)
    channels = int(input.shape[-1])
    filt = helper.create_parameter(
        param_attr or ParamAttr(), shape=[channels, filter_size],
        dtype=input.dtype,
        default_initializer=Xavier(fan_in=filter_size,
                                   fan_out=filter_size))
    inputs = {"X": [input], "Filter": [filt]}
    if bias_attr is not False:
        inputs["Bias"] = [helper.create_parameter(
            bias_attr or ParamAttr(), shape=[channels], dtype=input.dtype,
            default_initializer=Constant(0.0))]
    elif tail is None:
        raise ValueError("causal_conv1d: bias_attr=False is a cached "
                         "step's (beside `tail`)")
    out = helper.create_tmp_variable(input.dtype)
    outputs = {"Out": [out]}
    if tail is not None:
        inputs["Tail"] = [tail]
        outputs["TailOut"] = [helper.create_tmp_variable(tail.dtype)]
    helper.append_op(
        type="causal_conv1d", inputs=inputs, outputs=outputs,
        attrs={"activation": activation or ""})
    return out if tail is None else (out, outputs["TailOut"][0])


def gated_delta_rule(q, k, v, g, beta, state, qk_l2norm=True, chunk=64,
                     name=None, gate_floor=None, state_pack=1):
    """The gated delta rule over a block of T >= 1 consecutive positions
    of every row, through a recurrent state (ops/linear_attention.py
    gated_delta_rule; T = 1 is a decode step): `q`, `k` [batch, T, key
    heads * key_dim], `v` [batch, T, value heads * value_dim], `g` (the
    log of a decay, <= 0) and `beta` [batch, T, value heads] float32,
    `state` [batch, value heads, key_dim, value_dim] float32.  Per value
    head, position by position: S = exp(g) S; S += k (beta (v - S^T
    k))^T; o = S^T q, with q and k l2-normed a head and q scaled by
    key_dim ** -0.5 under `qk_l2norm`; value head j reads key head j //
    (value heads / key heads).  With `g` [batch, T, value heads *
    key_dim] the gate is one a key channel (Kimi Delta Attention): S =
    diag(exp(g)) S.  Such a caller states `gate_floor`, the least value
    an element of `g` takes (< 0), from which the block form's
    sub-blocks are sized so that its one growing factor stays inside
    float32 (the op's `sub_chunk`).  `beta`'s range is the caller's: (0,
    1), or (0, 2) where negative eigenvalues are allowed.  With
    `state_pack` p > 1, `state` is [batch, value heads / p, key_dim, p *
    value_dim], p heads side by side (kernels/gdn_step.py `pack_state`:
    a state of 192 values a head is not padded to 256 in HBM).  T may be
    left open (-1) in the Program.
    Returns (out [batch, T, value heads * value_dim], state_out): thread
    `state_out` back as decode state (`fluid.ProgramDecoder` state
    pairs).  Forward only."""
    helper = LayerHelper("gated_delta_rule", name=name)
    out = helper.create_tmp_variable(v.dtype)
    state_out = helper.create_tmp_variable(state.dtype)
    attrs = {"qk_l2norm": bool(qk_l2norm), "chunk": int(chunk)}
    # an op carries only what it was given: a gate a head's is the op it
    # was
    if gate_floor is not None:
        from ...ops.linear_attention import sub_chunk
        attrs["sub_chunk"] = sub_chunk(int(chunk), float(gate_floor))
    if state_pack != 1:
        attrs["state_pack"] = int(state_pack)
    helper.append_op(
        type="gated_delta_rule",
        inputs={"Q": [q], "K": [k], "V": [v], "G": [g], "Beta": [beta],
                "State": [state]},
        outputs={"Out": [out], "StateOut": [state_out]}, attrs=attrs)
    return out, state_out


def selective_scan(x, dt, b, c, state, d_state, a_log_attr=None,
                   d_attr=None, dt_bias_attr=None, name=None):
    """Mamba-1's selective scan over a block of T >= 1 consecutive
    positions of every row, through a carried state (ops/ssm.py
    selective_scan; T = 1 is a decode step): `x` and `dt` (before the
    softplus) [batch, T, channels], `b` and `c` [batch, T, d_state],
    `state` [batch, d_state, channels] float32.  A channel and state
    entry at a time: S = exp(dt A) S + dt x B; y = S . C + D x, with dt =
    softplus(dt + dt_bias) and A = -exp(a_log).  Creates `a_log`
    [channels, d_state] (decay rates log-uniform on [1, d_state]), `d`
    (ones) and `dt_bias` [channels] (steps log-uniform on [0.001, 0.1],
    as `ssd_scan`'s), float32.  T may
    be left open (-1) in the Program.  Returns (out [batch, T, channels],
    state_out): thread `state_out` back as decode state
    (`fluid.ProgramDecoder` state pairs).  Forward only."""
    helper = LayerHelper("selective_scan", name=name)
    channels = int(x.shape[-1])
    a_log = helper.create_parameter(
        a_log_attr or ParamAttr(), shape=[channels, d_state],
        dtype="float32",
        default_initializer=LogScale(1.0, float(d_state), "log_uniform"))
    d_skip = helper.create_parameter(
        d_attr or ParamAttr(), shape=[channels], dtype="float32",
        default_initializer=Constant(1.0))
    dt_bias = helper.create_parameter(
        dt_bias_attr or ParamAttr(), shape=[channels], dtype="float32",
        default_initializer=LogScale(1e-3, 1e-1,
                                     "inverse_softplus_log_uniform"))
    out = helper.create_tmp_variable(x.dtype)
    state_out = helper.create_tmp_variable(state.dtype)
    helper.append_op(
        type="selective_scan",
        inputs={"X": [x], "Dt": [dt], "DtBias": [dt_bias],
                "ALog": [a_log], "B": [b], "C": [c], "D": [d_skip],
                "State": [state]},
        outputs={"Out": [out], "StateOut": [state_out]})
    return out, state_out


def diff_combine(x, width, lambda_init, epsilon=1e-5, lambda_attrs=None,
                 scale_attr=None, subtract=True, name=None):
    """The second half of differential attention (ops/attention.py
    diff_combine): `x` [batch, T, 2 * pairs * width] holds, a pair of
    heads, the pair's two attention maps applied to its `width` values,
    side by side; the layer gives (1 - lambda_init) * RMSNorm(first -
    lambda * second) [batch, T, pairs * width] with lambda = exp(lq1 .
    lk1) - exp(lq2 . lk2) + lambda_init.  Creates the four [width // 2]
    vectors (`lambda_attrs`: four ParamAttr in the order lq1, lk1, lq2,
    lk2; N(0, 0.1) at the start) and the norm's scale [width] (ones),
    float32.  `subtract=False` drops the second map (a control)."""
    helper = LayerHelper("diff_combine", name=name)
    inputs = {"X": [x]}
    for slot, attr in zip(("LambdaQ1", "LambdaK1", "LambdaQ2", "LambdaK2"),
                          lambda_attrs or (None,) * 4):
        inputs[slot] = [helper.create_parameter(
            attr or ParamAttr(), shape=[width // 2], dtype="float32",
            default_initializer=Normal(0.0, 0.1))]
    inputs["Scale"] = [helper.create_parameter(
        scale_attr or ParamAttr(), shape=[width], dtype="float32",
        default_initializer=Constant(1.0))]
    attrs = {"width": int(width), "lambda_init": float(lambda_init),
             "epsilon": float(epsilon)}
    if not subtract:
        attrs["subtract"] = False
    out = helper.create_tmp_variable(x.dtype)
    helper.append_op(type="diff_combine", inputs=inputs,
                     outputs={"Out": [out]}, attrs=attrs)
    return out


def gather(input, index, **kwargs):
    """The rows of `input` [rows, ...] that `index` (int, any shape, read
    flat) names, in its order (the `gather` op; reference:
    gather_op.cc)."""
    helper = LayerHelper("gather", **kwargs)
    out = helper.create_tmp_variable(input.dtype)
    helper.append_op(type="gather", inputs={"X": [input], "Index": [index]},
                     outputs={"Out": [out]})
    return out


def expand(x, expand_times, **kwargs):
    """`x` tiled `expand_times[i]` times along axis i (the `expand` op)."""
    helper = LayerHelper("expand", **kwargs)
    out = helper.create_tmp_variable(x.dtype)
    helper.append_op(type="expand", inputs={"X": [x]},
                     outputs={"Out": [out]},
                     attrs={"expand_times": [int(t) for t in expand_times]})
    return out


def lrn(input, n=5, k=1.0, alpha=1e-4, beta=0.75, **kwargs):
    helper = LayerHelper("lrn", **kwargs)
    out = helper.create_tmp_variable(input.dtype)
    mid = helper.create_tmp_variable(input.dtype, stop_gradient=True)
    helper.append_op(type="lrn", inputs={"X": [input]},
                     outputs={"Out": [out], "MidOut": [mid]},
                     attrs={"n": n, "k": k, "alpha": alpha, "beta": beta})
    return out


def transpose(x, perm, **kwargs):
    helper = LayerHelper("transpose", **kwargs)
    out = helper.create_tmp_variable(x.dtype)
    helper.append_op(type="transpose", inputs={"X": [x]},
                     outputs={"Out": [out]}, attrs={"axis": list(perm)})
    return out


def matmul(x, y, transpose_x=False, transpose_y=False, name=None, **kwargs):
    helper = LayerHelper("matmul", **kwargs)
    out = helper.create_tmp_variable(x.dtype)
    helper.append_op(
        type="matmul", inputs={"X": [x], "Y": [y]}, outputs={"Out": [out]},
        attrs={"transpose_X": transpose_x, "transpose_Y": transpose_y})
    return out


def cos_sim(X, Y, **kwargs):
    helper = LayerHelper("cos_sim", **kwargs)
    out = helper.create_tmp_variable(X.dtype)
    xnorm = helper.create_tmp_variable(X.dtype)
    ynorm = helper.create_tmp_variable(X.dtype)
    helper.append_op(type="cos_sim", inputs={"X": [X], "Y": [Y]},
                     outputs={"Out": [out], "XNorm": [xnorm],
                              "YNorm": [ynorm]})
    return out


def clip(x, min, max, **kwargs):
    helper = LayerHelper("clip", **kwargs)
    out = helper.create_tmp_variable(x.dtype)
    helper.append_op(type="clip", inputs={"X": [x]}, outputs={"Out": [out]},
                     attrs={"min": min, "max": max})
    return out


def clip_by_norm(x, max_norm, **kwargs):
    helper = LayerHelper("clip_by_norm", **kwargs)
    out = helper.create_tmp_variable(x.dtype)
    helper.append_op(type="clip_by_norm", inputs={"X": [x]},
                     outputs={"Out": [out]}, attrs={"max_norm": max_norm})
    return out


def l2_normalize(x, axis, epsilon=1e-12, **kwargs):
    helper = LayerHelper("l2_normalize", **kwargs)
    out = helper.create_tmp_variable(x.dtype)
    helper.append_op(type="norm", inputs={"X": [x]}, outputs={"Out": [out]},
                     attrs={"axis": axis, "epsilon": epsilon})
    return out


def one_hot(input, depth, **kwargs):
    helper = LayerHelper("one_hot", **kwargs)
    out = helper.create_tmp_variable(dtype="float32")
    helper.append_op(type="one_hot", inputs={"X": [input]},
                     outputs={"Out": [out]}, attrs={"depth": depth})
    return out


def _reduce_layer(op_type):
    def layer(input, dim=None, keep_dim=False, name=None, **kwargs):
        helper = LayerHelper(op_type, name=name, **kwargs)
        out = helper.create_tmp_variable(input.dtype)
        attrs = {"keep_dim": keep_dim,
                 "reduce_all": dim is None,
                 "dim": 0 if dim is None else dim}
        helper.append_op(type=op_type, inputs={"X": [input]},
                         outputs={"Out": [out]}, attrs=attrs)
        return out
    layer.__name__ = op_type
    return layer


reduce_sum = _reduce_layer("reduce_sum")
reduce_mean = _reduce_layer("reduce_mean")
reduce_max = _reduce_layer("reduce_max")
reduce_min = _reduce_layer("reduce_min")


def split(input, num_or_sections, dim=-1, **kwargs):
    helper = LayerHelper("split", **kwargs)
    input_shape = input.shape
    dim = dim if dim >= 0 else dim + len(input_shape)
    if isinstance(num_or_sections, int):
        num = num_or_sections
        sections = []
    else:
        num = len(num_or_sections)
        sections = list(num_or_sections)
    outs = [helper.create_tmp_variable(input.dtype,
                                       lod_level=input.lod_level
                                       if dim != 0 else 0)
            for _ in range(num)]
    helper.append_op(type="split", inputs={"X": [input]},
                     outputs={"Out": outs},
                     attrs={"axis": dim, "sections": sections, "num":
                            0 if sections else num})
    return outs


def slice(input, axes, starts, ends, own_layout=False, **kwargs):
    """`input[starts[i]:ends[i]]` along each of `axes` (reference:
    slice_op.cc): a negative index counts from the end, so `starts=[-1],
    ends=[2 ** 31 - 1]` is the last element of an axis whose extent the
    Program leaves open.  `own_layout`: the result is an array of its
    own, laid out as it is declared, whatever reads it (a few rows cut
    from a large array and read transposed: without it the compiler may
    lay the whole array out anew for the transpose's sake, a copy of all
    of it where one of the rows would do)."""
    helper = LayerHelper("slice", **kwargs)
    out = helper.create_tmp_variable(input.dtype)
    attrs = {"axes": list(axes), "starts": list(starts), "ends": list(ends)}
    if own_layout:
        attrs["own_layout"] = True
    helper.append_op(type="slice", inputs={"Input": [input]},
                     outputs={"Out": [out]}, attrs=attrs)
    return out


def cumsum(x, axis=-1, exclusive=False, **kwargs):
    """Running sum along `axis` (reference: cum_op.cc); `exclusive`
    leaves each element out of its own sum."""
    helper = LayerHelper("cumsum", **kwargs)
    out = helper.create_tmp_variable(x.dtype)
    helper.append_op(type="cumsum", inputs={"X": [x]},
                     outputs={"Out": [out]},
                     attrs={"axis": axis, "exclusive": exclusive})
    return out


def multiplex(inputs, index, **kwargs):
    helper = LayerHelper("multiplex", **kwargs)
    out = helper.create_tmp_variable(inputs[0].dtype)
    helper.append_op(type="multiplex",
                     inputs={"X": inputs, "Ids": [index]},
                     outputs={"Out": [out]})
    return out


def smooth_l1(x, y, inside_weight=None, outside_weight=None, sigma=None,
              **kwargs):
    helper = LayerHelper("smooth_l1_loss", **kwargs)
    diff = helper.create_tmp_variable(x.dtype, stop_gradient=True)
    loss = helper.create_tmp_variable(x.dtype)
    inputs = {"X": [x], "Y": [y]}
    if inside_weight is not None:
        inputs["InsideWeight"] = [inside_weight]
    if outside_weight is not None:
        inputs["OutsideWeight"] = [outside_weight]
    helper.append_op(type="smooth_l1_loss", inputs=inputs,
                     outputs={"Diff": [diff], "Out": [loss]},
                     attrs={"sigma": sigma or 1.0})
    return loss


# --- sequence layers (ragged ops; defined in ops/sequence.py) -------------

def sequence_conv(input, num_filters, filter_size=3, filter_stride=1,
                  padding=None, bias_attr=None, param_attr=None, act=None,
                  **kwargs):
    """reference: layers/nn.py sequence_conv, sequence_conv_op.cc."""
    helper = LayerHelper("sequence_conv", input=input, act=act,
                         param_attr=param_attr, bias_attr=bias_attr,
                         **kwargs)
    dtype = input.dtype
    filter_shape = [filter_size * input.shape[1], num_filters]
    filter_param = helper.create_parameter(
        helper.param_attr, shape=filter_shape, dtype=dtype)
    pre_bias = helper.create_tmp_variable(dtype, lod_level=input.lod_level)
    helper.append_op(
        type="sequence_conv",
        inputs={"X": [input], "Filter": [filter_param]},
        outputs={"Out": [pre_bias]},
        attrs={"contextStride": filter_stride, "contextStart":
               -int(filter_size // 2), "contextLength": filter_size})
    pre_act = helper.append_bias_op(pre_bias)
    return helper.append_activation(pre_act)


def sequence_pool(input, pool_type, **kwargs):
    helper = LayerHelper("sequence_pool", input=input, **kwargs)
    out = helper.create_tmp_variable(input.dtype)
    max_index = helper.create_tmp_variable(dtype="int32",
                                           stop_gradient=True)
    helper.append_op(
        type="sequence_pool", inputs={"X": [input]},
        outputs={"Out": [out], "MaxIndex": [max_index]},
        attrs={"pooltype": pool_type.upper()})
    return out


def sequence_first_step(input, **kwargs):
    return sequence_pool(input, "first", **kwargs)


def sequence_last_step(input, **kwargs):
    return sequence_pool(input, "last", **kwargs)


def sequence_reverse(x, **kwargs):
    """Reverse each sequence's time order (reference: reversed inlinks of
    RecurrentLayerGroup, api parity with later sequence_reverse op)."""
    helper = LayerHelper("sequence_reverse", input=x, **kwargs)
    out = helper.create_tmp_variable(x.dtype, lod_level=x.lod_level)
    helper.append_op(type="sequence_reverse", inputs={"X": [x]},
                     outputs={"Y": [out]})
    return out


def sequence_expand(x, y, **kwargs):
    helper = LayerHelper("sequence_expand", input=x, **kwargs)
    out = helper.create_tmp_variable(x.dtype, lod_level=y.lod_level)
    helper.append_op(type="sequence_expand",
                     inputs={"X": [x], "Y": [y]}, outputs={"Out": [out]})
    return out


def sequence_unnest(x, **kwargs):
    """Flatten a nested (lod_level-2) sequence's outer level into the
    batch: returns (inner, outer_ref) where `inner` is the lod-1 batch
    of all subsequences and `outer_ref` carries the outer row_splits for
    sequence_renest (the compiled lowering of the reference's
    nested-sequence mode, RecurrentGradientMachine.h:32)."""
    helper = LayerHelper("sequence_unnest", input=x, **kwargs)
    inner = helper.create_tmp_variable(x.dtype, lod_level=1)
    outer_ref = helper.create_tmp_variable("float32", lod_level=1)
    helper.append_op(type="seq_unnest", inputs={"X": [x]},
                     outputs={"Inner": [inner], "OuterRef": [outer_ref]})
    return inner, outer_ref


def sequence_renest(x, outer_ref, **kwargs):
    """Reattach outer row_splits dropped by sequence_unnest: dense
    per-subsequence rows become a sentence-level lod-1 sequence; a
    lod-1 ragged becomes the full lod-2 nested sequence."""
    helper = LayerHelper("sequence_renest", input=x, **kwargs)
    lod = 2 if x.lod_level else 1
    out = helper.create_tmp_variable(x.dtype, lod_level=lod)
    helper.append_op(type="seq_renest",
                     inputs={"X": [x], "OuterRef": [outer_ref]},
                     outputs={"Out": [out]})
    return out


def sequence_reshape(input, new_dim, **kwargs):
    helper = LayerHelper("sequence_reshape", **kwargs)
    out = helper.create_tmp_variable(input.dtype, lod_level=1)
    helper.append_op(type="sequence_reshape", inputs={"X": [input]},
                     outputs={"Out": [out]}, attrs={"new_dim": new_dim})
    return out


def lstm_unit(x_t, hidden_t_prev, cell_t_prev, forget_bias=0.0,
              param_attr=None, bias_attr=None, **kwargs):
    """One LSTM step on dense tensors (reference: layers/nn.py lstm_unit,
    lstm_unit_op.cc)."""
    helper = LayerHelper("lstm_unit", param_attr=param_attr,
                         bias_attr=bias_attr, **kwargs)
    size = cell_t_prev.shape[1]
    concat_out = concat_ = fc(
        input=[x_t, hidden_t_prev], size=4 * size,
        param_attr=param_attr, bias_attr=bias_attr, act=None)
    c = helper.create_tmp_variable(x_t.dtype)
    h = helper.create_tmp_variable(x_t.dtype)
    helper.append_op(
        type="lstm_unit",
        inputs={"X": [concat_out], "C_prev": [cell_t_prev]},
        outputs={"C": [c], "H": [h]},
        attrs={"forget_bias": forget_bias})
    return h, c


def im2sequence(input, filter_size=1, stride=1, padding=0, **kwargs):
    helper = LayerHelper("im2sequence", **kwargs)
    out = helper.create_tmp_variable(input.dtype, lod_level=1)
    helper.append_op(
        type="im2sequence", inputs={"X": [input]}, outputs={"Out": [out]},
        attrs={"kernels": _pair(filter_size), "strides": _pair(stride),
               "paddings": _pair(padding) + _pair(padding)})
    return out


def row_conv(input, future_context_size, param_attr=None, act=None,
             **kwargs):
    helper = LayerHelper("row_conv", param_attr=param_attr, act=act,
                         **kwargs)
    dtype = input.dtype
    filter_shape = [future_context_size + 1, input.shape[1]]
    filter_param = helper.create_parameter(helper.param_attr,
                                           shape=filter_shape, dtype=dtype)
    out = helper.create_tmp_variable(dtype, lod_level=input.lod_level)
    helper.append_op(type="row_conv",
                     inputs={"X": [input], "Filter": [filter_param]},
                     outputs={"Out": [out]})
    return helper.append_activation(out)


def warpctc(input, label, blank=0, norm_by_times=False, **kwargs):
    """CTC loss on ragged logits/labels (reference: warpctc_op.cc — here a
    native XLA lowering, no libwarpctc)."""
    helper = LayerHelper("warpctc", **kwargs)
    loss_out = helper.create_tmp_variable(input.dtype)
    grad_out = helper.create_tmp_variable(input.dtype,
                                          stop_gradient=True)
    helper.append_op(
        type="warpctc", inputs={"Logits": [input], "Label": [label]},
        outputs={"WarpCTCGrad": [grad_out], "Loss": [loss_out]},
        attrs={"blank": blank, "norm_by_times": norm_by_times})
    return loss_out


def nce(input, label, num_total_classes, sample_weight=None,
        param_attr=None, bias_attr=None, num_neg_samples=None, **kwargs):
    """Noise-contrastive estimation (reference: nce_op.cc)."""
    helper = LayerHelper("nce", param_attr=param_attr, bias_attr=bias_attr,
                         **kwargs)
    dim = input.shape[1]
    num_neg = num_neg_samples or 10
    w = helper.create_parameter(helper.param_attr,
                                shape=[num_total_classes, dim],
                                dtype=input.dtype)
    inputs = {"Input": [input], "Label": [label], "Weight": [w]}
    if helper.bias_attr is not None:
        b = helper.create_parameter(helper.bias_attr,
                                    shape=[num_total_classes, 1],
                                    dtype=input.dtype, is_bias=True)
        inputs["Bias"] = [b]
    if sample_weight is not None:
        inputs["SampleWeight"] = [sample_weight]
    cost = helper.create_tmp_variable(input.dtype)
    sample_logits = helper.create_tmp_variable(input.dtype,
                                               stop_gradient=True)
    sample_labels = helper.create_tmp_variable(dtype="int32",
                                               stop_gradient=True)
    helper.append_op(
        type="nce", inputs=inputs,
        outputs={"Cost": [cost], "SampleLogits": [sample_logits],
                 "SampleLabels": [sample_labels]},
        attrs={"num_total_classes": num_total_classes,
               "num_neg_samples": num_neg})
    return cost


def linear_chain_crf(input, label, param_attr=None, **kwargs):
    """reference: linear_chain_crf_op.cc."""
    helper = LayerHelper("linear_chain_crf", param_attr=param_attr,
                         **kwargs)
    size = input.shape[1]
    transition = helper.create_parameter(
        helper.param_attr, shape=[size + 2, size], dtype=input.dtype)
    alpha = helper.create_tmp_variable(input.dtype, stop_gradient=True)
    emission_exps = helper.create_tmp_variable(input.dtype,
                                               stop_gradient=True)
    transition_exps = helper.create_tmp_variable(input.dtype,
                                                 stop_gradient=True)
    log_likelihood = helper.create_tmp_variable(input.dtype)
    helper.append_op(
        type="linear_chain_crf",
        inputs={"Emission": [input], "Transition": [transition],
                "Label": [label]},
        outputs={"Alpha": [alpha], "EmissionExps": [emission_exps],
                 "TransitionExps": [transition_exps],
                 "LogLikelihood": [log_likelihood]})
    return log_likelihood


def crf_decoding(input, param_attr, label=None, **kwargs):
    helper = LayerHelper("crf_decoding", **kwargs)
    transition = helper.main_program.global_block().var(
        ParamAttr.to_attr(param_attr).name)
    viterbi_path = helper.create_tmp_variable(dtype="int32",
                                              stop_gradient=True)
    inputs = {"Emission": [input], "Transition": [transition]}
    if label is not None:
        inputs["Label"] = [label]
    helper.append_op(type="crf_decoding", inputs=inputs,
                     outputs={"ViterbiPath": [viterbi_path]})
    return viterbi_path


def chunk_eval(input, label, chunk_scheme, num_chunk_types,
               excluded_chunk_types=None, **kwargs):
    helper = LayerHelper("chunk_eval", **kwargs)
    precision = helper.create_tmp_variable(dtype="float32",
                                           stop_gradient=True)
    recall = helper.create_tmp_variable(dtype="float32",
                                        stop_gradient=True)
    f1 = helper.create_tmp_variable(dtype="float32", stop_gradient=True)
    num_infer = helper.create_tmp_variable(dtype="int32",
                                           stop_gradient=True)
    num_label = helper.create_tmp_variable(dtype="int32",
                                           stop_gradient=True)
    num_correct = helper.create_tmp_variable(dtype="int32",
                                             stop_gradient=True)
    helper.append_op(
        type="chunk_eval", inputs={"Inference": [input], "Label": [label]},
        outputs={"Precision": [precision], "Recall": [recall],
                 "F1-Score": [f1], "NumInferChunks": [num_infer],
                 "NumLabelChunks": [num_label],
                 "NumCorrectChunks": [num_correct]},
        attrs={"chunk_scheme": chunk_scheme,
               "num_chunk_types": num_chunk_types,
               "excluded_chunk_types": excluded_chunk_types or []})
    return precision, recall, f1, num_infer, num_label, num_correct
