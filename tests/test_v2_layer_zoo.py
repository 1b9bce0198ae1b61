"""v2 layer zoo: export surface + forward/backward checks for the
extended layers (reference: trainer_config_helpers/layers.py ~100
`*_layer` functions + tests/layers_test_config.py build-everything
style)."""

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
import paddle_tpu.v2 as paddle
from paddle_tpu.v2 import layer as v2_layer


def _forward(fetches, feeds):
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    outs = exe.run(fluid.default_main_program(), feed=feeds,
                   fetch_list=list(fetches))
    return [np.asarray(o) for o in outs]


def test_export_surface():
    """The DSL exports at least 80 layer names and every one resolves
    to a callable (the reference's layer surface: >= 80)."""
    assert len(v2_layer.__all__) >= 80, len(v2_layer.__all__)
    for n in v2_layer.__all__:
        assert callable(getattr(v2_layer, n)), n
    # the trainer_config_helpers DSL mirrors the reference *_layer names
    from paddle_tpu import trainer_config_helpers as tch

    for ref_name in ["maxout_layer", "spp_layer", "bilinear_interp_layer",
                     "tensor_layer", "conv_projection", "dotmul_operator",
                     "conv_operator", "scaling_projection",
                     "slice_projection", "trans_full_matrix_projection",
                     "nce_layer", "hsigmoid", "multibox_loss_layer",
                     "factorization_machine", "gated_unit_layer"]:
        assert callable(getattr(tch, ref_name)), ref_name


def test_mixed_layer_projection_family():
    """mixed() summing every projection type trains end to end."""
    x = paddle.layer.data(name="x",
                          type=paddle.data_type.dense_vector(8))
    y = paddle.layer.data(name="y",
                          type=paddle.data_type.dense_vector(8))
    out = paddle.layer.mixed(
        size=8,
        input=[
            paddle.layer.full_matrix_projection(input=x, size=8),
            paddle.layer.trans_full_matrix_projection(input=x, size=8),
            paddle.layer.scaling_projection(input=x),
            paddle.layer.slice_projection(input=x,
                                          slices=[(0, 4), (4, 8)]),
            paddle.layer.identity_projection(input=x),
            paddle.layer.dotmul_projection(input=x),
            paddle.layer.dotmul_operator(a=x, b=y),
        ])
    cost = paddle.layer.mse_cost(input=out, label=y)
    fluid.optimizer.SGD(learning_rate=0.1).minimize(cost)

    rs = np.random.RandomState(0)
    feeds = {"x": rs.rand(4, 8).astype(np.float32),
             "y": rs.rand(4, 8).astype(np.float32)}
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    losses = [float(np.asarray(exe.run(
        fluid.default_main_program(), feed=feeds,
        fetch_list=[cost])[0]).reshape(-1)[0]) for _ in range(6)]
    assert losses[-1] < losses[0], losses


def test_slice_projection_values():
    x = paddle.layer.data(name="x",
                          type=paddle.data_type.dense_vector(6))
    out = paddle.layer.mixed(input=[
        paddle.layer.slice_projection(input=x, slices=[(1, 3), (5, 6)])])
    feeds = {"x": np.arange(12, dtype=np.float32).reshape(2, 6)}
    got, = _forward([out], feeds)
    np.testing.assert_allclose(got, [[1, 2, 5], [7, 8, 11]])


def test_slice_projection_rejects_bad_ranges():
    x = paddle.layer.data(name="x",
                          type=paddle.data_type.dense_vector(6))
    with pytest.raises(ValueError):
        paddle.layer.slice_projection(input=x, slices=[(4, 9)])


def test_conv_projection_and_operator():
    img = paddle.layer.data(
        name="img", type=paddle.data_type.dense_vector(3 * 8 * 8))
    img4 = fluid.layers.reshape(x=img, shape=[-1, 3, 8, 8])
    filt = paddle.layer.data(
        name="filt", type=paddle.data_type.dense_vector(2 * 3 * 3 * 3))
    proj_out = paddle.layer.mixed(input=[
        paddle.layer.conv_projection(input=img4, filter_size=3,
                                     num_filters=2, padding=1)])
    op_out = paddle.layer.mixed(input=[
        paddle.layer.conv_operator(img=img4, filter=filt, filter_size=3,
                                   num_filters=2, padding=1)])
    rs = np.random.RandomState(0)
    feeds = {"img": rs.rand(2, 3 * 8 * 8).astype(np.float32),
             "filt": rs.rand(2, 2 * 3 * 3 * 3).astype(np.float32)[:1]
             .repeat(2, 0)}
    a, b = _forward([proj_out, op_out], feeds)
    assert a.shape == (2, 2, 8, 8) and b.shape == (2, 2, 8, 8)
    assert np.isfinite(a).all() and np.isfinite(b).all()


def test_elementwise_zoo_forward():
    x = paddle.layer.data(name="x",
                          type=paddle.data_type.dense_vector(8))
    y = paddle.layer.data(name="y",
                          type=paddle.data_type.dense_vector(8))
    w = paddle.layer.data(name="w",
                          type=paddle.data_type.dense_vector(1))
    fetches = [
        paddle.layer.interpolation(input=[x, y], weight=w),
        paddle.layer.power(input=x, weight=w),
        paddle.layer.sum_to_one_norm(input=x),
        paddle.layer.row_l2_norm(input=x),
        paddle.layer.dot_prod(a=x, b=y),
        paddle.layer.l2_distance(a=x, b=y),
        paddle.layer.clip(input=x, min=0.2, max=0.8),
        paddle.layer.scale_shift(input=x),
        paddle.layer.repeat(input=x, num_repeats=2),
        paddle.layer.resize(input=x, size=4),
        paddle.layer.out_prod(a=x, b=y),
        paddle.layer.factorization_machine(input=x, factor_size=3),
        paddle.layer.gated_unit(input=x, size=5),
        paddle.layer.tensor(a=x, b=y, size=3),
        paddle.layer.selective_fc(input=x, size=6),
    ]
    rs = np.random.RandomState(1)
    feeds = {"x": rs.rand(4, 8).astype(np.float32) + 0.1,
             "y": rs.rand(4, 8).astype(np.float32) + 0.1,
             "w": rs.rand(4, 1).astype(np.float32)}
    outs = _forward(fetches, feeds)
    shapes = [o.shape for o in outs]
    assert shapes[0] == (4, 8)            # interpolation
    assert shapes[4] == (4, 1)            # dot_prod
    assert shapes[5] == (4, 1)            # l2_distance
    assert shapes[8] == (4, 16)           # repeat
    assert shapes[9] == (8, 4)            # resize
    assert shapes[10] == (4, 64)          # out_prod (flattened, as ref)
    assert shapes[13] == (4, 3)           # tensor
    for o in outs:
        assert np.isfinite(o).all()
    # clip actually clips
    assert outs[6].min() >= 0.2 and outs[6].max() <= 0.8


def test_image_zoo_forward():
    img = paddle.layer.data(
        name="img", type=paddle.data_type.dense_vector(4 * 8 * 8))
    x = fluid.layers.reshape(x=img, shape=[-1, 4, 8, 8])
    fetches = [
        paddle.layer.maxout(input=x, groups=2),
        paddle.layer.spp(input=x, pyramid_height=2),
        paddle.layer.img_cmrnorm(input=x, size=3),
        paddle.layer.pad(input=x, pad_c=(0, 0), pad_h=(1, 1),
                         pad_w=(1, 1)),
        paddle.layer.bilinear_interp(input=x, out_size_x=16,
                                     out_size_y=16),
        paddle.layer.switch_order(input=x),
        paddle.layer.block_expand(input=x, block_x=4, block_y=4,
                                  stride_x=4, stride_y=4),
    ]
    rs = np.random.RandomState(2)
    feeds = {"img": rs.rand(2, 4 * 8 * 8).astype(np.float32)}
    outs = _forward(fetches, feeds)
    assert outs[0].shape == (2, 2, 8, 8)     # maxout: c/groups
    assert outs[1].shape[0] == 2             # spp flattens
    assert outs[2].shape == (2, 4, 8, 8)     # lrn
    assert outs[3].shape == (2, 4, 10, 10)   # pad
    assert outs[4].shape == (2, 4, 16, 16)   # bilinear
    assert outs[5].shape == (2, 8, 8, 4)     # NCHW->NHWC
    for o in outs:
        assert np.isfinite(np.asarray(o, dtype=object).astype(
            np.float32)).all() if o.dtype != object else True


def test_cost_zoo():
    left = paddle.layer.data(name="l",
                             type=paddle.data_type.dense_vector(1))
    right = paddle.layer.data(name="r",
                              type=paddle.data_type.dense_vector(1))
    lbl = paddle.layer.data(name="lab",
                            type=paddle.data_type.dense_vector(1))
    x = paddle.layer.data(name="x",
                          type=paddle.data_type.dense_vector(4))
    multi_lbl = paddle.layer.data(
        name="mlab", type=paddle.data_type.dense_vector(4))
    fetches = [
        paddle.layer.rank_cost(left=left, right=right, label=lbl),
        paddle.layer.huber_regression_cost(input=left, label=lbl),
        paddle.layer.huber_classification_cost(input=left, label=lbl),
        paddle.layer.smooth_l1_cost(input=x, label=multi_lbl),
        paddle.layer.multi_binary_label_cross_entropy(
            input=x, label=multi_lbl),
    ]
    rs = np.random.RandomState(3)
    sig = 1 / (1 + np.exp(-rs.randn(4, 4).astype(np.float32)))
    feeds = {"l": rs.rand(4, 1).astype(np.float32),
             "r": rs.rand(4, 1).astype(np.float32),
             "lab": (rs.rand(4, 1) > 0.5).astype(np.float32),
             "x": sig,
             "mlab": (rs.rand(4, 4) > 0.5).astype(np.float32)}
    outs = _forward(fetches, feeds)
    for o in outs:
        assert o.size == 1 and np.isfinite(o).all(), o


def test_multibox_loss_bipartite_guarantee():
    """A gt box whose best prior IoU is below the threshold must still
    produce a positive match (reference MultiBoxLossLayer.cpp matches
    every gt to its best prior unconditionally first)."""
    import jax.numpy as jnp
    from paddle_tpu.ops.registry import get_op_info
    from paddle_tpu.core.ragged import RaggedTensor

    P, C = 2, 2
    pboxes = np.array([[0.0, 0.0, 0.2, 0.2], [0.8, 0.8, 1.0, 1.0]],
                      np.float32)
    prior = np.concatenate([pboxes, np.full((P, 4), 0.1, np.float32)])
    # one tiny gt barely overlapping prior 0: IoU << 0.5
    gt = RaggedTensor(jnp.asarray([[0.15, 0.15, 0.5, 0.5]], jnp.float32),
                      [jnp.asarray([0, 1], jnp.int32)])
    lab = RaggedTensor(jnp.asarray([[1]], jnp.int32),
                       [jnp.asarray([0, 1], jnp.int32)])
    kernel = get_op_info("multibox_loss").kernel
    out = kernel(None, {
        "Loc": [jnp.zeros((1, P * 4))], "Conf": [jnp.zeros((1, P * C))],
        "PriorBox": [jnp.asarray(prior)], "GtBox": [gt],
        "GtLabel": [lab]}, {"num_classes": C})
    loss = float(np.asarray(out["Loss"][0]).reshape(-1)[0])
    assert loss > 0.0, loss  # the object is learned, not dropped


def test_multibox_loss_trains():
    """SSD loss: loc/conf heads + priors + ragged gt, loss decreases
    under SGD (reference: MultiBoxLossLayer.cpp semantics)."""
    P, C = 6, 3
    feat = fluid.layers.data(name="feat", shape=[16], dtype="float32")
    loc = fluid.layers.fc(input=feat, size=P * 4)
    conf = fluid.layers.fc(input=feat, size=P * C)
    prior = fluid.layers.data(name="prior", shape=[2 * P, 4],
                              dtype="float32",
                              append_batch_size=False)
    gt_box = fluid.layers.data(name="gt_box", shape=[4],
                               dtype="float32", lod_level=1)
    gt_lab = fluid.layers.data(name="gt_lab", shape=[1],
                               dtype="int64", lod_level=1)
    cost = paddle.layer.multibox_loss(
        input_loc=loc, input_conf=conf, priorbox=prior, label=gt_lab,
        gt_box=gt_box, num_classes=C)
    fluid.optimizer.SGD(learning_rate=0.05).minimize(cost)

    pboxes = np.array(
        [[0.0, 0.0, 0.4, 0.4], [0.3, 0.3, 0.7, 0.7],
         [0.6, 0.6, 1.0, 1.0], [0.0, 0.5, 0.5, 1.0],
         [0.5, 0.0, 1.0, 0.5], [0.2, 0.2, 0.8, 0.8]], np.float32)
    prior_np = np.concatenate([pboxes, np.full((P, 4), 0.1,
                                               np.float32)], 0)
    rs = np.random.RandomState(0)
    place = fluid.CPUPlace()
    feeder = fluid.DataFeeder(feed_list=[feat, gt_box, gt_lab],
                              place=place)
    samples = [
        (rs.rand(16).astype(np.float32),
         [[0.05, 0.05, 0.35, 0.35], [0.55, 0.55, 0.95, 0.95]],
         [[1], [2]]),
        (rs.rand(16).astype(np.float32),
         [[0.25, 0.25, 0.75, 0.75]],
         [[1]]),
    ]
    feeds = feeder.feed(samples)
    feeds["prior"] = prior_np
    exe = fluid.Executor(place)
    exe.run(fluid.default_startup_program())
    losses = [float(np.asarray(exe.run(
        fluid.default_main_program(), feed=feeds,
        fetch_list=[cost])[0]).reshape(-1)[0]) for _ in range(8)]
    assert all(np.isfinite(l) for l in losses), losses
    assert losses[-1] < losses[0], losses
