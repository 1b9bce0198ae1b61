"""ResNet-50 as a fluid Program, from the numbers of a configuration file.

The network is the program's own `paddle_tpu.models.image.resnet`; this
file only asks for it at the configuration's depth and sizes, checks that
what was built has the filter shapes the configuration states, and reads
the parameter names off the op descs in layer order so that the plain
reference (benchmark/reference/resnet50.py) can be given the program's
own weights.
"""

FEED_NAMES = ("image", "label")


def filter_shapes(cfg):
    """[K, C, kh, kw] of every convolution in creation order: stem, then
    per block (shortcut when the shape changes), 1x1, 3x3, 1x1."""
    exp = cfg["bottleneck_expansion"]
    shapes = [[cfg["stem_width"], cfg["channels"], 7, 7]]
    ch_in = cfg["stem_width"]
    for stage, (width, blocks) in enumerate(
            zip(cfg["stage_widths"], cfg["stage_blocks"])):
        for block in range(blocks):
            stride = 2 if (stage > 0 and block == 0) else 1
            if ch_in != width * exp or stride != 1:
                shapes.append([width * exp, ch_in, 1, 1])
            shapes += [[width, ch_in, 1, 1], [width, width, 3, 3],
                       [width * exp, width, 1, 1]]
            ch_in = width * exp
    return shapes


def param_names(program):
    """Parameter names by layer, read from the forward ops in order."""
    names = {"conv": [], "bn": [], "fc": None}
    ops = program.global_block().desc.ops
    for i, od in enumerate(ops):
        if od.type == "conv2d":
            names["conv"].append(od.input("Filter")[0])
        elif od.type == "batch_norm":
            names["bn"].append(tuple(
                od.input(slot)[0]
                for slot in ("Scale", "Bias", "Mean", "Variance")))
        elif od.type == "mul":
            names["fc"] = (od.input("Y")[0], ops[i + 1].input("Y")[0])
    return names


def build(cfg, batch, train):
    """The Program pair and what a driver needs to run it.

    train: forward, loss, backward and the configuration's optimizer on
    a fixed batch; otherwise the inference clone ending in softmax, with
    a free batch dimension as `save_inference_model` exports it."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.models import image as image_models

    size, channels = cfg["image_size"], cfg["channels"]
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        if train:
            image = fluid.layers.data(
                name="image", shape=[batch, channels, size, size],
                dtype="float32", append_batch_size=False)
        else:
            image = fluid.layers.data(
                name="image", shape=[channels, size, size],
                dtype="float32")
        logits = image_models.resnet(image, class_dim=cfg["class_dim"],
                                     depth=cfg["depth"])
        if train:
            label = fluid.layers.data(name="label", shape=[batch, 1],
                                      dtype="int64",
                                      append_batch_size=False)
            fetch = fluid.layers.mean(
                fluid.layers.softmax_with_cross_entropy(logits, label))
            opt = cfg["optimizer"]
            if opt["type"] != "momentum":
                raise ValueError("resnet50 builder: optimizer %r"
                                 % opt["type"])
            fluid.optimizer.MomentumOptimizer(
                learning_rate=opt["learning_rate"],
                momentum=opt["momentum"]).minimize(fetch)
        else:
            fetch = fluid.layers.softmax(logits)
    names = param_names(main)
    built = [list(main.global_block().var(n).shape) for n in names["conv"]]
    if built != filter_shapes(cfg):
        raise ValueError(
            "the program's ResNet does not have the filter shapes "
            "configuration %r states" % cfg["name"])
    if not train:
        main = main.clone(for_test=True)
    return {"main": main, "startup": startup,
            "feed_names": list(FEED_NAMES if train else FEED_NAMES[:1]),
            "fetch": fetch, "param_names": names,
            "items_per_step": batch}


def sample(cfg, batch, key):
    """One seeded batch (pure jax): uniform [0, 1) images, uniform labels."""
    import jax
    import jax.numpy as jnp

    k_image, k_label = jax.random.split(key)
    size, channels = cfg["image_size"], cfg["channels"]
    return {
        "image": jax.random.uniform(
            k_image, (batch, channels, size, size), jnp.float32),
        "label": jax.random.randint(
            k_label, (batch, 1), 0, cfg["class_dim"], jnp.int32),
    }
