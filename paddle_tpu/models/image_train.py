"""Model-name -> training-program builder for the image models.

Turns the image-model names into `builder(model)(batch)` callables
that construct the training topology of
`__graft_entry__._build_model` (concrete-shape feeds,
softmax-with-cross-entropy loss, Momentum update), so the tools that
take a `--model` name (`pmem`, `pshard`) and `chip_smoke.py` describe
the same program."""

__all__ = ["MODELS", "builder", "model_names"]

# channels / default image size / default class count per model —
# lenet5 is the canonical 1x28x28 MNIST topology (the selftests'
# flagship); the rest are the reference benchmark set's shapes
MODELS = {
    "lenet5": dict(channels=1, image_size=28, class_dim=10),
    "smallnet": dict(channels=3, image_size=32, class_dim=10),
    "alexnet": dict(channels=3, image_size=224, class_dim=1000),
    "vgg16": dict(channels=3, image_size=224, class_dim=1000),
    "vgg19": dict(channels=3, image_size=224, class_dim=1000),
    "googlenet": dict(channels=3, image_size=224, class_dim=1000),
    "resnet50": dict(channels=3, image_size=224, class_dim=1000),
}


def model_names():
    return sorted(MODELS)


def _model_fn(name):
    from . import image as model_zoo

    return {"lenet5": model_zoo.lenet5,
            "smallnet": model_zoo.smallnet_mnist_cifar,
            "alexnet": model_zoo.alexnet,
            "vgg16": model_zoo.vgg16,
            "vgg19": model_zoo.vgg19,
            "googlenet": model_zoo.googlenet,
            "resnet50": model_zoo.resnet50}[name]


def builder(model, image_size=None, class_dim=None):
    """batch -> (main_program, startup_program, loss_name) for `model`.

    The training program: concrete feed shapes
    (append_batch_size=False, so the sharding analyzer sees the real
    batch dim), softmax_with_cross_entropy -> mean, Momentum(0.01,
    0.9).  Raises KeyError-style ValueError for unknown names so the
    CLI can list what exists."""
    if model not in MODELS:
        raise ValueError("unknown model %r; known: %s"
                         % (model, ", ".join(model_names())))
    spec = MODELS[model]
    channels = spec["channels"]
    size = int(image_size or spec["image_size"])
    classes = int(class_dim or spec["class_dim"])
    fn = _model_fn(model)

    def build(batch):
        import paddle_tpu.fluid as fluid

        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            image = fluid.layers.data(
                name="image", shape=[batch, channels, size, size],
                dtype="float32", append_batch_size=False)
            logits = fn(image, class_dim=classes)
            label = fluid.layers.data(
                name="label", shape=[batch, 1], dtype="int64",
                append_batch_size=False)
            loss = fluid.layers.softmax_with_cross_entropy(logits,
                                                           label)
            avg_loss = fluid.layers.mean(loss)
            fluid.optimizer.MomentumOptimizer(
                learning_rate=0.01, momentum=0.9).minimize(avg_loss)
        return main, startup, avg_loss.name

    return build
