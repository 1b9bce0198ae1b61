"""Plain float32 reference of ResNet-50 (He et al., arXiv:1512.03385,
Table 1): forward pass and softmax cross-entropy in straightforward
`jax.numpy`, no kernels, no mixed precision, nothing from the program.

It follows the paper's network: the stride of a bottleneck sits on its
first 1x1 convolution; batch normalisation uses the statistics of the
whole batch it is given when training and the stored moving statistics
at inference.  `params` holds the weights by layer in creation order
(benchmark/models/resnet50.param_names says which program variable is
which).
"""

import jax
import jax.numpy as jnp


def _conv(x, w, stride, pad):
    return jax.lax.conv_general_dilated(
        x, w, window_strides=(stride, stride),
        padding=((pad, pad), (pad, pad)),
        dimension_numbers=("NCHW", "OIHW", "NCHW"))


class _Net:
    """Walks the layers in creation order, taking weights as it goes."""

    def __init__(self, cfg, params, train):
        self.cfg, self.train = cfg, train
        self.convs = iter(params["conv"])
        self.bns = iter(params["bn"])
        self.batch_stats = []   # (mean, variance) each BN saw, training

    def conv_bn(self, x, stride, pad, relu):
        x = _conv(x, next(self.convs), stride, pad)
        scale, bias, mean, var = next(self.bns)
        if self.train:
            mean = jnp.mean(x, axis=(0, 2, 3))
            var = jnp.mean(jnp.square(x - mean[None, :, None, None]),
                           axis=(0, 2, 3))
            self.batch_stats.append((mean, var))
        x = (x - mean[None, :, None, None]) * jax.lax.rsqrt(
            var + self.cfg["bn_epsilon"])[None, :, None, None]
        x = x * scale[None, :, None, None] + bias[None, :, None, None]
        return jax.nn.relu(x) if relu else x

    def bottleneck(self, x, width, stride):
        out_ch = width * self.cfg["bottleneck_expansion"]
        short = x
        if x.shape[1] != out_ch or stride != 1:
            short = self.conv_bn(x, stride, 0, relu=False)
        y = self.conv_bn(x, stride, 0, relu=True)
        y = self.conv_bn(y, 1, 1, relu=True)
        y = self.conv_bn(y, 1, 0, relu=False)
        return jax.nn.relu(short + y)


def logits(cfg, params, image, train, net=None):
    params = jax.tree_util.tree_map(
        lambda a: jnp.asarray(a, jnp.float32), params)
    net = net or _Net(cfg, params, train)
    x = net.conv_bn(jnp.asarray(image, jnp.float32), 2, 3, relu=True)
    x = jax.lax.reduce_window(
        x, -jnp.inf, jax.lax.max, (1, 1, 3, 3), (1, 1, 2, 2),
        ((0, 0), (0, 0), (1, 1), (1, 1)))
    for stage, (width, blocks) in enumerate(
            zip(cfg["stage_widths"], cfg["stage_blocks"])):
        for block in range(blocks):
            x = net.bottleneck(
                x, width, 2 if (stage > 0 and block == 0) else 1)
    x = jnp.mean(x, axis=(2, 3))
    w, b = params["fc"]
    return x @ w + b


def loss(cfg, params, feeds):
    """Mean softmax cross-entropy of a training-mode forward pass."""
    with jax.default_matmul_precision("highest"):
        z = logits(cfg, params, feeds["image"], train=True)
        logp = jax.nn.log_softmax(z, axis=-1)
        picked = jnp.take_along_axis(
            logp, feeds["label"].reshape(-1, 1).astype(jnp.int32), axis=1)
        return -jnp.mean(picked)


def batch_statistics(cfg, params, image):
    """(mean, variance) of every batch normalisation, in layer order, from
    a training-mode forward pass over `image`: what the moving statistics
    of a model trained on such images converge to."""
    with jax.default_matmul_precision("highest"):
        params = jax.tree_util.tree_map(
            lambda a: jnp.asarray(a, jnp.float32), params)
        net = _Net(cfg, params, train=True)
        logits(cfg, params, image, train=True, net=net)
        return net.batch_stats


def probabilities(cfg, params, image):
    """Inference-mode forward pass ending in softmax."""
    with jax.default_matmul_precision("highest"):
        return jax.nn.softmax(logits(cfg, params, image, train=False),
                              axis=-1)
