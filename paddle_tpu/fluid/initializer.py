"""Parameter initializers as startup-program ops.

TPU-native equivalent of reference initializers
(reference: python/paddle/v2/fluid/initializer.py — Constant, Uniform,
Normal, Xavier, MSRA).  Each __call__ appends the corresponding init op
(fill_constant / uniform_random / gaussian_random) to the startup block;
XLA compiles the whole startup program into one executable.
"""

import math

from . import framework

__all__ = ["Constant", "Uniform", "Normal", "Xavier", "MSRA", "LogScale",
           "LogScaleInitializer",
           "ConstantInitializer", "UniformInitializer", "NormalInitializer",
           "XavierInitializer", "MSRAInitializer", "force_init_on_cpu"]


def force_init_on_cpu():
    # placement is XLA's concern on TPU; kept for API parity
    return False


class Initializer:
    def __call__(self, var, block):
        raise NotImplementedError

    def _fan_in_out(self, var, stacked=False):
        shape = var.shape
        if stacked:
            # [count, in, out]: `count` matrices side by side (the
            # experts of a routed layer), each with its own fans
            if len(shape) != 3:
                raise ValueError("a stacked parameter is [count, in, out], "
                                 "got shape %s" % (tuple(shape),))
            return shape[1], shape[2]
        if len(shape) < 2:
            return (1, shape[0] if shape else 1)
        receptive = 1
        for d in shape[2:]:
            receptive *= d
        # conv weight [out_c, in_c, kh, kw] (reference initializer.py
        # computes fan from the first two dims times receptive field)
        fan_in = shape[1] * receptive
        fan_out = shape[0] * receptive
        return fan_in, fan_out


class ConstantInitializer(Initializer):
    def __init__(self, value=0.0, force_cpu=False):
        self.value = value

    def __call__(self, var, block):
        block.append_op(
            type="fill_constant", outputs={"Out": var},
            attrs={"shape": list(var.shape), "dtype": var.dtype,
                   "value": float(self.value)})


class UniformInitializer(Initializer):
    def __init__(self, low=-1.0, high=1.0, seed=0):
        self.low, self.high, self.seed = low, high, seed

    def __call__(self, var, block):
        block.append_op(
            type="uniform_random", outputs={"Out": var},
            attrs={"shape": list(var.shape), "dtype": var.dtype,
                   "min": float(self.low), "max": float(self.high),
                   "seed": self.seed})


class NormalInitializer(Initializer):
    def __init__(self, loc=0.0, scale=1.0, seed=0):
        self.loc, self.scale, self.seed = loc, scale, seed

    def __call__(self, var, block):
        block.append_op(
            type="gaussian_random", outputs={"Out": var},
            attrs={"shape": list(var.shape), "dtype": var.dtype,
                   "mean": float(self.loc), "std": float(self.scale),
                   "seed": self.seed})


class XavierInitializer(Initializer):
    """reference: initializer.py XavierInitializer (Glorot & Bengio 2010).
    `stacked`: the parameter is [count, in, out], `count` matrices with
    the fans of one, not a convolution's [out, in, width]."""

    def __init__(self, uniform=True, fan_in=None, fan_out=None, seed=0,
                 stacked=False):
        self.uniform, self.fan_in, self.fan_out, self.seed = \
            uniform, fan_in, fan_out, seed
        self.stacked = stacked

    def __call__(self, var, block):
        fan_in, fan_out = self._fan_in_out(var, self.stacked)
        fan_in = self.fan_in if self.fan_in is not None else fan_in
        fan_out = self.fan_out if self.fan_out is not None else fan_out
        if self.uniform:
            limit = math.sqrt(6.0 / (fan_in + fan_out))
            UniformInitializer(-limit, limit, self.seed)(var, block)
        else:
            std = math.sqrt(2.0 / (fan_in + fan_out))
            NormalInitializer(0.0, std, self.seed)(var, block)


class MSRAInitializer(Initializer):
    """reference: initializer.py MSRAInitializer (He et al. 2015)."""

    def __init__(self, uniform=True, fan_in=None, seed=0):
        self.uniform, self.fan_in, self.seed = uniform, fan_in, seed

    def __call__(self, var, block):
        fan_in, _ = self._fan_in_out(var)
        fan_in = self.fan_in if self.fan_in is not None else fan_in
        if self.uniform:
            limit = math.sqrt(6.0 / fan_in)
            UniformInitializer(-limit, limit, self.seed)(var, block)
        else:
            std = math.sqrt(2.0 / fan_in)
            NormalInitializer(0.0, std, self.seed)(var, block)


class LogScaleInitializer(Initializer):
    """A state-space layer's scalars, drawn on a log scale as
    arXiv:2405.21060 draws them.  `kind` "log_uniform": log(u), u
    uniform on [low, high] (`A_log`, the log of a decay rate).
    "inverse_softplus_log_uniform": the x with softplus(x) = s, s
    log-uniform on [low, high] (`dt_bias`, so that a step starts at
    s)."""

    KINDS = ("log_uniform", "inverse_softplus_log_uniform")

    def __init__(self, low, high, kind="log_uniform", seed=0):
        if kind not in self.KINDS or not 0 < low < high:
            raise ValueError("LogScale(%r, %r, %r)" % (low, high, kind))
        self.low, self.high, self.kind, self.seed = low, high, kind, seed

    def __call__(self, var, block):
        def in_place(op_type, **attrs):
            block.append_op(type=op_type, inputs={"X": var},
                            outputs={"Out": var}, attrs=attrs)

        if self.kind == "log_uniform":
            UniformInitializer(self.low, self.high, self.seed)(var, block)
            in_place("log")
            return
        UniformInitializer(math.log(self.low), math.log(self.high),
                           self.seed)(var, block)
        # s = exp(u); x = log(exp(s) - 1)
        in_place("exp")
        in_place("exp")
        in_place("increment", step=-1.0)
        in_place("log")


Constant = ConstantInitializer
Uniform = UniformInitializer
Normal = NormalInitializer
Xavier = XavierInitializer
MSRA = MSRAInitializer
LogScale = LogScaleInitializer
