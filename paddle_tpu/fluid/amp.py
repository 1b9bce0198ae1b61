"""Automatic mixed precision: bf16 compute, f32 master weights.

TPU-native counterpart of the reference's float16 support (reference:
paddle/math/float16.h — CUDA half/ARM fp16 interop; fp16 design docs).
On TPU the native fast dtype is bfloat16: when enabled, the heavy MXU
ops (mul/matmul/conv/lstm projections) cast their f32 operands to bf16
and accumulate in f32 (`preferred_element_type`) — master-weight
semantics without loss scaling (bf16 keeps f32's exponent range).

Activations BETWEEN ops also stay bf16 by default
(`FLAGS_amp_bf16_act`): conv/matmul results are not cast back to f32,
so the elementwise/norm chains read and write half the bytes (HBM
bandwidth is the usual TPU bottleneck).  What remains f32 regardless:
parameters + optimizer state (masters), all reduction statistics
(batch/layer norm mean/var), losses, the router of an expert layer
(`moe_router`: its product at the highest precision, softmax, top-k and
both auxiliary losses, since a rounded logit sends a token to another
expert; the experts' own products are bf16 with f32 accumulation and
their weight gradients add up in f32), inside a state-space scan
(`ssd_scan`) the steps dt = softplus(Dt + DtBias), dt A, their sums,
every decay and every carried state (the products with X, B and C are
bf16 with f32 accumulation; the gradients of ALog, D and DtBias add up
in f32), and everything crossing the feed/fetch boundary.  Set FLAGS_amp_bf16_act=0 for the conservative
cast-back-to-f32 behaviour.
"""

import contextlib

from ..utils import flags

__all__ = ["enable_bf16", "disable_bf16", "bf16_enabled", "bf16_guard",
           "LossScaler"]


class LossScaler:
    """Dynamic loss scaling with a health-signal surface.

    bf16 keeps f32's exponent range, so the default AMP path needs no
    scaling — this exists for float16-style flows (reference: the fp16
    design docs' loss-scaling recipe) and, more importantly here, as
    the `amp_loss_scale` health gauge: `update(found_nonfinite)` backs
    off on overflow and grows after `growth_interval` clean steps, and
    every update publishes the current scale into the unified registry
    (`obs.health.NumericsMonitor(loss_scaler=...)` drives it from the
    on-device nonfinite counters automatically).
    """

    def __init__(self, init_scale=2.0 ** 15, growth_factor=2.0,
                 backoff_factor=0.5, growth_interval=1000,
                 min_scale=1.0, max_scale=2.0 ** 24):
        if init_scale <= 0:
            raise ValueError("init_scale must be positive")
        self._scale = float(init_scale)
        self.growth_factor = float(growth_factor)
        self.backoff_factor = float(backoff_factor)
        self.growth_interval = int(growth_interval)
        self.min_scale = float(min_scale)
        self.max_scale = float(max_scale)
        self._good_steps = 0
        self._publish()

    def _publish(self):
        from ..obs import telemetry as obs_tele

        obs_tele.set_gauge("amp_loss_scale", self._scale)

    @property
    def scale(self):
        return self._scale

    def set_scale(self, value):
        """Restore the scale directly (checkpoint resume — the
        resilience supervisor round-trips it through the snapshot
        meta); clamps to [min_scale, max_scale], resets the clean-step
        streak, and republishes the gauge."""
        self._scale = min(self.max_scale,
                          max(self.min_scale, float(value)))
        self._good_steps = 0
        self._publish()
        return self._scale

    def update(self, found_nonfinite):
        """One step's verdict: overflow halves the scale (and the step
        should be skipped by the caller), a clean streak of
        `growth_interval` steps doubles it.  Returns the new scale."""
        if found_nonfinite:
            self._scale = max(self.min_scale,
                              self._scale * self.backoff_factor)
            self._good_steps = 0
        else:
            self._good_steps += 1
            if self._good_steps >= self.growth_interval:
                self._scale = min(self.max_scale,
                                  self._scale * self.growth_factor)
                self._good_steps = 0
        self._publish()
        return self._scale


def enable_bf16():
    flags.set_flag("amp_bf16", True)


def disable_bf16():
    flags.set_flag("amp_bf16", False)


def bf16_enabled():
    return flags.get_flag("amp_bf16")


@contextlib.contextmanager
def bf16_guard():
    prev = bf16_enabled()
    flags.set_flag("amp_bf16", True)
    try:
        yield
    finally:
        flags.set_flag("amp_bf16", prev)
