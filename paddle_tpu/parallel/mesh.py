"""Device mesh construction.

Replaces the reference's device enumeration + communicator setup
(reference: operators/get_places_op.cc, operators/nccl/nccl_gpu_common.h:35
platform::Communicator, MultiGradientMachine device threads).  A Mesh with
named axes is the TPU-native "communicator": collectives are implied by
shardings over its axes and ride ICI.
"""

from collections import OrderedDict

import numpy as np

import jax
from jax.sharding import Mesh

__all__ = ["make_mesh", "MeshConfig", "parse_mesh_spec"]

# the canonical axis vocabulary (docs/ANALYSIS.md "mesh axes"):
# dp data, mp model/tensor, sp sequence, pp pipeline, ep expert
AXIS_NAMES = ("dp", "mp", "sp", "pp", "ep")


class MeshConfig:
    """Axis layout for a training job.

    dp: data parallel (batch) — gradient all-reduce rides this axis.
    mp: model/tensor parallel — weight shards; matmul partials reduce here.
    sp/pp/ep: sequence / pipeline / expert parallelism over the same
    device list.

    A MeshConfig is a *static* mesh description: `.shape` exposes the
    same axis-name -> size mapping a built `jax.sharding.Mesh` has, so
    the sharding analyzer (`paddle_tpu.analysis.shard`) and the spec
    helpers in `sharding.py` accept either one — no devices needed to
    reason about a layout.
    """

    def __init__(self, dp=None, mp=1, sp=1, pp=1, ep=1, axes=None):
        self.dp = dp
        self.mp = mp
        self.sp = sp
        self.pp = pp
        self.ep = ep
        sizes = {"dp": dp, "mp": mp, "sp": sp, "pp": pp, "ep": ep}
        if axes is None:
            axes = ("dp", "mp") if (sp == pp == ep == 1) else tuple(
                a for a in AXIS_NAMES
                if a == "dp" or (sizes[a] or 1) > 1)
        self.axes = tuple(axes)

    @property
    def shape(self):
        """axis name -> size, in axis order (a dp of None means
        'whatever devices remain' and reads as size 1 here)."""
        sizes = {"dp": self.dp, "mp": self.mp, "sp": self.sp,
                 "pp": self.pp, "ep": self.ep}
        return OrderedDict(
            (a, int(sizes.get(a) or 1)) for a in self.axes)

    def validate(self, n_devices):
        """Check the axis product against a device count; raises a
        ValueError NAMING the axes (instead of the opaque numpy
        reshape error a bad product used to surface as)."""
        shape = self.shape
        product = int(np.prod(list(shape.values()))) if shape else 1
        if self.dp is None:
            denom = int(np.prod(
                [s for a, s in shape.items() if a != "dp"]))
            if denom == 0 or n_devices % denom:
                raise ValueError(
                    "%d device(s) not divisible by the non-dp axis "
                    "product %s = %d" % (n_devices, _axis_product_str(
                        {a: s for a, s in shape.items() if a != "dp"}),
                        denom))
        elif product != n_devices:
            raise ValueError(
                "mesh axis product %s = %d != %d device(s); resize an "
                "axis or the device set" % (_axis_product_str(shape),
                                            product, n_devices))
        return self

    @classmethod
    def parse(cls, spec):
        """Parse "dp=4,mp=2"-style mesh specs (the proglint --mesh
        syntax) into a MeshConfig with that exact axis order."""
        return parse_mesh_spec(spec)

    def __repr__(self):
        return "MeshConfig(%s)" % ",".join(
            "%s=%d" % (a, s) for a, s in self.shape.items())


def _axis_product_str(shape):
    return " * ".join("%s=%s" % (a, s) for a, s in shape.items()) \
        or "(no axes)"


def parse_mesh_spec(spec):
    """"dp=4,mp=2" -> MeshConfig(dp=4, mp=2, axes=("dp", "mp"))."""
    if isinstance(spec, MeshConfig):
        return spec
    sizes, axes = {}, []
    for part in str(spec).split(","):
        part = part.strip()
        if not part:
            continue
        if "=" not in part:
            raise ValueError(
                "bad mesh spec %r: expected comma-separated axis=size "
                "pairs like 'dp=4,mp=2'" % (spec,))
        name, _, val = part.partition("=")
        name = name.strip()
        if name not in AXIS_NAMES:
            raise ValueError(
                "bad mesh spec %r: unknown axis %r (axes are %s)"
                % (spec, name, "/".join(AXIS_NAMES)))
        try:
            size = int(val)
        except ValueError:
            raise ValueError("bad mesh spec %r: size of axis %r is not "
                             "an integer" % (spec, name))
        if size < 1:
            raise ValueError("bad mesh spec %r: axis %r must be >= 1"
                             % (spec, name))
        if name in sizes:
            raise ValueError("bad mesh spec %r: axis %r named twice"
                             % (spec, name))
        sizes[name] = size
        axes.append(name)
    if not axes:
        raise ValueError("bad mesh spec %r: no axes" % (spec,))
    return MeshConfig(axes=tuple(axes), **sizes)


def make_mesh(n_devices=None, dp=None, mp=1, sp=1, pp=1, ep=1,
              axes=None, devices=None, drop_unit_axes=False):
    """Build a Mesh over the five parallelism axes.

    dp defaults to n_devices // (mp*sp*pp*ep).  With mp=1 this is pure
    data parallelism (the MultiGradientMachine/parallel_do capability);
    mp>1 shards weights (tensor parallelism), sp shards sequences
    (ring/Ulysses attention), pp pipelines stages, ep shards experts.
    By default the mesh keeps the ("dp", "mp") axes even at size 1
    (back-compat with ParallelTrainer); extended axes appear when
    requested, and drop_unit_axes=True trims every size-1 axis
    (at least "dp" always remains).
    """
    sizes = {"dp": dp, "mp": mp, "sp": sp, "pp": pp, "ep": ep}
    if axes is None:
        axes = ("dp", "mp") if (sp == pp == ep == 1) else tuple(
            a for a in ("dp", "mp", "sp", "pp", "ep")
            if a == "dp" or sizes[a] > 1)
    if devices is None:
        devices = jax.devices()
        if n_devices is not None and len(devices) < n_devices:
            raise ValueError(
                "requested a %d-device mesh but the %s platform has %d "
                "device(s); a CPU dry run selects the cpu platform "
                "itself (JAX_PLATFORMS=cpu with "
                "xla_force_host_platform_device_count), or pass "
                "devices= explicitly"
                % (n_devices, devices[0].platform, len(devices)))
    if n_devices is None:
        n_devices = len(devices)
    devices = devices[:n_devices]
    if any(a not in sizes for a in axes):
        # custom axis NAMES with (dp, mp) semantics, e.g.
        # axes=("data", "model"): sizes map positionally
        if len(axes) != 2:
            raise ValueError("custom axis names are only supported for "
                             "two-axis (dp, mp)-shaped meshes; got %r"
                             % (axes,))
        if sp != 1 or pp != 1 or ep != 1:
            raise ValueError("sp/pp/ep cannot combine with custom axis "
                             "names %r" % (axes,))
        sizes = {axes[0]: dp, axes[1]: mp}
        dp_name = axes[0]
    else:
        dp_name = "dp"
        dropped = [a for a, s in sizes.items()
                   if a not in axes and s not in (None, 1)]
        if dropped:
            raise ValueError(
                "axis size(s) %s requested but axes=%r omits them — an "
                "explicit axes tuple must name every non-unit axis"
                % ({a: sizes[a] for a in dropped}, tuple(axes)))
    denom = int(np.prod([sizes[a] for a in axes if a != dp_name]))
    if dp is None:
        if n_devices % denom != 0:
            raise ValueError(
                "%d device(s) not divisible by the non-%s axis product "
                "%s = %d; resize an axis or pass %s explicitly"
                % (n_devices, dp_name,
                   _axis_product_str({a: sizes[a] for a in axes
                                      if a != dp_name}), denom, dp_name))
        dp = n_devices // denom
    if dp * denom != n_devices:
        raise ValueError(
            "mesh axis product %s = %d != %d device(s); resize an axis "
            "or the device set"
            % (_axis_product_str(
                {a: (dp if a == dp_name else sizes[a]) for a in axes}),
               dp * denom, n_devices))
    sizes[dp_name] = dp
    if drop_unit_axes:
        # "dp" always survives: batch_spec / trainer / moe default to a
        # dp axis existing, and a dp=1 axis costs nothing
        axes = tuple(a for a in axes if sizes[a] > 1 or a == dp_name)
    dev_array = np.array(devices).reshape([sizes[a] for a in axes])
    return Mesh(dev_array, axis_names=tuple(axes))
