"""LayerHelper: the op-assembly toolkit behind ``fluid.layers``.

Every layer function funnels its variable creation, parameter
registration, and op appends through one of these.  Capability parity
with the reference helper (reference: python/paddle/v2/fluid/
layer_helper.py:24) with a local design: program resolution, attr
broadcasting, and startup-block initialization are factored into
free-standing helpers, and parameters are declared once in the main
program and initialized exactly once in the startup program via
:meth:`_declare_initialized`.
"""

from .framework import Variable, Parameter, unique_name, \
    default_main_program, default_startup_program
from .param_attr import ParamAttr

__all__ = ["LayerHelper"]


def _clone_attr(attr):
    """A fresh unnamed ParamAttr carrying `attr`'s settings (each
    parameter needs its own name slot)."""
    return ParamAttr(name=None, initializer=attr.initializer,
                     learning_rate=attr.learning_rate,
                     regularizer=attr.regularizer,
                     trainable=attr.trainable,
                     gradient_clip=attr.gradient_clip)


def _broadcast_attrs(attr, n):
    """Expand one ParamAttr (or a list) to exactly n entries."""
    attrs = [attr] if isinstance(attr, ParamAttr) else list(attr)
    if len(attrs) == n:
        return attrs
    if len(attrs) == 1:
        return attrs[:1] + [_clone_attr(attrs[0]) for _ in range(n - 1)]
    raise ValueError("got %d param_attr entries for %d inputs"
                     % (len(attrs), n))


class LayerHelper:
    """One instance per layer call; `args` are that call's kwargs."""

    def __init__(self, layer_type, **args):
        self.layer_type = layer_type
        if not args.get("name"):
            # name within the program being built (which may not be the
            # default one when main_program is passed explicitly)
            args["name"] = unique_name(layer_type,
                                       program=args.get("main_program"))
        self.kwargs = args  # exposed: a few layers stash extras here

    # ---- naming / program targets -----------------------------------

    @property
    def name(self):
        return self.kwargs["name"]

    @property
    def main_program(self):
        return self.kwargs.get("main_program") or default_main_program()

    @property
    def startup_program(self):
        return self.kwargs.get("startup_program") or \
            default_startup_program()

    def _uniq(self, suffix):
        return unique_name("%s.%s" % (self.name, suffix),
                           program=self.kwargs.get("main_program"))

    # ---- inputs -----------------------------------------------------

    def multiple_input(self, input_param_name="input"):
        given = self.kwargs.get(input_param_name, [])
        return [given] if isinstance(given, Variable) else list(given)

    def input(self, input_param_name="input"):
        inputs = self.multiple_input(input_param_name)
        if len(inputs) != 1:
            raise ValueError("%s layer needs exactly one input"
                             % self.layer_type)
        return inputs[0]

    @property
    def input_dtype(self):
        dtypes = {v.dtype for v in self.multiple_input()}
        if len(dtypes) > 1:
            raise ValueError("mixed input dtypes in %s: %s"
                             % (self.layer_type, sorted(map(str, dtypes))))
        return dtypes.pop() if dtypes else None

    # ---- parameter attributes ---------------------------------------

    @property
    def param_attr(self):
        return ParamAttr.to_attr(self.kwargs.get("param_attr"))

    @property
    def bias_attr(self):
        return ParamAttr.to_attr(self.kwargs.get("bias_attr"))

    def multiple_param_attr(self, length):
        return _broadcast_attrs(self.param_attr, length)

    def iter_inputs_and_params(self, input_param_name="input"):
        inputs = self.multiple_input(input_param_name)
        return zip(inputs, self.multiple_param_attr(len(inputs)))

    # ---- variable / parameter creation ------------------------------

    def _declare_initialized(self, name, shape, dtype, initializer):
        """Declare `name` persistable in the startup program and append
        its init op there — the single path by which anything acquires
        an initial value."""
        block = self.startup_program.global_block()
        svar = block.create_var(name=name, shape=shape, dtype=dtype,
                                persistable=True)
        initializer(svar, block)
        return svar

    def create_parameter(self, attr, shape, dtype, is_bias=False,
                         default_initializer=None):
        assert isinstance(attr, ParamAttr)
        if attr.name is None:
            attr.name = self._uniq("w")
        shape = [int(s) for s in shape]
        shared = self.main_program.global_block().vars.get(attr.name)
        if shared is not None:
            # a second layer names a parameter that exists: the weight
            # is shared.  It keeps its one declaration (and that one's
            # type: under AMP the second reader's input may be bfloat16
            # where the first's was float32) and its one initialiser in
            # the start-up program.
            if not isinstance(shared, Parameter) or \
                    list(shared.shape) != shape:
                raise ValueError(
                    "parameter %r is shared with shape %s but exists as %r"
                    % (attr.name, shape, shared))
            return shared
        if default_initializer is not None:
            attr.set_default_initializer(default_initializer)
        elif is_bias:
            attr.set_default_bias_initializer()
        else:
            attr.set_default_param_initializer()

        param_kwargs = attr.to_kwargs()
        param_kwargs.pop("name", None)
        param = self.main_program.global_block().create_parameter(
            shape=shape, dtype=dtype, name=attr.name, **param_kwargs)
        self._declare_initialized(attr.name, shape, dtype,
                                  attr.initializer)
        return param

    def set_variable_initializer(self, var, initializer):
        self._declare_initialized(var.name, var.shape, var.dtype,
                                  initializer)
        return var

    def create_tmp_variable(self, dtype, stop_gradient=False,
                            lod_level=None, shape=None):
        """`shape` is only needed for host (non-jittable) ops, whose
        outputs keep their declared meta instead of inferred shapes."""
        kwargs = {} if shape is None else {"shape": list(shape)}
        return self.main_program.current_block().create_var(
            name=self._uniq("tmp"), dtype=dtype,
            stop_gradient=stop_gradient, lod_level=lod_level, **kwargs)

    def create_variable(self, *args, **kwargs):
        return self.main_program.current_block().create_var(
            *args, **kwargs)

    def create_global_variable(self, persistable=False, *args, **kwargs):
        return self.main_program.global_block().create_var(
            *args, persistable=persistable, **kwargs)

    # ---- op appends -------------------------------------------------

    def append_op(self, *args, **kwargs):
        return self.main_program.current_block().append_op(*args, **kwargs)

    def append_bias_op(self, input_var, dim_start=1, dim_end=None):
        """out = input + b, with b shaped like dims [dim_start, dim_end)
        of the input; no-op when the layer was given bias_attr=False."""
        attr = self.bias_attr
        if attr is None:
            return input_var
        bias = self.create_parameter(
            attr, shape=list(input_var.shape[dim_start:dim_end]),
            dtype=input_var.dtype, is_bias=True)
        out = self.create_tmp_variable(dtype=input_var.dtype,
                                       lod_level=input_var.lod_level)
        self.append_op(type="elementwise_add",
                       inputs={"X": [input_var], "Y": [bias]},
                       outputs={"Out": [out]},
                       attrs={"axis": dim_start})
        return out

    def append_activation(self, input_var):
        """Apply the layer's `act` kwarg ('relu' or {'type': ..., attrs})
        to `input_var`; identity when absent."""
        act = self.kwargs.get("act")
        if act is None:
            return input_var
        attrs = dict({"type": act} if isinstance(act, str) else act)
        act_type = attrs.pop("type")
        out = self.create_tmp_variable(dtype=input_var.dtype,
                                       lod_level=input_var.lod_level)
        self.append_op(type=act_type, inputs={"X": [input_var]},
                       outputs={"Out": [out]}, attrs=attrs)
        return out
