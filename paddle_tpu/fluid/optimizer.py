"""Optimizers: declarative update rules compiled into program ops.

Capability parity with the reference optimizer layer (reference:
python/paddle/v2/fluid/optimizer.py — minimize:204, the SGD/Momentum/
Adagrad/Adam/Adamax/DecayedAdagrad zoo :228-550), with a different
internal architecture.  The reference extends optimizers by overriding
a template-method triple (create accumulators / append op / finish
update); here an optimizer *declares* its update rule as data —

  * ``op_type``        — the per-parameter update op it emits,
  * ``state_slots``    — per-parameter accumulators (velocity, moments),
  * ``shared_scalars`` — cross-parameter scalar state (Adam beta powers)
                         with a per-step decay factor,
  * ``_hyper_attrs()`` — the op's hyperparameter attrs,

and a single engine materialises the state variables and emits the ops.
`minimize` = append_backward + clipping + regularization +
this pass; the whole train step then compiles into one XLA executable
with parameter buffers donated for in-place update.
"""

from collections import namedtuple

from . import framework
from .framework import unique_name, Variable
from .backward import append_backward
from .initializer import Constant
from .layer_helper import LayerHelper
from .regularizer import append_regularization_ops
from . import clip as clip_mod
from ..obs import trace as obs_trace

__all__ = ["SGD", "Momentum", "Adagrad", "Adam", "Adamax", "DecayedAdagrad",
           "Adadelta", "RMSProp", "Ftrl",
           "SGDOptimizer", "MomentumOptimizer", "AdagradOptimizer",
           "AdamOptimizer", "AdamaxOptimizer", "DecayedAdagradOptimizer",
           "AdadeltaOptimizer", "RMSPropOptimizer", "FtrlOptimizer",
           "Optimizer"]

# a per-parameter accumulator: variable named {param}_{name}, wired into
# the update op at in_key and written back at out_key
StateSlot = namedtuple("StateSlot", ["name", "in_key", "out_key", "fill"])

# a cross-parameter scalar (e.g. beta1^t): initialised to `init`, read by
# every update op at in_key, multiplied by step_factor once per step
SharedScalar = namedtuple("SharedScalar",
                          ["name", "in_key", "init", "step_factor"])


class Optimizer:
    """Engine over a declared update rule; subclasses declare, not code."""

    op_type = None
    state_slots = ()
    shared_scalars = ()
    uses_lr = True  # adadelta's rule derives its step size from state

    def __init__(self, learning_rate, regularization=None, global_step=None):
        if not isinstance(learning_rate, (float, Variable)):
            raise TypeError("learning_rate should be float or Variable")
        self._learning_rate = learning_rate
        self.regularization = regularization
        self._global_step = global_step
        # all state caches key by program: one optimizer instance may
        # minimize losses in several programs, each needing its own vars
        self._lr_by_program = {}
        self._slot_vars = {}     # (program, slot name, param name) -> var
        self._shared_vars = {}   # (program, name) -> var
        self.helper = None
        # the program minimize() operates on, so state lands in the right
        # program even when it is not the default one
        self._target_program = None

    def _hyper_attrs(self):
        return {}

    @property
    def type(self):
        return self.op_type

    # -- learning rate ------------------------------------------------------
    def _ensure_lr(self, program):
        if program in self._lr_by_program:
            return
        if isinstance(self._learning_rate, Variable):
            self._lr_by_program[program] = self._learning_rate
            return
        var = program.global_block().create_var(
            name=unique_name("learning_rate", program=program),
            shape=[1], dtype="float32",
            persistable=True)
        self.helper.set_variable_initializer(
            var, Constant(float(self._learning_rate)))
        self._lr_by_program[program] = var

    def learning_rate_var(self, program=None):
        if program is None:
            program = self._target_program or framework.default_main_program()
        return self._lr_by_program.get(program)

    def _param_lr(self, param):
        """Per-parameter LR: the global rate scaled by the parameter's
        optimize_attr learning_rate, if it has one."""
        base = self.learning_rate_var()
        scale = getattr(param, "optimize_attr", None) or {}
        scale = scale.get("learning_rate", 1.0)
        if scale == 1.0:
            return base
        out = self.helper.create_tmp_variable("float32", stop_gradient=True)
        self.helper.append_op(type="scale", inputs={"X": [base]},
                              outputs={"Out": [out]},
                              attrs={"scale": float(scale)})
        return out

    # -- state --------------------------------------------------------------
    def _slot_var(self, block, spec, param):
        key = (block.program, spec.name, param.name)
        if key not in self._slot_vars:
            var = block.create_var(
                name=unique_name("%s_%s" % (param.name, spec.name),
                                 program=block.program),
                shape=list(param.shape), dtype=param.dtype, persistable=True)
            self.helper.set_variable_initializer(var, Constant(spec.fill))
            self._slot_vars[key] = var
        return self._slot_vars[key]

    def _shared_var(self, program, spec):
        return self._shared_vars[(program, spec.name)]

    def _ensure_shared(self, block, spec):
        key = (block.program, spec.name)
        if key in self._shared_vars:
            return
        var = block.create_var(name=unique_name(spec.name,
                                               program=block.program),
                               shape=[1],
                               dtype="float32", persistable=True)
        self.helper.set_variable_initializer(var, Constant(spec.init))
        self._shared_vars[key] = var

    # -- op emission --------------------------------------------------------
    def _emit_update(self, block, param, grad):
        if isinstance(grad, str):
            grad = block.var(grad)
        ins = {"Param": [param], "Grad": [grad]}
        outs = {"ParamOut": [param]}
        if self.uses_lr:
            ins["LearningRate"] = [self._param_lr(param)]
        for spec in self.state_slots:
            var = self._slot_var(block, spec, param)
            ins[spec.in_key] = [var]
            outs[spec.out_key] = [var]
        for spec in self.shared_scalars:
            ins[spec.in_key] = [self._shared_var(block.program, spec)]
        return block.append_op(type=self.op_type, inputs=ins, outputs=outs,
                               attrs=self._hyper_attrs())

    def create_optimization_pass(self, parameters_and_grads, loss,
                                 startup_program=None):
        """Materialise state and emit one update op per parameter
        (reference entry point: optimizer.py:151)."""
        program = loss.block.program
        block = program.global_block()
        self._target_program = program
        self.helper = LayerHelper(self.__class__.__name__,
                                  main_program=program,
                                  startup_program=startup_program)
        self._ensure_lr(program)
        for spec in self.shared_scalars:
            self._ensure_shared(block, spec)

        live = [(p, g) for p, g in parameters_and_grads
                if g is not None and getattr(p, "trainable", True)]
        update_ops = [self._emit_update(block, p, g) for p, g in live]

        # advance shared scalars once per step (beta1^t *= beta1, ...)
        for spec in self.shared_scalars:
            if spec.step_factor is not None:
                var = self._shared_var(program, spec)
                block.append_op(type="scale", inputs={"X": [var]},
                                outputs={"Out": [var]},
                                attrs={"scale": spec.step_factor})

        if self._global_step is not None:
            from .layers import tensor as tensor_layers
            tensor_layers.increment(self._global_step, value=1.0,
                                    in_place=True)

        return update_ops

    def minimize(self, loss, startup_program=None, parameter_list=None,
                 no_grad_set=None):
        """reference: optimizer.py:204."""
        with obs_trace.span("startup/program_optimize",
                            cat=obs_trace.STARTUP,
                            op_type=self.op_type) as minimized:
            params_grads = append_backward(loss, parameter_list,
                                           no_grad_set)
            params_grads = sorted(params_grads, key=lambda x: x[0].name)
            params_grads, clip_ops = clip_mod.append_gradient_clip_ops(
                params_grads)
            params_grads = append_regularization_ops(params_grads,
                                                     self.regularization)
            optimize_ops = self.create_optimization_pass(
                params_grads, loss, startup_program)
            minimized.set(parameters=len(params_grads))
        return optimize_ops, params_grads


class SGDOptimizer(Optimizer):
    op_type = "sgd"


class MomentumOptimizer(Optimizer):
    op_type = "momentum"
    state_slots = (StateSlot("velocity", "Velocity", "VelocityOut", 0.0),)

    def __init__(self, learning_rate, momentum, use_nesterov=False,
                 **kwargs):
        super().__init__(learning_rate, **kwargs)
        self._momentum = momentum
        self._use_nesterov = use_nesterov

    def _hyper_attrs(self):
        return {"mu": self._momentum, "use_nesterov": self._use_nesterov}


class AdagradOptimizer(Optimizer):
    op_type = "adagrad"
    state_slots = (StateSlot("moment", "Moment", "MomentOut", 0.0),)

    def __init__(self, learning_rate, epsilon=1e-6, **kwargs):
        super().__init__(learning_rate, **kwargs)
        self._epsilon = epsilon

    def _hyper_attrs(self):
        return {"epsilon": self._epsilon}


class AdamOptimizer(Optimizer):
    op_type = "adam"
    state_slots = (StateSlot("moment1", "Moment1", "Moment1Out", 0.0),
                   StateSlot("moment2", "Moment2", "Moment2Out", 0.0))

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, **kwargs):
        super().__init__(learning_rate, **kwargs)
        self._beta1 = beta1
        self._beta2 = beta2
        self._epsilon = epsilon
        self.shared_scalars = (
            SharedScalar("beta1_pow_acc", "Beta1Pow", beta1, beta1),
            SharedScalar("beta2_pow_acc", "Beta2Pow", beta2, beta2))

    def _hyper_attrs(self):
        return {"beta1": self._beta1, "beta2": self._beta2,
                "epsilon": self._epsilon}


class AdamaxOptimizer(Optimizer):
    op_type = "adamax"
    state_slots = (StateSlot("moment", "Moment", "MomentOut", 0.0),
                   StateSlot("inf_norm", "InfNorm", "InfNormOut", 0.0))

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, **kwargs):
        super().__init__(learning_rate, **kwargs)
        self._beta1 = beta1
        self._beta2 = beta2
        self._epsilon = epsilon
        self.shared_scalars = (
            SharedScalar("beta1_pow_acc", "Beta1Pow", beta1, beta1),)

    def _hyper_attrs(self):
        return {"beta1": self._beta1, "beta2": self._beta2,
                "epsilon": self._epsilon}


class DecayedAdagradOptimizer(Optimizer):
    op_type = "decayed_adagrad"
    state_slots = (StateSlot("moment", "Moment", "MomentOut", 0.0),)

    def __init__(self, learning_rate, decay=0.95, epsilon=1e-6, **kwargs):
        super().__init__(learning_rate, **kwargs)
        self._decay = decay
        self._epsilon = epsilon

    def _hyper_attrs(self):
        return {"decay": self._decay, "epsilon": self._epsilon}


class AdadeltaOptimizer(Optimizer):
    op_type = "adadelta"
    uses_lr = False
    state_slots = (
        StateSlot("avg_squared_grad", "AvgSquaredGrad",
                  "AvgSquaredGradOut", 0.0),
        StateSlot("avg_squared_update", "AvgSquaredUpdate",
                  "AvgSquaredUpdateOut", 0.0))

    def __init__(self, learning_rate=1.0, epsilon=1e-6, rho=0.95, **kwargs):
        super().__init__(learning_rate, **kwargs)
        self._epsilon = epsilon
        self._rho = rho

    def _hyper_attrs(self):
        return {"epsilon": self._epsilon, "rho": self._rho}


class RMSPropOptimizer(Optimizer):
    op_type = "rmsprop"
    state_slots = (StateSlot("mean_square", "MeanSquare",
                             "MeanSquareOut", 0.0),
                   StateSlot("moment", "Moment", "MomentOut", 0.0))

    def __init__(self, learning_rate, decay=0.9, epsilon=1e-6, momentum=0.0,
                 **kwargs):
        super().__init__(learning_rate, **kwargs)
        self._decay = decay
        self._epsilon = epsilon
        self._momentum = momentum

    def _hyper_attrs(self):
        return {"decay": self._decay, "epsilon": self._epsilon,
                "momentum": self._momentum}


class FtrlOptimizer(Optimizer):
    op_type = "ftrl"
    state_slots = (StateSlot("squared", "SquaredAccumulator",
                             "SquaredAccumOut", 0.0),
                   StateSlot("linear", "LinearAccumulator",
                             "LinearAccumOut", 0.0))

    def __init__(self, learning_rate, l1=0.0, l2=0.0, lr_power=-0.5,
                 **kwargs):
        super().__init__(learning_rate, **kwargs)
        self._l1 = l1
        self._l2 = l2
        self._lr_power = lr_power

    def _hyper_attrs(self):
        return {"l1": self._l1, "l2": self._l2, "lr_power": self._lr_power}


SGD = SGDOptimizer
Momentum = MomentumOptimizer
Adagrad = AdagradOptimizer
Adam = AdamOptimizer
Adamax = AdamaxOptimizer
DecayedAdagrad = DecayedAdagradOptimizer
Adadelta = AdadeltaOptimizer
RMSProp = RMSPropOptimizer
Ftrl = FtrlOptimizer
