"""Optimizers (reference: python/paddle/fluid/optimizer.py:50).

Each optimizer is a Python class that appends its C++-equivalent op per
parameter (``minimize`` = append_backward + apply_gradients, optimizer.py:566)
— here the appended ops lower to fused XLA update expressions that donate the
parameter buffers (ops/optimizer_ops.py).
"""

import contextlib

import numpy as np

from . import framework
from .framework import (OpRole, OP_ROLE_KEY, OP_ROLE_VAR_KEY, Variable,
                        default_main_program, default_startup_program,
                        program_guard)
from .backward import append_backward
from .initializer import ConstantInitializer
from .layer_helper import LayerHelper
from .clip import append_gradient_clip_ops
from .regularizer import append_regularization_ops
from . import unique_name


# Per-optimizer-op map of VECTOR state slots (input slot -> output slot,
# accumulators shaped like the param) whose update rule is purely
# ELEMENTWISE in (param, grad, state) given the op's scalar inputs/attrs.
# This is the contract weight-update sharding
# (transpiler.collective.GradAllReduce(weight_update_sharding=True))
# depends on: an elementwise update applied to a contiguous 1/N shard of
# the coalesced (param, grad, state) bucket equals the same shard of the
# full update, so each device can own just its slice of the moments.
# Deliberately absent: lamb / lars_momentum (trust ratios need the whole
# param's norm) and dgc_momentum (communicates inside the op).
ELEMENTWISE_OPTIMIZER_STATE = {
    "sgd": {},
    "momentum": {"Velocity": "VelocityOut"},
    "adam": {"Moment1": "Moment1Out", "Moment2": "Moment2Out"},
    "adamax": {"Moment": "MomentOut", "InfNorm": "InfNormOut"},
    "adagrad": {"Moment": "MomentOut"},
    "decayed_adagrad": {"Moment": "MomentOut"},
    "adadelta": {"AvgSquaredGrad": "AvgSquaredGradOut",
                 "AvgSquaredUpdate": "AvgSquaredUpdateOut"},
    "rmsprop": {"Moment": "MomentOut", "MeanSquare": "MeanSquareOut",
                "MeanGrad": "MeanGradOut"},
    "ftrl": {"SquaredAccumulator": "SquaredAccumOut",
             "LinearAccumulator": "LinearAccumOut"},
}


def elementwise_state_slots(op_type):
    """Vector-state slot map of an optimizer op whose update shards
    elementwise (see ELEMENTWISE_OPTIMIZER_STATE), or None when the op
    cannot be weight-update-sharded."""
    return ELEMENTWISE_OPTIMIZER_STATE.get(op_type)


class Optimizer:
    def __init__(self, learning_rate, regularization=None, name=None):
        self.regularization = regularization
        self._name = name
        self._learning_rate = learning_rate
        self._learning_rate_map = {}
        self._accumulators = {}
        self.helper = None
        self.type = getattr(self, "type", "optimizer")

    # -- learning rate -----------------------------------------------------
    def _create_global_learning_rate(self):
        program = default_main_program()
        lr = self._learning_rate_map.get(program)
        if lr is not None:
            return
        if isinstance(self._learning_rate, Variable):
            self._learning_rate_map[program] = self._learning_rate
            return
        helper = LayerHelper("learning_rate")
        lr_var = helper.create_global_variable(
            name=unique_name.generate("learning_rate"), shape=(1,),
            dtype="float32", persistable=True)
        lr_var.stop_gradient = True
        helper.set_variable_initializer(
            lr_var, ConstantInitializer(self._static_lr_value()))
        self._learning_rate_map[program] = lr_var

    def _static_lr_value(self):
        if callable(self._learning_rate) and \
                not isinstance(self._learning_rate, (int, float)):
            from .dygraph import tracer as _dytracer
            if not _dytracer.enabled():
                # reference optimizer.py rejects dygraph LR schedules in
                # static mode — use layers.learning_rate_scheduler there
                raise TypeError(
                    "a dygraph LearningRateDecay schedule only works in "
                    "dygraph mode; use fluid.layers."
                    "exponential_decay/... in static graphs")
            return 0.0   # overwritten each step by _dygraph_minimize
        return float(self._learning_rate)

    def _global_learning_rate(self, program=None):
        program = program or default_main_program()
        return self._learning_rate_map.get(program)

    def _create_param_lr(self, param_and_grad):
        param = param_and_grad[0]
        base = self._global_learning_rate()
        param_lr = param.optimize_attr.get("learning_rate", 1.0)
        if param_lr == 1.0:
            return base
        from . import layers
        with default_main_program()._lr_schedule_guard():
            return layers.scale(base, float(param_lr))

    # -- accumulators ------------------------------------------------------
    def _add_accumulator(self, name, param, fill_value=0.0, shape=None,
                         dtype=None):
        if self._name is not None:
            name = self._name + "_" + name
        key = (name, param.name)
        if key in self._accumulators:
            return self._accumulators[key]
        helper = LayerHelper(name)
        var = helper.create_global_variable(
            name=unique_name.generate(param.name + "_" + name),
            shape=shape if shape is not None else param.shape,
            dtype=dtype or param.dtype, persistable=True)
        var.stop_gradient = True
        helper.set_variable_initializer(
            var, ConstantInitializer(float(fill_value)))
        self._accumulators[key] = var
        # Record the param→state link STRUCTURALLY at creation (the
        # reference also keys state by (name, param) — optimizer.py:50
        # _add_accumulator) on both programs, so sharding consumers
        # (TP/EP state specs, ZeRO-1, pp-ZeRO) never have to
        # reverse-engineer the link from <param>_<suffix> names.
        # Carried by clone() and compile cache keys via
        # framework.PROGRAM_ANNOTATIONS.
        for prog in (helper.main_program, helper.startup_program):
            links = dict(getattr(prog, "_opt_state_of", None) or {})
            links[var.name] = param.name
            prog._opt_state_of = links
        return var

    def _get_accumulator(self, name, param):
        if self._name is not None:
            name = self._name + "_" + name
        return self._accumulators[(name, param.name)]

    # -- main entry points -------------------------------------------------
    def backward(self, loss, startup_program=None, parameter_list=None,
                 no_grad_set=None, callbacks=None):
        return append_backward(loss, parameter_list, no_grad_set, callbacks)

    def apply_gradients(self, params_grads):
        params_grads = sorted(params_grads, key=lambda x: x[0].name)
        # the whole grad post-processing chain (incl. every layers.* sub-op
        # the clip helpers emit) must carry the Optimize role: the pipeline
        # planner keys off roles to run these in its post phase
        program = default_main_program()
        with program._optimized_guard([]):
            params_grads = append_gradient_clip_ops(params_grads)
            params_grads = append_regularization_ops(params_grads,
                                                     self.regularization)
        optimize_ops = self._create_optimization_pass(params_grads)
        return optimize_ops

    def _create_optimization_pass(self, params_grads):
        program = default_main_program()
        self.helper = LayerHelper(self.__class__.__name__)
        self._create_accumulators(program.global_block(),
                                  [p for p, g in params_grads if g is not None])
        self._create_global_learning_rate()
        optimize_ops = []
        # append into the *current* block: normally the global block, but a
        # wrapper (GradientMergeOptimizer) may be building a conditional
        # sub-block around the update tier
        for param_and_grad in params_grads:
            if param_and_grad[1] is None:
                continue
            with program._optimized_guard(param_and_grad):
                op = self._append_optimize_op(program.current_block(),
                                              param_and_grad)
                optimize_ops.append(op)
        with program._optimized_guard([]):
            self._finish_update(program.current_block(), params_grads)
        return optimize_ops

    def minimize(self, loss, startup_program=None, parameter_list=None,
                 no_grad_set=None):
        from .dygraph import tracer as _dytracer
        if _dytracer.enabled():
            return self._dygraph_minimize(loss, parameter_list)
        params_grads = self.backward(loss, startup_program, parameter_list,
                                     no_grad_set)
        optimize_ops = self.apply_gradients(params_grads)
        return optimize_ops, params_grads

    # -- dygraph (eager) path ----------------------------------------------
    def _dygraph_minimize(self, loss, parameter_list):
        """Apply this optimizer eagerly to VarBase parameters.

        Reuses the declarative machinery wholesale: a tiny program holding
        only this optimizer's ops is built once and run through the cached
        executor each step, with params/grads/accumulators living in a
        private scope (the eager analogue of the reference's shared
        Scope between Tracer and optimizer ops, dygraph/parallel.py era).
        ``loss.backward()`` must have run first.
        """
        from . import framework as fw
        from .executor import Executor, CPUPlace, Scope, scope_guard
        from .initializer import ConstantInitializer

        if parameter_list is None:
            raise ValueError(
                "dygraph minimize needs parameter_list=model.parameters()")
        all_params = [p for p in parameter_list
                      if getattr(p, "trainable", True) and not p.stop_gradient]
        if all_params and all(p.grad is None for p in all_params):
            raise RuntimeError(
                "no parameter has a gradient: call loss.backward() before "
                "optimizer.minimize")
        # params unused this step (grad None) are skipped, as the static
        # path skips (param, None) pairs
        params = [p for p in all_params if p.grad is not None]

        # one scope + executor for this optimizer's lifetime: accumulator
        # values (Adam moments, beta pows, ...) persist across program
        # rebuilds because _add_accumulator caches stable var names
        if not hasattr(self, "_dy_scope"):
            self._dy_scope = Scope()
            self._dy_exe = Executor(CPUPlace())
            self._dy_progs = {}

        sig = tuple((p.name, p.shape, p.dtype) for p in params)
        if sig not in self._dy_progs:
            main, startup = fw.Program(), fw.Program()
            with fw.program_guard(main, startup):
                pgs = []
                gb = main.global_block()
                for p in params:
                    pv = fw.Parameter(
                        gb, shape=list(p.shape), dtype=p.dtype, name=p.name,
                        initializer=ConstantInitializer(0.0),
                        regularizer=getattr(p, "regularizer", None))
                    pv.gradient_clip_attr = getattr(p, "gradient_clip_attr",
                                                    None)
                    gb.vars[pv.name] = pv
                    gv = gb.create_var(name=p.name + "@GRAD",
                                       shape=list(p.shape), dtype=p.dtype,
                                       persistable=True)
                    pgs.append((pv, gv))
                # full static pipeline: clip + regularization + optimize ops
                self.apply_gradients(pgs)
            self._dy_progs[sig] = main
            with scope_guard(self._dy_scope):
                # this startup initializes only vars created by THIS build
                # (accumulator creation is cached), so existing state stays
                self._dy_exe.run(startup)
        main = self._dy_progs[sig]

        scope = self._dy_scope
        with scope_guard(scope):
            for p in params:
                scope.set_var(p.name, p.value)
                scope.set_var(p.name + "@GRAD", p.grad)
            if callable(self._learning_rate):
                # dygraph LR schedule: evaluate-and-advance per step
                # (dygraph/learning_rate_scheduler.py contract)
                import numpy as _np
                lr_var = self._global_learning_rate(main)
                scope.set_var(lr_var.name,
                              _np.asarray([float(self._learning_rate())],
                                          _np.float32))
            self._dy_exe.run(main)
            for p in params:
                p.value = scope.find_var(p.name)
        return [], [(p, p.grad) for p in params]

    # -- per-optimizer hooks ----------------------------------------------
    def _create_accumulators(self, block, parameters):
        pass

    def _finish_update(self, block, params_grads):
        pass

    def _append_optimize_op(self, block, param_and_grad):
        raise NotImplementedError


class SGDOptimizer(Optimizer):
    """operators/optimizers/sgd_op.cc (reference optimizer.py:609)."""

    type = "sgd"

    def _append_optimize_op(self, block, param_and_grad):
        param, grad = param_and_grad
        return block.append_op(
            "sgd",
            inputs={"Param": [param], "Grad": [grad],
                    "LearningRate": [self._create_param_lr(param_and_grad)]},
            outputs={"ParamOut": [param]})


class MomentumOptimizer(Optimizer):
    """operators/optimizers/momentum_op (reference optimizer.py:679)."""

    type = "momentum"

    def __init__(self, learning_rate, momentum, use_nesterov=False, **kwargs):
        super().__init__(learning_rate, **kwargs)
        self._momentum = momentum
        self._use_nesterov = use_nesterov

    def _create_accumulators(self, block, parameters):
        for p in parameters:
            self._add_accumulator("velocity", p)

    def _append_optimize_op(self, block, param_and_grad):
        param, grad = param_and_grad
        velocity = self._get_accumulator("velocity", param)
        return block.append_op(
            "momentum",
            inputs={"Param": [param], "Grad": [grad],
                    "Velocity": [velocity],
                    "LearningRate": [self._create_param_lr(param_and_grad)]},
            outputs={"ParamOut": [param], "VelocityOut": [velocity]},
            attrs={"mu": self._momentum,
                   "use_nesterov": self._use_nesterov})


class LarsMomentumOptimizer(Optimizer):
    """operators/optimizers/lars_momentum_op (reference optimizer.py:1046)."""

    type = "lars_momentum"

    def __init__(self, learning_rate, momentum, lars_coeff=0.001,
                 lars_weight_decay=0.0005, **kwargs):
        super().__init__(learning_rate, **kwargs)
        self._momentum = momentum
        self._lars_coeff = lars_coeff
        self._lars_weight_decay = lars_weight_decay

    def _create_accumulators(self, block, parameters):
        for p in parameters:
            self._add_accumulator("velocity", p)

    def _append_optimize_op(self, block, param_and_grad):
        param, grad = param_and_grad
        velocity = self._get_accumulator("velocity", param)
        return block.append_op(
            "lars_momentum",
            inputs={"Param": [param], "Grad": [grad],
                    "Velocity": [velocity],
                    "LearningRate": [self._create_param_lr(param_and_grad)]},
            outputs={"ParamOut": [param], "VelocityOut": [velocity]},
            attrs={"mu": self._momentum, "lars_coeff": self._lars_coeff,
                   "lars_weight_decay": self._lars_weight_decay})


class AdamOptimizer(Optimizer):
    """operators/optimizers/adam_op (reference optimizer.py:1249)."""

    type = "adam"

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, lazy_mode=False, **kwargs):
        super().__init__(learning_rate, **kwargs)
        self._beta1 = beta1
        self._beta2 = beta2
        self._epsilon = epsilon

    def _create_accumulators(self, block, parameters):
        for p in parameters:
            self._add_accumulator("moment1", p)
            self._add_accumulator("moment2", p)
            self._add_accumulator("beta1_pow_acc", p, fill_value=self._beta1,
                                  shape=(1,))
            self._add_accumulator("beta2_pow_acc", p, fill_value=self._beta2,
                                  shape=(1,))

    def _append_optimize_op(self, block, param_and_grad):
        param, grad = param_and_grad
        m1 = self._get_accumulator("moment1", param)
        m2 = self._get_accumulator("moment2", param)
        b1p = self._get_accumulator("beta1_pow_acc", param)
        b2p = self._get_accumulator("beta2_pow_acc", param)
        return block.append_op(
            "adam",
            inputs={"Param": [param], "Grad": [grad], "Moment1": [m1],
                    "Moment2": [m2], "Beta1Pow": [b1p], "Beta2Pow": [b2p],
                    "LearningRate": [self._create_param_lr(param_and_grad)]},
            outputs={"ParamOut": [param], "Moment1Out": [m1],
                     "Moment2Out": [m2]},
            attrs={"beta1": self._beta1, "beta2": self._beta2,
                   "epsilon": self._epsilon})

    def _finish_update(self, block, params_grads):
        # advance beta^t accumulators with scale ops, as the reference does in
        # AdamOptimizer._finish_update
        for param, grad in params_grads:
            if grad is None:
                continue
            b1p = self._get_accumulator("beta1_pow_acc", param)
            b2p = self._get_accumulator("beta2_pow_acc", param)
            block.append_op("scale", inputs={"X": [b1p]},
                            outputs={"Out": [b1p]},
                            attrs={"scale": self._beta1})
            block.append_op("scale", inputs={"X": [b2p]},
                            outputs={"Out": [b2p]},
                            attrs={"scale": self._beta2})


class AdamaxOptimizer(Optimizer):
    """operators/optimizers/adamax_op (reference optimizer.py:1430)."""

    type = "adamax"

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, **kwargs):
        super().__init__(learning_rate, **kwargs)
        self._beta1 = beta1
        self._beta2 = beta2
        self._epsilon = epsilon

    def _create_accumulators(self, block, parameters):
        for p in parameters:
            self._add_accumulator("moment", p)
            self._add_accumulator("inf_norm", p)
            self._add_accumulator("beta1_pow_acc", p, fill_value=self._beta1,
                                  shape=(1,))

    def _append_optimize_op(self, block, param_and_grad):
        param, grad = param_and_grad
        moment = self._get_accumulator("moment", param)
        inf_norm = self._get_accumulator("inf_norm", param)
        b1p = self._get_accumulator("beta1_pow_acc", param)
        return block.append_op(
            "adamax",
            inputs={"Param": [param], "Grad": [grad], "Moment": [moment],
                    "InfNorm": [inf_norm], "Beta1Pow": [b1p],
                    "LearningRate": [self._create_param_lr(param_and_grad)]},
            outputs={"ParamOut": [param], "MomentOut": [moment],
                     "InfNormOut": [inf_norm]},
            attrs={"beta1": self._beta1, "beta2": self._beta2,
                   "epsilon": self._epsilon})

    def _finish_update(self, block, params_grads):
        for param, grad in params_grads:
            if grad is None:
                continue
            b1p = self._get_accumulator("beta1_pow_acc", param)
            block.append_op("scale", inputs={"X": [b1p]},
                            outputs={"Out": [b1p]},
                            attrs={"scale": self._beta1})


class AdagradOptimizer(Optimizer):
    """operators/optimizers/adagrad_op (reference optimizer.py:1146)."""

    type = "adagrad"

    def __init__(self, learning_rate, epsilon=1e-6, initial_accumulator_value=0.0,
                 **kwargs):
        super().__init__(learning_rate, **kwargs)
        self._epsilon = epsilon
        self._initial_accumulator_value = initial_accumulator_value

    def _create_accumulators(self, block, parameters):
        for p in parameters:
            self._add_accumulator("moment", p,
                                  fill_value=self._initial_accumulator_value)

    def _append_optimize_op(self, block, param_and_grad):
        param, grad = param_and_grad
        moment = self._get_accumulator("moment", param)
        return block.append_op(
            "adagrad",
            inputs={"Param": [param], "Grad": [grad], "Moment": [moment],
                    "LearningRate": [self._create_param_lr(param_and_grad)]},
            outputs={"ParamOut": [param], "MomentOut": [moment]},
            attrs={"epsilon": self._epsilon})


class DecayedAdagradOptimizer(Optimizer):
    """operators/optimizers/decayed_adagrad_op (reference optimizer.py:1584)."""

    type = "decayed_adagrad"

    def __init__(self, learning_rate, decay=0.95, epsilon=1e-6, **kwargs):
        super().__init__(learning_rate, **kwargs)
        self._decay = decay
        self._epsilon = epsilon

    def _create_accumulators(self, block, parameters):
        for p in parameters:
            self._add_accumulator("moment", p)

    def _append_optimize_op(self, block, param_and_grad):
        param, grad = param_and_grad
        moment = self._get_accumulator("moment", param)
        return block.append_op(
            "decayed_adagrad",
            inputs={"Param": [param], "Grad": [grad], "Moment": [moment],
                    "LearningRate": [self._create_param_lr(param_and_grad)]},
            outputs={"ParamOut": [param], "MomentOut": [moment]},
            attrs={"decay": self._decay, "epsilon": self._epsilon})


class AdadeltaOptimizer(Optimizer):
    """operators/optimizers/adadelta_op (reference optimizer.py:1676)."""

    type = "adadelta"

    def __init__(self, learning_rate, epsilon=1e-6, rho=0.95, **kwargs):
        super().__init__(learning_rate, **kwargs)
        self._epsilon = epsilon
        self._rho = rho

    def _create_accumulators(self, block, parameters):
        for p in parameters:
            self._add_accumulator("__avg_squared_grad", p)
            self._add_accumulator("__avg_squared_update", p)

    def _append_optimize_op(self, block, param_and_grad):
        param, grad = param_and_grad
        asg = self._get_accumulator("__avg_squared_grad", param)
        asu = self._get_accumulator("__avg_squared_update", param)
        return block.append_op(
            "adadelta",
            inputs={"Param": [param], "Grad": [grad],
                    "AvgSquaredGrad": [asg], "AvgSquaredUpdate": [asu]},
            outputs={"ParamOut": [param], "AvgSquaredGradOut": [asg],
                     "AvgSquaredUpdateOut": [asu]},
            attrs={"epsilon": self._epsilon, "rho": self._rho})


class RMSPropOptimizer(Optimizer):
    """operators/optimizers/rmsprop_op (reference optimizer.py:1774)."""

    type = "rmsprop"

    def __init__(self, learning_rate, rho=0.95, epsilon=1e-6, momentum=0.0,
                 centered=False, **kwargs):
        super().__init__(learning_rate, **kwargs)
        self._rho = rho
        self._epsilon = epsilon
        self._momentum = momentum
        self._centered = centered

    def _create_accumulators(self, block, parameters):
        for p in parameters:
            self._add_accumulator("momentum", p)
            self._add_accumulator("mean_square", p)
            self._add_accumulator("mean_grad", p)

    def _append_optimize_op(self, block, param_and_grad):
        param, grad = param_and_grad
        momentum = self._get_accumulator("momentum", param)
        mean_square = self._get_accumulator("mean_square", param)
        mean_grad = self._get_accumulator("mean_grad", param)
        return block.append_op(
            "rmsprop",
            inputs={"Param": [param], "Grad": [grad],
                    "Moment": [momentum], "MeanSquare": [mean_square],
                    "MeanGrad": [mean_grad],
                    "LearningRate": [self._create_param_lr(param_and_grad)]},
            outputs={"ParamOut": [param], "MomentOut": [momentum],
                     "MeanSquareOut": [mean_square],
                     "MeanGradOut": [mean_grad]},
            attrs={"epsilon": self._epsilon, "decay": self._rho,
                   "momentum": self._momentum, "centered": self._centered})


class FtrlOptimizer(Optimizer):
    """operators/optimizers/ftrl_op (reference optimizer.py:1947)."""

    type = "ftrl"

    def __init__(self, learning_rate, l1=0.0, l2=0.0, lr_power=-0.5,
                 **kwargs):
        super().__init__(learning_rate, **kwargs)
        self._l1 = l1
        self._l2 = l2
        self._lr_power = lr_power

    def _create_accumulators(self, block, parameters):
        for p in parameters:
            self._add_accumulator("squared", p)
            self._add_accumulator("linear", p)

    def _append_optimize_op(self, block, param_and_grad):
        param, grad = param_and_grad
        sq = self._get_accumulator("squared", param)
        lin = self._get_accumulator("linear", param)
        return block.append_op(
            "ftrl",
            inputs={"Param": [param], "Grad": [grad],
                    "SquaredAccumulator": [sq], "LinearAccumulator": [lin],
                    "LearningRate": [self._create_param_lr(param_and_grad)]},
            outputs={"ParamOut": [param], "SquaredAccumOut": [sq],
                     "LinearAccumOut": [lin]},
            attrs={"l1": self._l1, "l2": self._l2,
                   "lr_power": self._lr_power})


class LambOptimizer(Optimizer):
    """operators/optimizers/lamb_op (reference optimizer.py:2091)."""

    type = "lamb"

    def __init__(self, learning_rate=0.001, lamb_weight_decay=0.01,
                 beta1=0.9, beta2=0.999, epsilon=1e-6, **kwargs):
        super().__init__(learning_rate, **kwargs)
        self._weight_decay = lamb_weight_decay
        self._beta1 = beta1
        self._beta2 = beta2
        self._epsilon = epsilon

    def _create_accumulators(self, block, parameters):
        for p in parameters:
            self._add_accumulator("moment1", p)
            self._add_accumulator("moment2", p)
            self._add_accumulator("beta1_pow_acc", p, fill_value=self._beta1,
                                  shape=(1,))
            self._add_accumulator("beta2_pow_acc", p, fill_value=self._beta2,
                                  shape=(1,))

    def _append_optimize_op(self, block, param_and_grad):
        param, grad = param_and_grad
        m1 = self._get_accumulator("moment1", param)
        m2 = self._get_accumulator("moment2", param)
        b1p = self._get_accumulator("beta1_pow_acc", param)
        b2p = self._get_accumulator("beta2_pow_acc", param)
        return block.append_op(
            "lamb",
            inputs={"Param": [param], "Grad": [grad], "Moment1": [m1],
                    "Moment2": [m2], "Beta1Pow": [b1p], "Beta2Pow": [b2p],
                    "LearningRate": [self._create_param_lr(param_and_grad)]},
            outputs={"ParamOut": [param], "Moment1Out": [m1],
                     "Moment2Out": [m2]},
            attrs={"beta1": self._beta1, "beta2": self._beta2,
                   "epsilon": self._epsilon,
                   "weight_decay": self._weight_decay})

    _finish_update = AdamOptimizer._finish_update


def _swap_programs(param_infos, source_of):
    """Build (apply_program, restore_program) that swap params with
    substitute values by name through the scope.

    ``param_infos``: [(name, shape, dtype)]; ``source_of(name, block, pvar)``
    appends ops into ``block`` returning the substitute var to install."""
    apply_prog, restore_prog = framework.Program(), framework.Program()
    for prog, is_apply in ((apply_prog, True), (restore_prog, False)):
        blk = prog.global_block()
        for name, shape, dtype in param_infos:
            p = blk.create_var(name=name, shape=shape, dtype=dtype,
                               persistable=True)
            bak = blk.create_var(name=name + "@BACKUP", shape=shape,
                                 dtype=dtype, persistable=True)
            with program_guard(prog, framework.Program()):
                if is_apply:
                    blk.append_op("assign", inputs={"X": [p]},
                                  outputs={"Out": [bak]})
                    sub = source_of(name, blk, p)
                    blk.append_op("assign", inputs={"X": [sub]},
                                  outputs={"Out": [p]})
                else:
                    blk.append_op("assign", inputs={"X": [bak]},
                                  outputs={"Out": [p]})
    return apply_prog, restore_prog


class ModelAverage(Optimizer):
    """Parameter averaging (reference optimizer.py:2244): keeps a running
    sum of parameter values over a trailing window; ``apply`` swaps the
    window average in (for eval/save), ``restore`` swaps back.

    Simplification vs the reference: one (sum, count) pair reset at
    ``max_average_window`` instead of the reference's rotating
    sum_1/sum_2/sum_3 buffers — same trailing-window average, fewer
    moving parts."""

    def __init__(self, average_window_rate=0.15, min_average_window=10000,
                 max_average_window=10000, **kwargs):
        super().__init__(0.0, **kwargs)
        self.average_window = average_window_rate
        self.min_average_window = min_average_window
        self.max_average_window = max_average_window
        self._param_infos = []
        self._programs = None
        # the reference appends the accumulation ops at construction time
        # (inside the program build, after the optimizer's minimize)
        self.build()

    def _build(self, program):
        from . import layers
        from .layers.control_flow import ConditionalBlock
        block = program.global_block()
        helper = LayerHelper("model_average")
        with program._optimized_guard([]):
            cnt = helper.create_global_variable(
                name=unique_name.generate("ma_count"), shape=(1,),
                dtype="float32", persistable=True)
            helper.set_variable_initializer(cnt, ConstantInitializer(0.0))
            layers.increment(cnt, 1.0, in_place=True)
            self._count_name = cnt.name
            for p in block.all_parameters():
                s = helper.create_global_variable(
                    name=p.name + "_ma_sum", shape=p.shape, dtype=p.dtype,
                    persistable=True)
                helper.set_variable_initializer(s, ConstantInitializer(0.0))
                block.append_op("elementwise_add",
                                inputs={"X": [s], "Y": [p]},
                                outputs={"Out": [s]},
                                attrs={"axis": -1,
                                       OP_ROLE_KEY: OpRole.Optimize})
                self._param_infos.append((p.name, tuple(p.shape), p.dtype))
            # window reset: count > max_window → sum = param*1, count = 1
            mx = layers.fill_constant(shape=[1], dtype="float32",
                                      value=float(self.max_average_window))
            over = layers.greater_than(cnt, mx)
            cb = ConditionalBlock([over])
            with cb.block():
                one = layers.fill_constant(shape=[1], dtype="float32",
                                           value=1.0)
                cur = program.current_block()
                cur.append_op("assign", inputs={"X": [one]},
                              outputs={"Out": [cnt]})
                for pname, _sh, _dt in self._param_infos:
                    cur.append_op("assign", inputs={"X": [pname]},
                                  outputs={"Out": [pname + "_ma_sum"]})

    def minimize(self, loss, startup_program=None, parameter_list=None,
                 no_grad_set=None):
        raise TypeError(
            "ModelAverage wraps an already-optimized program: build your "
            "optimizer, call its minimize, then ModelAverage(...) — "
            "matching the reference usage")

    def build(self, program=None):
        """Append averaging ops (call after the inner optimizer's
        minimize, inside the program build)."""
        program = program or default_main_program()
        self._build(program)

        def avg_of(name, blk, pvar):
            s = blk.create_var(name=name + "_ma_sum", shape=pvar.shape,
                               dtype=pvar.dtype, persistable=True)
            c = blk.create_var(name=self._count_name, shape=(1,),
                               dtype="float32", persistable=True)
            out = blk.create_var(name=unique_name.generate(name + "_ma"))
            blk.append_op("elementwise_div", inputs={"X": [s], "Y": [c]},
                          outputs={"Out": [out]}, attrs={"axis": -1})
            return out

        self._programs = _swap_programs(self._param_infos, avg_of)
        return self

    @contextlib.contextmanager
    def apply(self, executor, need_restore=True):
        assert self._programs is not None, "call .build() in the program"
        executor.run(self._programs[0])
        try:
            yield
        finally:
            if need_restore:
                self.restore(executor)

    def restore(self, executor):
        executor.run(self._programs[1])


class ExponentialMovingAverage:
    """EMA of parameters (reference optimizer.py ExponentialMovingAverage):
    shadow = decay*shadow + (1-decay)*param each step; ``apply`` installs
    the bias-corrected shadow (shadow / (1 - decay^t)) for eval/save,
    ``restore`` puts the training params back."""

    def __init__(self, decay=0.999, thres_steps=None, name=None):
        self._decay = float(decay)
        self._name = name or "ema"
        self._param_infos = []
        self._programs = None

    def update(self):
        """Append EMA update ops; call inside the train program build,
        after the optimizer's minimize (reference contract)."""
        from . import layers
        program = default_main_program()
        block = program.global_block()
        helper = LayerHelper(self._name)
        with program._optimized_guard([]):
            step = helper.create_global_variable(
                name=unique_name.generate("ema_step"), shape=(1,),
                dtype="float32", persistable=True)
            helper.set_variable_initializer(step, ConstantInitializer(0.0))
            layers.increment(step, 1.0, in_place=True)
            self._step_name = step.name
            for p in block.all_parameters():
                ema = helper.create_global_variable(
                    name=p.name + "_" + self._name, shape=p.shape,
                    dtype=p.dtype, persistable=True)
                helper.set_variable_initializer(ema,
                                                ConstantInitializer(0.0))
                scaled_e = layers.scale(ema, scale=self._decay)
                scaled_p = layers.scale(p, scale=1.0 - self._decay)
                block.append_op("elementwise_add",
                                inputs={"X": [scaled_e], "Y": [scaled_p]},
                                outputs={"Out": [ema]},
                                attrs={"axis": -1,
                                       OP_ROLE_KEY: OpRole.Optimize})
                self._param_infos.append((p.name, tuple(p.shape), p.dtype))

        def ema_of(name, blk, pvar):
            from . import layers
            ema = blk.create_var(name=name + "_" + self._name,
                                 shape=pvar.shape, dtype=pvar.dtype,
                                 persistable=True)
            st = blk.create_var(name=self._step_name, shape=(1,),
                                dtype="float32", persistable=True)
            # bias correction: / (1 - decay^t), decay^t = exp(t*ln(decay))
            ln_d = float(np.log(self._decay)) if self._decay > 0 else -80.0
            decay_pow = layers.exp(layers.scale(st, scale=ln_d))
            denom = layers.scale(decay_pow, scale=-1.0, bias=1.0)
            out = blk.create_var(name=unique_name.generate(name + "_emac"))
            blk.append_op("elementwise_div",
                          inputs={"X": [ema], "Y": [denom]},
                          outputs={"Out": [out]}, attrs={"axis": -1})
            return out

        self._programs = _swap_programs(self._param_infos, ema_of)

    @contextlib.contextmanager
    def apply(self, executor, need_restore=True):
        assert self._programs is not None, "call update() in the program"
        executor.run(self._programs[0])
        try:
            yield
        finally:
            if need_restore:
                self.restore(executor)

    def restore(self, executor):
        executor.run(self._programs[1])


class LookaheadOptimizer:
    """Lookahead (reference optimizer.py LookaheadOptimizer): the inner
    (fast) optimizer steps every iteration; every k steps the slow weights
    move alpha of the way to the fast weights and the fast weights reset
    to the slow ones — one conditional_block, same machinery as
    GradientMergeOptimizer."""

    def __init__(self, inner_optimizer, alpha=0.5, k=5):
        assert 0.0 <= alpha <= 1.0
        self.inner_optimizer = inner_optimizer
        self.alpha = float(alpha)
        self.k = int(k)

    def minimize(self, loss, startup_program=None, parameter_list=None,
                 no_grad_set=None):
        from . import layers
        from .layers.control_flow import ConditionalBlock
        result = self.inner_optimizer.minimize(
            loss, startup_program, parameter_list, no_grad_set)
        program = default_main_program()
        startup = startup_program or default_startup_program()
        block = program.global_block()
        helper = LayerHelper("lookahead")
        with program._optimized_guard([]):
            cnt = helper.create_global_variable(
                name=unique_name.generate("la_step"), shape=(1,),
                dtype="float32", persistable=True)
            helper.set_variable_initializer(cnt, ConstantInitializer(0.0))
            layers.increment(cnt, 1.0, in_place=True)
            slows = []
            sb = startup.global_block()
            for p in block.all_parameters():
                slow = helper.create_global_variable(
                    name=p.name + "_la_slow", shape=p.shape, dtype=p.dtype,
                    persistable=True)
                # slow weights start AT the initialized fast weights
                if not sb.has_var_local(slow.name):
                    sb.create_var(name=slow.name, shape=p.shape,
                                  dtype=p.dtype, persistable=True)
                    sb.append_op("assign", inputs={"X": [p.name]},
                                 outputs={"Out": [slow.name]})
                slows.append((p, slow))
            kconst = layers.fill_constant(shape=[1], dtype="float32",
                                          value=float(self.k))
            rem = block.create_var(name=unique_name.generate("la_rem"),
                                   dtype="float32", stop_gradient=True)
            rem.shape = (1,)
            block.append_op("elementwise_mod",
                            inputs={"X": [cnt], "Y": [kconst]},
                            outputs={"Out": [rem]},
                            attrs={"axis": -1, OP_ROLE_KEY: OpRole.Optimize})
            half = layers.fill_constant(shape=[1], dtype="float32",
                                        value=0.5)
            is_sync = layers.less_than(rem, half, force_cpu=False)
            is_sync.stop_gradient = True
        cb = ConditionalBlock([is_sync])
        with cb.block():
            cur = program.current_block()
            for p, slow in slows:
                # slow += alpha * (fast - slow);  fast = slow
                diff = layers.elementwise_sub(p, slow)
                step_v = layers.scale(diff, scale=self.alpha)
                cur.append_op("elementwise_add",
                              inputs={"X": [slow], "Y": [step_v]},
                              outputs={"Out": [slow]},
                              attrs={"axis": -1,
                                     OP_ROLE_KEY: OpRole.Optimize})
                cur.append_op("assign", inputs={"X": [slow]},
                              outputs={"Out": [p]})
        return result


class DGCMomentumOptimizer(Optimizer):
    """DGC-momentum **convergence mode** (reference optimizer.py:787).

    Top-k gradient sparsification with local residual accumulation and
    momentum correction (ops/optimizer_ops.py dgc_momentum).  Parameters
    below ``sparsity`` rampup communicate their own masked psum inside the
    update op, so the collective transpiler must NOT also allreduce their
    grads — minimize() records them in ``program._dgc_param_names`` and
    GradAllReduce skips those (the reference's DGC pass does the same by
    replacing allreduce with sparse_all_reduce,
    ``details/sparse_all_reduce_op_handle.h:30``).

    **What you get on TPU, honestly**: DGC's convergence semantics
    (top-k selection, residual accumulation, momentum correction) are
    exact — but NOT its wire-bandwidth savings.  XLA has no sparse
    allreduce, so the exchange is a masked dense psum over ICI; on ICI
    the dense collective is faster than any gather/scatter encoding
    anyway.  Use this optimizer to reproduce DGC training curves, not to
    reduce interconnect traffic.
    """

    type = "dgc_momentum"

    def __init__(self, learning_rate, momentum, rampup_begin_step,
                 rampup_step=1,
                 sparsity=(0.75, 0.9375, 0.984375, 0.996, 0.999),
                 use_nesterov=False, num_trainers=None, **kwargs):
        super().__init__(learning_rate, **kwargs)
        self._momentum = momentum
        self._rampup_begin_step = int(rampup_begin_step)
        self._rampup_step = int(rampup_step)
        self._sparsity = [float(s) for s in sparsity]

    def _create_accumulators(self, block, parameters):
        for p in parameters:
            self._add_accumulator("dgc_u", p)
            self._add_accumulator("dgc_v", p)

    def _append_optimize_op(self, block, param_and_grad):
        param, grad = param_and_grad
        u = self._get_accumulator("dgc_u", param)
        v = self._get_accumulator("dgc_v", param)
        prog = block.program
        if not hasattr(prog, "_dgc_param_names"):
            prog._dgc_param_names = set()
        prog._dgc_param_names.add(param.name)
        return block.append_op(
            "dgc_momentum",
            inputs={"Param": [param], "Grad": [grad], "U": [u], "V": [v],
                    "LearningRate": [self._create_param_lr(param_and_grad)]},
            outputs={"ParamOut": [param], "UOut": [u], "VOut": [v]},
            attrs={"momentum": self._momentum,
                   "rampup_begin_step": self._rampup_begin_step,
                   "rampup_step": self._rampup_step,
                   "sparsity": self._sparsity})


class GradientMergeOptimizer:
    """k-microbatch gradient accumulation (the reference's multi-batch-merge
    contract: ``framework/ir/multi_batch_merge_pass.cc`` repeats the
    forward/backward k times and averages the grads before one update).

    TPU-native form: per-parameter accumulator vars gather grads every step;
    a ``conditional_block`` guarded by ``step % k == 0`` runs the inner
    optimizer on the averaged accumulation and zeroes the accumulators —
    one XLA computation, the branch lowered to ``lax.cond``.
    """

    def __init__(self, inner_optimizer, k_steps=1, avg=True):
        self.inner_optimizer = inner_optimizer
        self.k_steps = int(k_steps)
        self.avg = avg

    def minimize(self, loss, startup_program=None, parameter_list=None,
                 no_grad_set=None):
        from . import layers
        from .layers.control_flow import ConditionalBlock
        assert self.k_steps >= 1
        if self.k_steps == 1:
            return self.inner_optimizer.minimize(
                loss, startup_program, parameter_list, no_grad_set)
        params_grads = self.inner_optimizer.backward(
            loss, startup_program, parameter_list, no_grad_set)
        program = default_main_program()
        block = program.global_block()
        helper = LayerHelper("gradient_merge")

        with program._optimized_guard([]):
            counter = helper.create_global_variable(
                name=unique_name.generate("gm_step"), shape=(1,),
                dtype="float32", persistable=True)
            counter.stop_gradient = True
            helper.set_variable_initializer(counter,
                                            ConstantInitializer(0.0))
            layers.increment(counter, value=1.0, in_place=True)

            merged = []
            for p, g in params_grads:
                if g is None:
                    continue
                acc = helper.create_global_variable(
                    name=unique_name.generate(p.name + "_gm_acc"),
                    shape=p.shape, dtype=p.dtype, persistable=True)
                acc.stop_gradient = True
                helper.set_variable_initializer(acc,
                                                ConstantInitializer(0.0))
                block.append_op("elementwise_add",
                                inputs={"X": [acc], "Y": [g]},
                                outputs={"Out": [acc]},
                                attrs={"axis": -1,
                                       OP_ROLE_KEY: OpRole.Backward})
                merged.append((p, g, acc))

            # apply-step predicate: step % k == 0  (mod result is >= 0,
            # so "== 0" is "< 0.5" exactly in float)
            kconst = layers.fill_constant(shape=[1], dtype="float32",
                                          value=float(self.k_steps))
            rem = block.create_var(
                name=unique_name.generate("gm_rem"), dtype="float32",
                stop_gradient=True)
            rem.shape = (1,)
            block.append_op("elementwise_mod",
                            inputs={"X": [counter], "Y": [kconst]},
                            outputs={"Out": [rem]},
                            attrs={"axis": -1, OP_ROLE_KEY: OpRole.Optimize})
            half = layers.fill_constant(shape=[1], dtype="float32",
                                        value=0.5)
            is_apply = layers.less_than(rem, half, force_cpu=False)
            is_apply.stop_gradient = True

        cond_blk = ConditionalBlock([is_apply])
        with cond_blk.block():
            apply_pg = []
            for p, g, acc in merged:
                eff = layers.scale(
                    acc, scale=1.0 / self.k_steps if self.avg else 1.0)
                apply_pg.append((p, eff))
            optimize_ops = self.inner_optimizer.apply_gradients(apply_pg)
            cur = program.current_block()
            for _p, _g, acc in merged:
                # zero the accumulator in place for the next round
                cur.append_op("scale", inputs={"X": [acc]},
                              outputs={"Out": [acc]},
                              attrs={"scale": 0.0,
                                     OP_ROLE_KEY: OpRole.Optimize})
        return optimize_ops, params_grads


# Reference-style short aliases
SGD = SGDOptimizer
Momentum = MomentumOptimizer
Adagrad = AdagradOptimizer
Adam = AdamOptimizer
Adamax = AdamaxOptimizer
DecayedAdagrad = DecayedAdagradOptimizer
Adadelta = AdadeltaOptimizer
RMSProp = RMSPropOptimizer
Ftrl = FtrlOptimizer
Lamb = LambOptimizer
LarsMomentum = LarsMomentumOptimizer


# Pipeline optimizer lives in pipeline.py (the stage partition + GPipe
# schedule are executor-level machinery); re-exported here to match the
# reference namespace (optimizer.py:2664).
from .pipeline import PipelineOptimizer  # noqa: E402,F401


class RecomputeOptimizer:
    """Gradient checkpointing / rematerialization wrapper.

    Matches the reference RecomputeOptimizer contract (introduced right
    after 1.5): ``_set_checkpoints([...])`` names the activations to keep;
    every forward span between checkpoints is packed into a ``recompute``
    sub-block op whose backward replays the span (jax.checkpoint) instead
    of retaining its intermediates — trading FLOPs for HBM, the standard
    long-context/large-batch memory lever on TPU.

    Caveat (same as the reference): vars inside a rematerialized span
    cannot be fetched directly; fetch checkpoints or segment outputs.
    """

    def __init__(self, optimizer):
        self.inner_optimizer = optimizer
        self._checkpoints = None

    def _set_checkpoints(self, checkpoints):
        from .framework import Variable
        self._checkpoints = [c.name if isinstance(c, Variable) else c
                             for c in checkpoints]
        return self

    def backward(self, loss, startup_program=None, parameter_list=None,
                 no_grad_set=None, callbacks=None):
        """Segment the forward, then delegate (reference wrapper
        contract: backward/apply_gradients/apply_optimize compose with
        Fleet's DistributedOptimizer delegation)."""
        self._apply_segmentation(loss, no_grad_set)
        return self.inner_optimizer.backward(
            loss, startup_program, parameter_list, no_grad_set, callbacks)

    def apply_gradients(self, params_grads):
        return self.inner_optimizer.apply_gradients(params_grads)

    def apply_optimize(self, loss, startup_program, params_grads):
        return self.inner_optimizer.apply_gradients(params_grads)

    def _apply_segmentation(self, loss, no_grad_set):
        if not self._checkpoints:
            raise ValueError(
                "call _set_checkpoints([...]) before minimize — recompute "
                "needs segment boundaries")
        if not getattr(loss.block.program, "_recompute_segmented", False):
            _segment_for_recompute(loss.block.program, self._checkpoints,
                                   loss.name, no_grad_set or ())
            loss.block.program._recompute_segmented = True

    def minimize(self, loss, startup_program=None, parameter_list=None,
                 no_grad_set=None):
        self._apply_segmentation(loss, no_grad_set)
        return self.inner_optimizer.minimize(
            loss, startup_program, parameter_list, no_grad_set)


def _segment_for_recompute(program, checkpoints, loss_name, no_grad_set=()):
    """Rewrite the (forward-only) main block: pack each op span ending at
    a checkpoint var into one ``recompute`` sub-block op."""
    from .framework import Block, Operator, op_sub_block_indices

    block = program.global_block()
    ck = set(checkpoints)
    segments, cur = [], []
    for op in block.ops:
        if op_sub_block_indices(op) or op.type in ("feed", "fetch") or \
                op.op_role & (OpRole.LRSched | OpRole.Optimize):
            # control-flow/structural ops break (and are never wrapped);
            # so do the learning-rate schedule's ops, built before the
            # model: what they write is read by optimizer ops that do not
            # exist yet, and a span would hide it from them
            if cur:
                segments.append(("wrap", cur))
                cur = []
            segments.append(("keep", [op]))
            continue
        cur.append(op)
        writes = {n for names in op.outputs.values() for n in names}
        if writes & ck:
            segments.append(("wrap", cur))
            cur = []
    if cur:
        # the tail segment produces the loss; wrapping it buys no memory
        segments.append(("keep", cur))

    # suffix read-sets: later_reads[i] = names read by any op in segments
    # AFTER i (one reverse pass, so segmentation stays O(total ops))
    later_reads = [set() for _ in segments]
    acc = set()
    for i in range(len(segments) - 1, -1, -1):
        later_reads[i] = set(acc)
        for op in segments[i][1]:
            for names in op.inputs.values():
                acc.update(n for n in names if n)

    def _is_persistable(name):
        v = block._find_var_recursive(name)
        return v is not None and getattr(v, "persistable", False)

    def _stops_gradient(name):
        if name in no_grad_set:
            return True
        v = block._find_var_recursive(name)
        return v is not None and getattr(v, "stop_gradient", False) \
            and not getattr(v, "is_data", False)

    new_ops = []
    for i, (kind, ops) in enumerate(segments):
        if kind == "keep" or len(ops) < 2:
            new_ops.extend(ops)
            continue
        reads, writes = [], set()
        for op in ops:
            for names in op.inputs.values():
                for n in names:
                    if n and n not in writes and n not in reads:
                        reads.append(n)
            for names in op.outputs.values():
                writes.update(n for n in names if n)
        # survivors: vars later segments read, checkpoints, the loss, and
        # every persistable write (in-place state like BN moving stats
        # must reach the scope even when no later op reads it)
        later = later_reads[i] | ck | {loss_name}
        later |= {n for n in writes if _is_persistable(n)}
        outs = sorted(writes & later)
        if not outs:
            new_ops.extend(ops)
            continue
        # interior stop_gradient / no_grad vars: append_backward would
        # have cut grad flow at these; the in-span replay must too
        stop_vars = sorted(n for n in (writes | set(reads))
                           if _stops_gradient(n))
        sub = Block(program, len(program.blocks), parent_idx=block.idx)
        sub.ops = list(ops)
        program.blocks.append(sub)
        rec = Operator(block, "recompute",
                       inputs={"X": list(reads)},
                       outputs={"Out": outs},
                       attrs={"sub_block": sub.idx,
                              "input_vars": list(reads),
                              "output_vars": outs,
                              "stop_gradient_vars": stop_vars})
        new_ops.append(rec)
    block.ops = new_ops
    program._bump_version()
