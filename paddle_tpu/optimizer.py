"""Optimizer classes: minimize() = append_backward + per-param update ops.

Parity: reference python/paddle/fluid/optimizer.py (Optimizer :50,
_create_optimization_pass :339, backward :441, apply_gradients :499; SGD,
Momentum, Adagrad, Adam, Adamax, DecayedAdagrad, Adadelta, RMSProp, Ftrl,
Lamb, LarsMomentum + ModelAverage/ExponentialMovingAverage/
PipelineOptimizer). Accumulators are persistable vars initialized in the
startup program; update ops bind ParamOut to Param so engine donation makes
them in-place on TPU.
"""
from __future__ import annotations

from collections import defaultdict
from typing import Dict, List, Optional

import numpy as np

from . import framework
from .framework import Variable, default_main_program, \
    default_startup_program, program_guard, unique_name, \
    in_dygraph_mode, _name_scope_path
from .backward import append_backward
from .initializer import Constant
from .layer_helper import LayerHelper
from .param_attr import ParamAttr
from . import layers
from .observability import tracing as _obs_tracing

__all__ = [
    "SGD", "Momentum", "Adagrad", "Adam", "Adamax", "DecayedAdagrad",
    "Adadelta", "RMSProp", "Ftrl", "Lamb", "LarsMomentum",
    "SGDOptimizer", "MomentumOptimizer", "AdagradOptimizer",
    "AdamOptimizer", "AdamaxOptimizer", "DecayedAdagradOptimizer",
    "AdadeltaOptimizer", "RMSPropOptimizer", "FtrlOptimizer",
    "LambOptimizer", "LarsMomentumOptimizer", "ModelAverage",
    "ExponentialMovingAverage", "PipelineOptimizer", "DGCMomentumOptimizer",
]


class Optimizer:
    def __init__(self, learning_rate, regularization=None, name=None):
        self.regularization = regularization
        self._name = name
        self._learning_rate = learning_rate
        self._learning_rate_map: Dict[int, Variable] = {}
        self._accumulators: Dict[str, Dict[str, Variable]] = \
            defaultdict(dict)
        self.helper = None

    def get_opti_var_name_list(self):
        """Names of this optimizer's state variables (reference
        optimizer.py Optimizer.get_opti_var_name_list — io.save/load
        use it to persist moments alongside params)."""
        names = []
        for per_param in self._accumulators.values():
            for v in per_param.values():
                names.append(getattr(v, "name", None))
        return [n for n in names if n]

    def load(self, stat_dict):
        """Restore optimizer state from a {name: ndarray} dict
        (reference Optimizer.load, dygraph checkpointing)."""
        import numpy as np
        if in_dygraph_mode():
            from .dygraph.tracer import VarBase
            for per_param in self._accumulators.values():
                for pname, v in list(per_param.items()):
                    name = getattr(v, "name", None)
                    if name in stat_dict:
                        val = np.asarray(stat_dict[name])
                        if isinstance(v, VarBase):
                            v.value = val
                        else:
                            per_param[pname] = val
            return
        from .core.scope import global_scope
        scope = global_scope()
        for name in self.get_opti_var_name_list():
            if name in stat_dict:
                scope.var(name).set_value(
                    np.asarray(stat_dict[name]))

    # ---- dygraph (eager) path --------------------------------------------
    # Reference parity: in dygraph mode optimizer ops run eagerly per
    # param (reference optimizer.py dispatches through the same
    # _append_optimize_op with an imperative block). Here the eager
    # "block" routes append_op to the tracer, so graph and dygraph share
    # one update-rule source (the registered optimizer-op lowerings).
    class _EagerBlock:
        def append_op(self, type=None, inputs=None, outputs=None,
                      attrs=None, infer_shape=True, **kw):
            from .framework import _dygraph_tracer
            return _dygraph_tracer().trace_op(type, inputs or {},
                                              outputs or {}, attrs or {})

    def _dygraph_params_grads(self, parameter_list=None):
        from .framework import _dygraph_tracer
        tracer = _dygraph_tracer()
        from .dygraph.tracer import VarBase
        pgs = []
        for p in tracer._params.values():
            if parameter_list is not None and p.name not in set(
                    v if isinstance(v, str) else v.name
                    for v in parameter_list):
                continue
            if not p.trainable or p.grad is None:
                continue
            g = p.grad if isinstance(p.grad, VarBase) else \
                VarBase(p.grad, stop_gradient=True)
            pgs.append((p, g))
        return pgs

    # ---- learning rate ----------------------------------------------------
    def _create_global_learning_rate(self):
        if in_dygraph_mode():
            from .dygraph.learning_rate_scheduler import \
                LearningRateDecay
            if isinstance(self._learning_rate, LearningRateDecay):
                # scheduler object: step it and refresh the lr var on
                # every minimize (reference dygraph optimizer calls
                # self._learning_rate() per step)
                import jax.numpy as jnp
                lr_now = float(self._learning_rate())
                holder = self._learning_rate_map.get("dygraph")
                if holder is None:
                    from .dygraph.tracer import VarBase
                    holder = VarBase(jnp.asarray([lr_now], jnp.float32),
                                     stop_gradient=True)
                    self._learning_rate_map["dygraph"] = holder
                else:
                    holder.set_value(jnp.asarray([lr_now], jnp.float32))
                return
            if "dygraph" not in self._learning_rate_map:
                if isinstance(self._learning_rate, Variable):
                    self._learning_rate_map["dygraph"] = \
                        self._learning_rate
                else:
                    from .dygraph.tracer import VarBase
                    import jax.numpy as jnp
                    self._learning_rate_map["dygraph"] = VarBase(
                        jnp.asarray([float(self._learning_rate)],
                                    jnp.float32), stop_gradient=True)
            return
        prog = default_main_program()
        lr = self._learning_rate_map.get(id(prog))
        if lr is not None:
            return
        if isinstance(self._learning_rate, Variable):
            self._learning_rate_map[id(prog)] = self._learning_rate
            return
        self._learning_rate_map[id(prog)] = layers.tensor.create_global_var(
            name=unique_name.generate("learning_rate"),
            shape=[1], value=float(self._learning_rate), dtype="float32",
            persistable=True)

    def _global_learning_rate(self, program=None):
        if in_dygraph_mode():
            return self._learning_rate_map.get("dygraph")
        program = program or default_main_program()
        return self._learning_rate_map.get(id(program))

    def _create_param_lr(self, param_and_grad):
        param = param_and_grad[0]
        param_lr = (getattr(param, "optimize_attr", None) or
                    {}).get("learning_rate", 1.0)
        base = self._global_learning_rate()
        if param_lr == 1.0:
            return base
        return layers.tensor.scale(base, scale=float(param_lr))

    # ---- accumulators -----------------------------------------------------
    def _add_accumulator(self, name, param, dtype=None, fill_value=0.0,
                         shape=None):
        cached = self._accumulators[name].get(param.name)
        if cached is not None:
            if in_dygraph_mode():
                return cached
            # one optimizer may minimize a SECOND program (slim's
            # compressor re-minimizes rewritten graphs): the cached
            # Variable belongs to the first program's block, so
            # re-declare it in the current program and append its
            # Constant initializer to the NEW startup program. NOTE:
            # running that startup RE-INITIALIZES the accumulator —
            # moment state does not carry across re-minimize (the
            # rewritten graph's params generally differ, so fresh
            # moments are the sound default); skip running the new
            # startup to keep existing scope state instead
            blk = default_main_program().global_block()
            if blk._find_var_recursive(cached.name) is not None:
                return cached
            assert self.helper is not None
            var = self.helper.create_global_variable(
                name=cached.name, persistable=True,
                dtype=cached.dtype, shape=list(cached.shape))
            sb = default_startup_program().global_block()
            sv = sb.create_var(name=cached.name,
                               shape=list(cached.shape),
                               dtype=cached.dtype, persistable=True)
            Constant(float(fill_value))(sv, sb)
            self._accumulators[name][param.name] = var
            return var
        shape = shape if shape is not None else list(param.shape)
        if in_dygraph_mode():
            import jax.numpy as jnp
            from .dygraph.tracer import VarBase
            from .core.types import dtype_to_np
            acc = VarBase(jnp.full(shape, float(fill_value),
                                   dtype_to_np(dtype or param.dtype)),
                          stop_gradient=True)
            self._accumulators[name][param.name] = acc
            return acc
        assert self.helper is not None
        var_name = unique_name.generate(f"{param.name}_{name}")
        var = self.helper.create_global_variable(
            name=var_name, persistable=True,
            dtype=dtype or param.dtype, shape=shape)
        # init in startup
        sb = default_startup_program().global_block()
        sv = sb.create_var(name=var_name, shape=shape,
                           dtype=dtype or param.dtype, persistable=True)
        Constant(float(fill_value))(sv, sb)
        self._accumulators[name][param.name] = var
        return var

    def _get_accumulator(self, name, param):
        return self._accumulators[name][param.name]

    # ---- to be implemented by subclasses ----------------------------------
    def _create_accumulators(self, block, parameters):
        pass

    def _append_optimize_op(self, block, param_and_grad):
        raise NotImplementedError

    def _finish_update(self, block, parameters_and_grads):
        pass

    # ---- the pass ---------------------------------------------------------
    def _create_optimization_pass(self, parameters_and_grads):
        if in_dygraph_mode():
            block = Optimizer._EagerBlock()
        else:
            prog = default_main_program()
            block = prog.global_block()
        self.helper = LayerHelper(self.__class__.__name__)
        self._create_global_learning_rate()
        self._create_accumulators(
            block, [p for p, g in parameters_and_grads if g is not None])
        optimize_ops = []
        for param_and_grad in parameters_and_grads:
            if param_and_grad[1] is None:
                continue
            if param_and_grad[0].trainable:
                with _name_scope_path(getattr(param_and_grad[0],
                                              "_name_scope", "")):
                    op = self._append_optimize_op(block, param_and_grad)
                optimize_ops.append(op)
        self._finish_update(block, parameters_and_grads)
        return optimize_ops

    def backward(self, loss, startup_program=None, parameter_list=None,
                 no_grad_set=None, callbacks=None):
        if in_dygraph_mode():
            # loss.backward() has populated VarBase.grad on the tape's
            # params (reference dygraph flow); collect them.
            return self._dygraph_params_grads(parameter_list)
        with program_guard(loss.block.program,
                           startup_program or
                           default_startup_program()):
            return append_backward(loss, parameter_list, no_grad_set,
                                   callbacks)

    def apply_gradients(self, params_grads):
        if in_dygraph_mode():
            return self._create_optimization_pass(params_grads)
        # grad clipping + regularization (reference optimizer.py:499-535)
        from .clip import append_gradient_clip_ops
        from .regularizer import append_regularization_ops
        program = default_main_program()
        block = program.global_block()
        start = len(block.ops)
        with _obs_tracing.setup_span("program_build.optimize",
                                     program=program.fingerprint[0]):
            params_grads = append_gradient_clip_ops(params_grads)
            params_grads = append_regularization_ops(
                params_grads, self.regularization)
            ops = self._create_optimization_pass(params_grads)
            # tag the whole optimize phase (clip + regularization + LR
            # schedule + update rules) so the engine can split
            # compute-vs-update for gradient accumulation
            # (reference multi_batch_merge_pass works off the same role)
            from .backward import OP_ROLE_ATTR
            for op in block.ops[start:]:
                op._attrs[OP_ROLE_ATTR] = "optimize"
        return ops

    def apply_optimize(self, loss, startup_program, params_grads):
        if in_dygraph_mode():
            return self.apply_gradients(params_grads)
        with program_guard(loss.block.program,
                           startup_program or
                           default_startup_program()):
            return self.apply_gradients(params_grads)

    def minimize(self, loss, startup_program=None, parameter_list=None,
                 no_grad_set=None, grad_clip=None):
        params_grads = self.backward(loss, startup_program,
                                     parameter_list, no_grad_set)
        optimize_ops = self.apply_optimize(loss, startup_program,
                                           params_grads)
        return optimize_ops, params_grads


class SGDOptimizer(Optimizer):
    def __init__(self, learning_rate, **kw):
        super().__init__(learning_rate, **kw)
        self.type = "sgd"

    def _append_optimize_op(self, block, param_and_grad):
        p, g = param_and_grad
        return block.append_op(
            "sgd",
            inputs={"Param": p, "Grad": g,
                    "LearningRate": self._create_param_lr(param_and_grad)},
            outputs={"ParamOut": p}, infer_shape=False)


class MomentumOptimizer(Optimizer):
    def __init__(self, learning_rate, momentum, use_nesterov=False, **kw):
        super().__init__(learning_rate, **kw)
        self.type = "momentum"
        self._momentum = momentum
        self._use_nesterov = use_nesterov

    def _create_accumulators(self, block, parameters):
        for p in parameters:
            self._add_accumulator("velocity", p)

    def _append_optimize_op(self, block, param_and_grad):
        p, g = param_and_grad
        v = self._get_accumulator("velocity", p)
        return block.append_op(
            "momentum",
            inputs={"Param": p, "Grad": g, "Velocity": v,
                    "LearningRate": self._create_param_lr(param_and_grad)},
            outputs={"ParamOut": p, "VelocityOut": v},
            attrs={"mu": self._momentum,
                   "use_nesterov": self._use_nesterov},
            infer_shape=False)


class LarsMomentumOptimizer(Optimizer):
    def __init__(self, learning_rate, momentum, lars_coeff=0.001,
                 lars_weight_decay=0.0005, **kw):
        super().__init__(learning_rate, **kw)
        self.type = "lars_momentum"
        self._momentum = momentum
        self._lars_coeff = lars_coeff
        self._lars_weight_decay = lars_weight_decay

    def _create_accumulators(self, block, parameters):
        for p in parameters:
            self._add_accumulator("velocity", p)

    def _append_optimize_op(self, block, param_and_grad):
        p, g = param_and_grad
        v = self._get_accumulator("velocity", p)
        return block.append_op(
            "lars_momentum",
            inputs={"Param": p, "Grad": g, "Velocity": v,
                    "LearningRate": self._create_param_lr(param_and_grad)},
            outputs={"ParamOut": p, "VelocityOut": v},
            attrs={"mu": self._momentum, "lars_coeff": self._lars_coeff,
                   "lars_weight_decay": self._lars_weight_decay},
            infer_shape=False)


class AdagradOptimizer(Optimizer):
    def __init__(self, learning_rate, epsilon=1e-6,
                 initial_accumulator_value=0.0, **kw):
        super().__init__(learning_rate, **kw)
        self.type = "adagrad"
        self._epsilon = epsilon
        self._initial = initial_accumulator_value

    def _create_accumulators(self, block, parameters):
        for p in parameters:
            self._add_accumulator("moment", p, fill_value=self._initial)

    def _append_optimize_op(self, block, param_and_grad):
        p, g = param_and_grad
        m = self._get_accumulator("moment", p)
        return block.append_op(
            "adagrad",
            inputs={"Param": p, "Grad": g, "Moment": m,
                    "LearningRate": self._create_param_lr(param_and_grad)},
            outputs={"ParamOut": p, "MomentOut": m},
            attrs={"epsilon": self._epsilon}, infer_shape=False)


class AdamOptimizer(Optimizer):
    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, lazy_mode=False, **kw):
        super().__init__(learning_rate, **kw)
        self.type = "adam"
        self._beta1, self._beta2, self._epsilon = beta1, beta2, epsilon

    def _create_accumulators(self, block, parameters):
        for p in parameters:
            self._add_accumulator("moment1", p)
            self._add_accumulator("moment2", p)
            self._add_accumulator("beta1_pow_acc", p, shape=[1],
                                  fill_value=self._beta1)
            self._add_accumulator("beta2_pow_acc", p, shape=[1],
                                  fill_value=self._beta2)

    def _append_optimize_op(self, block, param_and_grad):
        p, g = param_and_grad
        m1 = self._get_accumulator("moment1", p)
        m2 = self._get_accumulator("moment2", p)
        b1p = self._get_accumulator("beta1_pow_acc", p)
        b2p = self._get_accumulator("beta2_pow_acc", p)
        return block.append_op(
            "adam",
            inputs={"Param": p, "Grad": g, "Moment1": m1, "Moment2": m2,
                    "Beta1Pow": b1p, "Beta2Pow": b2p,
                    "LearningRate": self._create_param_lr(param_and_grad)},
            outputs={"ParamOut": p, "Moment1Out": m1, "Moment2Out": m2,
                     "Beta1PowOut": b1p, "Beta2PowOut": b2p},
            attrs={"beta1": self._beta1, "beta2": self._beta2,
                   "epsilon": self._epsilon}, infer_shape=False)


class AdamaxOptimizer(Optimizer):
    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, **kw):
        super().__init__(learning_rate, **kw)
        self.type = "adamax"
        self._beta1, self._beta2, self._epsilon = beta1, beta2, epsilon

    def _create_accumulators(self, block, parameters):
        for p in parameters:
            self._add_accumulator("moment", p)
            self._add_accumulator("inf_norm", p)
            self._add_accumulator("beta1_pow_acc", p, shape=[1],
                                  fill_value=self._beta1)

    def _append_optimize_op(self, block, param_and_grad):
        p, g = param_and_grad
        return block.append_op(
            "adamax",
            inputs={"Param": p, "Grad": g,
                    "Moment": self._get_accumulator("moment", p),
                    "InfNorm": self._get_accumulator("inf_norm", p),
                    "Beta1Pow": self._get_accumulator("beta1_pow_acc", p),
                    "LearningRate": self._create_param_lr(param_and_grad)},
            outputs={"ParamOut": p,
                     "MomentOut": self._get_accumulator("moment", p),
                     "InfNormOut": self._get_accumulator("inf_norm", p)},
            attrs={"beta1": self._beta1, "beta2": self._beta2,
                   "epsilon": self._epsilon}, infer_shape=False)

    def _finish_update(self, block, parameters_and_grads):
        for p, g in parameters_and_grads:
            if g is None:
                continue
            b1p = self._get_accumulator("beta1_pow_acc", p)
            block.append_op("scale", inputs={"X": b1p},
                            outputs={"Out": b1p},
                            attrs={"scale": self._beta1},
                            infer_shape=False)


class DecayedAdagradOptimizer(Optimizer):
    def __init__(self, learning_rate, decay=0.95, epsilon=1e-6, **kw):
        super().__init__(learning_rate, **kw)
        self.type = "decayed_adagrad"
        self._decay, self._epsilon = decay, epsilon

    def _create_accumulators(self, block, parameters):
        for p in parameters:
            self._add_accumulator("moment", p)

    def _append_optimize_op(self, block, param_and_grad):
        p, g = param_and_grad
        m = self._get_accumulator("moment", p)
        return block.append_op(
            "decayed_adagrad",
            inputs={"Param": p, "Grad": g, "Moment": m,
                    "LearningRate": self._create_param_lr(param_and_grad)},
            outputs={"ParamOut": p, "MomentOut": m},
            attrs={"decay": self._decay, "epsilon": self._epsilon},
            infer_shape=False)


class AdadeltaOptimizer(Optimizer):
    def __init__(self, learning_rate, epsilon=1e-6, rho=0.95, **kw):
        super().__init__(learning_rate, **kw)
        self.type = "adadelta"
        self._epsilon, self._rho = epsilon, rho

    def _create_accumulators(self, block, parameters):
        for p in parameters:
            self._add_accumulator("_avg_squared_grad", p)
            self._add_accumulator("_avg_squared_update", p)

    def _append_optimize_op(self, block, param_and_grad):
        p, g = param_and_grad
        asg = self._get_accumulator("_avg_squared_grad", p)
        asu = self._get_accumulator("_avg_squared_update", p)
        return block.append_op(
            "adadelta",
            inputs={"Param": p, "Grad": g, "AvgSquaredGrad": asg,
                    "AvgSquaredUpdate": asu},
            outputs={"ParamOut": p, "AvgSquaredGradOut": asg,
                     "AvgSquaredUpdateOut": asu},
            attrs={"epsilon": self._epsilon, "rho": self._rho},
            infer_shape=False)


class RMSPropOptimizer(Optimizer):
    def __init__(self, learning_rate, rho=0.95, epsilon=1e-6,
                 momentum=0.0, centered=False, **kw):
        super().__init__(learning_rate, **kw)
        self.type = "rmsprop"
        self._rho, self._epsilon = rho, epsilon
        self._momentum, self._centered = momentum, centered

    def _create_accumulators(self, block, parameters):
        for p in parameters:
            self._add_accumulator("momentum", p)
            self._add_accumulator("mean_square", p)
            self._add_accumulator("mean_grad", p)

    def _append_optimize_op(self, block, param_and_grad):
        p, g = param_and_grad
        mom = self._get_accumulator("momentum", p)
        ms = self._get_accumulator("mean_square", p)
        mg = self._get_accumulator("mean_grad", p)
        return block.append_op(
            "rmsprop",
            inputs={"Param": p, "Grad": g, "Moment": mom,
                    "MeanSquare": ms, "MeanGrad": mg,
                    "LearningRate": self._create_param_lr(param_and_grad)},
            outputs={"ParamOut": p, "MomentOut": mom,
                     "MeanSquareOut": ms, "MeanGradOut": mg},
            attrs={"epsilon": self._epsilon, "decay": self._rho,
                   "momentum": self._momentum,
                   "centered": self._centered}, infer_shape=False)


class FtrlOptimizer(Optimizer):
    def __init__(self, learning_rate, l1=0.0, l2=0.0, lr_power=-0.5, **kw):
        super().__init__(learning_rate, **kw)
        self.type = "ftrl"
        self._l1, self._l2, self._lr_power = l1, l2, lr_power

    def _create_accumulators(self, block, parameters):
        for p in parameters:
            self._add_accumulator("squared", p)
            self._add_accumulator("linear", p)

    def _append_optimize_op(self, block, param_and_grad):
        p, g = param_and_grad
        sq = self._get_accumulator("squared", p)
        lin = self._get_accumulator("linear", p)
        return block.append_op(
            "ftrl",
            inputs={"Param": p, "Grad": g, "SquaredAccumulator": sq,
                    "LinearAccumulator": lin,
                    "LearningRate": self._create_param_lr(param_and_grad)},
            outputs={"ParamOut": p, "SquaredAccumOut": sq,
                     "LinearAccumOut": lin},
            attrs={"l1": self._l1, "l2": self._l2,
                   "lr_power": self._lr_power}, infer_shape=False)


class LambOptimizer(AdamOptimizer):
    def __init__(self, learning_rate=0.001, lamb_weight_decay=0.01,
                 beta1=0.9, beta2=0.999, epsilon=1e-6, **kw):
        super().__init__(learning_rate, beta1, beta2, epsilon, **kw)
        self.type = "lamb"
        self._weight_decay = lamb_weight_decay

    def _append_optimize_op(self, block, param_and_grad):
        p, g = param_and_grad
        m1 = self._get_accumulator("moment1", p)
        m2 = self._get_accumulator("moment2", p)
        b1p = self._get_accumulator("beta1_pow_acc", p)
        b2p = self._get_accumulator("beta2_pow_acc", p)
        return block.append_op(
            "lamb",
            inputs={"Param": p, "Grad": g, "Moment1": m1, "Moment2": m2,
                    "Beta1Pow": b1p, "Beta2Pow": b2p,
                    "LearningRate": self._create_param_lr(param_and_grad)},
            outputs={"ParamOut": p, "Moment1Out": m1, "Moment2Out": m2,
                     "Beta1PowOut": b1p, "Beta2PowOut": b2p},
            attrs={"beta1": self._beta1, "beta2": self._beta2,
                   "epsilon": self._epsilon,
                   "weight_decay": self._weight_decay},
            infer_shape=False)


class ModelAverage(Optimizer):
    """reference optimizer.py:2423 — maintains window-averaged params for
    eval via apply()/restore() context managers."""

    def __init__(self, average_window_rate, min_average_window=10000,
                 max_average_window=10000, **kw):
        super().__init__(0.0, **kw)
        self.average_window = average_window_rate
        self.min_average_window = min_average_window
        self.max_average_window = max_average_window
        self.params_grads = []
        prog = default_main_program()
        self.helper = LayerHelper(self.__class__.__name__)
        for param in prog.global_block().all_parameters():
            if param.do_model_average is not False:
                self.params_grads.append((param, None))
        for param, _ in self.params_grads:
            self._append_average_accumulate_op(param)

    def _append_average_accumulate_op(self, param):
        self._add_accumulator("sum_1", param)
        self._add_accumulator("sum_2", param)
        self._add_accumulator("sum_3", param)
        self._add_accumulator("num_accumulates", param, dtype="int64",
                              shape=[1])
        self._add_accumulator("old_num_accumulates", param,
                              dtype="int64", shape=[1])
        self._add_accumulator("num_updates", param, dtype="int64",
                              shape=[1])
        block = default_main_program().global_block()
        block.append_op(
            "average_accumulates",
            inputs={"param": param,
                    "in_sum_1": self._get_accumulator("sum_1", param),
                    "in_sum_2": self._get_accumulator("sum_2", param),
                    "in_sum_3": self._get_accumulator("sum_3", param),
                    "in_num_accumulates":
                        self._get_accumulator("num_accumulates", param),
                    "in_old_num_accumulates":
                        self._get_accumulator("old_num_accumulates",
                                              param),
                    "in_num_updates":
                        self._get_accumulator("num_updates", param)},
            outputs={"out_sum_1": self._get_accumulator("sum_1", param),
                     "out_sum_2": self._get_accumulator("sum_2", param),
                     "out_sum_3": self._get_accumulator("sum_3", param),
                     "out_num_accumulates":
                         self._get_accumulator("num_accumulates", param),
                     "out_old_num_accumulates":
                         self._get_accumulator("old_num_accumulates",
                                               param),
                     "out_num_updates":
                         self._get_accumulator("num_updates", param)},
            attrs={"average_window": float(self.average_window),
                   "min_average_window": self.min_average_window,
                   "max_average_window": self.max_average_window},
            infer_shape=False)

    def _averaged(self, scope, param):
        s1 = np.asarray(_scope_arr(scope,
                                   self._get_accumulator("sum_1",
                                                         param).name))
        s2 = np.asarray(_scope_arr(scope,
                                   self._get_accumulator("sum_2",
                                                         param).name))
        s3 = np.asarray(_scope_arr(scope,
                                   self._get_accumulator("sum_3",
                                                         param).name))
        na = int(np.asarray(_scope_arr(
            scope, self._get_accumulator("num_accumulates",
                                         param).name)))
        ona = int(np.asarray(_scope_arr(
            scope, self._get_accumulator("old_num_accumulates",
                                         param).name)))
        total = max(na + ona, 1)
        return (s1 + s2 + s3) / float(total)

    def apply(self, executor, need_restore=True):
        """Swap params for their window averages (reference
        ModelAverage.apply — context manager form supported via
        restore())."""
        import contextlib
        from .core.scope import global_scope
        scope = global_scope()
        self._backup = {}
        for param, _ in self.params_grads:
            cur = np.asarray(_scope_arr(scope, param.name))
            self._backup[param.name] = cur
            scope.var(param.name).set_value(
                self._averaged(scope, param).astype(cur.dtype))

        @contextlib.contextmanager
        def _ctx():
            try:
                yield
            finally:
                if need_restore:
                    self.restore(executor)
        return _ctx()

    def restore(self, executor):
        """Restore the raw (non-averaged) params after apply()."""
        from .core.scope import global_scope
        scope = global_scope()
        for name, val in getattr(self, "_backup", {}).items():
            scope.var(name).set_value(val)
        self._backup = {}


def _scope_arr(scope, name):
    v = scope.find_var(name).get_value()
    from .core.scope import LoDTensor as _LT
    return v.array if isinstance(v, _LT) else v


class ExponentialMovingAverage:
    """reference optimizer.py:2524 — EMA shadow params + apply/restore."""

    def __init__(self, decay=0.999, thres_steps=None, name=None):
        self._decay = decay
        self._name = name or ""
        self._shadows = {}
        block = default_main_program().global_block()
        helper = LayerHelper("ema")
        for param in block.all_parameters():
            if not param.trainable:
                continue
            shadow = layers.tensor.create_global_var(
                shape=list(param.shape), value=0.0, dtype=param.dtype,
                persistable=True,
                name=unique_name.generate(f"{param.name}_ema"))
            self._shadows[param.name] = shadow
            block.append_op(
                "scale", inputs={"X": shadow}, outputs={"Out": shadow},
                attrs={"scale": decay}, infer_shape=False)
            tmp = block.create_var(
                name=unique_name.generate("ema_tmp"), dtype=param.dtype)
            block.append_op(
                "scale", inputs={"X": param}, outputs={"Out": tmp},
                attrs={"scale": 1.0 - decay}, infer_shape=False)
            block.append_op(
                "elementwise_add", inputs={"X": shadow, "Y": tmp},
                outputs={"Out": shadow}, infer_shape=False)

    def update(self):
        pass  # folded into main program above

    def apply(self, executor, need_restore=True):
        import contextlib

        @contextlib.contextmanager
        def _guard():
            from .core.scope import global_scope
            import numpy as _np
            scope = global_scope()
            saved = {}
            for pname, shadow in self._shadows.items():
                pv = scope.find_var(pname)
                sv = scope.find_var(shadow.name)
                if pv is None or sv is None:
                    continue
                saved[pname] = pv.get_value()
                pv.set_value(sv.get_value())
            try:
                yield
            finally:
                if need_restore:
                    for pname, val in saved.items():
                        scope.find_var(pname).set_value(val)
        return _guard()

    def restore(self, executor):
        pass


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


class PipelineOptimizer:
    """Pipeline-parallel optimizer wrapper.

    Parity: reference optimizer.py:2664 PipelineOptimizer(optimizer,
    cut_list, place_list, concurrency_list, queue_size, sync_steps) — the
    program is split into device-pinned sections connected by queues and
    run by PipelineTrainer/SectionWorker. TPU-native: minimize() builds a
    separate optimizer-ops program from the inner optimizer (the GPipe
    engine replays those update lowerings functionally after jax.grad of
    the pipelined forward); the schedule itself lives in
    parallel/pipeline.py (ppermute ring over the "pp" mesh axis).
    """

    def __init__(self, optimizer, cut_list=None, place_list=None,
                 concurrency_list=None, queue_size=30, sync_steps=1,
                 start_cpu_core_id=0, num_microbatches=4):
        self._optimizer = optimizer
        self._cut_list = cut_list or []
        self._place_list = place_list
        self._concurrency_list = concurrency_list
        self._queue_size = queue_size
        self._sync_steps = sync_steps
        self.num_microbatches = num_microbatches
        self.opt_program = None

    def minimize(self, loss, startup_program=None, parameter_list=None,
                 no_grad_set=None):
        from .framework import Program, program_guard, \
            default_startup_program
        main = loss.block.program
        self.opt_program = Program()
        startup = startup_program or default_startup_program()
        params = main.all_parameters()
        if parameter_list:
            names = set(parameter_list)
            params = [p for p in params if p.name in names]
        with program_guard(self.opt_program, startup):
            block = self.opt_program.global_block()
            params_grads = []
            for p in params:
                g = block.create_var(name=p.name + "@GRAD",
                                     dtype=p.dtype, shape=p.shape)
                params_grads.append((p, g))
            optimize_ops = self._optimizer.apply_gradients(params_grads)
        return optimize_ops, params_grads

    def cut_vars(self):
        """Variable names at which the forward block is split (from
        cut_list: reference passes Variables; we accept names too)."""
        out = []
        for c in self._cut_list:
            items = c if isinstance(c, (list, tuple)) else [c]
            for v in items:
                out.append(v if isinstance(v, str) else v.name)
        return out


class DGCMomentumOptimizer(MomentumOptimizer):
    """API parity with reference optimizer.py:787 (Deep Gradient
    Compression: top-k sparse allreduce). Sparse collectives rarely win
    over ICI (SURVEY §2.3 row DGC — documented non-goal), so this trains
    as dense Momentum; the rampup/sparsity args are accepted and
    recorded."""

    def __init__(self, learning_rate, momentum, rampup_begin_step=0,
                 rampup_step=1, sparsity=(0.999,), use_nesterov=False,
                 local_grad_clip_norm=None, num_trainers=None,
                 regularization=None, name=None):
        super().__init__(learning_rate, momentum,
                         use_nesterov=use_nesterov,
                         regularization=regularization, name=name)
        self._rampup_begin_step = rampup_begin_step
        self._rampup_step = rampup_step
        self._sparsity = list(sparsity)
