"""Mixed-precision optimizer decorator.

Parity: reference contrib/mixed_precision/decorator.py:27
(OptimizerWithMixedPrecison: fp16 compute + fp32 master weights
decorator.py:131-140, loss scaling, white/black lists). TPU-native: the
default dtype is bfloat16 — same exponent range as fp32, so loss scaling
is mathematically unnecessary (kept for API parity and for explicit
float16 mode) and master weights are simply the fp32 params the engine
already holds; casts happen inside the matmul/conv lowerings
(core/amp.py) where XLA fuses them into the MXU op.
"""
from __future__ import annotations

import warnings

import jax.numpy as jnp

from ... import layers
from ...observability import tracing as _obs_tracing
from .fp16_lists import AutoMixedPrecisionLists

__all__ = ["decorate", "OptimizerWithMixedPrecision"]

_GUARD_SCALING_WARNED = [False]


class OptimizerWithMixedPrecision:
    def __init__(self, optimizer, amp_lists=None, init_loss_scaling=1.0,
                 use_dynamic_loss_scaling=False, incr_every_n_steps=1000,
                 decr_every_n_nan_or_inf=2, incr_ratio=2.0,
                 decr_ratio=0.8, dtype="bfloat16"):
        self._optimizer = optimizer
        self._amp_lists = amp_lists or AutoMixedPrecisionLists()
        self._loss_scaling = float(init_loss_scaling)
        self._use_dynamic_loss_scaling = use_dynamic_loss_scaling
        self._incr_every_n_steps = int(incr_every_n_steps)
        self._decr_every_n_nan_or_inf = int(decr_every_n_nan_or_inf)
        self._incr_ratio = float(incr_ratio)
        self._decr_ratio = float(decr_ratio)
        self._dtype = jnp.float16 if dtype in ("float16", "fp16") \
            else jnp.bfloat16
        self._use_guard_scaling = False
        if use_dynamic_loss_scaling and self._dtype == jnp.bfloat16:
            # bf16 has fp32's exponent range, so the fp16-style host-side
            # incr/decr loop is pointless — but a scale is still useful as
            # the stability guard's rescale lever, so route bf16 through
            # the engine-integrated on-device scale var instead of
            # silently dropping the request (pre-guard behaviour).
            self._use_dynamic_loss_scaling = False
            self._use_guard_scaling = True
            if not _GUARD_SCALING_WARNED[0]:
                _GUARD_SCALING_WARNED[0] = True
                warnings.warn(
                    "dynamic loss scaling with bfloat16: host-side "
                    "incr/decr is unnecessary (bf16 has fp32 exponent "
                    "range); routing through the on-device scale var "
                    "driven by FLAGS_stability_guard instead "
                    "(docs/STABILITY.md)")

    def get_loss_scaling(self):
        return self._loss_scaling

    def backward(self, loss, startup_program=None, parameter_list=None,
                 no_grad_set=None, callbacks=None):
        """The mixed-precision rewrite: the program's AMP lists set,
        the loss scaled, the inner optimizer's backward (its own
        `program_build.backward` inside this span), the gradients
        unscaled."""
        with _obs_tracing.setup_span(
                "program_build.amp",
                program=loss.block.program.fingerprint[0]):
            return self._backward(loss, startup_program, parameter_list,
                                  no_grad_set)

    def _backward(self, loss, startup_program, parameter_list,
                  no_grad_set):
        program = loss.block.program
        program._amp = {"dtype": self._dtype,
                        "black_ops": frozenset(self._amp_lists.black_list),
                        "white_ops": frozenset(self._amp_lists.white_list)}
        program._bump_version()
        if self._use_guard_scaling:
            return self._backward_guard_scaled(
                loss, program, startup_program, parameter_list,
                no_grad_set)
        scale = self._loss_scaling
        if scale != 1.0:
            scaled_loss = layers.scale(loss, scale=scale)
        else:
            scaled_loss = loss
        params_grads = self._optimizer.backward(
            scaled_loss, startup_program=startup_program,
            parameter_list=parameter_list, no_grad_set=no_grad_set)
        if scale != 1.0:
            params_grads = [
                (p, layers.scale(g, scale=1.0 / scale))
                for p, g in params_grads]
        return scaled_loss, params_grads

    def _backward_guard_scaled(self, loss, program, startup_program,
                               parameter_list, no_grad_set):
        # Engine-integrated dynamic loss scaling: the scale lives in a
        # persistable on-device var updated inside the traced step by the
        # stability guard's verdict (grow after incr_every_n clean steps,
        # shrink on every non-finite step), so no host round-trip per
        # step. build_plan() picks the config up from
        # program._dynamic_loss_scale.
        from ...stability.guard import LOSS_SCALE_VAR
        program._dynamic_loss_scale = {
            "init": self._loss_scaling,
            "incr_every_n": self._incr_every_n_steps,
            "incr_ratio": self._incr_ratio,
            "decr_ratio": self._decr_ratio,
        }
        block = program.global_block()
        if LOSS_SCALE_VAR in block.vars:
            scale_var = block.vars[LOSS_SCALE_VAR]
        else:
            scale_var = layers.create_global_var(
                shape=[1], value=self._loss_scaling, dtype="float32",
                persistable=True, name=LOSS_SCALE_VAR)
        scale_var.stop_gradient = True
        scaled_loss = layers.elementwise_mul(loss, scale_var)
        params_grads = self._optimizer.backward(
            scaled_loss, startup_program=startup_program,
            parameter_list=parameter_list, no_grad_set=no_grad_set)
        params_grads = [
            (p, layers.elementwise_div(g, scale_var))
            for p, g in params_grads]
        return scaled_loss, params_grads

    def apply_gradients(self, params_grads):
        return self._optimizer.apply_gradients(params_grads)

    def minimize(self, loss, startup_program=None, parameter_list=None,
                 no_grad_set=None):
        scaled_loss, params_grads = self.backward(
            loss, startup_program, parameter_list, no_grad_set)
        optimize_ops = self._optimizer.apply_gradients(params_grads)
        return scaled_loss, params_grads if optimize_ops is None \
            else (scaled_loss, params_grads)


def decorate(optimizer, amp_lists=None, init_loss_scaling=1.0,
             incr_every_n_steps=1000, decr_every_n_nan_or_inf=2,
             incr_ratio=2.0, decr_ratio=0.8,
             use_dynamic_loss_scaling=False, dtype="bfloat16"):
    """Reference decorate() (decorator.py:223)."""
    return OptimizerWithMixedPrecision(
        optimizer, amp_lists, init_loss_scaling,
        use_dynamic_loss_scaling, incr_every_n_steps,
        decr_every_n_nan_or_inf, incr_ratio, decr_ratio, dtype)
