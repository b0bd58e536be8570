"""AMP optimizer decorator.

Reference contract: ``contrib/mixed_precision/decorator.py:27``
OptimizerWithMixedPrecision — scale the loss, run backward, check grads for
inf/nan, unscale, update the loss scaling, then apply.  The reference
rewrites the whole forward graph to fp16 with cast ops
(``fp16_utils.py``); here the program is tagged with an AMP compute dtype
(bf16) and the MXU lowerings (matmul/conv — lowering.py ``amp_operands``)
run bf16 inputs with fp32 accumulation, which is the idiomatic TPU recipe:
same MXU speedup, no fp16 range cliff, master weights implicit.

bf16 shares fp32's exponent range so loss scaling is numerically
unnecessary; it is still implemented (default off) to keep the reference's
dynamic-loss-scaling contract testable and for users pinning float16.
"""

from ... import layers
from ...framework import default_main_program
from ...initializer import Constant
from ...layer_helper import LayerHelper
from ... import unique_name
from .fp16_lists import AutoMixedPrecisionLists


class OptimizerWithMixedPrecision:
    """Wraps an optimizer; reference decorator.py:27."""

    def __init__(self, optimizer, amp_lists=None, init_loss_scaling=1.0,
                 use_dynamic_loss_scaling=False, incr_every_n_steps=1000,
                 decr_every_n_nan_or_inf=2, incr_ratio=2.0, decr_ratio=0.8,
                 amp_dtype="bfloat16", use_pure_bf16=False):
        self._optimizer = optimizer
        self._amp_lists = amp_lists or AutoMixedPrecisionLists()
        self._amp_dtype = amp_dtype
        self._use_pure_bf16 = use_pure_bf16
        self._init_loss_scaling = float(init_loss_scaling)
        self._use_dynamic_loss_scaling = use_dynamic_loss_scaling
        self._incr_every_n_steps = incr_every_n_steps
        self._decr_every_n_nan_or_inf = decr_every_n_nan_or_inf
        self._incr_ratio = float(incr_ratio)
        self._decr_ratio = float(decr_ratio)
        self._loss_scaling = None

    def get_loss_scaling(self):
        return self._loss_scaling

    def _make_state_var(self, name, value):
        helper = LayerHelper("amp_state")
        var = helper.create_global_variable(
            name=unique_name.generate(name), shape=(1,), dtype="float32",
            persistable=True)
        var.stop_gradient = True
        helper.set_variable_initializer(var, Constant(value))
        return var

    def backward(self, loss, startup_program=None, parameter_list=None,
                 no_grad_set=None, callbacks=None):
        program = loss.block.program
        program._amp_dtype = self._amp_dtype
        # pure-bf16: MXU outputs stay bf16 end to end (activations and
        # their HBM traffic halve; bf16 keeps fp32's exponent range so no
        # extra loss-scaling pressure)
        program._amp_keep = self._use_pure_bf16
        scaling = self._need_scaling()
        if scaling:
            self._loss_scaling = self._make_state_var(
                "loss_scaling", self._init_loss_scaling)
            scaled_loss = loss * self._loss_scaling
        else:
            scaled_loss = loss
        params_grads = self._optimizer.backward(
            scaled_loss, startup_program=startup_program,
            parameter_list=parameter_list, no_grad_set=no_grad_set,
            callbacks=callbacks)
        return params_grads

    def _need_scaling(self):
        return (self._use_dynamic_loss_scaling
                or self._init_loss_scaling != 1.0)

    def apply_gradients(self, params_grads):
        if not self._need_scaling():
            return self._optimizer.apply_gradients(params_grads)

        program = default_main_program()
        with program._backward_role_guard():
            # check_finite_and_unscale (reference fp16_utils): one fused
            # finiteness reduction over every grad, then gate + unscale.
            grads = [g for _, g in params_grads if g is not None]
            helper = LayerHelper("isfinite")
            finite = helper.create_variable_for_type_inference(
                "bool", stop_gradient=True)
            finite.shape = (1,)
            helper.append_op("isfinite", inputs={"X": grads},
                             outputs={"Out": [finite]})
            gate = layers.cast(finite, "float32")          # 1.0 if finite
            inv_scale = layers.elementwise_div(gate, self._loss_scaling)
            new_pg = []
            for p, g in params_grads:
                if g is None:
                    new_pg.append((p, g))
                    continue
                # non-finite step → grads replaced by zeros (select, not
                # multiply: inf*0 would be nan) → param update is a no-op
                clean = layers.where(finite, g * inv_scale,
                                     layers.zeros_like(g))
                new_pg.append((p, clean))
            if self._use_dynamic_loss_scaling:
                self._update_loss_scaling(gate)
        return self._optimizer.apply_gradients(new_pg)

    def _update_loss_scaling(self, gate):
        """update_loss_scaling op semantics (reference decorator.py:61
        dynamic loss scaling), built from arithmetic gating — no host
        control flow, so the whole step stays one XLA computation."""
        good = self._make_state_var("amp_good_steps", 0.0)
        bad = self._make_state_var("amp_bad_steps", 0.0)
        scale = self._loss_scaling
        one = layers.fill_constant((1,), "float32", 1.0)
        bad_gate = one - gate                               # 1.0 if inf/nan

        new_good = (good + one) * gate                      # reset on bad
        new_bad = (bad + one) * bad_gate                    # reset on good

        # hit thresholds? (sign(x - n + 0.5)+1)/2 ∈ {0,1}
        incr_hit = layers.clip(
            layers.sign(new_good - float(self._incr_every_n_steps) + 0.5),
            0.0, 1.0)
        decr_hit = layers.clip(
            layers.sign(new_bad - float(self._decr_every_n_nan_or_inf) + 0.5),
            0.0, 1.0)

        factor = (one + incr_hit * (self._incr_ratio - 1.0)) \
            * (one - decr_hit * (1.0 - self._decr_ratio))
        new_scale = layers.elementwise_max(scale * factor, one)
        new_good = new_good * (one - incr_hit)
        new_bad = new_bad * (one - decr_hit)

        layers.assign(new_scale, scale)
        layers.assign(new_good, good)
        layers.assign(new_bad, bad)

    def minimize(self, loss, startup_program=None, parameter_list=None,
                 no_grad_set=None):
        params_grads = self.backward(loss, startup_program=startup_program,
                                     parameter_list=parameter_list,
                                     no_grad_set=no_grad_set)
        optimize_ops = self.apply_gradients(params_grads)
        return optimize_ops, params_grads


def decorate(optimizer, amp_lists=None, init_loss_scaling=1.0,
             incr_every_n_steps=1000, decr_every_n_nan_or_inf=2,
             incr_ratio=2.0, decr_ratio=0.8,
             use_dynamic_loss_scaling=False, amp_dtype="bfloat16",
             use_pure_bf16=False):
    """Reference ``fluid.contrib.mixed_precision.decorate`` entry point.

    ``use_pure_bf16`` (TPU extension): keep MXU outputs in bf16 instead of
    round-tripping activations through fp32 — halves activation HBM
    traffic; params, optimizer state, BN statistics and the loss stay
    fp32."""
    return OptimizerWithMixedPrecision(
        optimizer, amp_lists=amp_lists, init_loss_scaling=init_loss_scaling,
        use_dynamic_loss_scaling=use_dynamic_loss_scaling,
        incr_every_n_steps=incr_every_n_steps,
        decr_every_n_nan_or_inf=decr_every_n_nan_or_inf,
        incr_ratio=incr_ratio, decr_ratio=decr_ratio, amp_dtype=amp_dtype,
        use_pure_bf16=use_pure_bf16)
