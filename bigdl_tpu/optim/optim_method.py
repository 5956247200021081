"""OptimMethod interface + SGD / Adagrad (ref optim/OptimMethod.scala:98,
SGD.scala:26, Adagrad.scala:26).

Dual interface:
- ``optimize(feval, x, config, state)`` — the reference's functional
  interface over any pytree ``x`` (feval returns (loss, grad-pytree)).
- ``init_state(params)`` + ``update(grads, opt_state, params, hyper)`` —
  pure pytree functions the trainers close over inside ``jit``; all
  branches resolved at trace time, all arithmetic jnp, so the whole
  optimizer fuses into the train step (the reference instead runs SGD on
  each node's weight slice after all-reduce, DistriOptimizer.scala:232).

Config/state live in ``Table``s keyed exactly as the reference
(learningRate, weightDecay, momentum, dampening, nesterov, learningRateDecay,
learningRateSchedule, evalCounter, epoch...) for checkpoint parity.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bigdl_tpu.utils.table import Table, T


def _tree_map(f, *trees):
    return jax.tree_util.tree_map(f, *trees)


class OptimMethod:
    def optimize(self, feval, x, config: Table = None, state: Table = None):
        raise NotImplementedError

    def clear_history(self, state: Table):
        raise NotImplementedError

    def get_hyper_parameter(self, config: Table) -> str:
        return ""

    def update_hyper_parameter(self, config: Table, state: Table):
        pass

    # pure-pytree interface
    def init_state(self, params):
        return {}

    def update(self, grads, opt_state, params, hyper):
        """Returns (new_params, new_opt_state). ``hyper`` is a dict of
        scalars (may be traced values for schedules inside jit)."""
        raise NotImplementedError


class SGD(OptimMethod):
    """SGD with weight decay / momentum / dampening / nesterov + LR schedules
    (ref SGD.scala:26; schedules :128-210).

    ``update`` is a chain of ``tree_map``s that XLA fuses into the backward
    pass; a one-pass Pallas kernel over p, g, v measured ~2x slower on the
    v5e, because the opaque call keeps XLA from doing that (PERF_NOTES
    round 1)."""

    def optimize(self, feval, x, config: Table = None, state: Table = None):
        config = config if config is not None else T()
        state = state if state is not None else config

        schedule = config.get("learningRateSchedule", Default())
        schedule.update_hyper_parameter(config, state)
        clr = -config.get("currentLearningRate", -config.get("learningRate", 1e-3))
        # schedule writes currentLearningRate as a negative value (Torch habit)

        wd = config.get("weightDecay", 0.0)
        mom = config.get("momentum", 0.0)
        damp = config.get("dampening", mom)  # Torch default: dampening = momentum
        nesterov = config.get("nesterov", False)
        lrs = config.get("learningRates", None)

        loss, dfdx = feval(x)
        if wd != 0:
            dfdx = _tree_map(lambda g, p: g + wd * p, dfdx, x)
        if mom != 0:
            if "dfdx" not in state:
                state["dfdx"] = _tree_map(lambda g: g, dfdx)
            else:
                state["dfdx"] = _tree_map(lambda v, g: mom * v + (1 - damp) * g,
                                          state["dfdx"], dfdx)
            if nesterov:
                dfdx = _tree_map(lambda g, v: g + mom * v, dfdx, state["dfdx"])
            else:
                dfdx = state["dfdx"]
        if lrs is not None:
            x = _tree_map(lambda p, g, s: p - clr * s * g, x, dfdx, lrs)
        else:
            x = _tree_map(lambda p, g: p - clr * g, x, dfdx)
        state["evalCounter"] = state.get("evalCounter", 0) + 1
        return x, [loss]

    def clear_history(self, state: Table):
        if "dfdx" in state:
            del state["dfdx"]
        return state

    def get_hyper_parameter(self, config: Table) -> str:
        lr = -config.get("currentLearningRate", -config.get("learningRate", 1e-3))
        return f"Current learning rate is {lr}. "

    def update_hyper_parameter(self, config: Table, state: Table):
        schedule = config.get("learningRateSchedule", Default())
        schedule.update_hyper_parameter(config, state)

    # -- pure interface ----------------------------------------------------
    def init_state(self, params):
        return {"velocity": _tree_map(jnp.zeros_like, params)}

    def update(self, grads, opt_state, params, hyper):
        lr = hyper.get("lr", 1e-3)
        wd = hyper.get("weight_decay", 0.0)
        mom = hyper.get("momentum", 0.0)
        damp = hyper.get("dampening", 0.0)
        nesterov = hyper.get("nesterov", False)
        lr_scales = hyper.get("lr_scales")  # per-param lr multipliers
        # (ref SGD.scala "learningRates" Tensor: per-weight lr scaling)
        if wd != 0.0:
            grads = _tree_map(lambda g, p: g + wd * p, grads, params)
        vel = opt_state["velocity"]
        if mom != 0.0:
            vel = _tree_map(lambda v, g: mom * v + (1 - damp) * g, vel, grads)
            step_dir = (_tree_map(lambda g, v: g + mom * v, grads, vel)
                        if nesterov else vel)
        else:
            step_dir = grads
        if lr_scales is not None:
            step_dir = _tree_map(lambda d, s: d * s, step_dir, lr_scales)
        new_params = _tree_map(lambda p, d: p - lr * d, params, step_dir)
        return new_params, {"velocity": vel}


class Adagrad(OptimMethod):
    """(ref Adagrad.scala:26)"""

    def optimize(self, feval, x, config: Table = None, state: Table = None):
        config = config if config is not None else T()
        state = state if state is not None else config
        lr = config.get("learningRate", 1e-3)
        lrd = config.get("learningRateDecay", 0.0)
        wd = config.get("weightDecay", 0.0)

        loss, dfdx = feval(x)
        if wd != 0:
            dfdx = _tree_map(lambda g, p: g + wd * p, dfdx, x)
        n_eval = state.get("evalCounter", 0)
        clr = lr / (1 + n_eval * lrd)
        if "paramVariance" not in state:
            state["paramVariance"] = _tree_map(jnp.zeros_like, dfdx)
        state["paramVariance"] = _tree_map(lambda v, g: v + g * g,
                                           state["paramVariance"], dfdx)
        std = _tree_map(lambda v: jnp.sqrt(v) + 1e-10, state["paramVariance"])
        x = _tree_map(lambda p, g, s: p - clr * g / s, x, dfdx, std)
        state["evalCounter"] = n_eval + 1
        return x, [loss]

    def clear_history(self, state: Table):
        for k in ("paramVariance",):
            if k in state:
                del state[k]
        return state

    def init_state(self, params):
        return {"variance": _tree_map(jnp.zeros_like, params),
                "step": jnp.zeros((), jnp.int32)}

    def update(self, grads, opt_state, params, hyper):
        lr = hyper.get("lr", 1e-3)
        lrd = hyper.get("lr_decay", 0.0)
        wd = hyper.get("weight_decay", 0.0)
        if wd != 0.0:
            grads = _tree_map(lambda g, p: g + wd * p, grads, params)
        step = opt_state["step"]
        clr = lr / (1 + step.astype(jnp.float32) * lrd)
        var = _tree_map(lambda v, g: v + g * g, opt_state["variance"], grads)
        new_params = _tree_map(
            lambda p, g, v: p - clr * g / (jnp.sqrt(v) + 1e-10), params, grads, var)
        return new_params, {"variance": var, "step": step + 1}


# ---------------------------------------------------------------------------
# learning-rate schedules (ref SGD.scala:128-210)
# ---------------------------------------------------------------------------

class LearningRateSchedule:
    def update_hyper_parameter(self, config: Table, state: Table):
        raise NotImplementedError

    def scale_at(self, step: int, config: Table) -> float:
        """Pure variant for jitted trainers: multiplicative factor at step."""
        raise NotImplementedError


class Default(LearningRateSchedule):
    """lr / (1 + evalCounter * learningRateDecay) (ref SGD.scala Default)."""

    def update_hyper_parameter(self, config: Table, state: Table):
        lr = config.get("learningRate", 1e-3)
        lrd = config.get("learningRateDecay", 0.0)
        n = state.get("evalCounter", 0)
        config["currentLearningRate"] = -lr / (1 + n * lrd)

    def scale_at(self, step, config):
        lrd = config.get("learningRateDecay", 0.0)
        return 1.0 / (1.0 + step * lrd)


class Step(LearningRateSchedule):
    """lr * gamma^(floor(evalCounter / stepSize)) (ref SGD.Step)."""

    def __init__(self, step_size: int, gamma: float):
        self.step_size = step_size
        self.gamma = gamma

    def update_hyper_parameter(self, config: Table, state: Table):
        lr = config.get("learningRate", 1e-3)
        n = state.get("evalCounter", 0)
        config["currentLearningRate"] = -lr * self.gamma ** (n // self.step_size)

    def scale_at(self, step, config):
        return self.gamma ** (step // self.step_size)


class Poly(LearningRateSchedule):
    """lr * (1 - iter/maxIter)^power (ref SGD.Poly — used by Inception
    Train.scala:39-51)."""

    def __init__(self, power: float, max_iteration: int):
        self.power = power
        self.max_iteration = max_iteration

    def update_hyper_parameter(self, config: Table, state: Table):
        lr = config.get("learningRate", 1e-3)
        n = state.get("evalCounter", 0)
        if n > self.max_iteration:
            config["currentLearningRate"] = 0.0
        else:
            config["currentLearningRate"] = -lr * (1 - n / self.max_iteration) ** self.power

    def scale_at(self, step, config):
        import jax.numpy as jnp
        frac = jnp.clip(1.0 - step / self.max_iteration, 0.0, 1.0)
        return frac ** self.power


class EpochDecay(LearningRateSchedule):
    """lr * 0.1^decayFn(epoch) (ref SGD.EpochDecay)."""

    def __init__(self, decay_fn):
        self.decay_fn = decay_fn

    def update_hyper_parameter(self, config: Table, state: Table):
        lr = config.get("learningRate", 1e-3)
        epoch = state.get("epoch", 1)
        config["currentLearningRate"] = -lr * 0.1 ** self.decay_fn(epoch)


class EpochStep(LearningRateSchedule):
    """lr * gamma^floor((epoch-1)/stepSize) (ref SGD.EpochStep)."""

    def __init__(self, step_size: int, gamma: float):
        self.step_size = step_size
        self.gamma = gamma

    def update_hyper_parameter(self, config: Table, state: Table):
        lr = config.get("learningRate", 1e-3)
        epoch = state.get("epoch", 1)
        config["currentLearningRate"] = -lr * self.gamma ** ((epoch - 1) // self.step_size)


class EpochSchedule(LearningRateSchedule):
    """Explicit per-epoch-range rates (ref SGD.EpochSchedule / Regime)."""

    class Regime:
        def __init__(self, start_epoch, end_epoch, config: Table):
            self.start_epoch = start_epoch
            self.end_epoch = end_epoch
            self.config = config

    def __init__(self, regimes):
        self.regimes = regimes

    def update_hyper_parameter(self, config: Table, state: Table):
        epoch = state.get("epoch", 1)
        for r in self.regimes:
            if r.start_epoch <= epoch <= r.end_epoch:
                config.update(r.config)
        config["currentLearningRate"] = -config.get("learningRate", 1e-3)
