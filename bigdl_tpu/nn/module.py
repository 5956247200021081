"""Core module system: Torch ergonomics over a pure functional JAX core.

Reference contract (nn/abstractnn/AbstractModule.scala:41):
  forward(input)                -> output            (timed)
  backward(input, gradOutput)   -> gradInput + accumulates param grads (timed)
  parameters()                  -> (weights, gradWeights)
  getParameters()               -> flattened (weight, grad) vectors
  zeroGradParameters / training / evaluate / clearState / cloneModule

TPU-first redesign: the reference implements ~20k LoC of hand-written
``updateGradInput``/``accGradParameters`` pairs; here every layer defines a
single pure function and gradients come from ``jax.vjp``.  Each module
exposes:

  _forward(P, x, S, ctx) -> (y, new_S | None)     # leaf layers override
  apply(params, x, state, ctx) -> (y, new_state)  # containers override

where ``params``/``state`` are pytrees mirroring the module tree
(``{'~': own_dict, child_name: child_tree, ...}``), ``ctx`` carries the
training flag and a PRNG key stream.  Trainers jit ``apply`` directly; the
eager ``forward``/``backward`` wrappers reproduce the reference's mutable
ergonomics on top of it.

Activity (= Tensor | Table, abstractnn/Activity.scala:26): inputs/outputs may
be jnp arrays, Tables, or any pytree — everything here is pytree-polymorphic.
"""
from __future__ import annotations

import contextlib
import copy
import time
from collections import OrderedDict

import jax
import jax.numpy as jnp
import numpy as np

from bigdl_tpu.utils.random import RNG
from bigdl_tpu.tensor import default_dtype


class Context:
    """Per-call context threaded through ``apply``: train/eval mode + RNG.

    The key stream is split deterministically at trace time, so the same
    ``apply`` traced under jit produces the same key-derivation graph.

    ``seq_mesh``/``seq_axis``: set by a sequence-parallel trainer
    (``DistriOptimizer(sequence_parallel=True)``); attention layers read
    them to route through the exact ring-attention collective instead of
    the single-device softmax (``nn/attention.py``).
    """

    __slots__ = ("training", "key", "seq_mesh", "seq_axis")

    def __init__(self, training: bool = False, key=None, seq_mesh=None,
                 seq_axis: str = "seq"):
        self.training = training
        self.key = key
        self.seq_mesh = seq_mesh
        self.seq_axis = seq_axis

    def next_key(self):
        if self.key is None:
            # Eager-mode convenience; inside jit always pass a key in.
            self.key = RNG.next_key()
        self.key, sub = jax.random.split(self.key)
        return sub


@contextlib.contextmanager
def stripped_caches(module):
    """Temporarily remove ``_cached_*`` attrs (jitted fn wrappers) from the
    module tree: they must never be deep-copied or pickled.  Shared by
    ``Module.clone_module`` and checkpoint pickling
    (utils/file._pickle_architecture)."""
    stash = []

    def pop(mod):
        cached = {k: mod.__dict__.pop(k) for k in list(mod.__dict__)
                  if k.startswith("_cached_")}
        stash.append((mod, cached))
        for child in mod._modules.values():
            pop(child)

    pop(module)
    try:
        yield
    finally:
        for mod, cached in stash:
            mod.__dict__.update(cached)


def _tree_zeros_like(tree):
    return jax.tree_util.tree_map(jnp.zeros_like, tree)


def _tree_add(a, b):
    return jax.tree_util.tree_map(jnp.add, a, b)


class Module:
    """Base class for all layers (ref AbstractModule.scala:41)."""

    def __init__(self):
        self._params: "OrderedDict[str, jnp.ndarray]" = OrderedDict()
        self._buffers: "OrderedDict[str, jnp.ndarray]" = OrderedDict()
        self._grads: "OrderedDict[str, jnp.ndarray]" = OrderedDict()
        self._modules: "OrderedDict[str, Module]" = OrderedDict()
        self.training_mode = True
        self.output = None
        self.grad_input = None
        # per-module wall-clock profiling (ref AbstractModule.scala:125-136)
        self.forward_time = 0.0
        self.backward_time = 0.0
        self._last_key = None
        self.name = None

    # ------------------------------------------------------------------
    # registration
    # ------------------------------------------------------------------
    def _add_param(self, name, value):
        value = jnp.asarray(value, dtype=default_dtype())
        self._params[name] = value
        # made on first use (``_own_grads``): only the eager backward()
        # accumulates into it, and a model that an Optimizer trains would
        # hold a zero copy of itself on the device for nothing
        self._grads[name] = None
        return value

    def _own_grads(self):
        for name, g in self._grads.items():
            if g is None:
                self._grads[name] = jnp.zeros_like(self._params[name])
        return self._grads

    def _add_buffer(self, name, value):
        value = jnp.asarray(value)
        self._buffers[name] = value
        return value

    def __getstate__(self):
        # the validator/serve eval-fn cache (optim._eval_fn) holds a
        # jitted closure: process-local by nature and unpicklable.
        # Dropping it here keeps a model that has been validated or
        # served in-process shippable to a subprocess replica
        # (serve/cluster.ProcessReplica pickles the model at spawn).
        state = dict(self.__dict__)
        state.pop("_cached_eval_fn", None)
        return state

    def set_name(self, name):
        self.name = name
        return self

    def get_name(self):
        return self.name if self.name is not None else type(self).__name__

    # ------------------------------------------------------------------
    # pytree extraction / loading
    # ------------------------------------------------------------------
    def params(self):
        tree = {"~": dict(self._params)}
        for name, m in self._modules.items():
            tree[name] = m.params()
        return tree

    def state(self):
        tree = {"~": dict(self._buffers)}
        for name, m in self._modules.items():
            tree[name] = m.state()
        return tree

    def grads(self):
        tree = {"~": dict(self._own_grads())}
        for name, m in self._modules.items():
            tree[name] = m.grads()
        return tree

    def load_params(self, tree):
        for k, v in tree.get("~", {}).items():
            self._params[k] = jnp.asarray(v)
        for name, m in self._modules.items():
            if name in tree:
                m.load_params(tree[name])
        return self

    def load_state(self, tree):
        for k, v in tree.get("~", {}).items():
            self._buffers[k] = jnp.asarray(v)
        for name, m in self._modules.items():
            if name in tree:
                m.load_state(tree[name])
        return self

    def load_grads(self, tree):
        for k, v in tree.get("~", {}).items():
            self._grads[k] = jnp.asarray(v)
        for name, m in self._modules.items():
            if name in tree:
                m.load_grads(tree[name])
        return self

    # ------------------------------------------------------------------
    # pure functional path (what trainers jit)
    # ------------------------------------------------------------------
    def _forward(self, P, x, S, ctx):
        """Leaf computation. Override in subclasses.

        P: own param dict; S: own buffer dict; returns (y, new_S or None).
        """
        raise NotImplementedError(type(self).__name__)

    def apply(self, params, x, state, ctx):
        y, new_own = self._forward(params.get("~", {}), x, state.get("~", {}), ctx)
        if new_own is None:
            return y, state
        ns = dict(state)
        ns["~"] = new_own
        return y, ns

    # ------------------------------------------------------------------
    # eager Torch-style path (ref forward/backward AbstractModule.scala:145-170)
    # ------------------------------------------------------------------
    def forward(self, x):
        t0 = time.perf_counter()
        self._last_key = RNG.next_key() if self.training_mode else jax.random.PRNGKey(0)
        ctx = Context(training=self.training_mode, key=self._last_key)
        y, new_state = self.apply(self.params(), x, self.state(), ctx)
        self.load_state(new_state)
        self.output = y
        self.forward_time += time.perf_counter() - t0
        return y

    def __call__(self, x):
        return self.forward(x)

    def update_output(self, x):
        return self.forward(x)

    def backward(self, x, grad_output):
        """Returns gradInput and accumulates parameter gradients
        (= updateGradInput + accGradParameters of the reference)."""
        t0 = time.perf_counter()
        ctx_key = self._last_key if self._last_key is not None else jax.random.PRNGKey(0)
        state = self.state()

        def f(p, inp):
            ctx = Context(training=self.training_mode, key=ctx_key)
            y, _ = self.apply(p, inp, state, ctx)
            return y

        _, vjp = jax.vjp(f, self.params(), x)
        gp, gx = vjp(grad_output)
        self.load_grads(_tree_add(self.grads(), gp))
        self.grad_input = gx
        self.backward_time += time.perf_counter() - t0
        return gx

    def update_grad_input(self, x, grad_output):
        """Input gradient only (no param-grad accumulation)."""
        ctx_key = self._last_key if self._last_key is not None else jax.random.PRNGKey(0)
        state = self.state()

        def f(inp):
            ctx = Context(training=self.training_mode, key=ctx_key)
            return self.apply(self.params(), inp, state, ctx)[0]

        _, vjp = jax.vjp(f, x)
        (gx,) = vjp(grad_output)
        self.grad_input = gx
        return gx

    def acc_grad_parameters(self, x, grad_output):
        ctx_key = self._last_key if self._last_key is not None else jax.random.PRNGKey(0)
        state = self.state()

        def f(p):
            ctx = Context(training=self.training_mode, key=ctx_key)
            return self.apply(p, x, state, ctx)[0]

        _, vjp = jax.vjp(f, self.params())
        (gp,) = vjp(grad_output)
        self.load_grads(_tree_add(self.grads(), gp))

    # ------------------------------------------------------------------
    # parameter access (ref parameters()/getParameters(), AbstractModule.scala:217-228)
    # ------------------------------------------------------------------
    def parameters(self):
        """(list of weight arrays, list of grad arrays), depth-first."""
        ws = list(self._params.values())
        gs = list(self._own_grads().values())
        for m in self._modules.values():
            w2, g2 = m.parameters()
            ws += w2
            gs += g2
        return ws, gs

    def get_parameters(self):
        """Flattened (weight, grad) vectors (ref Module.flatten Module.scala:42).

        Divergence from the reference: the returned vectors are snapshots,
        not live views — JAX arrays are immutable, so storage aliasing is
        impossible (and unnecessary: trainers operate on pytrees).
        """
        ws, gs = self.parameters()
        if not ws:
            return jnp.zeros((0,)), jnp.zeros((0,))
        return (jnp.concatenate([w.reshape(-1) for w in ws]),
                jnp.concatenate([g.reshape(-1) for g in gs]))

    def zero_grad_parameters(self):
        self._grads = OrderedDict((k, None) for k in self._grads)
        for m in self._modules.values():
            m.zero_grad_parameters()
        return self

    def n_parameters(self):
        ws, _ = self.parameters()
        return sum(int(np.prod(w.shape)) for w in ws)

    # ------------------------------------------------------------------
    # mode / lifecycle (ref AbstractModule.scala:248-287)
    # ------------------------------------------------------------------
    def training(self):
        self.training_mode = True
        for m in self._modules.values():
            m.training()
        return self

    def evaluate(self):
        self.training_mode = False
        for m in self._modules.values():
            m.evaluate()
        return self

    def is_training(self):
        return self.training_mode

    def clear_state(self):
        self.output = None
        self.grad_input = None
        for m in self._modules.values():
            m.clear_state()
        return self

    def clone_module(self):
        # strip cached jitted fns BEFORE the copy: avoids deep-copying jax
        # function wrappers (and depending on them supporting deepcopy)
        with stripped_caches(self):
            return copy.deepcopy(self)

    def copy_status(self, src: "Module"):
        """Copy running-status buffers (e.g. BN stats) from ``src``
        (ref AbstractModule.copyStatus:65)."""
        self.load_state(src.state())
        return self

    def reset(self):
        """Re-initialize parameters. Layers with params override."""
        for m in self._modules.values():
            m.reset()
        return self

    def reset_times(self):
        self.forward_time = 0.0
        self.backward_time = 0.0
        for m in self._modules.values():
            m.reset_times()

    def get_times(self):
        """[(module, forward_s, backward_s)] recursively
        (ref Container.getTimes Container.scala:71)."""
        out = [(self, self.forward_time, self.backward_time)]
        for m in self._modules.values():
            out += m.get_times()
        return out

    # -- persistence (ref AbstractModule.save:306; utils/File.scala) ------
    def save(self, path, overwrite=True):
        from bigdl_tpu.utils import file as File
        File.save_module(self, path, overwrite=overwrite)
        return self

    def predict(self, x):
        was_training = self.training_mode
        self.evaluate()
        out = self.forward(x)
        if was_training:
            self.training()
        return out

    def __repr__(self):
        return f"{type(self).__name__}()"


class TensorModule(Module):
    """Marker base for modules mapping Tensor -> Tensor (ref TensorModule)."""


class Container(Module):
    """Base for modules holding submodules (ref Container.scala:30)."""

    def __init__(self, *modules):
        super().__init__()
        for m in modules:
            self.add(m)

    def add(self, module: Module):
        self._modules[str(len(self._modules))] = module
        return self

    @property
    def modules(self):
        return list(self._modules.values())

    def __len__(self):
        return len(self._modules)

    def get(self, index: int):
        """1-based indexing, like Torch ``container:get(i)``."""
        return self.modules[index - 1]

    def __repr__(self):
        inner = "\n".join(
            "  " + repr(m).replace("\n", "\n  ") for m in self.modules)
        return f"{type(self).__name__} {{\n{inner}\n}}"


class Criterion:
    """Loss base (ref abstractnn/AbstractCriterion.scala).

    ``apply_loss(input, target) -> scalar`` is the pure function; eager
    ``forward``/``backward`` mirror the reference contract.
    """

    def __init__(self, size_average: bool = True):
        self.size_average = size_average
        self.output = None
        self.grad_input = None

    def apply_loss(self, input, target):
        raise NotImplementedError(type(self).__name__)

    def forward(self, input, target):
        self.output = self.apply_loss(input, target)
        return self.output

    def __call__(self, input, target):
        return self.forward(input, target)

    def backward(self, input, target):
        self.grad_input = jax.grad(lambda i: self.apply_loss(i, target))(input)
        return self.grad_input

    def clone_criterion(self):
        return copy.deepcopy(self)

    def __repr__(self):
        return f"{type(self).__name__}()"
