"""Module scopes in the compiled train step (``nn/containers._child_apply``,
``optim-update`` / ``obs-taps`` in ``LocalOptimizer._build_step``): every
operation's ``op_name`` carries the class of the module that traced it, and
nothing else about the program changes."""
import contextlib
import inspect
import os
import re
import sys

import pytest

import jax
import jax.numpy as jnp

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), os.pardir))
sys.path.insert(0, ROOT)

import bigdl_tpu.nn as nn  # noqa: E402
from benchmark.trace import ProgramText, categorize  # noqa: E402
from bigdl_tpu.dataset import DataSet, Sample  # noqa: E402
from bigdl_tpu.dataset.transformer import SampleToBatch  # noqa: E402
from bigdl_tpu.optim import LocalOptimizer  # noqa: E402
from bigdl_tpu.utils.random import set_seed  # noqa: E402
from bigdl_tpu.utils.table import T  # noqa: E402

BATCH = 8


def _toy_step_text():
    """The optimizer's own jitted step of a toy conv / pool / LRN /
    Inception-block / dropout model, compiled, as text."""
    import numpy as np
    set_seed(3)
    block = nn.Concat(
        2,
        nn.Sequential(nn.SpatialConvolution(8, 4, 1, 1), nn.ReLU()),
        nn.Sequential(nn.SpatialConvolution(8, 4, 1, 1), nn.ReLU(),
                      nn.SpatialConvolution(4, 4, 3, 3, 1, 1, 1, 1),
                      nn.ReLU()),
        nn.Sequential(nn.SpatialMaxPooling(3, 3, 1, 1, 1, 1),
                      nn.SpatialConvolution(8, 4, 1, 1), nn.ReLU()))
    model = nn.Sequential(
        nn.SpatialConvolution(3, 8, 3, 3, 1, 1, 1, 1), nn.ReLU(),
        nn.SpatialMaxPooling(2, 2, 2, 2), nn.SpatialCrossMapLRN(5),
        block, nn.SpatialAveragePooling(8, 8, 1, 1), nn.Dropout(0.4),
        nn.View(12), nn.Linear(12, 10), nn.LogSoftMax())
    samples = [Sample(np.zeros((3, 16, 16), np.float32),
                      np.asarray([1.0], np.float32))] * BATCH
    opt = LocalOptimizer(model, DataSet.array(samples) >> SampleToBatch(BATCH),
                         nn.ClassNLLCriterion())
    opt.set_state(T(learningRate=0.1, momentum=0.9))
    step = opt._build_step()
    params = model.params()
    shape = jax.ShapeDtypeStruct
    like = lambda t: jax.tree_util.tree_map(
        lambda a: shape(a.shape, a.dtype), t)
    lowered = step.jitted.lower(
        like(params), like(model.state()),
        like(opt.optim_method.init_state(params)),
        shape((BATCH, 3, 16, 16), jnp.float32),
        shape((BATCH, 1), jnp.float32), shape((), jnp.float32),
        like(jax.random.PRNGKey(0)), opt._lr_scales_arg)
    return lowered.compile().as_text()


@contextlib.contextmanager
def _no_compile_cache():
    """The persistent cache's key leaves op metadata out, so the second of
    two programs that differ in names alone would be served the first's
    executable, names included: compile both afresh."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def scoped_text():
    with _no_compile_cache():
        return _toy_step_text()


@pytest.fixture(scope="module")
def plain_text():
    with pytest.MonkeyPatch.context() as patch, _no_compile_cache():
        patch.setattr(jax, "named_scope",
                      lambda name: contextlib.nullcontext())
        return _toy_step_text()


def _instructions(text):
    """Every instruction line with its metadata taken out (the computations
    follow the stack-frame tables at the head of the text)."""
    body = text.split("\n\n", 1)[1] if "StackFrames" in text[:200000] \
        else text
    lines = [re.sub(r",? ?metadata=\{[^}]*\}", "", line)
             for line in body.splitlines() if " = " in line]
    assert len(lines) > 50
    return lines


def _op_names(text):
    return set(re.findall(r'op_name="([^"]*)"', text))


def test_scopes_change_names_and_not_one_instruction(scoped_text,
                                                      plain_text):
    assert _instructions(scoped_text) == _instructions(plain_text)
    assert _op_names(scoped_text) != _op_names(plain_text)


def test_scopes_leave_every_category_as_it_was(scoped_text, plain_text):
    scoped, plain = ProgramText(scoped_text), ProgramText(plain_text)
    assert list(scoped.by_name) == list(plain.by_name)
    kinds = {name: scoped.category(name) for name in scoped.by_name}
    assert kinds == {name: plain.category(name) for name in plain.by_name}
    assert {"CONV-FWD", "CONV-BWD", "POOL-FWD"} <= set(kinds.values())


def test_every_module_kind_is_in_the_op_names(scoped_text, plain_text):
    from benchmark.spanread import scope_of
    scopes = {scope_of(n) for n in _op_names(scoped_text)}
    kinds = {kind for kind, _ in scopes}
    assert {"SpatialConvolution", "ReLU", "SpatialMaxPooling",
            "SpatialAveragePooling", "SpatialCrossMapLRN", "Dropout",
            "Linear", "LogSoftMax", "optim-update",
            "obs-taps"} <= kinds
    # forward and backward of the pooling and of the LRN's custom rule
    for kind in ("SpatialMaxPooling", "SpatialCrossMapLRN"):
        assert {(kind, "fwd"), (kind, "bwd")} <= scopes
    assert ("optim-update", None) in scopes
    # without the scopes no name holds a module kind
    assert not any(kind for kind, _ in map(scope_of, _op_names(plain_text)))


def test_merged_pointwise_heads_are_booked_to_their_convolution(scoped_text):
    """``Concat._apply_merged`` runs the branches' 1x1 heads as one
    convolution, past ``_child_apply``: it carries their scope, and the
    modules after a merged head keep theirs."""
    from benchmark.spanread import scope_of
    convs = [n for n in _op_names(scoped_text)
             if n.endswith("conv_general_dilated") and "Concat" in n]
    assert convs
    assert all(scope_of(n)[0] == "SpatialConvolution" for n in convs)
    assert any("Concat)/Sequential/ReLU" in n for n in _op_names(scoped_text))


def _module_kinds():
    return sorted(name for name, cls in vars(nn).items()
                  if inspect.isclass(cls) and issubclass(cls, nn.Module))


def test_no_scope_name_reads_as_a_category():
    """``benchmark/trace.py categorize`` matches substrings of the
    ``op_name``: no module kind (and neither step scope) may read as a
    kernel, a pooling, a convolution, a product or a random draw."""
    kinds = _module_kinds()
    assert len(kinds) > 50 and "SpatialCrossMapLRN" in kinds
    for kind in kinds + ["optim-update", "obs-taps"]:
        for name in (f"jit(step)/jvp({kind})/add",
                     f"jit(step)/transpose(jvp(Sequential))/{kind}/mul"):
            assert categorize("add", name) == "ELTWISE/OTHER", kind
        # a convolution under the scope is forward until transposed
        assert categorize(
            "convolution", f"jit(step)/jvp({kind})/x") == "CONV-FWD", kind
