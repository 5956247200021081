"""Ask the chip's compiler, without the chip (on-chip-measurement guide §2).

The TPU compiler is installed in the sandbox and compiles for a chip that is
described, not attached.  These tests compile — never run — the Mosaic
kernels and step programs of the main paths at the shapes ``chip_smoke.py``
drives them at, so a block shape the tiling refuses, a kernel that outgrows
VMEM or a primitive Mosaic cannot lower fails here, on the CPU, at no chip
time.  Interpret-mode tests (tests/test_pallas_ops.py,
tests/test_paged_attention.py) hold the math; nothing here checks a value.

The topology is described inside a module-scoped fixture that skips when it
cannot be: only one process may hold the TPU library, so nothing in this
file touches it at import or collection time, and every test of the file
runs in the one worker that was handed the file.
"""
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from bigdl_tpu.ops import pallas_kernels as pk

pytestmark = pytest.mark.perf


@pytest.fixture(scope="module")
def topo():
    """A described (not attached) v5e 2x2 host.  The persistent compile
    cache is off for the module: an executable compiled for a described
    chip is written to it but cannot be read back without the chip.  And
    matmul precision is jax's default, as on the chip, not the
    full-f32 the suite's conftest pins for CPU value tests (Mosaic has
    no f32-precision contraction of bf16 operands)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    cache = jax.config.jax_enable_compilation_cache
    precision = jax.config.jax_default_matmul_precision
    jax.config.update("jax_enable_compilation_cache", False)
    jax.config.update("jax_default_matmul_precision", None)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", cache)
    jax.config.update("jax_default_matmul_precision", precision)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def as_if_on_tpu(monkeypatch):
    """Code that asks the backend still sees the CPU here; the whole-step
    tests steer its kernel gates the way the chip would."""
    monkeypatch.setattr(pk, "_on_tpu", lambda: True)


def compile_for(sharding, fn, *shapes):
    """Compile ``fn`` for the described chip; returns the optimized HLO."""
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
            for shape, dtype in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def grad_of(fn, argnums):
    return jax.grad(lambda *a: fn(*a).astype(jnp.float32).sum(),
                    argnums=argnums)


# the recurrent flagship's shapes: batch 128, T=500, hidden 128
T, B, H = 500, 128, 128
F32, BF16, I8, I32 = jnp.float32, jnp.bfloat16, jnp.int8, jnp.int32

RECURRENCES = {
    "bilstm": (lambda *a: pk.bilstm_recurrence(*a, False, 1),
               lambda d, dt: [((T, d, B, 4 * H), dt), ((d, H, 4 * H), dt)]),
    "gru": (lambda *a: pk.gru_recurrence(*a, False, 1),
            lambda d, dt: [((T, d, B, 2 * H), dt), ((T, d, B, H), dt),
                           ((d, H, 2 * H), dt), ((d, H, H), dt)]),
    "rnn": (lambda *a: pk.rnn_recurrence(*a, False, 1),
            lambda d, dt: [((T, d, B, H), dt), ((d, H, H), dt)]),
}


@pytest.mark.parametrize("directions", [1, 2])
@pytest.mark.parametrize("cell,dtype", [("bilstm", F32), ("bilstm", BF16),
                                        ("gru", F32), ("rnn", F32)])
@pytest.mark.parametrize("mode", ["fwd", "grad"])
def test_recurrence_kernels(one_chip, cell, dtype, directions, mode):
    fn, shapes = RECURRENCES[cell]
    shapes = shapes(directions, dtype)
    if mode == "grad":
        fn = grad_of(fn, tuple(range(len(shapes))))
    assert "tpu_custom_call" in compile_for(one_chip, fn, *shapes)


# Inception-v1's max pools at batch 128: the four 3x3/s2 ceil-mode pools
# and the 3x3/s1 pool inside the inception modules
POOLS = [((128, 64, 112, 112), (2, 2), ((0, 1), (0, 1))),
         ((128, 192, 56, 56), (2, 2), ((0, 1), (0, 1))),
         ((128, 480, 28, 28), (2, 2), ((0, 1), (0, 1))),
         ((128, 832, 14, 14), (2, 2), ((0, 1), (0, 1))),
         ((128, 192, 28, 28), (1, 1), ((1, 1), (1, 1)))]


@pytest.mark.parametrize("shape,strides,pads", POOLS,
                         ids=[f"{s[1]}x{s[2]}s{st[0]}" for s, st, _ in POOLS])
@pytest.mark.parametrize("mode", ["fwd", "grad"])
def test_mosaic_maxpool(one_chip, shape, strides, pads, mode):
    def fn(x):
        return pk.mosaic_maxpool2d(x, (3, 3), strides, pads, False)
    if mode == "grad":
        fn = grad_of(fn, 0)
    # bf16: the dtype the pool runs in under the bf16-compute policy
    assert "tpu_custom_call" in compile_for(one_chip, fn, (shape, BF16))


@pytest.mark.parametrize("shape", [(128, 64, 56, 56), (128, 192, 56, 56)],
                         ids=["c64", "c192"])
@pytest.mark.parametrize("dtype", [F32, BF16], ids=["f32", "bf16"])
@pytest.mark.parametrize("mode", ["fwd", "grad"])
def test_lrn_channel(one_chip, shape, dtype, mode):
    """Inception's two LRNs.  The C=192 backward used to ask for 21 MB of
    the 16 MB scoped VMEM (a whole 192 x 3136 image per block)."""
    def fn(x):
        return pk.lrn_channel(x, 5, 1e-4, 0.75, 1.0, False)
    if mode == "grad":
        fn = grad_of(fn, 0)
    assert "tpu_custom_call" in compile_for(one_chip, fn, (shape, dtype))


# the decode kernels at the widest LM geometry: 4 heads x 256, 8 slots,
# 288 positions of context in pages of 16 (18 pages a slot)
SLOTS, HEADS, HD, PAGE, PAGES_PER_SLOT = 8, 4, 256, 16, 18
N_PAGES = SLOTS * PAGES_PER_SLOT


def paged_shapes(window, quantized):
    pool = ((N_PAGES, PAGE, HEADS, HD), I8 if quantized else F32)
    shapes = [((SLOTS, window, HEADS, HD), F32), pool, pool,
              ((SLOTS, PAGES_PER_SLOT), I32), ((SLOTS, window), I32)]
    if quantized:
        shapes += [((N_PAGES, PAGE, HEADS), F32)] * 2
    return shapes


@pytest.mark.parametrize("kernel,window", [(pk.paged_attention, 1),
                                           (pk.paged_spec_verify, 5)],
                         ids=["paged_attention", "paged_spec_verify"])
@pytest.mark.parametrize("quantized", [False, True], ids=["f32", "int8"])
@pytest.mark.parametrize("precision", ["default", "highest"])
def test_paged_attention_kernels(one_chip, kernel, window, quantized,
                                 precision):
    """PR 16's two kernels blocked ``pos`` one row at a time and K/V one
    head at a time — block shapes the Mosaic tiling refuses, so only the
    interpreter had ever taken them.  ``highest`` is what the smoke's
    token-parity phase decodes under."""
    fn = functools.partial(kernel, interpret=False)
    with jax.default_matmul_precision(precision):
        text = compile_for(one_chip, fn, *paged_shapes(window, quantized))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("kernels", [False, True], ids=["xla", "mosaic"])
def test_whole_decode_step_d1024(one_chip, as_if_on_tpu, monkeypatch,
                                 kernels):
    """One paged decode step of the widest LM the repo supports (d_model
    1024, 4 heads, FFN 4096, 6 layers, vocab 4096), weights as arguments
    like the tensor-parallel step takes them."""
    from bigdl_tpu.models import transformer as tf
    from bigdl_tpu.utils.random import set_seed

    monkeypatch.setattr(tf, "_PALLAS_PAGED_ATTN", kernels)
    set_seed(1)
    lm = tf.TransformerLM(vocab_size=4096, d_model=1024, n_heads=HEADS,
                          n_layers=6, hidden=4096, dropout=0.0)
    handles = tf._lm_handles(lm)
    weights = {"emb": handles.emb, "blocks": handles.blocks,
               "ln_f": handles.ln_f, "head": handles.head}
    pe = jnp.asarray(lm.modules[1].table(PAGES_PER_SLOT * PAGE))
    pool = ((6, N_PAGES, PAGE, HEADS, HD), F32)

    def step(weights, tok, pos, kpool, vpool, ptab):
        logp, _ = tf._lm_forward_window(
            tok, pos, (kpool, vpool), handles._replace(**weights), pe,
            (ptab, PAGE))
        return logp

    abstract = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        weights)
    others = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
              for shape, dtype in (((SLOTS, 1), I32), ((SLOTS, 1), I32),
                                   pool, pool,
                                   ((SLOTS, PAGES_PER_SLOT), I32))]
    with jax.default_matmul_precision("highest"):   # as the smoke decodes
        text = jax.jit(step).lower(abstract, *others).compile().as_text()
    assert ("tpu_custom_call" in text) == kernels


def test_paged_attention_sharded_over_four_chips(topo):
    """The tensor-parallel decoder's layout on the described 2x2 host: heads
    split over a 4-wide ``model`` axis, the kernel inside ``jax.shard_map``
    with the vma check ON (its out_shape carries the operands' vma), one
    psum merging the shards."""
    mesh = Mesh(np.array(topo.devices).reshape(1, 4), ("data", "model"))
    heads = P(None, None, "model", None)
    specs = (heads, heads, heads, P(), P())

    def local(q, kpool, vpool, ptab, pos):
        out = pk.paged_attention(q, kpool, vpool, ptab, pos,
                                 interpret=False)
        return jax.lax.psum(out.sum(axis=(2, 3)), "model")

    sharded = jax.shard_map(local, mesh=mesh, in_specs=specs,
                            out_specs=P())
    args = [jax.ShapeDtypeStruct(shape, dtype,
                                 sharding=NamedSharding(mesh, spec))
            for (shape, dtype), spec in zip(paged_shapes(1, False), specs)]
    compiled = jax.jit(sharded).lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "all-reduce" in text
    # each chip holds its quarter of the pool, not a copy of it
    per_chip = compiled.memory_analysis().argument_size_in_bytes
    pool_bytes = 2 * N_PAGES * PAGE * HEADS * HD * 4
    assert per_chip < pool_bytes / 2


@pytest.mark.parametrize("batch", [1, 4, 8])
@pytest.mark.parametrize("mode", ["eval", "train"])
def test_conv_then_lrn_compiles_at_small_batch(one_chip, batch, mode):
    """Inception's conv2 -> ReLU -> LRN.  Below batch 8 the TPU compiler's
    space-to-batch rewrite of the conv ran into the LRN's channel-window
    sum: the serving buckets 1/2/4 failed to compile ("Binary op with
    incompatible shapes") and a small-batch training step aborted the
    compiler.  nn/normalization.py now fences the window sum there."""
    import bigdl_tpu.nn as nn
    from bigdl_tpu import tensor as bt
    from bigdl_tpu.nn.module import Context

    model = nn.Sequential(
        nn.SpatialConvolution(64, 192, 3, 3, 1, 1, 1, 1), nn.ReLU(True),
        nn.SpatialCrossMapLRN(5, 0.0001, 0.75))
    params, state = model.params(), model.state()
    training = mode == "train"

    def forward(p, x):
        y, _ = model.apply(p, x, state, Context(
            training=training, key=jax.random.PRNGKey(0)))
        return y

    fn = grad_of(forward, 0) if training else forward
    abstract = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        params)
    x = jax.ShapeDtypeStruct((batch, 64, 56, 56), F32, sharding=one_chip)
    before = bt.policy()
    bt.set_policy(bt.BF16_COMPUTE)
    try:
        jax.jit(fn).lower(abstract, x).compile()
    finally:
        bt.set_policy(before)


def _loop_carries(text):
    """[[(shape, layout) of every array a ``while`` of the compiled text
    carries]], one list a loop."""
    return [re.findall(r"(\w+\[[\d,]*\])(\{[^}]*\})", carried)
            for carried in re.findall(r"^\s*\S+ = (\(.*?\)) while\(", text,
                                      re.M)]


@pytest.mark.parametrize("hq,hk,d,dv,window,heads,in_vmem,temp", [
    (32, 32, 192, 128, None, 4, True, 1.07e9),      # kanana-2-30b-a3b
    (32, 4, 128, 128, None, 2, True, 0.3e9),        # trinity-mini, full
    (32, 4, 128, 128, 2048, 2, False, 0.3e9),       # and window
], ids=["mla", "afmoe_full", "afmoe_window"])
def test_attention_backward_accumulates_in_vmem(one_chip, hq, hk, d, dv,
                                                window, heads, in_vmem,
                                                temp):
    """``blockwise_attention``'s forward and gradients at the benchmark
    cells' core shapes (2 x 8,192 tokens, blocks of 512, bfloat16), compiled
    for the described v5e.  **This pins a decision of the compiler, not of
    the program**: ``_walk_plan`` sizes a pass of the backward (4 key heads
    of kanana's 32, 2 of trinity-mini's 4) so that the float32 accumulators
    *can* live in VMEM, and the memory space in the layout of the inner
    loop's carries (``S(1)``) says whether they *do*: dq's block at every
    shape, and a pass's whole dk and dv where the loop runs from key block
    0 (the window layer's are added to by slice in HBM: its inner loop
    starts at a block the compiler cannot bound).  Before PR 34 all three
    went through HBM at every block pair (PERF.md section 6).  A jax or
    libtpu upgrade that flips the placement fails here, on the CPU, and not
    as a slower step on the chip.  No (T, T) array; one more loop than the
    one-pass walk (2 forward, 3 backward); temporaries no higher than
    before the walk was split (1.07 GB at kanana's shape)."""
    from bigdl_tpu.parallel.ring_attention import blockwise_attention
    b, t, block, g = 2, 8192, 512, hq // hk
    fn = jax.value_and_grad(
        lambda q, k, v: blockwise_attention(q, k, v, window).sum(),
        argnums=(0, 1, 2))
    args = [jax.ShapeDtypeStruct(shape, BF16, sharding=one_chip)
            for shape in ((b, t, hq, d), (b, t, hk, d), (b, t, hk, dv))]
    compiled = jax.jit(fn).lower(*args).compile()
    text = compiled.as_text()
    assert f"{t},{t}]" not in text
    loops = _loop_carries(text)
    assert len(loops) == 5
    dq_blk = f"f32[{b},{block},{heads},{g},{d}]"
    inner, = [dict(loop) for loop in loops if dq_blk in dict(loop)]
    assert "S(1)" in inner[dq_blk]
    for width in {d, dv}:
        assert ("S(1)" in inner[f"f32[{b},{t},{heads},{width}]"]) == in_vmem
    assert compiled.memory_analysis().temp_size_in_bytes <= temp


# A routing is decided once: a ``Recompute``d expert layer's gradient holds
# one top-k (a sort of the scores' lanes on this chip) and one sort of the
# assignments, none in the recomputation (2 of them, 4 before PR 36; the
# passes' scatter-adds sort their rows too, under other names).
ROUTING_SORT = (r' sort\(.*op_name="[^"]*MoeRoute/'
                r'(top_k|jit\(argsort\)/sort)"')


@pytest.mark.parametrize("kind", ["sliding_attention", "full_attention"])
def test_afmoe_expert_layer_compiles_at_published_widths(one_chip, kind):
    """One decoder layer of ``models/afmoe.py`` at Trinity-Mini's widths
    (hidden 2048, 32/4 heads of 128, window 2048, 16 of 128 experts of
    width 1024, top-8), one 8,192-token sequence, forward and backward
    under bf16 compute: the block loops of the attention core hold no
    T x T array, the grouped products become the TPU's own ragged-dot
    kernels, and the layer's ``Recompute`` runs neither the core's loop
    nor the routed pass again (a core is a loop in a loop: 2 + 2 for its
    forward and backward, one sequence's four key heads being one pass of
    the backward, and a chunk loop each way; 9 with all three
    recomputed, as before PR 30; the chunks are walked by a loop for each
    of the three steps a pass may shorten to, so 3 + 3 of them).  Every
    float32 sum is added to in place in whichever loop runs: none is
    copied on its way from one loop to the next."""
    import bigdl_tpu.nn as nn
    from bigdl_tpu import tensor as bt
    from bigdl_tpu.models.afmoe import afmoe_layer
    from bigdl_tpu.nn import init as init_
    from bigdl_tpu.nn.module import Context

    t, d = 8192, 2048
    drawn = init_.normal_on_device
    init_.normal_on_device = lambda shape, std=None: np.broadcast_to(
        np.float32(0), shape)           # shapes only: nothing is run
    try:
        ffn = nn.DroplessMoE(d, 1024, 128, 8, experts_held=range(16),
                             route_scale=2.826, shared_hidden=1024)
        layer = afmoe_layer(d, 32, 4, 128, ffn,
                            2048 if kind == "sliding_attention" else None,
                            10000.0, 1e-5)
    finally:
        init_.normal_on_device = drawn
    abstract = lambda tree: jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        tree)

    def loss(p, s, x):
        y, _ = layer.apply(p, x, s, Context(training=True,
                                            key=jax.random.PRNGKey(0)))
        return y.sum()

    before = bt.policy()
    bt.set_policy(bt.BF16_COMPUTE)
    try:
        compiled = jax.jit(jax.grad(loss)).lower(
            abstract(layer.params()), abstract(layer.state()),
            jax.ShapeDtypeStruct((1, t, d), F32, sharding=one_chip)
        ).compile()
    finally:
        bt.set_policy(before)
    text = compiled.as_text()
    assert "ragged-dot" in text and "tpu_custom_call" in text
    assert f"{t},{t}]" not in text                  # no T x T array
    assert len(re.findall(r" while\(", text)) == 4 + 2 * ffn.STEPS_OF_CHUNK
    assert not re.findall(r"= f32\[16,(2048,1024|1024,2048)\]\S* copy\(", text)
    assert len(re.findall(ROUTING_SORT, text)) == 2
    assert compiled.memory_analysis().temp_size_in_bytes < 6e9


def test_deepseek_v3_expert_layer_compiles_at_published_widths(one_chip):
    """One decoder layer of ``models/deepseek_v3.py`` at kanana-2-30b-a3b's
    widths (hidden 2048, 32 heads of 128 + 64 for the scores and 128 for
    the values out of a 512 latent, 16 of 128 experts of width 768, top-6,
    2 shared), one 8,192-token sequence, forward and backward under bf16
    compute: the core's block loops take a value head of their own size
    and hold no T x T array, and the layer's ``Recompute`` runs neither the
    core's loop nor the routed pass again: 2 + 3 loops for the core's
    forward and backward (the third walks one sequence's 32 key heads in
    four passes of 8), and one chunk loop for each step a backward pass may
    shorten to.  The routed experts' sum goes straight into the
    residual add, so no backward computation reads it, and this gradient,
    which needs no forward value, runs no forward pass over the chunks at
    all (an afmoe layer's closing norm reads the sum)."""
    import bigdl_tpu.nn as nn
    from bigdl_tpu import tensor as bt
    from bigdl_tpu.models.deepseek_v3 import DeepseekV3LM
    from bigdl_tpu.nn import init as init_
    from bigdl_tpu.nn.module import Context

    t, d = 8192, 2048
    drawn = init_.normal_on_device
    init_.normal_on_device = lambda shape, std=None: np.broadcast_to(
        np.float32(0), shape)           # shapes only: nothing is run
    try:
        # the model's own construction of its first expert layer (a
        # vocabulary of 256 rows: the embedding and the head are not run)
        layer = DeepseekV3LM(
            256, d, 2, 1, 32, 512, 128, 64, 128, 6144, 768, 128, 6,
            experts_held=range(16), n_shared_experts=2,
            routed_scaling_factor=2.448, rope_theta=1e6,
            rms_norm_eps=1e-6).modules[2]
    finally:
        init_.normal_on_device = drawn
    abstract = lambda tree: jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        tree)

    def loss(p, s, x):
        y, _ = layer.apply(p, x, s, Context(training=True,
                                            key=jax.random.PRNGKey(0)))
        return y.sum()

    before = bt.policy()
    bt.set_policy(bt.BF16_COMPUTE)
    try:
        compiled = jax.jit(jax.grad(loss)).lower(
            abstract(layer.params()), abstract(layer.state()),
            jax.ShapeDtypeStruct((1, t, d), F32, sharding=one_chip)
        ).compile()
    finally:
        bt.set_policy(before)
    text = compiled.as_text()
    assert "ragged-dot" in text and "tpu_custom_call" in text
    # no T x T array of scores (32 heads of them; the up-projection's
    # 32 x (128 + 128) columns make a (T, 8192) array that is none)
    assert not re.search(r"\b32,(1,)?%d,%d\]" % (t, t), text)
    assert isinstance(layer, nn.Recompute)
    assert len(re.findall(r" while\(", text)) == \
        5 + nn.DroplessMoE.STEPS_OF_CHUNK
    assert not re.findall(r"= f32\[16,(2048,768|768,2048)\]\S* copy\(", text)
    assert len(re.findall(ROUTING_SORT, text)) == 2
    assert compiled.memory_analysis().temp_size_in_bytes < 6e9


@pytest.mark.parametrize("kind,core_loops", [("conv", 0),
                                             ("full_attention", 4)])
def test_lfm2_moe_expert_layer_compiles_at_published_widths(one_chip, kind,
                                                            core_loops):
    """One decoder layer of ``models/lfm2_moe.py`` at LFM2-24B-A2B's widths
    (hidden 2048; a gated short convolution of 3 taps, or 32/8 heads of 64
    with q/k norms and rotary and no gate; 8 of 64 experts of width 1536,
    top-4, no shared expert), one 8,192-token sequence, forward and
    backward under bf16 compute.  The short convolution's taps are shifted
    multiply-adds: no depthwise convolution and no loop (the layer's
    ``Recompute`` makes the (T, 3 x 2048) projection again in the backward
    pass; nothing of it is held).  The
    attention layer's core holds no T x T array (2 + 2 loops: one
    sequence's 8 key heads are one pass of the backward).  As in a deepseek_v3 layer the routed experts'
    sum goes straight into the residual add: one chunk loop for each step
    a backward pass may shorten to, and no forward pass over the chunks."""
    import bigdl_tpu.nn as nn
    from bigdl_tpu import tensor as bt
    from bigdl_tpu.models.lfm2_moe import Lfm2MoeLM
    from bigdl_tpu.nn import init as init_
    from bigdl_tpu.nn.module import Context

    t, d = 8192, 2048
    drawn = init_.normal_on_device
    init_.normal_on_device = lambda shape, std=None: np.broadcast_to(
        np.float32(0), shape)           # shapes only: nothing is run
    try:
        # the model's own construction of an expert layer of this kind (a
        # vocabulary of 256 rows: the tied table is not run)
        layer = Lfm2MoeLM(
            256, d, [kind], 0, 32, 8, 11776, 1536, 64, 4,
            experts_held=range(8)).modules[0].modules[0]
    finally:
        init_.normal_on_device = drawn
    abstract = lambda tree: jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        tree)

    def loss(p, s, x):
        y, _ = layer.apply(p, x, s, Context(training=True,
                                            key=jax.random.PRNGKey(0)))
        return y.sum()

    before = bt.policy()
    bt.set_policy(bt.BF16_COMPUTE)
    try:
        compiled = jax.jit(jax.grad(loss)).lower(
            abstract(layer.params()), abstract(layer.state()),
            jax.ShapeDtypeStruct((1, t, d), F32, sharding=one_chip)
        ).compile()
    finally:
        bt.set_policy(before)
    text = compiled.as_text()
    assert isinstance(layer, nn.Recompute)
    assert "ragged-dot" in text and "tpu_custom_call" in text
    assert "feature_group_count" not in text        # no depthwise conv
    assert not re.search(r"\b32,(1,)?%d,%d\]" % (t, t), text)
    assert len(re.findall(r" while\(", text)) == \
        core_loops + nn.DroplessMoE.STEPS_OF_CHUNK
    assert not re.findall(r"= f32\[8,(2048,1536|1536,2048)\]\S* copy\(", text)
    assert len(re.findall(ROUTING_SORT, text)) == 2
    assert compiled.memory_analysis().temp_size_in_bytes < 6e9


@pytest.mark.parametrize("top_k,experts,held", [(8, 128, 16), (6, 128, 16),
                                                (4, 64, 8)],
                         ids=["afmoe", "deepseek_v3", "lfm2_moe"])
def test_the_routing_fuses_its_selections(one_chip, top_k, experts, held):
    """``MoeRoute`` outside the passes at the three cells' sizes (16,384
    tokens a step), forward and backward: the chosen scores and the local
    indices are compares that the compiler fuses into the reductions that
    read them, so no (token, choice, expert) array goes through HBM (67 MB
    at afmoe's size) and no gather or scatter is left."""
    from bigdl_tpu.parallel.moe import sigmoid_topk_routing, sort_assignments
    t, d = 16384, 2048

    def routed(x, w, bias, c):
        idx, weights = sigmoid_topk_routing(x, w, bias, top_k, True, 1.0)
        order, sizes = sort_assignments(idx, tuple(range(held)))
        return jnp.sum(weights * c), (idx, order, sizes)

    text = compile_for(one_chip,
                       jax.value_and_grad(routed, (0, 1), has_aux=True),
                       ((t, d), F32), ((d, experts), F32), ((experts,), F32),
                       ((t, top_k), F32))
    entry = text[text.index("\nENTRY "):]
    assert not re.search(r"= \w+\[(%d,%d,%d|%d,%d)\]" % (
        t, top_k, experts, t * top_k, held), entry)
    assert not re.search(r" (gather|scatter)\(", text)
    assert len(re.findall(r" sort\(", text)) == 2
