"""Stage-partitioning Sequential models for pipeline training through the
Optimizer API.

The reference hides ALL distribution behind the Optimizer factory
(ref optim/Optimizer.scala:151-186: the caller never touches the transport);
``DistriOptimizer(pipeline_stages=P)`` gives pipeline parallelism the same
front door.  This module turns an arbitrary ``Sequential`` model into the
homogeneous stage representation the shard_map pipeline engines
(``parallel/pipeline.py``) require:

- **partition**: top-level modules are split into P contiguous stages
  balanced by an analytic FLOP estimate (conv/linear ≈ 2·|W|·spatial_out·mb,
  else output bytes) via the classic linear-partition DP;
- **homogenize**: per-stage parameter/state pytrees are raveled
  (``jax.flatten_util.ravel_pytree``), zero-padded to the max stage size,
  and stacked into one ``(P, maxlen)`` array sharded over the ``pipe``
  axis — boundary activations likewise ride the ring as per-sample
  flattened ``(mb, max_act)`` buffers, so every stage has identical
  operand shapes;
- **dispatch**: one stage function selects its stage's computation with
  ``lax.switch(rank, ...)`` — each rank executes only its branch at
  runtime; the compiled program is the same SPMD executable everywhere.

RNG contract: stochastic layers (Dropout) derive their key from
``fold_in(fold_in(base_key, micro_idx), stage)`` so the 1F1B backward-time
recompute draws the identical mask.  This stream intentionally differs
from the DP step's stream (per-microbatch masks vs one full-batch mask) —
the same divergence the reference has between a single model and its
per-clone thread RNGs (Dropout.scala threads over Engine.model).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.flatten_util import ravel_pytree

from bigdl_tpu.nn.module import Context


def _flat_size(tree):
    return int(sum(np.prod(l.shape) for l in jax.tree_util.tree_leaves(tree)))


def _module_cost(module, mb, out_shape):
    """Analytic per-module cost for stage balancing: matmul/conv-style
    modules cost ~2·|W|·spatial_out·mb FLOPs (exact for SpatialConvolution
    and Linear; a same-spatial approximation for container blocks like an
    Inception mixed unit), everything else is bandwidth — counted as output
    elements.  Only relative magnitudes matter here."""
    psize = _flat_size(module.params())
    spatial = int(np.prod(out_shape[2:])) if len(out_shape) > 2 else 1
    out_elems = int(np.prod(out_shape))
    return 2.0 * psize * spatial * mb + out_elems


def _linear_partition(costs, n_stages):
    """Split ``costs`` into ``n_stages`` contiguous non-empty groups
    minimizing the max group sum (O(n² P) DP; n is the module count)."""
    n = len(costs)
    prefix = np.concatenate([[0.0], np.cumsum(costs)])
    INF = float("inf")
    dp = np.full((n + 1, n_stages + 1), INF)
    par = np.zeros((n + 1, n_stages + 1), np.int64)
    dp[0, 0] = 0.0
    for p in range(1, n_stages + 1):
        for i in range(p, n + 1):
            for j in range(p - 1, i):
                c = max(dp[j, p - 1], prefix[i] - prefix[j])
                if c < dp[i, p]:
                    dp[i, p] = c
                    par[i, p] = j
    ranges = []
    i = n
    for p in range(n_stages, 0, -1):
        j = int(par[i, p])
        ranges.append((j, i))
        i = j
    return ranges[::-1]


class StagePlan:
    """Everything needed to run a partitioned Sequential through the
    pipeline engines: stage ranges, boundary shapes, ravel/unravel
    templates, and the pack/unpack/stage-fn builders.  Built once per
    training run by :func:`partition_sequential`."""

    def __init__(self, model, n_stages, ranges, in_shapes, out_shape,
                 axis="pipe"):
        self.model = model
        self.modules = model.modules
        self.n_stages = n_stages
        self.ranges = ranges
        self.in_shapes = in_shapes        # per-stage input shape, incl. mb
        self.out_shape = out_shape        # final output shape, incl. mb
        self.mb = in_shapes[0][0]
        self.axis = axis

        self.act_sizes = [int(np.prod(s[1:])) for s in in_shapes]
        self.out_size = int(np.prod(out_shape[1:]))
        self.max_act = max(self.act_sizes + [self.out_size])

        self.unravel_p, self.p_sizes = [], []
        self.unravel_s, self.s_sizes = [], []
        for (a, b) in ranges:
            pt = [self.modules[j].params() for j in range(a, b)]
            st = [self.modules[j].state() for j in range(a, b)]
            fp, up = ravel_pytree(pt)
            fs, us = ravel_pytree(st)
            self.unravel_p.append(up)
            self.p_sizes.append(int(fp.size))
            self.unravel_s.append(us)
            self.s_sizes.append(int(fs.size))
        self.max_p = max(self.p_sizes)
        # width >= 1 so fully stateless models still carry a well-formed
        # (P, 1) array through the scan
        self.max_s = max(self.s_sizes + [1])

    # -- packing -----------------------------------------------------------
    def _pack(self, tree, width):
        rows = []
        for (a, b) in self.ranges:
            flat, _ = ravel_pytree([tree[str(j)] for j in range(a, b)])
            flat = flat.astype(jnp.float32) if flat.size == 0 else flat
            rows.append(jnp.pad(flat, (0, width - flat.size)))
        return jnp.stack(rows)

    def pack_params(self, tree):
        """Module-tree params pytree -> (P, max_p) stage-stacked array."""
        return self._pack(tree, self.max_p)

    def pack_state(self, tree):
        return self._pack(tree, self.max_s)

    @staticmethod
    def _gather_stacked(stacked):
        """Host copy of a (P, width) stage-stacked array.  Single-process:
        a plain device_get.  Multi-host (stages span processes over DCN):
        rows are placed by their GLOBAL dim-0 index and de-duplicated —
        under a hybrid dp x pp mesh each stage row is replicated across
        the data axis, so a naive concat of addressable shards would
        duplicate or misplace rows.  The assembly is a COLLECTIVE
        (process_allgather), so every process must reach the call site
        together (checkpoint/validation unpacks run on all processes
        before any process-0 gating)."""
        if jax.process_count() == 1 or getattr(
                stacked, "is_fully_addressable", True):
            return np.asarray(jax.device_get(stacked))
        # row ownership is GLOBAL sharding metadata — every process
        # computes the identical map, so no ownership collective is
        # needed and the short-circuit below is process-consistent
        n_rows = stacked.shape[0]
        owner = {}                       # global row -> owning process
        rows_of = {}                     # process -> set of rows
        for dev, idx in stacked.sharding.devices_indices_map(
                stacked.shape).items():
            sl = idx[0]
            for r in range(sl.start or 0, sl.stop if sl.stop is not None
                           else n_rows):
                owner.setdefault(r, dev.process_index)
                rows_of.setdefault(dev.process_index, set()).add(r)
        assert len(owner) == n_rows, "stage rows with no owner"
        if all(len(rows_of.get(p, ())) == n_rows
               for p in range(jax.process_count())):
            # e.g. hybrid dp x pp data-major layouts: every process holds
            # every stage row — purely local assembly, no collective
            local = np.zeros(stacked.shape, stacked.dtype)
            for s in stacked.addressable_shards:
                start = s.index[0].start or 0
                data = np.asarray(s.data)
                local[start:start + data.shape[0]] = data
            return local
        from jax.experimental import multihost_utils
        local = np.zeros(stacked.shape, stacked.dtype)
        for s in stacked.addressable_shards:
            start = s.index[0].start or 0
            data = np.asarray(s.data)
            local[start:start + data.shape[0]] = data
        g_rows = np.asarray(multihost_utils.process_allgather(
            local, tiled=False))          # (nproc, P, width)
        out = np.zeros(stacked.shape, stacked.dtype)
        for r in range(n_rows):
            out[r] = g_rows[owner[r], r]
        return out

    def _unpack(self, stacked, sizes, unravels):
        stacked = self._gather_stacked(stacked)
        tree = {"~": {}}
        for i, (a, b) in enumerate(self.ranges):
            stage = unravels[i](jnp.asarray(stacked[i, :sizes[i]]))
            for k, j in enumerate(range(a, b)):
                tree[str(j)] = stage[k]
        return tree

    def unpack_params(self, stacked):
        """(P, max_p) stage-stacked array -> module-tree params pytree
        (host-side: gathers the stage shards)."""
        return self._unpack(stacked, self.p_sizes, self.unravel_p)

    def unpack_state(self, stacked):
        return self._unpack(stacked, self.s_sizes, self.unravel_s)

    # -- the stage function ------------------------------------------------
    def make_branches(self, base_key, training=True):
        """Per-stage computation functions ``run(flat_p, flat_s, flat_x, m)
        -> (flat_y, flat_s')`` — the switch targets of
        :meth:`make_stage_fn`, also usable directly as a sequential
        single-device oracle (tests compare the pipeline against exactly
        these branches run in order)."""
        mb = self.mb

        def branch(i):
            a, b = self.ranges[i]
            in_shape, in_size = self.in_shapes[i], self.act_sizes[i]
            p_size, s_size = self.p_sizes[i], self.s_sizes[i]

            def run(flat_p, flat_s, flat_x, m):
                p_list = self.unravel_p[i](flat_p[:p_size])
                s_list = self.unravel_s[i](flat_s[:s_size])
                # batch dim stays -1: under a hybrid dp x pp mesh the
                # stage sees the LOCAL microbatch shard, not plan.mb
                x = flat_x[:, :in_size].reshape((-1,) + in_shape[1:])
                key = jax.random.fold_in(
                    jax.random.fold_in(base_key, jnp.maximum(m, 0)), i)
                ctx = Context(training=training, key=key)
                new_s = []
                for k, j in enumerate(range(a, b)):
                    x, ns = self.modules[j].apply(p_list[k], x, s_list[k], ctx)
                    new_s.append(ns)
                y = x.reshape(x.shape[0], -1).astype(jnp.float32)
                y = jnp.pad(y, ((0, 0), (0, self.max_act - y.shape[1])))
                fs, _ = ravel_pytree(new_s)
                fs = (fs.astype(jnp.float32) if fs.size else
                      jnp.zeros((0,), jnp.float32))
                fs = jnp.pad(fs, (0, self.max_s - fs.size))
                return y, fs

            return run

        return [branch(i) for i in range(self.n_stages)]

    def make_stage_fn(self, base_key, training=True, fold_axis=None):
        """Build the engine-facing ``stage_fn(flat_p, flat_s, flat_x, m)
        -> (flat_y, flat_s')`` dispatching on the pipe rank.
        ``fold_axis`` decorrelates stochastic layers per data-parallel
        replica (the DP step's per-replica key fold)."""
        axis = self.axis

        def varying(v, target_vma):
            # a stateless stage emits its (empty-padded) state as a
            # CONSTANT, so its vma lacks axes that stateful branches'
            # outputs carry (pipe, and data under hybrid dp x pp) —
            # switch requires equal output types, so promote every
            # branch output to the operands' varying axes
            from bigdl_tpu.parallel.collectives import pvary
            vma = jax.typeof(v).vma
            missing = tuple(a for a in target_vma if a not in vma)
            return pvary(v, missing) if missing else v

        # without a per-replica key fold the branch closures are key-
        # independent: build them ONCE (stage_fn is retraced many times —
        # fwd + vjp per 1F1B tick)
        static_branches = (self.make_branches(base_key, training)
                           if fold_axis is None else None)

        def stage_fn(flat_p, flat_s, flat_x, m):
            if static_branches is not None:
                branches = static_branches
            else:
                key = jax.random.fold_in(base_key,
                                         lax.axis_index(fold_axis))
                branches = self.make_branches(key, training)
            target = (set(jax.typeof(flat_x).vma)
                      | set(jax.typeof(flat_p).vma) | {axis})
            wrapped = [
                (lambda p, s, x, mm, b=b:
                 jax.tree_util.tree_map(
                     lambda v: varying(v, sorted(target)), b(p, s, x, mm)))
                for b in branches
            ]
            rank = lax.axis_index(axis)
            return lax.switch(rank, wrapped, flat_p, flat_s, flat_x, m)

        return stage_fn

    def make_loss_fn(self, criterion):
        def loss_fn(y_flat, tgt):
            # -1 batch dim: the local microbatch under dp x pp, the
            # global one in the GPipe outside-shard_map loss
            out = y_flat[:, :self.out_size].reshape(
                (-1,) + self.out_shape[1:])
            return criterion.apply_loss(out, tgt)
        return loss_fn

    def pack_input(self, x_micro):
        """(M, mb, ...) microbatched input -> (M, mb, max_act) flat-padded
        ring buffers."""
        m, mb = x_micro.shape[0], x_micro.shape[1]
        xf = x_micro.reshape(m, mb, -1).astype(jnp.float32)
        return jnp.pad(xf, ((0, 0), (0, 0), (0, self.max_act - xf.shape[2])))

    def describe(self):
        lines = []
        for i, (a, b) in enumerate(self.ranges):
            names = [type(self.modules[j]).__name__ for j in range(a, b)]
            lines.append(f"stage {i}: modules [{a}:{b}) "
                         f"({self.p_sizes[i]:,} params) {names}")
        return "\n".join(lines)


def partition_sequential(model, n_stages, micro_shape, axis="pipe",
                         training=True):
    """Partition a ``Sequential`` model into ``n_stages`` pipeline stages.

    ``micro_shape`` is the shape of ONE microbatch including its batch dim
    ``(mb, ...)``.  Boundary shapes come from an ``eval_shape`` sweep (no
    FLOPs spent); stages are balanced by the analytic cost model.  Every
    stage boundary must be a single array (true of the Sequential model
    zoo; Table-valued boundaries would need a table-flattening hop).
    """
    from bigdl_tpu.nn.containers import Sequential
    if not isinstance(model, Sequential):
        raise ValueError(
            f"pipeline_stages requires a Sequential model, got "
            f"{type(model).__name__}")
    modules = model.modules
    if len(modules) < n_stages:
        raise ValueError(f"model has {len(modules)} top-level modules, "
                         f"cannot make {n_stages} stages")

    key = jax.random.PRNGKey(0)
    cur = jax.ShapeDtypeStruct(tuple(micro_shape), jnp.float32)
    shapes = [cur.shape]
    costs = []
    for m in modules:
        p, s = m.params(), m.state()

        def one(x, m=m, p=p, s=s):
            return m.apply(p, x, s, Context(training=training, key=key))[0]

        cur = jax.eval_shape(one, cur)
        if not hasattr(cur, "shape"):
            raise ValueError(
                f"stage boundary after {type(m).__name__} is not a single "
                "array; pipeline partitioning needs tensor boundaries")
        shapes.append(cur.shape)
        costs.append(_module_cost(m, micro_shape[0], cur.shape))

    ranges = _linear_partition(costs, n_stages)
    in_shapes = [shapes[a] for a, _ in ranges]
    return StagePlan(model, n_stages, ranges, in_shapes, shapes[-1],
                     axis=axis)
