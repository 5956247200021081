"""Recurrence (ref Recurrent.scala:27, RNN.scala:28, TimeDistributed.scala).

The reference's ``Recurrent`` container runs a serial Scala time loop with
truncated BPTT (bpttTruncate, Recurrent.scala:66-110).  TPU-native design:
the time loop is ``lax.scan`` — one compiled region, weights resident in
HBM, per-step matmuls batched onto the MXU.  Truncated BPTT maps to chunked
scans with ``stop_gradient`` on the carry at chunk boundaries.

The reference ships only the vanilla ``RnnCell``; BASELINE.json config 4
("Bi-LSTM text classifier ... recurrence via scan") additionally requires
LSTM and bidirectional wrappers, provided here as ``LSTMCell``, ``GRUCell``
and ``BiRecurrent``.

Layout: batch-first (N, T, D) input; hidden state (N, H).
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from bigdl_tpu.nn.module import Module, TensorModule, Container, Context
from bigdl_tpu.nn import init as init_
from bigdl_tpu.nn.activations import Tanh
from bigdl_tpu.tensor import policy
from bigdl_tpu.utils.table import Table

# LSTM/GRU recurrence through the Pallas kernel pairs on TPU (2.3-4x
# the scan's autodiff, ops/pallas_kernels — PERF_NOTES round 5).
# False = lax.scan everywhere; "interpret" forces the kernels through
# the Pallas interpreter on any backend (tests).  The kernels compute
# gates/carries in f32, so they only replace the scan when the policy's
# output dtype is f32 (FP32/BF16_COMPUTE); BF16_ACT keeps the scan,
# whose gates round through bf16.  Adopted; no benchmark cell runs a
# recurrent train step yet, so ROADMAP C3 gives the chip's verdict.
_PALLAS_BILSTM = True
# Multi-timestep blocking (round 6): timesteps per kernel grid step for
# ALL five recurrence paths (LSTM/Bi-LSTM/GRU/BiGRU/RNN).  >1 amortizes
# per-grid-step overhead, moves the zx/h streams in block-sized DMAs
# and batches the backward's weight-grad gemms over the block (the
# serial dh chain is untouched — it is the real dependency).  Exact
# math (time axis zero-padded; weight-grad f32 summation order
# differs).  DEFAULT 1 (= round-5 behavior) pending a device-clock A/B
# win, per the adoption rule (PERF_NOTES round 6): ROADMAP C3 gives it.
_BLOCK_T = 1


def _pallas_gate():
    """(use, interpret) — the ONE activation gate for the fused
    recurrence kernels, shared by every dispatch site."""
    from bigdl_tpu.ops.pallas_kernels import _on_tpu
    interp = _PALLAS_BILSTM == "interpret"
    use = (bool(_PALLAS_BILSTM)
           and policy().output_dtype == jnp.float32
           and (interp or _on_tpu()))
    return use, interp


class Cell(Module):
    """Recurrent cell protocol: ``_step(P, x_t, h, ctx) -> (out_t, h_new)``
    where ``h`` is an array or a tuple of arrays (LSTM)."""

    hidden_size: int

    def init_hidden(self, batch):
        return jnp.zeros((batch, self.hidden_size))

    def _step(self, P, x, h, ctx):
        raise NotImplementedError

    def _forward(self, P, x, S, ctx):
        # standalone use: input Table(x, h) -> h' (ref RnnCell contract)
        out, h = self._step(P, x[1], x[2], ctx)
        return out, None


class RnnCell(Cell):
    """Vanilla RNN: h' = act(W_i x + b_i + W_h h + b_h) (ref RNN.scala:28)."""

    def __init__(self, input_size: int, hidden_size: int, activation=None):
        super().__init__()
        self.input_size = input_size
        self.hidden_size = hidden_size
        self.activation = activation if activation is not None else Tanh()
        self.reset()

    def reset(self):
        stdv = 1.0 / np.sqrt(self.hidden_size)
        self._add_param("i2h", init_.uniform((self.hidden_size, self.input_size), -stdv, stdv))
        self._add_param("h2h", init_.uniform((self.hidden_size, self.hidden_size), -stdv, stdv))
        self._add_param("bias_i", init_.uniform((self.hidden_size,), -stdv, stdv))
        self._add_param("bias_h", init_.uniform((self.hidden_size,), -stdv, stdv))
        return self

    def _step(self, P, x, h, ctx):
        p = policy()
        pre = (jnp.matmul(p.cast_compute(x), p.cast_compute(P["i2h"].T),
                          preferred_element_type=jnp.float32) + P["bias_i"] +
               jnp.matmul(p.cast_compute(h), p.cast_compute(P["h2h"].T),
                          preferred_element_type=jnp.float32) + P["bias_h"])
        h_new = self.activation._fn(pre.astype(p.output_dtype), ctx)
        return h_new, h_new


class LSTMCell(Cell):
    """Standard LSTM cell; hidden is (h, c).  One fused (4H, D+H) gemm per
    step keeps the MXU busy."""

    def __init__(self, input_size: int, hidden_size: int):
        super().__init__()
        self.input_size = input_size
        self.hidden_size = hidden_size
        self.reset()

    def reset(self):
        stdv = 1.0 / np.sqrt(self.hidden_size)
        h, d = self.hidden_size, self.input_size
        self._add_param("w", init_.uniform((4 * h, d + h), -stdv, stdv))
        self._add_param("bias", init_.uniform((4 * h,), -stdv, stdv))
        return self

    def init_hidden(self, batch):
        z = jnp.zeros((batch, self.hidden_size))
        return (z, z)

    def _step(self, P, x, hc, ctx):
        h, c = hc
        p = policy()
        z = jnp.matmul(p.cast_compute(jnp.concatenate([x, h], axis=-1)),
                       p.cast_compute(P["w"].T),
                       preferred_element_type=jnp.float32) + P["bias"]
        z = z.astype(p.output_dtype)
        return self._gates(z, c)

    @staticmethod
    def _gates(z, c):
        i, f, g, o = jnp.split(z, 4, axis=-1)
        c_new = jax.nn.sigmoid(f) * c + jax.nn.sigmoid(i) * jnp.tanh(g)
        h_new = jax.nn.sigmoid(o) * jnp.tanh(c_new)
        return h_new, (h_new, c_new)

    # NOTE (round-3 correction): round 2 measured a hoisted input
    # projection "40% slower" with the chained wall-clock harness; the
    # device-clock trace reverses that verdict — the hoisted projection
    # is FASTER and ships in BiRecurrent._apply_fused_lstm (PERF_NOTES
    # round 3 "LSTM").  The single-direction path here keeps the
    # concat-gemm body (simplest form; the win comes from direction
    # batching, which needs the bidirectional wrapper).  A Pallas scan
    # kernel with one grid step a timestep measured within 1% of lax.scan
    # (PERF_NOTES round 2) and was deleted.


class GRUCell(Cell):
    def __init__(self, input_size: int, hidden_size: int):
        super().__init__()
        self.input_size = input_size
        self.hidden_size = hidden_size
        self.reset()

    def reset(self):
        stdv = 1.0 / np.sqrt(self.hidden_size)
        h, d = self.hidden_size, self.input_size
        self._add_param("w_rz", init_.uniform((2 * h, d + h), -stdv, stdv))
        self._add_param("b_rz", init_.uniform((2 * h,), -stdv, stdv))
        self._add_param("w_h", init_.uniform((h, d + h), -stdv, stdv))
        self._add_param("b_h", init_.uniform((h,), -stdv, stdv))
        return self

    def _step(self, P, x, h, ctx):
        xh = jnp.concatenate([x, h], axis=-1)
        rz = jax.nn.sigmoid(jnp.matmul(xh, P["w_rz"].T) + P["b_rz"])
        r, z = jnp.split(rz, 2, axis=-1)
        xrh = jnp.concatenate([x, r * h], axis=-1)
        n = jnp.tanh(jnp.matmul(xrh, P["w_h"].T) + P["b_h"])
        h_new = (1 - z) * n + z * h
        return h_new, h_new


class Recurrent(Container):
    """Time-loop container (ref Recurrent.scala:27).

    ``Recurrent().add(cell)``; forward over (N, T, D) returns (N, T, H).
    ``bptt_truncate > 0`` stops gradients at chunk boundaries (the scan
    equivalent of the reference's truncated backward loop).
    ``reverse=True`` scans right-to-left (for BiRecurrent).
    """

    def __init__(self, bptt_truncate: int = 0, reverse: bool = False):
        super().__init__()
        self.bptt_truncate = int(bptt_truncate)
        self.reverse = reverse

    @property
    def cell(self) -> Cell:
        return self.modules[0]

    def _finish_pallas(self, outs):
        """Shared epilogue of the kernel branches: undo the reverse-time
        flip and return to batch-major (N, T, H)."""
        if self.reverse:
            outs = jnp.flip(outs, axis=0)
        return jnp.swapaxes(outs, 0, 1)

    def apply(self, params, x, state, ctx):
        cell = self.cell
        cp = params["0"]["~"]  # cells keep all params in their own dict
        cs = state["0"]
        n, t = x.shape[0], x.shape[1]
        h0 = cell.init_hidden(n)
        xs = jnp.swapaxes(x, 0, 1)  # (T, N, D) scan-major
        if self.reverse:
            xs = jnp.flip(xs, axis=0)
        key = ctx.next_key() if ctx.training else jax.random.PRNGKey(0)

        p = policy()
        gate, interp = _pallas_gate()
        use_pallas = (gate
                      # exact types only: a subclass's overridden _step
                      # would silently be bypassed
                      and (type(cell) in (LSTMCell, GRUCell)
                           or (type(cell) is RnnCell
                               and type(cell.activation) is Tanh))
                      and (self.bptt_truncate <= 0
                           or self.bptt_truncate >= t))
        if use_pallas and type(cell) is RnnCell:
            # vanilla tanh RNN (the reference's own RnnCell) through the
            # same pattern; backward reuses the stored h stack directly
            from bigdl_tpu.ops.pallas_kernels import rnn_recurrence
            zx = (jnp.matmul(p.cast_compute(xs),
                             p.cast_compute(cp["i2h"].T),
                             preferred_element_type=jnp.float32)
                  + cp["bias_i"] + cp["bias_h"])      # (T, N, H)
            wh = p.cast_compute(cp["h2h"].T)          # (H, H)
            outs = rnn_recurrence(zx[:, None], wh[None], interp,
                                  _BLOCK_T)[:, 0]
            return self._finish_pallas(outs), state
        if use_pallas and type(cell) is GRUCell:
            # GRU case of the VMEM-carry kernel pattern
            # (ops/pallas_kernels.gru_recurrence): hoist the two input
            # projections, run the recurrence with a direction dim of 1.
            # GRUCell._step computes in f32 (no policy cast) — so does
            # the kernel.
            from bigdl_tpu.ops.pallas_kernels import gru_recurrence
            d = cell.input_size
            zrz = jnp.matmul(xs, cp["w_rz"][:, :d].T) + cp["b_rz"]
            zn = jnp.matmul(xs, cp["w_h"][:, :d].T) + cp["b_h"]
            outs = gru_recurrence(zrz[:, None], zn[:, None],
                                  cp["w_rz"][:, d:].T[None],
                                  cp["w_h"][:, d:].T[None], interp,
                                  _BLOCK_T)[:, 0]
            return self._finish_pallas(outs), state
        if use_pallas:
            # single-direction case of the same VMEM-carry kernel pair
            # that earned the Bi-LSTM 2.3x (PERF_NOTES round 5): hoist
            # the input projection to one MXU matmul, run the
            # recurrence with a direction dim of 1.  The key drawn
            # above keeps the ctx stream identical to the scan path
            # (LSTMCell._step ignores its per-step keys).
            from bigdl_tpu.ops.pallas_kernels import bilstm_recurrence
            d = cell.input_size
            wx = p.cast_compute(cp["w"][:, :d].T)     # (D, 4H)
            wh = p.cast_compute(cp["w"][:, d:].T)     # (H, 4H)
            zx = (jnp.matmul(p.cast_compute(xs), wx,
                             preferred_element_type=jnp.float32)
                  + cp["bias"])                       # (T, N, 4H)
            outs = bilstm_recurrence(zx[:, None], wh[None], interp,
                                     _BLOCK_T)[:, 0]
            return self._finish_pallas(outs), state

        def step(carry, x_t):
            h, k = carry
            k, sub = jax.random.split(k)
            sctx = Context(training=ctx.training, key=sub)
            out, h_new = cell._step(cp, x_t, h, sctx)
            return (h_new, k), out

        k = self.bptt_truncate
        if k <= 0 or k >= t:
            (_, _), outs = lax.scan(step, (h0, key), xs)
        else:
            # chunked scan; stop_gradient on the carry between chunks
            outs_list = []
            carry = (h0, key)
            for start in range(0, t, k):
                chunk = xs[start:start + k]
                carry, o = lax.scan(step, carry, chunk)
                h_c, k_c = carry
                carry = (jax.tree_util.tree_map(lax.stop_gradient, h_c), k_c)
                outs_list.append(o)
            outs = jnp.concatenate(outs_list, axis=0)
        if self.reverse:
            outs = jnp.flip(outs, axis=0)
        return jnp.swapaxes(outs, 0, 1), state


class BiRecurrent(Container):
    """Bidirectional wrapper: runs a forward and a backward Recurrent over
    the same input and merges (concat on feature dim, or add).  Not in the
    reference (capability extension for BASELINE config 4 Bi-LSTM)."""

    def __init__(self, cell_fwd: Cell, cell_bwd: Cell, merge: str = "concat",
                 bptt_truncate: int = 0):
        super().__init__()
        self.merge = merge
        self.add(Recurrent(bptt_truncate).add(cell_fwd))
        self.add(Recurrent(bptt_truncate, reverse=True).add(cell_bwd))

    def _cells_eligible(self, cell_type):
        """Both children hold exactly ``cell_type`` with matching sizes
        and no truncation — the structural half of fused eligibility."""
        cf = self.modules[0].cell
        cb = self.modules[1].cell
        return (type(cf) is cell_type and type(cb) is cell_type
                and cf.input_size == cb.input_size
                and cf.hidden_size == cb.hidden_size
                and self.modules[0].bptt_truncate <= 0
                and self.modules[1].bptt_truncate <= 0)

    def _fused_lstm_eligible(self):
        return self._cells_eligible(LSTMCell)

    def _fused_gru_eligible(self):
        # no scan form of the fused GRU exists: the kernels must be
        # usable, so the gate joins the structural check
        return self._cells_eligible(GRUCell) and _pallas_gate()[0]

    def apply(self, params, x, state, ctx):
        fused = (self._apply_fused_lstm if self._fused_lstm_eligible()
                 else self._apply_fused_gru if self._fused_gru_eligible()
                 else None)
        if fused is not None:
            if ctx.training:
                # consume exactly the two keys the two-scan path draws
                # (one per Recurrent.apply): a model with stochastic
                # layers AFTER this module must see the same downstream
                # key stream whichever path runs
                ctx.next_key()
                ctx.next_key()
            return fused(params, x, ctx), state
        yf, sf = self.modules[0].apply(params["0"], x, state["0"], ctx)
        yb, sb = self.modules[1].apply(params["1"], x, state["1"], ctx)
        y = jnp.concatenate([yf, yb], axis=-1) if self.merge == "concat" else yf + yb
        return y, {"~": state.get("~", {}), "0": sf, "1": sb}

    def _apply_fused_gru(self, params, x, ctx):
        """Both GRU directions through ONE direction-batched kernel pair
        (ops/pallas_kernels.gru_recurrence, nd=2) with the two input
        projections hoisted to batched MXU matmuls — the GRU analogue of
        _apply_fused_lstm, half the kernel dispatches of two nd=1
        Recurrent applies.  GRUCell math is f32 (no policy cast)."""
        cf = self.modules[0].cell
        from bigdl_tpu.ops.pallas_kernels import gru_recurrence
        d = cf.input_size
        xs = jnp.swapaxes(x, 0, 1)                        # (T, N, D)
        xs2 = jnp.stack([xs, jnp.flip(xs, axis=0)], axis=1)  # (T, 2, N, D)
        wrz2 = jnp.stack([params["0"]["0"]["~"]["w_rz"],
                          params["1"]["0"]["~"]["w_rz"]])  # (2, 2H, D+H)
        wh2 = jnp.stack([params["0"]["0"]["~"]["w_h"],
                         params["1"]["0"]["~"]["w_h"]])    # (2, H, D+H)
        brz2 = jnp.stack([params["0"]["0"]["~"]["b_rz"],
                          params["1"]["0"]["~"]["b_rz"]])
        bh2 = jnp.stack([params["0"]["0"]["~"]["b_h"],
                         params["1"]["0"]["~"]["b_h"]])
        # batched input projections over (dir, time*batch)
        zrz = lax.dot_general(xs2, jnp.swapaxes(wrz2[:, :, :d], 1, 2),
                              (((3,), (1,)), ((1,), (0,))))
        zrz = jnp.swapaxes(zrz, 0, 1) + brz2[:, None]     # (T, 2, N, 2H)
        zn = lax.dot_general(xs2, jnp.swapaxes(wh2[:, :, :d], 1, 2),
                             (((3,), (1,)), ((1,), (0,))))
        zn = jnp.swapaxes(zn, 0, 1) + bh2[:, None]        # (T, 2, N, H)
        outs = gru_recurrence(zrz, zn,
                              jnp.swapaxes(wrz2[:, :, d:], 1, 2),
                              jnp.swapaxes(wh2[:, :, d:], 1, 2),
                              _pallas_gate()[1], _BLOCK_T)
        yf = jnp.swapaxes(outs[:, 0], 0, 1)               # (N, T, H)
        yb = jnp.swapaxes(jnp.flip(outs[:, 1], axis=0), 0, 1)
        return (jnp.concatenate([yf, yb], axis=-1)
                if self.merge == "concat" else yf + yb)

    def _apply_fused_lstm(self, params, x, ctx):
        """Both directions in ONE scan with the input projection hoisted
        out: per timestep only one direction-batched (2, N, H) x
        (2, H, 4H) recurrent gemm; the (T*N, D) x (D, 4H) input
        projection runs as one big MXU matmul outside the loop.

        Measured on the BASELINE Bi-LSTM config (B128 T500 D200 H128,
        v5e, DEVICE-clock trace timing): two-scan 13.75 ms/step ->
        direction-batched concat-gemm 11.70 -> + hoisted projection
        ~10.1 ms (1.36x).  The remaining floor is the serial recurrence
        itself: gemm-only scan body = 1.3 us/step, full cell = 3.5
        us/step fwd; see PERF_NOTES round 3 "LSTM".  Exact same math as
        the two-scan path (equivalence-tested incl. gradients).

        NOTE: round 2 rejected the hoisted projection as "40% slower" —
        that measurement came from the chained-wall-clock harness whose
        serialization noise exceeded the effect; the device-clock trace
        reverses the verdict."""
        cf = self.modules[0].cell
        p = policy()
        n, t = x.shape[0], x.shape[1]
        hdim = cf.hidden_size
        d = cf.input_size
        w2 = jnp.stack([params["0"]["0"]["~"]["w"],
                        params["1"]["0"]["~"]["w"]])      # (2, 4H, D+H)
        b2 = jnp.stack([params["0"]["0"]["~"]["bias"],
                        params["1"]["0"]["~"]["bias"]])
        wx = p.cast_compute(jnp.swapaxes(w2[:, :, :d], 1, 2))  # (2, D, 4H)
        wh = p.cast_compute(jnp.swapaxes(w2[:, :, d:], 1, 2))  # (2, H, 4H)
        xs = jnp.swapaxes(x, 0, 1)                        # (T, N, D)
        xs2 = jnp.stack([xs, jnp.flip(xs, axis=0)], axis=1)  # (T, 2, N, D)
        # input projection for every timestep in one batched matmul
        zx = lax.dot_general(p.cast_compute(xs2), wx,
                             (((3,), (1,)), ((1,), (0,))),
                             preferred_element_type=jnp.float32)
        zx = jnp.swapaxes(zx, 0, 1) + b2[:, None]         # (T, 2, N, 4H)
        # under a reduced-precision policy the two big scan-adjacent
        # buffers ride in the COMPUTE dtype: zx (T,2,N,4H — written once,
        # re-read per step and again in the backward replay) and the
        # stacked per-step outputs (T,2,N,H).  The serial recurrence
        # itself stays f32 (carry h/c and gate math) — only the streamed
        # tensors halve their bytes.  Device-clock A/B: PERF_NOTES r4.
        reduced = p.compute_dtype != jnp.float32
        if reduced:
            zx = zx.astype(p.compute_dtype)
        z0 = jnp.zeros((2, n, hdim))

        def step(carry, zx_t):
            h, c = carry
            z = zx_t.astype(jnp.float32) + lax.dot_general(
                p.cast_compute(h), wh,
                (((2,), (1,)), ((0,), (0,))),
                preferred_element_type=jnp.float32)
            z = z.astype(p.output_dtype)
            h_new, hc = LSTMCell._gates(z, c)
            out = h_new.astype(p.compute_dtype) if reduced else h_new
            return hc, out

        use_pallas, interp = _pallas_gate()
        if use_pallas:
            # whole-recurrence Pallas kernel pair (fwd + hand-derived
            # bwd), carries resident in VMEM across steps: 2.3x faster
            # than the scan's autodiff on the flagship shapes — the one
            # measured Mosaic win on this chip (ops/pallas_kernels.py
            # bilstm_recurrence, PERF_NOTES round 5).  f32-policy only:
            # forward bit-exact vs the scan body; grads differ by f32
            # accumulation order.
            from bigdl_tpu.ops.pallas_kernels import bilstm_recurrence
            outs = bilstm_recurrence(zx, wh, interp,
                                     _BLOCK_T)        # (T, 2, N, H)
            if reduced:
                outs = outs.astype(p.compute_dtype)
        else:
            _, outs = lax.scan(step, (z0, z0), zx)        # (T, 2, N, H)
        yf = jnp.swapaxes(outs[:, 0], 0, 1)               # (N, T, H)
        yb = jnp.swapaxes(jnp.flip(outs[:, 1], axis=0), 0, 1)
        y = (jnp.concatenate([yf, yb], axis=-1)
             if self.merge == "concat" else yf + yb)
        # back to the output dtype so the head's reductions (Mean over T)
        # accumulate in f32 over the rounded values
        return y.astype(p.output_dtype) if reduced else y


class TimeDistributed(Container):
    """Apply a module independently at every timestep of (N, T, ...)
    (ref TimeDistributed.scala): fold T into the batch so the inner module
    sees one big (N*T, ...) batch — a single large MXU-friendly call instead
    of T small ones."""

    def __init__(self, module: Module):
        super().__init__(module)

    def apply(self, params, x, state, ctx):
        n, t = x.shape[0], x.shape[1]
        flat = x.reshape((n * t,) + x.shape[2:])
        y, ns = self.modules[0].apply(params["0"], flat, state["0"], ctx)
        y = y.reshape((n, t) + y.shape[1:])
        new_state = dict(state)
        new_state["0"] = ns
        return y, new_state
