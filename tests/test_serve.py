"""Serving-engine suite (docs/serving.md, marker ``serve``).

Covers the tentpole contracts:

- batch-assembly determinism: however the batcher happens to close
  micro-batches, per-row outputs are bit-identical to the serial
  compiled forward;
- the single-compile invariant: after warmup, a mixed-size request
  stream spanning >= 3 buckets (including size-1 and tail sizes)
  triggers ZERO new XLA compiles — audited through the engine's compile
  counter AND a jax.jit call trap;
- deadline flush, drain-on-shutdown, poisoned-request isolation, the
  ``serve_h2d`` chaos site;
- continuous-batching decode bit-parity with serial ``lm_decode``;
- the Predictor regression set the old standalone loop never had
  (partial-batch trim, 1-based predict_class, refresh capture), plus
  the validators' tail-batch pad-and-trim single-compile routing.
"""
import math
import threading
import time

import jax
import numpy as np
import pytest

import bigdl_tpu.nn as nn
from bigdl_tpu.nn.module import Context
from bigdl_tpu.serve import (PoisonedRequestError, ServeEngine, bucket_for,
                             bucket_sizes, bucketing, continuous_decode,
                             pad_rows, trim, valid_mask)
from bigdl_tpu.serve.decode import ContinuousDecoder
from bigdl_tpu.utils.random import set_seed

pytestmark = pytest.mark.serve


def _small_model():
    set_seed(1)
    return nn.Sequential(nn.Linear(4, 3), nn.LogSoftMax())


def _serial_fwd(model):
    """The oracle: one jitted forward, whole array in one batch."""
    p, s = model.params(), model.state()

    @jax.jit
    def fwd(x):
        out, _ = model.apply(p, x, s,
                             Context(training=False,
                                     key=jax.random.PRNGKey(0)))
        return out

    return lambda x: np.asarray(fwd(x))


def _serial_fwd_at(model, x, max_batch):
    """The oracle at the SERVED batch shapes: ``x`` in chunks of
    ``max_batch`` rows, each zero-padded to its bucket and trimmed —
    what an engine does whose batches close on size (callers give it a
    wait long enough that timing cannot split a batch).  XLA's CPU
    backend may differ by one ulp between batch shapes, so an exact
    comparison needs the reference computed at the shape served."""
    fwd = _serial_fwd(model)
    out = []
    for i in range(0, len(x), max_batch):
        chunk = x[i:i + max_batch]
        padded, n = bucketing.pad_rows(
            chunk, bucketing.bucket_for(len(chunk), max_batch))
        out.append(fwd(padded)[:n])
    return np.concatenate(out)


class TestBucketing:
    def test_ladder(self):
        assert bucket_sizes(1) == (1,)
        assert bucket_sizes(8) == (1, 2, 4, 8)
        assert bucket_sizes(12) == (1, 2, 4, 8, 12)

    def test_bucket_for(self):
        assert bucket_for(1, 8) == 1
        assert bucket_for(3, 8) == 4
        assert bucket_for(8, 8) == 8
        assert bucket_for(9, 12) == 12
        with pytest.raises(ValueError):
            bucket_for(9, 8)
        with pytest.raises(ValueError):
            bucket_for(0, 8)

    def test_pad_rows_zero_fill_and_noop(self):
        x = np.arange(12, dtype=np.float32).reshape(3, 4)
        padded, n = pad_rows(x, 8)
        assert n == 3 and padded.shape == (8, 4)
        assert np.array_equal(padded[:3], x)
        assert np.all(padded[3:] == 0)          # zeros, NOT row repeats
        same, n = pad_rows(x, 3)
        assert same is x and n == 3
        with pytest.raises(ValueError):
            pad_rows(x, 2)

    def test_mask_and_trim(self):
        assert valid_mask(3, 8).sum() == 3
        out = np.arange(8)
        assert np.array_equal(trim(out, 3), out[:3])
        assert trim(out, 8) is out

    def test_zero_row_inputs_return_empty(self):
        """0-row guard: an empty batch pads to nothing (no all-pad batch
        manufactured, nothing raises) and trims to nothing; n >= 1
        behavior is untouched."""
        empty = np.zeros((0, 4), np.float32)
        padded, n = pad_rows(empty, 8)
        assert n == 0 and padded.shape == (0, 4)
        assert trim(np.arange(8), 0).shape == (0,)
        assert trim(np.zeros((0, 3)), 0).shape == (0, 3)
        # regression: n >= 1 still pads/trims exactly as before
        x = np.ones((2, 4), np.float32)
        padded, n = pad_rows(x, 4)
        assert n == 2 and padded.shape == (4, 4)
        assert np.all(padded[2:] == 0)


class TestServeEngine:
    def test_outputs_match_serial_forward(self):
        model = _small_model()
        x = np.random.RandomState(0).randn(37, 4).astype(np.float32)
        ref = _serial_fwd(model)(x)
        with ServeEngine(model, max_batch=8, max_wait_ms=5,
                         input_shape=(4,)) as eng:
            # three submission patterns; assembly timing may differ but
            # per-row outputs must not
            out1 = eng.predict(x)
            futs = [eng.submit(r) for r in x]
            out2 = np.stack([f.result() for f in futs])
        assert np.array_equal(out1, ref)
        assert np.array_equal(out2, ref)

    def test_single_compile_invariant_mixed_stream(self):
        """After warmup, sizes spanning >= 3 buckets (incl. size-1 and
        tails) trigger zero new compiles and zero new jit programs."""
        model = _small_model()
        rng = np.random.RandomState(1)
        eng = ServeEngine(model, max_batch=16, max_wait_ms=250,
                          input_shape=(4,))
        try:
            assert eng.compiles == len(eng.buckets) == 5  # 1,2,4,8,16
            warm_compiles = eng.compiles

            calls = []
            real_jit = jax.jit
            jax.jit = lambda fn, *a, **kw: (calls.append(fn),
                                            real_jit(fn, *a, **kw))[1]
            try:
                for size in (1, 16, 3, 9, 1, 5, 16):
                    xs = rng.randn(size, 4).astype(np.float32)
                    outs = np.stack([f.result()
                                     for f in eng.submit_many(xs)])
                    assert outs.shape == (size, 3)
            finally:
                jax.jit = real_jit
            stats = eng.stats()
            assert stats["compiles"] == warm_compiles, \
                "mixed-size stream hit a cold compile after warmup"
            assert not calls, "serving path built a new jit program"
            hit = [b for b, n in stats["bucket_hits"].items() if n]
            assert len(hit) >= 3 and 1 in hit and 16 in hit, hit
        finally:
            eng.close()

    def test_deadline_flush(self):
        """A partial batch (far below max_batch) must be served after
        the deadline, not held for more traffic."""
        model = _small_model()
        with ServeEngine(model, max_batch=64, max_wait_ms=20,
                         input_shape=(4,)) as eng:
            t0 = time.perf_counter()
            futs = eng.submit_many(np.ones((3, 4), np.float32))
            for f in futs:
                f.result(timeout=10)
            elapsed = time.perf_counter() - t0
        assert elapsed < 5.0
        # 3 rows pad to bucket 4 — never to max_batch
        assert eng.stats()["bucket_hits"][4] == 1

    def test_drain_on_shutdown(self):
        model = _small_model()
        eng = ServeEngine(model, max_batch=8, max_wait_ms=50,
                          input_shape=(4,))
        futs = eng.submit_many(np.ones((21, 4), np.float32))
        eng.close(drain=True)   # default: serve everything queued
        assert all(f.done() for f in futs)
        assert np.stack([f.result() for f in futs]).shape == (21, 3)
        with pytest.raises(RuntimeError):
            eng.submit(np.ones((4,), np.float32))

    def test_close_without_drain_fails_pending(self):
        model = _small_model()
        eng = ServeEngine(model, max_batch=64, max_wait_ms=5000,
                          input_shape=(4,))
        futs = eng.submit_many(np.ones((3, 4), np.float32))
        eng.close(drain=False)
        for f in futs:
            if not f.cancelled():
                with pytest.raises(BaseException):
                    f.result(timeout=10)

    def test_poisoned_request_fails_only_itself(self):
        from bigdl_tpu.obs import events
        model = _small_model()
        log = events.configure(None)
        try:
            x = np.random.RandomState(0).randn(5, 4).astype(np.float32)
            bad = np.full((4,), np.nan, np.float32)
            ref = _serial_fwd_at(model, x, 8)   # 5 good rows -> bucket 8
            with ServeEngine(model, max_batch=8, max_wait_ms=300,
                             input_shape=(4,)) as eng:
                futs = eng.submit_many(list(x[:3]) + [bad] + list(x[3:]))
                with pytest.raises(PoisonedRequestError):
                    futs[3].result(timeout=10)
                good = [f.result(timeout=10)
                        for i, f in enumerate(futs) if i != 3]
            assert np.array_equal(np.stack(good), ref)
            errs = [e for e in log.ring_events()
                    if e["type"] == "serve" and e.get("kind") == "error"]
            assert errs and errs[0]["requests"] == 1
        finally:
            events.reset()

    def test_serve_h2d_fault_site(self):
        """An injected H2D fault fails that batch's futures; the engine
        keeps serving the next batch."""
        from bigdl_tpu.resilience import faults
        model = _small_model()
        faults.configure("serve_h2d@at=0", process_index=0)
        try:
            with ServeEngine(model, max_batch=8, max_wait_ms=20,
                             input_shape=(4,)) as eng:
                first = eng.submit_many(np.ones((2, 4), np.float32))
                with pytest.raises(OSError):
                    first[0].result(timeout=10)
                with pytest.raises(OSError):
                    first[1].result(timeout=10)
                second = eng.submit(np.ones((4,), np.float32))
                assert second.result(timeout=10).shape == (3,)
        finally:
            faults.clear()

    def test_refresh_recaptures_without_recompile(self):
        model = _small_model()
        x = np.random.RandomState(0).randn(4, 4).astype(np.float32)
        with ServeEngine(model, max_batch=4, max_wait_ms=10,
                         input_shape=(4,)) as eng:
            before = eng.predict(x)
            compiles = eng.compiles
            zeroed = jax.tree_util.tree_map(np.zeros_like, model.params())
            model.load_params(zeroed)
            frozen = eng.predict(x)        # capture semantics: unchanged
            assert np.array_equal(frozen, before)
            eng.refresh()
            after = eng.predict(x)
            assert not np.array_equal(after, before)
            assert eng.compiles == compiles   # same shapes — no recompile

    def test_dtype_policy_scoped_to_serving_forward(self):
        """A bf16 compute policy applies to the engine's executables
        without leaking into the process-wide default."""
        from bigdl_tpu import tensor as bt
        model = _small_model()
        assert bt.policy() is bt.FP32
        with ServeEngine(model, max_batch=4, max_wait_ms=10,
                         input_shape=(4,), policy=bt.BF16_COMPUTE) as eng:
            assert bt.policy() is bt.FP32     # restored after warmup
            x = np.random.RandomState(0).randn(4, 4).astype(np.float32)
            out = eng.predict(x)
        assert out.shape == (4, 3) and np.all(np.isfinite(out))
        assert bt.policy() is bt.FP32

    def test_row_shape_mismatch_fails_future(self):
        model = _small_model()
        with ServeEngine(model, max_batch=4, max_wait_ms=10,
                         input_shape=(4,)) as eng:
            f = eng.submit(np.ones((5,), np.float32))
            with pytest.raises(ValueError):
                f.result(timeout=10)

    def test_monotonic_counters_and_stop_event_snapshot(self):
        """accepted/shed/completed/failed are monotonic from
        construction (never reset — the router rate-differences
        snapshots), accepted == completed + failed + inflight, and the
        ``serve`` stop event carries the final snapshot."""
        from bigdl_tpu.obs import events
        model = _small_model()
        log = events.configure(None)
        try:
            eng = ServeEngine(model, max_batch=8, max_wait_ms=10,
                              input_shape=(4,))
            x = np.random.RandomState(0).randn(9, 4).astype(np.float32)
            bad = np.full((4,), np.nan, np.float32)
            futs = eng.submit_many(list(x) + [bad])
            for f in futs[:-1]:
                f.result(timeout=10)
            with pytest.raises(PoisonedRequestError):
                futs[-1].result(timeout=10)
            s1 = eng.stats()
            assert s1["accepted"] == 10
            assert s1["completed"] == 9 and s1["failed"] == 1
            assert s1["shed"] == 0
            assert (s1["accepted"]
                    == s1["completed"] + s1["failed"] + s1["inflight"])
            eng.predict(x[:3])
            s2 = eng.stats()                      # counters only grow
            assert s2["accepted"] == 13 and s2["completed"] == 12
            assert s2["failed"] == s1["failed"]
            eng.close()
            stops = [e for e in log.ring_events()
                     if e["type"] == "serve" and e.get("kind") == "stop"]
            assert len(stops) == 1
            for key in ("accepted", "shed", "completed", "failed"):
                assert stops[0][key] == s2[key], (key, stops[0])
        finally:
            events.reset()

    def test_queue_bound_sheds_instead_of_queuing(self):
        """max_queue admission: requests past the bound fail fast with
        SheddedError, count in ``shed`` only, and never enter the
        pipeline."""
        from bigdl_tpu.serve import SheddedError
        model = _small_model()
        # max_wait large: the batcher holds the first batch open so the
        # queue visibly backs up behind it
        eng = ServeEngine(model, max_batch=64, max_wait_ms=2000,
                          input_shape=(4,), max_queue=4)
        try:
            rows = np.ones((10, 4), np.float32)
            futs = eng.submit_many(rows)
            shed = [f for f in futs if f.done()
                    and isinstance(f.exception(), SheddedError)]
            assert len(shed) >= 4                 # bound enforced
            s = eng.stats()
            assert s["shed"] == len(shed)
            assert s["accepted"] == 10 - len(shed)
        finally:
            eng.close()
        s = eng.stats()
        assert s["completed"] == s["accepted"]    # drained on close
        assert s["failed"] == 0

    def test_refresh_concurrent_submit_never_tears_weights(self):
        """The half-swap audit: a BatchNorm model makes (params, state)
        consistency observable — eval reads running stats from STATE
        and scale/shift from PARAMS, so pairing version-1 params with
        version-2 state would produce an output matching neither
        oracle.  A flipper thread hammers refresh() between two
        versions while the main thread streams requests; every output
        must match exactly one version."""
        set_seed(1)
        model = nn.Sequential(nn.Linear(4, 3),
                              nn.BatchNormalization(3), nn.LogSoftMax())
        p1 = jax.tree_util.tree_map(np.array, model.params())
        s1 = jax.tree_util.tree_map(np.array, model.state())
        p2 = jax.tree_util.tree_map(lambda a: a * 2.0, p1)
        s2 = jax.tree_util.tree_map(lambda a: a + 0.5, s1)

        def oracle(p, s):
            @jax.jit
            def fwd(x):
                out, _ = model.apply(p, x, s,
                                     Context(training=False,
                                             key=jax.random.PRNGKey(0)))
                return out
            return lambda x: np.asarray(fwd(np.atleast_2d(x)))

        o1, o2 = oracle(p1, s1), oracle(p2, s2)
        rng = np.random.RandomState(0)
        rows = rng.randn(60, 4).astype(np.float32)

        eng = ServeEngine(model, max_batch=4, max_wait_ms=1,
                          input_shape=(4,))
        stop = threading.Event()

        def flipper():
            flip = False
            while not stop.is_set():
                flip = not flip
                model.load_params(p2 if flip else p1)
                model.load_state(s2 if flip else s1)
                eng.refresh()

        t = threading.Thread(target=flipper, daemon=True)
        t.start()
        try:
            futs = [(r, eng.submit(r)) for _ in range(5) for r in rows]
            for r, f in futs:
                out = f.result(timeout=30)
                m1 = np.allclose(out, o1(r)[0], rtol=1e-5, atol=1e-6)
                m2 = np.allclose(out, o2(r)[0], rtol=1e-5, atol=1e-6)
                assert m1 != m2, (
                    f"output {out} matches neither weight version: "
                    "half-swapped (params, state) observed")
        finally:
            stop.set()
            t.join(timeout=10)
            # leave the module on version 1 for the engine drain
            model.load_params(p1)
            model.load_state(s1)
            eng.close()
        assert eng.stats()["failed"] == 0


class TestContinuousDecode:
    @pytest.fixture()
    def lm(self):
        from bigdl_tpu.models.transformer import TransformerLM
        set_seed(1)
        return TransformerLM(vocab_size=11, d_model=16, n_heads=2,
                             n_layers=2, hidden=32)

    def test_bit_parity_vs_serial_lm_decode(self, lm):
        """Staggered admissions (more requests than slots, mixed seed
        lengths) decode token-for-token what the serial lock-step scan
        produces per request."""
        from bigdl_tpu.models.transformer import lm_decode
        seeds = [[1, 2, 3], [4, 5], [6], [7, 8, 9, 10], [2, 4]]
        rows = continuous_decode(lm, seeds, 5, max_slots=2, n_pos=9,
                                 sync_interval=3)
        serial = [lm_decode(lm, s, 5, greedy=True) for s in seeds]
        assert rows == serial
        # the one-shot decoder tore down its registry series — repeated
        # continuous_decode calls must not grow the process registry
        from bigdl_tpu.obs import metrics as obs_metrics
        assert not [n for n in obs_metrics.get().snapshot()
                    if n.startswith("decode_")]

    def test_admit_retire_slot_reuse(self, lm):
        dec = ContinuousDecoder(lm, max_slots=2, n_pos=8, sync_interval=4)
        futs = [dec.submit([1, 2], 4) for _ in range(5)]
        dec.run()
        assert dec.admitted == dec.retired == 5
        assert all(f.done() for f in futs)
        first = futs[0].result()
        assert all(f.result() == first for f in futs)  # identical requests

    def test_direct_decoder_series_dropped_at_gc(self, lm):
        """A directly-constructed decoder (the TP-serving entry point;
        nothing guarantees a close() call) must not leak its uniquely-
        labelled registry series past its lifetime."""
        import gc
        from bigdl_tpu.obs import metrics as obs_metrics
        dec = ContinuousDecoder(lm, max_slots=1, n_pos=4)
        assert [n for n in obs_metrics.get().snapshot()
                if n.startswith("decode_")]
        del dec
        gc.collect()
        assert not [n for n in obs_metrics.get().snapshot()
                    if n.startswith("decode_")]

    def test_host_sync_cadence(self, lm):
        """The driver materializes tokens only at retiring boundaries —
        never per token."""
        dec = ContinuousDecoder(lm, max_slots=2, n_pos=16, sync_interval=4)
        for _ in range(2):
            dec.submit([1, 2, 3], 10)     # 12 fed positions each
        dec.run()
        assert dec.steps >= 12
        # both requests retire at the same boundary: ONE sync for 24
        # generated tokens
        assert dec.host_syncs == 1
        assert dec.host_syncs <= math.ceil(dec.steps / 4)

    def test_request_validation(self, lm):
        from bigdl_tpu.serve import RequestTooLongError
        dec = ContinuousDecoder(lm, max_slots=1, n_pos=4)
        with pytest.raises(ValueError):
            dec.submit([], 3)
        with pytest.raises(ValueError):
            dec.submit([1, 2], 0)
        # a too-long request fails ONLY its own future, at submit time
        f = dec.submit([1, 2, 3], 3)      # needs 5 positions > n_pos
        assert isinstance(f.exception(), RequestTooLongError)


class TestPolicyDrift:
    """The dtype policy is process-global at trace time (the engine
    docstring caveat) — serving across a policy flip must fail LOUDLY
    at submit, never silently answer with stale-precision executables."""

    def test_ambient_policy_drift_fails_submit(self):
        from bigdl_tpu import tensor as bt
        from bigdl_tpu.serve import DTypePolicyDriftError
        model = _small_model()
        eng = ServeEngine(model, max_batch=4, max_wait_ms=1,
                          input_shape=(4,))
        row = np.ones((4,), np.float32)
        eng.submit(row).result(timeout=30)
        prev = bt.policy()
        try:
            bt.set_policy(bt.BF16_COMPUTE)
            with pytest.raises(DTypePolicyDriftError):
                eng.submit(row)
        finally:
            bt.set_policy(prev)
        # restoring the policy restores service (no re-warm needed)
        out = eng.submit(row).result(timeout=30)
        assert out.shape == (3,)
        eng.close()

    def test_rewarm_under_drifted_policy_cannot_clear_the_guard(self):
        """A no-op re-warmup after a policy flip must not re-record the
        policy (nothing retraced — the old executables keep their old
        precision): warmup refuses, and submit still refuses after."""
        from bigdl_tpu import tensor as bt
        from bigdl_tpu.serve import DTypePolicyDriftError
        model = _small_model()
        eng = ServeEngine(model, max_batch=4, max_wait_ms=1,
                          input_shape=(4,))
        prev = bt.policy()
        try:
            bt.set_policy(bt.BF16_COMPUTE)
            with pytest.raises(DTypePolicyDriftError):
                eng.warmup((4,))
            with pytest.raises(DTypePolicyDriftError):
                eng.submit(np.ones((4,), np.float32))
        finally:
            bt.set_policy(prev)
        eng.close()

    def test_equivalent_policy_object_is_not_drift(self):
        """A NEW policy object with the same three dtypes is fine —
        the executables' precision is unchanged."""
        from bigdl_tpu import tensor as bt
        model = _small_model()
        eng = ServeEngine(model, max_batch=4, max_wait_ms=1,
                          input_shape=(4,))
        prev = bt.policy()
        try:
            bt.set_policy(bt.DTypePolicy())    # same dtypes as FP32
            out = eng.submit(np.ones((4,), np.float32)).result(timeout=30)
            assert out.shape == (3,)
        finally:
            bt.set_policy(prev)
        eng.close()

    def test_sibling_pinned_warmup_window_is_not_drift(self):
        """While a sibling engine's pinned-policy warmup holds the
        process policy swapped (a compilation-long transient), an
        ambient engine's submits must NOT false-positive — and the
        guard re-arms the moment the window closes."""
        from bigdl_tpu import tensor as bt
        from bigdl_tpu.serve import DTypePolicyDriftError
        from bigdl_tpu.serve import engine as engine_mod
        model = _small_model()
        eng = ServeEngine(model, max_batch=4, max_wait_ms=1,
                          input_shape=(4,))
        row = np.ones((4,), np.float32)
        prev = bt.policy()
        try:
            # simulate the sibling's warmup window: policy swapped AND
            # the pin depth held (exactly what warmup(policy=...) does)
            engine_mod._PIN_DEPTH += 1
            bt.set_policy(bt.BF16_COMPUTE)
            out = eng.submit(row).result(timeout=30)
            assert out.shape == (3,)
        finally:
            bt.set_policy(prev)
            engine_mod._PIN_DEPTH -= 1
        # a REAL drift (no pin held) still trips
        try:
            bt.set_policy(bt.BF16_COMPUTE)
            with pytest.raises(DTypePolicyDriftError):
                eng.submit(row)
        finally:
            bt.set_policy(prev)
        eng.close()

    def test_pinned_policy_engine_is_immune(self):
        """An engine constructed with an explicit policy re-pins it
        around every trace; the process policy flipping underneath is
        not its problem."""
        from bigdl_tpu import tensor as bt
        model = _small_model()
        eng = ServeEngine(model, max_batch=4, max_wait_ms=1,
                          input_shape=(4,), policy=bt.BF16_COMPUTE)
        prev = bt.policy()
        try:
            bt.set_policy(bt.BF16_ACT)
            out = eng.submit(np.ones((4,), np.float32)).result(timeout=30)
            assert out.shape == (3,)
        finally:
            bt.set_policy(prev)
        eng.close()


class TestPredictorRegression:
    """First-ever regression coverage for the Predictor surface."""

    def test_partial_batch_trim(self, monkeypatch):
        # batches close on size 8, the 4-row tail on this deadline
        monkeypatch.setenv("BIGDL_SERVE_MAX_WAIT_MS", "300")
        model = _small_model()
        x = np.random.RandomState(0).randn(20, 4).astype(np.float32)
        pred = __import__("bigdl_tpu.optim.predictor",
                          fromlist=["Predictor"]).Predictor(model,
                                                            batch_size=8)
        try:
            out = pred.predict(x)
            assert out.shape == (20, 3)           # tail trimmed, not padded
            assert np.array_equal(out, _serial_fwd_at(model, x, 8))
        finally:
            pred.close()

    def test_predict_class_is_one_based(self):
        from bigdl_tpu.optim.predictor import Predictor
        model = _small_model()
        pred = Predictor(model, batch_size=8)
        try:
            x = np.random.RandomState(0).randn(9, 4).astype(np.float32)
            classes = pred.predict_class(x)
            logp = pred.predict(x)
            assert np.array_equal(classes, logp.argmax(-1) + 1)
            assert classes.min() >= 1 and classes.max() <= 3
        finally:
            pred.close()

    def test_refresh_picks_up_new_weights(self):
        from bigdl_tpu.optim.predictor import Predictor
        model = _small_model()
        x = np.random.RandomState(0).randn(4, 4).astype(np.float32)
        pred = Predictor(model, batch_size=4)
        try:
            before = pred.predict(x)
            model.load_params(jax.tree_util.tree_map(np.zeros_like,
                                                     model.params()))
            assert np.array_equal(pred.predict(x), before)
            pred.refresh()
            assert not np.array_equal(pred.predict(x), before)
        finally:
            pred.close()

    def test_dlclassifier_transform_pairs(self):
        from bigdl_tpu.optim.predictor import DLClassifier
        model = _small_model()
        clf = DLClassifier(model, batch_size=8)
        try:
            rows = [np.ones((4,), np.float32) * i for i in range(5)]
            out = clf.transform(rows)
            assert len(out) == 5
            assert all(p in (1, 2, 3) for _, p in out)
        finally:
            clf.close()


class TestValidatorTailRouting:
    def test_tail_batch_reuses_full_batch_program(self):
        """An eval pass whose last batch is partial traces exactly ONE
        forward program (the tail pads to the full batch shape)."""
        from bigdl_tpu.dataset.sample import MiniBatch
        from bigdl_tpu.optim.local_optimizer import validate
        from bigdl_tpu.optim.validation import Top1Accuracy

        class _Eval:
            def data(self, train=False):
                rng = np.random.RandomState(0)
                for b in (8, 8, 3):            # 3-row tail
                    yield MiniBatch(rng.randn(b, 4).astype(np.float32),
                                    rng.randint(1, 4, (b, 1)))

        model = _small_model()
        traces = []
        real_jit = jax.jit

        def counting_jit(fn, *a, **kw):
            def counted(*args, **kwargs):
                traces.append(tuple(np.shape(args[-1])))
                return fn(*args, **kwargs)
            return real_jit(counted, *a, **kw)

        jax.jit = counting_jit
        try:
            res = validate(model, model.params(), model.state(), _Eval(),
                           [Top1Accuracy()])
        finally:
            jax.jit = real_jit
        assert res[0][1].count == 19           # every real row scored
        assert len(traces) == 1, (
            f"tail batch retraced the eval forward: {traces}")
        assert traces[0][0] == 8               # the full-batch shape

    def test_tail_padding_matches_unpadded_results(self):
        from bigdl_tpu.dataset.sample import MiniBatch
        from bigdl_tpu.optim.local_optimizer import validate
        from bigdl_tpu.optim.validation import Loss, Top1Accuracy

        rng = np.random.RandomState(3)
        data = rng.randn(19, 4).astype(np.float32)
        labels = rng.randint(1, 4, (19, 1))

        class _Chunked:
            def __init__(self, sizes):
                self.sizes = sizes

            def data(self, train=False):
                at = 0
                for b in self.sizes:
                    yield MiniBatch(data[at:at + b], labels[at:at + b])
                    at += b

        model = _small_model()
        p, s = model.params(), model.state()
        import bigdl_tpu.nn as bnn
        methods = [Top1Accuracy(), Loss(bnn.ClassNLLCriterion())]
        with_tail = validate(model, p, s, _Chunked((8, 8, 3)), methods)
        uniform = validate(model, p, s, _Chunked((19,)), methods)
        assert with_tail[0][1] == uniform[0][1]
        assert np.isclose(with_tail[1][1].loss, uniform[1][1].loss)
