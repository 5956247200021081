"""Asynchronous host input pipeline: bounded background fetch +
prefetch-to-device (docs/performance.md "host pipeline",
docs/observability.md "host pipeline" spans/events).

The reference hides data loading behind compute — Spark executors
materialize the next partition's mini-batches while the current
super-step trains (MTLabeledBGRImgToBatch + PreFetch) — while our serial
loop ran the whole Transformer chain on the main thread inside the
``data-load`` span.  This module moves that work off the critical path:

- :class:`PipelineRunner` executes the dataset's transformer chain on ONE
  background producer thread feeding a bounded queue.  The producer
  *owns the process seed stream* (``RNG.own_seed_stream``), so shuffle
  permutations and RNG-bearing transforms (random crop/flip/jitter) draw
  the exact values, in the exact order, the serial loop would have drawn
  — the loss trajectory is bit-identical with prefetch on or off
  (asserted by ``tests/test_prefetch.py``).  Pure per-record stages
  (decode, normalize — ``Transformer.pure_per_record``) may additionally
  fan out across a thread pool (``BIGDL_PREFETCH_WORKERS``) with order
  preserved; stochastic stages always stay on the single producer.
- Epoch semantics move WITH the draws: the producer mirrors the
  optimizer's rollover arithmetic (count/reset for single-step,
  count/subtract for chunked dispatch) and performs the epoch-boundary
  ``dataset.shuffle()`` + iterator rebuild itself, so the stream sees the
  identical draw sequence.  The consuming loop only advances its epoch
  counters.
- ``to_device`` adds a second stage: a transfer thread double-buffers
  batches onto the device (the optimizer passes its own
  ``_device_put_batch``, so local, sharded and multi-host layouts all
  overlap H2D with compute).
- Such a training runner **owns the host batch buffers**.  It borrows the
  slots of the chain's ``SampleToBatch`` (``depth + 2`` of them: one being
  filled, ``depth`` queued, one in transfer), lends the stage one free
  slot per draw, and takes it back on the transfer thread once
  ``to_device`` has returned AND the device arrays are ready
  (``jax.block_until_ready``: the copy reads the host buffer after the
  call returns, measured on the v5e, PERF.md PR 27); ``item.x``/``item.y``
  are dropped then, the loop reads ``item.device``.  The producer takes
  the slot BEFORE the draw and waits for one outside every span, clock and
  lock (``feed/slot-wait``), so the wait is never booked as draw time; it
  can cost a stall and never a wrong batch.  With ``depth + 2`` slots a
  producer ahead of the device blocks on the full queue (``_put``, which
  no span counts either) before it runs out of slots; the wait shows when
  a transfer holds its slot for long.  Batches
  that do not fit a slot, and every other reader of the dataset (direct
  iteration, ``BIGDL_PREFETCH=0``, a runner without ``to_device``,
  validation, chunked dispatch whose ``stack_chunk`` copies anyway), get
  fresh arrays as before.  :meth:`close` hands the slots back to the
  stage, which keeps the buffers for the next runner over the dataset.
- Telemetry is taken where the work happens and drained by the consuming
  loop through :meth:`PipelineRunner.take_spans`: the wall of every draw
  (``data-load/fetch``), the self time of the source and of each
  transformer stage inside it (``data-load/fetch/source:<DataSet>``,
  ``data-load/fetch/stage/<i>:<Stage>``), the wall of every
  ``to_device`` call up to its arrays being ready (``h2d/prefetch``) and
  ``feed/slot-wait`` (seconds the producer waited for a free slot; count
  = draws assembled into a recycled slot).  The draw and the transfer each
  run under a ``TraceAnnotation`` of that name, one name per thread, so a
  profiler trace shows them on the device's clock.  Only work is
  annotated: queue waits and whole iterations carry no span.
- Checkpoint/resume: every produced item carries the stream snapshot
  taken right after its draws.  :meth:`rng_snapshot` splices the snapshot
  of the last CONSUMED item with the live device-key counter, so a resume
  replays exactly the batches the interrupted run had consumed — not the
  ones it had merely prefetched.  :meth:`close` restores that state, so a
  finished run leaves the stream exactly where a serial run would.

Flags: ``BIGDL_PREFETCH`` (default on; ``0`` disables, ``N>=2`` sets the
queue depth), ``BIGDL_SYNC_EVERY_STEP=1`` (escape hatch: the training
loops also sync the loss every step, for debugging/chaos drills),
``BIGDL_PREFETCH_WORKERS`` (pure-stage fan-out width, default 0).

Chaos: the optimizers do NOT hand ``to_device`` to the runner while a
``FaultInjector`` is installed — batches then stay on host until consume
time so ``_chaos_prestep`` keys every site by the *consuming* step and
``BIGDL_FAULTS`` drills are unchanged (docs/resilience.md).
"""
from __future__ import annotations

import logging
import os
import queue
import threading
import time
from collections import deque

import jax
import numpy as np

from bigdl_tpu.utils.profiler import annotation
from bigdl_tpu.utils.random import RNG

logger = logging.getLogger("bigdl_tpu.dataset")

ENV_PREFETCH = "BIGDL_PREFETCH"
ENV_SYNC_EVERY_STEP = "BIGDL_SYNC_EVERY_STEP"
ENV_WORKERS = "BIGDL_PREFETCH_WORKERS"

DEFAULT_DEPTH = 2

#: span paths of the two background threads (docs/observability.md "host
#: pipeline"); each is also the name of the thread's ``TraceAnnotation``,
#: and is used on that thread only, so a trace reader finds the thread by it
FETCH = "data-load/fetch"       # producer: one draw of the transformer chain
H2D = "h2d/prefetch"            # transfer thread: one ``to_device`` call
#: no ``TraceAnnotation`` (a wait, not work) and outside ``data-load/fetch/``,
#: whose sub-paths are the chain's links: seconds the producer waited for a
#: free host slot, count = draws assembled into a recycled slot
SLOT_WAIT = "feed/slot-wait"


def enabled() -> bool:
    """Master switch: ``BIGDL_PREFETCH`` (default on)."""
    return os.environ.get(ENV_PREFETCH, "1").strip() != "0"


def depth() -> int:
    """Queue depth per stage.  ``BIGDL_PREFETCH=N`` with N >= 2 sets the
    depth; any other truthy value keeps the default double-buffer."""
    raw = os.environ.get(ENV_PREFETCH, "").strip()
    try:
        n = int(raw)
    except ValueError:
        return DEFAULT_DEPTH
    return n if n >= 2 else DEFAULT_DEPTH


def sync_every_step() -> bool:
    """``BIGDL_SYNC_EVERY_STEP=1``: the loops materialize loss/finite on
    the host every iteration (the pre-cadence behavior)."""
    return os.environ.get(ENV_SYNC_EVERY_STEP, "0").strip() == "1"


def workers() -> int:
    try:
        return max(0, int(os.environ.get(ENV_WORKERS, "0")))
    except ValueError:
        return 0


def stack_chunk(batches):
    """Stack n uniform-shape MiniBatches into (n, B, ...) host arrays.

    Each batch is converted ONCE — the converted arrays serve both the
    shape check and the stack (the old ``_next_chunk`` converted every
    batch twice: ``np.asarray`` for the check, ``np.stack`` again)."""
    xs = [np.asarray(b.data) for b in batches]
    ys = [np.asarray(b.labels) for b in batches]
    shapes = {a.shape for a in xs}
    if len(shapes) != 1:
        raise ValueError(
            "iterations_per_dispatch needs uniform batch shapes "
            f"within a chunk, got {shapes}")
    return np.stack(xs), np.stack(ys)


def background(iterator, depth: int = DEFAULT_DEPTH):
    """Plain bounded background prefetch of an iterator (no RNG
    ownership, no epoch machinery) — what validation batches ride."""
    from bigdl_tpu.dataset.transformer import PreFetch
    return PreFetch(depth)(iterator)


def has_stochastic_stage(dataset) -> bool:
    """True when the dataset's transformer chain contains an RNG-bearing
    stage.  ``validate`` keeps such (unconventional) eval pipelines on
    the calling thread instead of a background one, so their draws at
    least come from a deterministic per-thread stream rather than a
    fresh derived stream per validation pass."""
    return any(getattr(s, "stochastic", False)
               for s in _decompose(dataset)[1])


class Item:
    """One produced batch: host arrays, optional device arrays, the
    stream snapshot taken after its draws, and fetch-side telemetry."""

    __slots__ = ("x", "y", "device", "rng", "seq", "fetch_wall",
                 "queue_depth", "slot")

    def __init__(self, x, y, rng=None, seq=0, fetch_wall=0.0):
        self.x = x
        self.y = y
        self.device = None
        self.rng = rng
        self.seq = seq
        self.fetch_wall = fetch_wall
        self.queue_depth = 0
        self.slot = None         # the BatchSlot x/y live in, if recycled


class _End:
    pass


class _Error:
    # private wrapper so a pipeline legitimately yielding exception
    # objects as data is never confused with a worker failure
    def __init__(self, exc):
        self.exc = exc


_END = _End()


def _decompose(dataset):
    """Peel a TransformedDataSet chain into (base_dataset, [stages]),
    flattening ChainedTransformer trees into stage order."""
    from bigdl_tpu.dataset.dataset import TransformedDataSet
    from bigdl_tpu.dataset.transformer import ChainedTransformer

    def flatten(t):
        if isinstance(t, ChainedTransformer):
            return flatten(t.first) + flatten(t.last)
        return [t]

    stages = []
    while isinstance(dataset, TransformedDataSet):
        stages = flatten(dataset.transformer) + stages
        dataset = dataset.base
    return dataset, stages


class _Timed:
    """Iterator wrapper that adds the time spent inside its upstream's
    ``next()`` to ``clock[0]`` (inclusive of everything further up; the
    runner subtracts the upstream's clock to get a stage's self time).
    Two ``perf_counter`` reads and one float add per element, no lock and
    no profiler event: the producer thread alone touches the clocks."""

    __slots__ = ("_it", "_clock")

    def __init__(self, it, clock):
        self._it = iter(it)
        self._clock = clock

    def __iter__(self):
        return self

    def __next__(self):
        t0 = time.perf_counter()
        try:
            return next(self._it)
        finally:
            self._clock[0] += time.perf_counter() - t0


def _is_pure_map(stage) -> bool:
    """A stage eligible for worker fan-out: declared 1-to-1 per record
    (``pure_per_record``) and free of RNG draws (not ``stochastic``)."""
    return bool(getattr(stage, "pure_per_record", False)) and \
        not bool(getattr(stage, "stochastic", False))


def _reads_host(device, slot) -> bool:
    """Whether anything ``to_device`` returned still reads the slot's
    memory once it is ready.  A device's own memory never does; the CPU
    backend adopts a 64-byte-aligned host array, or a contiguous shard of
    one, without a copy, and a stub may hand back the host arrays."""
    spans = [(b.ctypes.data, b.ctypes.data + b.nbytes)
             for b in (slot.x, slot.y)]
    for leaf in jax.tree_util.tree_leaves(device):
        if isinstance(leaf, np.ndarray):
            if any(np.may_share_memory(leaf, b) for b in (slot.x, slot.y)):
                return True
        elif isinstance(leaf, jax.Array):
            for shard in leaf.addressable_shards:
                if shard.device.platform != "cpu":
                    continue
                at = shard.data.unsafe_buffer_pointer()
                if any(lo <= at < hi for lo, hi in spans):
                    return True
    return False


class PipelineRunner:
    """Bounded background input pipeline over one dataset.

    ``chunk > 1`` assembles stacked (n, B, ...) chunks for the device-side
    scanned loop (``set_iterations_per_dispatch``).  ``epoch_size``
    enables producer-side epoch rollover (training); ``records_scale``
    converts a local host batch to the GLOBAL record count the consuming
    loop's epoch arithmetic uses (multi-host data sharding).

    ``to_device(xh, yh) -> (x, y)`` arms the second stage: a transfer
    thread that double-buffers batches onto the device ahead of
    consumption; a training runner with one also owns the host batch
    buffers (module docstring).  ``own_rng`` (default: ``train``) moves
    the process seed stream onto the producer — see the module docstring.
    """

    def __init__(self, dataset, *, train: bool = True, chunk: int = 1,
                 epoch_size: int | None = None, depth: int | None = None,
                 to_device=None, records_scale: int = 1,
                 own_rng: bool | None = None, n_workers: int | None = None):
        self._dataset = dataset
        self._train = train
        self._chunk = max(1, int(chunk))
        self._epoch_size = int(epoch_size) if epoch_size else None
        self.depth = int(depth) if depth else globals()["depth"]()
        self._records_scale = max(1, int(records_scale))
        self._own_rng = train if own_rng is None else bool(own_rng)
        self._n_workers = workers() if n_workers is None else int(n_workers)
        self._to_device = to_device

        self._host_q = queue.Queue(maxsize=self.depth)
        self._out_q = (self._host_q if to_device is None
                       else queue.Queue(maxsize=self.depth))
        self._stop = threading.Event()
        self._pause = threading.Event()
        # held by the producer for the whole of one draw (transform chain
        # + epoch rollover); pause() acquires it to wait out an in-flight
        # draw — an Event-flag handshake alone would race (the producer
        # could pass the pause check right before the flag is set)
        self._work_lock = threading.Lock()
        self._stats_lock = threading.Lock()
        self._closed = False
        self._count = 0          # records into the current epoch
        self._pool = None
        base, stages = _decompose(dataset)
        n_prefix = 0             # leading stages fanned out over the pool
        if self._train and self._n_workers > 0:
            while n_prefix < len(stages) and _is_pure_map(stages[n_prefix]):
                n_prefix += 1
            # records per base-iterator cycle: the looped iterator draws
            # its shuffle permutation at each cycle start, so the
            # fan-out window must drain before crossing a boundary or
            # that draw lands early in the stream.  Only the list-backed
            # datasets have a knowable cycle (ShardedDataSet loops its
            # LOCAL shard — size() would be the global count; streaming
            # sets like ShardFolder reshuffle on their own schedule):
            # everything else keeps the single producer, preserving the
            # bit-parity guarantee over a fan-out speedup.
            from bigdl_tpu.dataset.dataset import (LocalArrayDataSet,
                                                   ShardedDataSet)
            if isinstance(base, ShardedDataSet):
                cycle = base.shard_size()
            elif isinstance(base, LocalArrayDataSet):
                cycle = base.size()
            else:
                cycle = None
                if n_prefix:
                    logger.info(
                        "prefetch worker fan-out disabled: %s has no "
                        "knowable shuffle-cycle length, so read-ahead "
                        "could reorder its RNG draws",
                        type(base).__name__)
            if n_prefix and cycle:
                from concurrent.futures import ThreadPoolExecutor
                self._pool = ThreadPoolExecutor(
                    max_workers=self._n_workers,
                    thread_name_prefix="bigdl-prefetch-worker")
                self._cycle = cycle
            else:
                n_prefix = 0
        # the chain as the producer builds it, link by link: the source,
        # the fanned-out prefix as one link (if any), then each stage
        self._base = base
        self._prefix = stages[:n_prefix]
        self._rest = stages[n_prefix:]
        # the host batch buffers (module docstring): borrowed from the
        # chain's batcher where this runner copies every batch to the
        # device itself.  A chunk is stacked into a fresh array anyway.
        self._batcher = self._slots = None
        self._lent = None        # the slot of the draw in progress
        self._free = queue.Queue()
        if train and to_device is not None and self._chunk <= 1:
            from bigdl_tpu.dataset.transformer import SampleToBatch
            for stage in self._rest:
                if isinstance(stage, SampleToBatch):
                    self._slots = stage.borrow_slots(self.depth + 2)
                    if self._slots is not None:
                        self._batcher = stage
                    break
            for slot in self._slots or ():
                slot.filled = False
                self._free.put(slot)
        names = ["source:" + type(base).__name__]
        if n_prefix:
            names.append("stage/0:" + "+".join(
                type(s).__name__ for s in self._prefix))
        names += [f"stage/{n_prefix + k}:{type(s).__name__}"
                  for k, s in enumerate(self._rest)]
        self._link_paths = [FETCH + "/" + n for n in names]
        self._clocks = [[0.0] for _ in names]   # see _Timed

        # telemetry drained by the consuming loop
        self.consumed = 0
        self.produced = 0
        self.epochs_rolled = 0
        self.stall_seconds = 0.0
        self._spans = {}         # path -> [seconds, count], see take_spans

        self._start_snap = RNG.snapshot() if self._own_rng else None
        self._last_rng = None    # snapshot of the last CONSUMED item

        self._producer = threading.Thread(
            target=self._produce, daemon=True,
            name="bigdl-prefetch-producer")
        self._transfer = None
        if to_device is not None:
            self._transfer = threading.Thread(
                target=self._transfer_loop, daemon=True,
                name="bigdl-prefetch-h2d")
        self._producer.start()
        if self._transfer is not None:
            self._transfer.start()

    # -- producer side -----------------------------------------------------
    def _make_iter(self):
        """``dataset.data(train)`` built link by link (``TransformedDataSet
        .data`` is exactly ``transformer(base.data(train))``, so the draws
        and the RNG stream are the same), each link under its clock."""
        clocks = iter(self._clocks)
        it = _Timed(self._base.data(train=self._train), next(clocks))
        if self._prefix:
            it = _Timed(self._parallel_map(it, self._prefix), next(clocks))
        for stage in self._rest:
            it = _Timed(stage.assemble_into(it, self._lent_slot)
                        if stage is self._batcher else stage(it),
                        next(clocks))
        return it

    def _lent_slot(self):
        """The slot of the draw in progress, to the producer's thread
        alone: a background stage downstream of the batcher (``PreFetch``)
        pulls batches at its own pace, and those are assembled fresh."""
        if threading.current_thread() is self._producer:
            return self._lent
        return None

    def _book(self, *entries):
        """Add ``seconds`` and ``count`` bookings to each ``(path,
        seconds, count)``."""
        with self._stats_lock:
            for path, seconds, count in entries:
                acc = self._spans.setdefault(path, [0.0, 0])
                acc[0] += seconds
                acc[1] += count

    def _book_draw(self, wall, slot_wait, recycled):
        """One draw is done: its wall, each link's self time (time inside
        its ``next()`` minus time inside its upstream's) and, where the
        runner lends slots, the wait for one that preceded the draw."""
        entries, upstream = [(FETCH, wall, 1)], 0.0
        for path, clock in zip(self._link_paths, self._clocks):
            entries.append((path, clock[0] - upstream, 1))
            upstream, clock[0] = clock[0], 0.0
        if self._batcher is not None:
            entries.append((SLOT_WAIT, slot_wait, int(recycled)))
        self._book(*entries)

    def _parallel_map(self, records, prefix):
        """Ordered fan-out of the pure per-record stage prefix across the
        worker pool (a bounded window of in-flight futures)."""
        pool, window = self._pool, self._n_workers * 2

        def apply(rec):
            out = rec
            for stage in prefix:
                res = list(stage(iter([out])))
                if len(res) != 1:
                    raise ValueError(
                        f"{type(stage).__name__} declared pure_per_record "
                        f"but produced {len(res)} records from 1")
                out = res[0]
            return out

        cycle = self._cycle if self._train else None

        def gen():
            """Bounded in-flight window, record order preserved.  The
            stream's draw interleaving must match the serial chain:
            stochastic downstream stages draw per YIELDED record, and
            pulling the base iterator across a cycle boundary draws the
            next shuffle permutation — so the window drains fully before
            the first pull of a new cycle."""
            futs = deque()
            pulled = 0
            it = iter(records)
            while True:
                if cycle and pulled and pulled % cycle == 0 and futs:
                    while futs:
                        yield futs.popleft().result()
                try:
                    rec = next(it)
                except StopIteration:
                    break
                futs.append(pool.submit(apply, rec))
                pulled += 1
                if len(futs) >= window:
                    yield futs.popleft().result()
            while futs:
                yield futs.popleft().result()

        return gen()

    def _advance_epoch(self, records: int):
        """Mirror of the optimizers' ``_advance_epochs`` arithmetic, run
        at PRODUCE time so the epoch-boundary shuffle + permutation draws
        land at the same point of the stream as in the serial loop."""
        if not self._epoch_size or not self._train:
            return
        self._count += records
        if self._chunk <= 1:
            if self._count >= self._epoch_size:
                self._count = 0
                self._rollover()
        else:
            while self._count >= self._epoch_size:
                self._count -= self._epoch_size
                self._rollover()

    def _rollover(self):
        self._dataset.shuffle()
        self._it = self._make_iter()
        self.epochs_rolled += 1

    def _produce(self):
        try:
            if self._own_rng:
                RNG.own_seed_stream()
            self._it = self._make_iter()
            seq = 0
            slot, slot_wait = None, 0.0
            while not self._stop.is_set():
                if self._pause.is_set():
                    time.sleep(0.002)
                    continue
                if self._batcher is not None and slot is None:
                    # before the draw, outside its lock, annotation and
                    # clocks: a wait is not work, and booked as a stage's
                    # time it would blame the feed for the device's pace
                    t0 = time.perf_counter()
                    try:
                        slot = self._free.get(timeout=0.1)
                    except queue.Empty:
                        continue
                    finally:
                        slot_wait += time.perf_counter() - t0
                with self._work_lock:
                    if self._pause.is_set():  # re-check under the lock
                        continue
                    t0 = time.perf_counter()
                    self._lent = slot
                    with annotation(FETCH):
                        if self._chunk <= 1:
                            try:
                                b = next(self._it)
                            except StopIteration:
                                self._put(self._host_q, _END)
                                return
                            x, y = b.data, b.labels
                            records = int(np.asarray(x).shape[0])
                        else:
                            x, y = stack_chunk(
                                [next(self._it) for _ in range(self._chunk)])
                            records = int(x.shape[0] * x.shape[1])
                        self._advance_epoch(records * self._records_scale)
                        snap = RNG.snapshot() if self._own_rng else None
                    wall = time.perf_counter() - t0
                item = Item(x, y, rng=snap, seq=seq, fetch_wall=wall)
                if slot is not None and slot.filled:
                    item.slot, slot = slot, None    # else: kept for the next
                self._book_draw(wall, slot_wait, item.slot is not None)
                slot_wait = 0.0
                if not self._put(self._host_q, item):
                    return
                self.produced += 1
                seq += 1
        except BaseException as e:  # surface on the consumer thread
            self._put(self._host_q, _Error(e))

    def _put(self, q, item) -> bool:
        """Bounded put that gives up once the consumer is gone, so an
        abandoned runner never leaves its threads blocked forever."""
        while not self._stop.is_set():
            try:
                q.put(item, timeout=0.1)
                return True
            except queue.Full:
                continue
        return False

    def _transfer_loop(self):
        while not self._stop.is_set():
            try:
                item = self._host_q.get(timeout=0.1)
            except queue.Empty:
                continue
            if isinstance(item, (_End, _Error)):
                self._put(self._out_q, item)
                return
            try:
                t0 = time.perf_counter()
                with annotation(H2D):
                    item.device = self._to_device(item.x, item.y)
                    if item.slot is not None:
                        # the copy reads the host buffer after the call
                        # has returned (PERF.md PR 27, probe e)
                        jax.block_until_ready(item.device)
                self._book((H2D, time.perf_counter() - t0, 1))
                if item.slot is not None:
                    self._release(item)
            except BaseException as e:
                self._put(self._out_q, _Error(e))
                return
            if not self._put(self._out_q, item):
                return

    def _release(self, item):
        """The transfer is over: the slot goes back to the free list and
        the item lets go of the host arrays.  Where the device arrays ARE
        the host memory, they keep it and the slot gets new buffers."""
        slot, item.slot = item.slot, None
        if _reads_host(item.device, slot):
            slot.x = slot.y = None
        item.x = item.y = None
        slot.filled = False
        self._free.put(slot)

    # -- consumer side -----------------------------------------------------
    def get(self):
        """Next item, blocking.  Returns ``(item, waited_seconds)``;
        raises StopIteration when a one-pass (eval) stream is exhausted
        and re-raises any producer/transfer failure."""
        t0 = time.perf_counter()
        item = self._out_q.get()
        waited = time.perf_counter() - t0
        if isinstance(item, _End):
            raise StopIteration
        if isinstance(item, _Error):
            raise item.exc
        self.stall_seconds += waited
        self.consumed += 1
        item.queue_depth = self._out_q.qsize()
        if item.rng is not None:
            self._last_rng = item.rng
        return item, waited

    def __iter__(self):
        while True:
            try:
                yield self.get()[0]
            except StopIteration:
                return

    def take_spans(self):
        """Drain what the two threads booked since the last call:
        ``{span path: (seconds, count)}`` — ``data-load/fetch`` (the wall
        of each draw), its links' self times and ``h2d/prefetch`` (the
        wall of each ``to_device`` call).  The consuming loop credits them
        to its span tree."""
        with self._stats_lock:
            out, self._spans = self._spans, {}
        return {path: (sec, n) for path, (sec, n) in out.items()}

    def rng_snapshot(self) -> dict:
        """Host-stream state as of the last CONSUMED batch, with the
        LIVE device-key counter spliced in — the checkpoint payload that
        makes a resumed run replay the serial trajectory (keys are
        minted at consume time on the loop thread, np draws at fetch
        time on the producer)."""
        base = self._last_rng or self._start_snap
        if base is None:
            return RNG.snapshot()
        snap = dict(base)
        snap["key_counter"] = RNG.key_counter()
        return snap

    def pause(self):
        """Hold the producer before its next draw (validation borrows the
        dataset's backing store; an epoch shuffle must not interleave).
        Acquiring the work lock waits out a draw already in flight."""
        self._pause.set()
        with self._work_lock:
            pass
        return self

    def resume(self):
        self._pause.clear()
        return self

    def close(self, restore_rng: bool = True):
        """Stop both threads, then (training runners) hand the seed
        stream back to the calling thread restored to the last-consumed
        state — erasing the ahead-draws of merely-prefetched batches so
        the process RNG ends exactly where a serial run would."""
        if self._closed:
            return
        self._closed = True
        self._stop.set()
        for q in {id(self._host_q): self._host_q,
                  id(self._out_q): self._out_q}.values():
            while True:
                try:
                    q.get_nowait()
                except queue.Empty:
                    break
        self._producer.join(timeout=5.0)
        if self._transfer is not None:
            self._transfer.join(timeout=5.0)
        if self._pool is not None:
            self._pool.shutdown(wait=False)
        if self._producer.is_alive():  # pragma: no cover - defensive
            logger.warning("prefetch producer did not stop within 5s")
        if self._batcher is not None:
            self._batcher.return_slots(self._slots)
            self._batcher = None
        if self._own_rng and restore_rng:
            RNG.restore(self.rng_snapshot())
