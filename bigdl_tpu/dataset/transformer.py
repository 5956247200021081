"""Transformer pipeline (ref dataset/Transformer.scala:40-55).

A ``Transformer[A, B]`` is ``Iterator[A] -> Iterator[B]``, composed with
``->`` — here the ``>>`` operator (and ``.chain()``).  SampleToBatch
(Transformer.scala:99-241) assembles fixed-shape padded MiniBatches, the
contact point with jit's static-shape requirement (SURVEY.md §7 hard parts:
variable-length batching must pad to fixed shapes).
"""
from __future__ import annotations

import numpy as np

from bigdl_tpu.dataset.sample import Sample, MiniBatch


class Transformer:
    """Iterator-to-iterator stage. Subclasses override __call__."""

    #: exactly one output record per input record, no RNG draws, no
    #: cross-record state — eligible for ordered worker fan-out in the
    #: prefetch pipeline (``dataset/prefetch.py``); decode/normalize
    #: stages set this
    pure_per_record = False
    #: draws from the framework RNG (``utils.random.RNG``) — must run on
    #: the prefetch producer thread (the seed-stream owner) so the draw
    #: sequence stays bit-identical to the serial path
    stochastic = False

    def __call__(self, iterator):
        raise NotImplementedError

    def __rshift__(self, other: "Transformer") -> "ChainedTransformer":
        """``a >> b`` == reference's ``a -> b``."""
        return ChainedTransformer(self, other)

    def chain(self, other):
        return self.__rshift__(other)

    def clone_transformer(self):
        import copy
        return copy.deepcopy(self)


class ChainedTransformer(Transformer):
    def __init__(self, first, last):
        self.first = first
        self.last = last

    def __call__(self, iterator):
        return self.last(self.first(iterator))


class Identity(Transformer):
    def __call__(self, iterator):
        return iterator


class FuncTransformer(Transformer):
    """Wrap a per-record function into a Transformer."""

    def __init__(self, fn):
        self.fn = fn

    def __call__(self, iterator):
        return (self.fn(x) for x in iterator)


class BatchSlot:
    """One recycled pair of host batch buffers, allocated by the first batch
    assembled into it.  ``filled`` says that a draw was assembled into it
    (the lender clears it when it takes the slot back)."""

    __slots__ = ("x", "y", "filled")

    def __init__(self):
        self.x = self.y = None
        self.filled = False


class SampleToBatch(Transformer):
    """Sample -> MiniBatch with optional fixed-length padding
    (ref Transformer.scala:99-241).

    ``feature_padding``/``label_padding``: pad value for variable-length
    features/labels.  ``fixed_length``: pad every batch to this length
    (keeps one static shape for jit instead of per-batch max).
    ``partition_num``: drop the tail so every partition yields whole batches.

    **Who owns a batch's arrays.**  Called as a transformer
    (``dataset.data()`` iterated directly, ``list(...)`` of batches, the
    serial training loop, validation, a prefetch runner that keeps batches
    on the host) every batch is a fresh allocation that belongs to the
    caller, for as long as the caller likes.  A training
    ``prefetch.PipelineRunner`` that copies batches to the device borrows
    this stage's slots (:meth:`borrow_slots`) and draws through
    :meth:`assemble_into`: a full batch whose rows match the slot in shape
    and dtype is copied into that recycled buffer, which belongs to the
    runner and is valid until its transfer to the device is over; a stage
    downstream of this one sees the batch during the draw and must not keep
    it beyond.  Anything else (the partial tail batch, a padded side
    without ``fixed_length``, rows that drift in shape or dtype) is
    assembled fresh, as above.  The buffers stay with the stage, so runners
    that follow one another over one dataset share them.
    """

    def __init__(self, batch_size: int = None, feature_padding=None,
                 label_padding=None, fixed_length: int = None,
                 drop_last: bool = False, global_batch_size: int = None):
        if (batch_size is None) == (global_batch_size is None):
            raise ValueError("pass exactly one of batch_size (per-process)"
                             " or global_batch_size (divided over the live"
                             " process world)")
        # global_batch_size is the reference's Utils.getBatchSize contract
        # (global batch ÷ node count, Utils.scala:26-48) resolved at
        # ITERATION time from the live jax topology instead of once at
        # construction — so an elastic re-form (docs/resilience.md) that
        # shrinks the world automatically grows each survivor's local
        # batch and the GLOBAL batch stays fixed.
        self.global_batch_size = (int(global_batch_size)
                                  if global_batch_size is not None else None)
        self.batch_size = batch_size
        self.feature_padding = feature_padding
        self.label_padding = label_padding
        self.fixed_length = fixed_length
        self.drop_last = drop_last
        # the slots, on the shelf while no runner has them: pop and append
        # of a list are atomic, so two runners cannot both take them
        self._shelf = [[]]
        self._slot_like = None   # what the slots look like, _slot_fits

    def __getstate__(self):
        # the slots are scratch memory, not configuration: a copy or a
        # pickle of the stage starts with none
        return dict(self.__dict__, _shelf=[[]], _slot_like=None)

    def borrow_slots(self, n: int):
        """The stage's ``n`` slots (made on first demand, kept with their
        buffers from one borrower to the next), or None while another
        borrower has them.  Give them back with :meth:`return_slots`."""
        try:
            slots = self._shelf.pop()
        except IndexError:
            return None
        slots.extend(BatchSlot() for _ in range(n - len(slots)))
        return slots

    def return_slots(self, slots):
        self._shelf.append(slots)

    def _slot_fits(self, slot, feats, labels):
        """Make ``slot`` ready for this full batch, or say that the batch
        has to be assembled fresh.  The first full batch decides what the
        slots look like; a batch of another row count (an elastic re-form
        changed the local batch) decides it anew."""
        rows = len(feats)
        like = self._slot_like      # [(shape, dtype)] of a slot's x and y
        if like is None or like[0][0][0] != rows:
            padded = [p is not None
                      for p in (self.feature_padding, self.label_padding)]
            # padded sides have data-dependent dim 1 unless pinned
            if any(padded) and self.fixed_length is None:
                return False
            like = self._slot_like = [
                ((rows, self.fixed_length) + row.shape[1:] if pad
                 else (rows,) + row.shape, row.dtype)
                for row, pad in zip((feats[0], labels[0]), padded)]
        if slot.x is None \
                or [(b.shape, b.dtype) for b in (slot.x, slot.y)] != like:
            slot.x, slot.y = (np.empty(*side) for side in like)
        return self._rows_fit(slot.x, feats, self.feature_padding) \
            and self._rows_fit(slot.y, labels, self.label_padding)

    @staticmethod
    def _rows_fit(buf, rows, pad_value):
        """Every row must match the buffer's dtype and row shape exactly
        (padded sides: the trailing dims; dim 0 is clipped/padded) — a
        drifting shape falls back to fresh allocation instead of crashing
        on the copy or, worse, broadcasting silently into wrong data, and
        a drifting dtype would be cast where ``np.stack`` promotes."""
        skip = 0 if pad_value is None else 1
        want = buf.shape[1 + skip:]
        return all(r.dtype == buf.dtype and r.shape[skip:] == want
                   for r in rows)

    @staticmethod
    def _fill(buf, arrays, pad_value):
        """Copy sample rows into a preallocated batch buffer (the padded
        path pre-fills with the pad value, then writes each prefix)."""
        if pad_value is None:
            for i, a in enumerate(arrays):
                buf[i] = a
            return buf
        buf.fill(pad_value)
        max_len = buf.shape[1]
        for i, a in enumerate(arrays):
            n = min(a.shape[0], max_len)
            buf[i, :n] = a[:n]
        return buf

    def _assemble(self, samples, slot=None):
        """One MiniBatch of ``samples``: into ``slot`` where one is lent,
        not yet filled in this draw, and fits; else into fresh arrays."""
        feats = [s.feature for s in samples]
        labels = [s.label for s in samples]
        if slot is not None and not slot.filled \
                and self._slot_fits(slot, feats, labels):
            slot.filled = True
            return MiniBatch(self._fill(slot.x, feats, self.feature_padding),
                             self._fill(slot.y, labels, self.label_padding))
        if self.feature_padding is not None:
            feats = _pad_stack(feats, self.feature_padding, self.fixed_length)
        else:
            feats = np.stack(feats)
        if self.label_padding is not None:
            labels = _pad_stack(labels, self.label_padding, self.fixed_length)
        else:
            labels = np.stack(labels)
        return MiniBatch(feats, labels)

    def _local_batch(self) -> int:
        if self.global_batch_size is None:
            return self.batch_size
        import jax
        from bigdl_tpu.dataset.dataset import get_batch_size
        return get_batch_size(self.global_batch_size, jax.process_count())

    def __call__(self, iterator):
        return self.assemble_into(iterator, lambda: None)

    def assemble_into(self, iterator, lend):
        """The batches of ``iterator``, each full one assembled into the
        slot ``lend()`` returns when it is drawn (a ``BatchSlot`` of
        :meth:`borrow_slots`, or None for a fresh allocation)."""
        batch = self._local_batch()
        buf = []
        for s in iterator:
            buf.append(s)
            if len(buf) == batch:
                yield self._assemble(buf, lend())
                buf = []
        if buf and not self.drop_last:
            yield self._assemble(buf)


def _pad_stack(arrays, pad_value, fixed_length=None):
    """Stack 1..nD arrays, padding dim 0 to max (or fixed) length."""
    max_len = fixed_length if fixed_length is not None else max(a.shape[0] for a in arrays)
    out_shape = (len(arrays), max_len) + arrays[0].shape[1:]
    out = np.full(out_shape, pad_value, dtype=arrays[0].dtype)
    for i, a in enumerate(arrays):
        n = min(a.shape[0], max_len)
        out[i, :n] = a[:n]
    return out


class PreFetch(Transformer):
    """Background-thread prefetch (the capability of the reference's
    MTLabeledBGRImgToBatch + PreFetch, MTLabeledBGRImgToBatch.scala:47,106:
    overlap host-side decode/augment with device compute)."""

    def __init__(self, depth: int = 2):
        self.depth = depth

    def __call__(self, iterator):
        import queue
        import threading

        q = queue.Queue(maxsize=self.depth)
        _END = object()
        stop = threading.Event()

        class _Error:
            # private sentinel so a pipeline that legitimately yields
            # exception *objects* as data items is not confused with a
            # worker failure
            def __init__(self, exc):
                self.exc = exc

        def put(item):
            # bounded put that gives up when the consumer is gone, so an
            # abandoned iterator can't leave this thread blocked forever
            while not stop.is_set():
                try:
                    q.put(item, timeout=0.1)
                    return True
                except queue.Full:
                    continue
            return False

        def worker():
            try:
                for item in iterator:
                    if not put(item):
                        return
                put(_END)
            except BaseException as e:  # propagate to the consumer
                put(_Error(e))

        t = threading.Thread(target=worker, daemon=True)
        t.start()
        try:
            while True:
                item = q.get()
                if item is _END:
                    break
                if isinstance(item, _Error):
                    raise item.exc
                yield item
        finally:
            stop.set()
