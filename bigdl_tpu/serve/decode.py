"""Continuous-batching decode: a slot-based driver over the
``TransformerLM`` KV-cache step (docs/serving.md).

``models.transformer.lm_decode`` compiles one lock-step scan: every row
starts together, ends together, and a new request waits for the whole
batch to finish.  A serving decoder cannot run lock-step — requests
arrive whenever they arrive and finish at their own lengths.  This
driver treats the rows of a fixed-width device batch as **slots**:

- each slot independently consumes its own seed and generates its own
  continuation (per-row positions — ``_lm_forward_one`` scatters the
  cache write and masks attention per row);
- requests are **admitted** into free slots and **retired** at step
  boundaries only, so the device sees one fixed-shape compiled step
  program for the engine's whole lifetime (slot index is a traced
  argument — admission never recompiles);
- the host syncs only every ``sync_interval`` steps (the
  ``BIGDL_OBS_TAPS_CADENCE``-style boundary, env ``BIGDL_SERVE_SYNC``):
  generated tokens feed back device-side, and the generated-token slab
  is materialized once per boundary that retires anything — never per
  token.

**Streaming delivery** (``serve/streaming.py``, docs/observability.md
"Streaming telemetry"): :meth:`ContinuousDecoder.submit` returns a
:class:`~bigdl_tpu.serve.streaming.StreamFuture` — register
``on_tokens(cb)`` (or ship the fleet payload's ``stream`` flag) and the
request's freshly generated tokens are delivered incrementally at each
sync boundary.  Delivery reuses the boundary's one slab
materialization (a boundary with live streams materializes exactly
once, for delivery AND retirement — never per token, never twice), the
committed stream is byte-identical to the all-at-once result in every
configuration, and consumer callbacks run on a dedicated delivery
thread so a slow or raising consumer can never stall the step loop.
Each streamed request lands a per-request token timeline (admit →
first-token boundary → per-boundary counts → retire) as a ``stream``
obs event plus trace hops when sampled, and feeds the
``decode_ttft_seconds`` / ``decode_itl_seconds`` / ``decode_stream_tokens_total``
SLO surface in the mergeable metrics registry.

**Paged KV (default, env ``BIGDL_SERVE_PAGED``)**: KV storage is a
block-paged pool — ``(layers, n_pages, page_size, heads, hd)`` plus a
per-slot slot→page table carried as traced state — instead of the PR-5
``(B, n_pos)`` slab.  Admit/retire allocate and free fixed-size pages
(``serve/paging.py``), so a short request holds only the pages its own
length needs and live concurrency scales with TOTAL POOLED TOKENS, not
slab width: ``max_slots`` can exceed ``pool_tokens / n_pos`` by far
when traffic skews short.  On top of the pool:

- **prefix caching** (``serve/prefix.py``, env
  ``BIGDL_SERVE_PREFIX_CACHE``): a retiring request donates the full
  pages inside its seed to a token-hash chain cache; a new request
  whose seed matches maps those pages read-only into its own table and
  starts at the (page-aligned) divergence point, skipping that much
  prefill.  Hits/misses and reused pages ride the metrics registry.
- **int8 KV pages** (env ``BIGDL_SERVE_KV_QUANT``, docs/serving.md
  "Quantized serving"): the pools store int8 with per-page-row,
  per-head scales in parallel ``(layers, n_pages, page_size, H)``
  traced arrays (``quant/kv.py``) — the scatter quantizes, the
  page-gathered attention view dequantizes, and because scales are
  pool-indexed like the values, prefix page donation ships them with
  the pages.  ~3-4x pooled tokens at equal HBM (scales included),
  which is live concurrency; greedy output may drift from the fp-KV
  stream within
  the declared budget (``bigdl_tpu.quant.KV_TOKEN_DRIFT_BUDGET``),
  while speculative decode stays EXACTLY identical to the
  non-speculative quantized stream for every k.
- **self-speculative decode** (env ``BIGDL_SERVE_SPEC_K``): the model
  drafts ``k`` tokens per step with a SHALLOW pass over its own first
  ``draft_layers`` blocks (same weights — no second model), then ONE
  batched verify pass over the ``k+1``-token window accepts the longest
  prefix whose drafted tokens match the full model's greedy argmax.
  Committed tokens are exactly the non-speculative greedy stream for
  every ``k`` (the acceptance rule only ever commits argmax-consistent
  tokens), and seed consumption rides the same window — chunked
  prefill for free.  The draft+verify pair is ONE fused program with a
  fixed ``k+1`` window, pre-warmed through the shared executable cache
  at construction, so acceptance-length variance never compiles.

**Sampled decode on the fast path** (``serve/sampling.py``,
docs/serving.md "Sampled decode"): :meth:`ContinuousDecoder.submit`
takes per-request :class:`~bigdl_tpu.serve.sampling.SamplingParams`
(temperature / top-k / top-p / seed / stop sequences / max_tokens)
carried as per-slot TRACED vectors — float temps, int ks, packed stop
buffers and a ``(B, 2)`` per-slot PRNG-key array ride the step program
as data, so a batch mixing greedy and any number of distinct sampling
configs runs the SAME compiled step with zero cold compiles.  Greedy is
the ``temperature == 0`` branch of a ``jnp.where`` whose selected lane
is exactly the historical argmax — greedy streams stay byte-identical
to the sampling-free decoder.  Draw keys are
``fold_in(request_key, DRAW_TAGS * gen_index + tag)`` — a pure function
of the request seed and generated-token index, never of slot, batch mix
or prefix-hit start position — so every sampled request replays
bit-exactly (``tools/request_replay.py``).  Under speculative decode
the argmax prefix-acceptance generalizes to the Leviathan lossless
accept/reject rule (accept draft ``x`` with prob ``min(1, p(x)/q(x))``,
resample the residual on rejection), so spec keeps its amortization at
temperature > 0 while committing EXACTLY the non-speculative sampling
distribution.  Requests with stop sequences retire early at the first
sync boundary after a device-side match — pages and the slot free
immediately instead of burning steps to ``max_tokens``
(``decode_stop_retired_total`` / ``decode_steps_saved_total``).

**Tensor-parallel serving** (``mesh=``): a model whose KV pool + weights
outgrow one chip's HBM serves by sharding the decode step over the
mesh's ``model`` axis (``parallel/mesh.hybrid_mesh``) with
``jax.shard_map`` — Megatron-style: attention heads and the
FFN hidden dim split across shards (wq/wk/wv columns + the KV pool's
head dim; lin1 rows), each branch's output projection psum-merges once,
and everything else (embeddings, LayerNorms, the LM head) replicates.
The per-head math is untouched, so TP decode is token-identical to the
single-device driver — the parity contract ``tests/test_serve_cluster.py``
asserts.  The step/admit/retire programs are warmed at construction
through the shared executable cache (``serve/xcache.py``), so admission
under TP stays compile-free exactly like the single-chip path.
"""
from __future__ import annotations

import itertools
import logging
import os
import time
import weakref
from collections import deque

import numpy as np

from bigdl_tpu.obs import recorder as obs_recorder
from bigdl_tpu.serve import sampling as smp
from bigdl_tpu.serve.paging import PagePool, RequestTooLongError
from bigdl_tpu.serve.prefix import PrefixCache, chain_keys
from bigdl_tpu.serve.streaming import StreamFuture, TokenDelivery

logger = logging.getLogger("bigdl_tpu.serve")

_DECODER_SEQ = itertools.count()

ENV_SYNC = "BIGDL_SERVE_SYNC"
DEFAULT_SYNC = 8
ENV_PAGED = "BIGDL_SERVE_PAGED"
ENV_PAGE_SIZE = "BIGDL_SERVE_PAGE_SIZE"
DEFAULT_PAGE_SIZE = 16
ENV_PAGES = "BIGDL_SERVE_PAGES"
ENV_PREFIX = "BIGDL_SERVE_PREFIX_CACHE"
ENV_SPEC_K = "BIGDL_SERVE_SPEC_K"
ENV_STOP_SEQS = "BIGDL_SERVE_MAX_STOP_SEQS"
DEFAULT_STOP_SEQS = 2
ENV_STOP_LEN = "BIGDL_SERVE_MAX_STOP_LEN"
DEFAULT_STOP_LEN = 8


def _env_int(name, default):
    try:
        return int(os.environ.get(name, default))
    except ValueError:
        return default


def sync_interval_default() -> int:
    return max(1, _env_int(ENV_SYNC, DEFAULT_SYNC))


def _decoder_gc_cleanup(reg, name, delivery_box):
    """weakref.finalize target for decoders nobody closes: stop the
    lazily created delivery thread (else one blocked daemon thread
    leaks per GC'd streaming decoder) and drop the registry series."""
    for d in delivery_box:
        try:
            d.close(timeout=2.0)
        except Exception:  # pragma: no cover - teardown
            pass
    reg.drop_series(decoder=name)


def _tp_weight_specs(handles, ax: str):
    """PartitionSpec tree mirroring the decode weight pytree for
    Megatron head/hidden sharding over mesh axis ``ax``:

    - attention: wq/wk/wv split on their OUTPUT columns (head-major, so
      a shard holds whole heads) with the matching bias slices; wo
      splits on its input rows; bo replicates (added once, post-psum);
    - FFN: lin1 (hidden, d) splits hidden rows + bias, lin2 (d, hidden)
      splits hidden columns, its bias replicates;
    - embeddings, LayerNorms and the LM head replicate.
    """
    import jax
    from jax.sharding import PartitionSpec as P

    def rep(tree):
        return jax.tree_util.tree_map(lambda _: P(), tree)

    attn = {"wq": P(None, ax), "wk": P(None, ax), "wv": P(None, ax),
            "bq": P(ax), "bk": P(ax), "bv": P(ax),
            "wo": P(ax, None), "bo": P()}
    blocks = []
    for (ln1, m, ln2, lin1, lin2) in handles.blocks:
        if set(m) != set(attn):
            raise ValueError(
                f"attention param keys {sorted(m)} diverged from the TP "
                f"sharding map {sorted(attn)} — update _tp_weight_specs")
        blocks.append((rep(ln1), dict(attn), rep(ln2),
                       {"weight": P(ax, None), "bias": P(ax)},
                       {"weight": P(None, ax), "bias": P()}))
    return {"emb": rep(handles.emb), "blocks": blocks,
            "ln_f": rep(handles.ln_f), "head": rep(handles.head)}


def _pages_needed(steps: int, page_size: int) -> int:
    """Pages a request's full lifetime reserves: ``ceil(steps /
    page_size)``, and nothing more.  The ONE authoritative spot for the
    reservation math (``submit()``'s too-long check and
    ``_try_admit_paged``'s allocation share it) so the two can never
    drift.  In particular speculative decode adds NO page headroom: the
    (k+1)-window's writes past a slot's capacity are valid-gated out
    (``spec_step_body``), so a seed + budget that exactly fills its
    last page admits without a speculative extra page — pinned at the
    boundary by ``tests/test_paged_attention.py``."""
    return -(-steps // page_size)


class _DecodeReq:
    __slots__ = ("seed", "n_words", "future", "slot", "steps_needed",
                 "steps_run", "start_pos", "pages", "rid", "trace",
                 "t_submit", "t_admit", "first_ts", "last_ts",
                 "streamed", "timeline", "params", "stop_retired")

    def __init__(self, seed, n_words, trace=None, params=None):
        self.seed = [int(t) for t in seed]
        self.n_words = int(n_words)
        self.params = params if params is not None else smp.GREEDY
        self.stop_retired = False    # retired early on a stop match
        self.future = StreamFuture()
        self.slot = None
        # positions fed through = n_seed + n_words - 1 (lm_decode's n_pos)
        self.steps_needed = len(self.seed) + self.n_words - 1
        self.steps_run = 0
        self.start_pos = 0       # > 0 on a prefix-cache hit
        self.pages = []          # pool page ids, logical order (paged)
        # per-request token timeline (streaming telemetry)
        self.rid = 0
        self.trace = trace       # obs.trace.Trace for sampled requests
        self.t_submit = time.perf_counter()
        self.t_admit = None      # slot admission boundary
        self.first_ts = None     # first-token boundary
        self.last_ts = None      # last boundary that delivered tokens
        self.streamed = 0        # generated tokens delivered so far
        self.timeline = []       # [(perf_counter ts, n new tokens)]


class ContinuousDecoder:
    """Continuous-batching decoder for one ``TransformerLM``.

    ``max_slots`` is the device batch width B; ``n_pos`` the per-request
    position capacity — a request needs ``len(seed) + n_words - 1 <=
    n_pos``, and one that does not fit fails ITS OWN future with
    :class:`RequestTooLongError` at submit time.  :meth:`submit` queues
    a request (future of the full token row, seed included, matching
    ``lm_decode``'s return); :meth:`run` drives admitted slots until
    queue and slots drain.

    ``paged`` (default from ``BIGDL_SERVE_PAGED``, on) stores KV in a
    block-paged pool of ``n_pages`` × ``page_size`` tokens instead of a
    ``(B, n_pos)`` slab; ``n_pages`` defaults to the slab-equivalent
    ``ceil(n_pos / page_size) * max_slots``.  ``prefix_cache`` enables
    token-hash prefix page reuse, ``spec_k`` > 0 self-speculative
    decode with a ``draft_layers``-deep draft pass (default: half the
    blocks), and ``kv_quant="int8"`` (default from
    ``BIGDL_SERVE_KV_QUANT``) int8 KV pages with per-page-row scales —
    all paged-only.

    ``host_tier`` attaches a host-RAM KV tier
    (:class:`~bigdl_tpu.serve.kvtier.HostKVTier`): prefix pages evicted
    under allocation pressure spill D2H instead of dying, and an
    admission whose chain walk runs past the device cache re-admits
    matching tier pages H2D as prefix hits.  Defaults from
    ``BIGDL_SERVE_KV_HOST_MB`` (> 0 builds an owned tier; requires the
    paged pool with the prefix cache).  ``prefill_adopt`` pre-compiles
    the page re-admit program so :meth:`adopt_pages` can accept KV
    pages shipped by a prefill replica (``serve/fleet.py``).
    """

    def __init__(self, model, max_slots: int = 4, n_pos: int = 64,
                 sync_interval: int | None = None, mesh=None,
                 paged: bool | None = None, page_size: int | None = None,
                 n_pages: int | None = None,
                 prefix_cache: bool | None = None,
                 spec_k: int | None = None,
                 draft_layers: int | None = None,
                 kv_quant: str | None = None,
                 host_tier=None, prefill_adopt: bool = False,
                 max_stop_seqs: int | None = None,
                 max_stop_len: int | None = None,
                 name: str | None = None, device=None):
        import jax
        import jax.numpy as jnp

        if device is not None and mesh is not None:
            raise ValueError("a decoder runs on one device= or shards "
                             "over a mesh=, not both")
        #: the jax device this decoder's state is committed to (None =
        #: jax's default device, or the mesh under tensor parallelism);
        #: every step program follows its carried state there
        self.device = device

        from bigdl_tpu.models.transformer import (_lm_forward_one,
                                                  _lm_forward_window,
                                                  _lm_handles)
        from bigdl_tpu.optim.local_optimizer import _model_fingerprint
        from bigdl_tpu.quant import kv as kvq
        from bigdl_tpu.quant import kv_mode_default, normalize_mode
        from bigdl_tpu.serve import xcache

        self.model = model
        self.B = int(max_slots)
        self.n_pos = int(n_pos)
        self.sync_interval = (sync_interval_default()
                              if sync_interval is None
                              else max(1, int(sync_interval)))
        self.paged = bool(_env_int(ENV_PAGED, 1)) if paged is None \
            else bool(paged)
        self.page_size = max(1, _env_int(ENV_PAGE_SIZE, DEFAULT_PAGE_SIZE)
                             if page_size is None else int(page_size))
        self.pages_per_slot = -(-self.n_pos // self.page_size)
        if n_pages is None:
            n_pages = _env_int(ENV_PAGES, 0) \
                or self.pages_per_slot * self.B
        self.spec_k = max(0, _env_int(ENV_SPEC_K, 0) if spec_k is None
                          else int(spec_k))
        # packed stop-sequence capacity: every slot carries an
        # (NS, LS) right-aligned token buffer; a submit whose stop list
        # exceeds either dim fails its own future
        self.max_stop_seqs = max(1, _env_int(ENV_STOP_SEQS,
                                             DEFAULT_STOP_SEQS)
                                 if max_stop_seqs is None
                                 else int(max_stop_seqs))
        self.max_stop_len = max(1, _env_int(ENV_STOP_LEN,
                                            DEFAULT_STOP_LEN)
                                if max_stop_len is None
                                else int(max_stop_len))
        use_prefix = bool(_env_int(ENV_PREFIX, 1)) \
            if prefix_cache is None else bool(prefix_cache)
        if kv_quant is None:
            # the env opts the PAGED pool in; a slab decoder (A/B
            # baseline) under the same env quietly serves fp — only an
            # explicit kv_quant= on a slab decoder is a hard error
            self.kv_quant = kv_mode_default() if self.paged else "off"
        else:
            self.kv_quant = normalize_mode(kv_quant, kvq.ON_MODES,
                                           "kv_quant")
        if not self.paged and (self.spec_k or prefix_cache
                               or self.kv_quant != "off"):
            raise ValueError("speculative decode, prefix caching and "
                             "KV quantization need the paged KV pool "
                             "(paged=True)")

        handles = _lm_handles(model)
        self._vocab = handles.vocab
        B, n_pos, ps = self.B, self.n_pos, self.page_size
        L, H, hd = handles.n_layers, handles.n_heads, handles.hd
        self.draft_layers = (max(1, L // 2) if draft_layers is None
                             else min(L, max(1, int(draft_layers))))
        Ld, k = self.draft_layers, self.spec_k
        # host-RAM KV tier: explicit instance, or owned-from-env when
        # BIGDL_SERVE_KV_HOST_MB > 0 (spill rides the prefix cache's
        # on_evict hook, so the tier needs paged + prefix)
        from bigdl_tpu.serve import kvtier
        self._tier_owned = False
        if host_tier is None and self.paged and use_prefix:
            mb = kvtier.host_mb_default()
            if mb > 0:
                host_tier = kvtier.HostKVTier(mb)
                self._tier_owned = True
        if host_tier is not None and not (self.paged and use_prefix):
            raise ValueError("the host KV tier spills evicted prefix "
                             "pages — it needs the paged pool with the "
                             "prefix cache enabled")
        self._tier = host_tier
        if self.paged:
            self._pool = PagePool(int(n_pages), ps)
            on_evict = self._spill_page if self._tier is not None else None
            self._prefix = (PrefixCache(self._pool, on_evict=on_evict)
                            if use_prefix else None)
            n_view = self.pages_per_slot * ps
        else:
            self._pool = self._prefix = None
            n_view = n_pos
        self._n_view = n_view
        pe = jnp.asarray(model.modules[1].table(n_view))

        self.mesh = mesh
        self.tp = (int(mesh.shape["model"])
                   if mesh is not None and "model" in mesh.axis_names
                   else 1)
        fp = _model_fingerprint(model)

        # ---- step bodies --------------------------------------------------
        # ``caches`` is the KV-storage pytree threaded through every
        # program: (k, v) pools, or (k, v, kscale, vscale) under int8
        # KV quantization (the scale arrays are traced state exactly
        # like the pools — serve/decode carries them, quant/kv.py and
        # _lm_forward_window do the math)
        #
        # Per-slot sampling state rides every body as traced vectors:
        # ``temp``/``topk``/``topp`` (B,), ``keys`` (B, 2) uint32,
        # ``stop_buf`` (B, NS, LS) right-aligned + ``stop_len`` (B, NS),
        # and ``finished`` (B,) — a stop-matched row freezes (drops out
        # of ``live``) until the boundary retires it.
        NS, LS = self.max_stop_seqs, self.max_stop_len

        def _next_token(logp, pos, seed_len, temp, topk, topp, keys):
            """The committed token for the write position ``pos``:
            greedy rows take the UNCHANGED argmax (the byte-identity
            lane), sampled rows draw from the filtered distribution
            under the request-keyed stream for this generated index."""
            greedy_tok = jnp.argmax(logp, axis=-1).astype(jnp.int32)
            gidx = jnp.maximum(pos - (seed_len - 1), 0)
            sub = smp.fold_in_rows(
                keys, smp.DRAW_TAGS * gidx + smp.TAG_MAIN)
            samp = smp.sample_tokens(logp, sub, temp, topk,
                                     topp).astype(jnp.int32)
            return jnp.where(temp > 0, samp, greedy_tok)

        def _stop_hit(gen, ends, seed_len, stop_buf, stop_len):
            """Device-side stop-sequence match: does any of the slot's
            stop sequences end EXACTLY at write position ``ends[b, s]``?
            ``ends`` is (B, S); returns (B, S) bool.  The window looks
            backward only, must lie entirely inside the OUTPUT region
            (write positions >= seed_len - 1 — seeds never match), and
            right-aligned buffers make the comparison one fixed-shape
            equality regardless of per-sequence length."""
            rows = jnp.arange(B)
            idx = (ends[:, :, None] - (LS - 1)
                   + jnp.arange(LS)[None, None, :])           # (B,S,LS)
            tok = gen[rows[:, None, None], jnp.clip(idx, 0, n_view - 1)]
            out_ok = idx >= (seed_len - 1)[:, None, None]
            eq = (tok[:, :, None, :] == stop_buf[:, None, :, :]
                  ) & out_ok[:, :, None, :]                 # (B,S,NS,LS)
            need = (jnp.arange(LS)[None, None, None, :]
                    >= (LS - stop_len)[:, None, :, None])
            hit = jnp.where(need, eq, True).all(axis=-1)      # (B,S,NS)
            return ((stop_len > 0)[:, None, :] & hit).any(axis=-1)

        def slab_step_body(local_handles, caches, pos, prev, active,
                           seeds, seed_len, gen, temp, topk, topp,
                           keys, stop_buf, stop_len, finished,
                           tp_axis=None):
            rows = jnp.arange(B)
            live = active & ~finished & (pos < n_pos)
            wp = jnp.clip(pos, 0, n_pos - 1)
            tok = jnp.where(pos < seed_len, seeds[rows, wp], prev)
            logp, caches = _lm_forward_one(
                tok.astype(jnp.int32), wp, caches, local_handles,
                n_pos, pe, tp_axis=tp_axis)
            nxt = _next_token(logp, pos, seed_len, temp, topk, topp,
                              keys)
            # parked/finished slots must not advance or write tokens
            gen = gen.at[rows, wp].set(jnp.where(live, nxt, gen[rows, wp]))
            prev = jnp.where(live, nxt, prev)
            pos = jnp.where(live, pos + 1, pos)
            hit = _stop_hit(gen, wp[:, None], seed_len, stop_buf,
                            stop_len)[:, 0]
            finished = finished | (live & hit)
            return caches, pos, prev, gen, finished

        def paged_step_body(local_handles, caches, ptab, pos, prev,
                            active, seeds, seed_len, cap, gen, temp,
                            topk, topp, keys, stop_buf, stop_len,
                            finished, tp_axis=None, view_pages=None):
            rows = jnp.arange(B)
            live = active & ~finished & (pos < cap)
            wp = jnp.clip(pos, 0, cap - 1)
            tok = jnp.where(pos < seed_len, seeds[rows, wp], prev)
            logp, caches = _lm_forward_one(
                tok.astype(jnp.int32), wp, caches, local_handles,
                n_view, pe, tp_axis=tp_axis, pages=(ptab, ps), valid=live,
                view_pages=view_pages)
            nxt = _next_token(logp, pos, seed_len, temp, topk, topp,
                              keys)
            # frozen rows route their token write out of bounds (dropped)
            gen = gen.at[rows, jnp.where(live, wp, n_view)].set(nxt)
            prev = jnp.where(live, nxt, prev)
            pos = jnp.where(live, pos + 1, pos)
            hit = _stop_hit(gen, wp[:, None], seed_len, stop_buf,
                            stop_len)[:, 0]
            finished = finished | (live & hit)
            return caches, pos, prev, gen, finished

        def spec_step_body(local_full, local_draft, caches, ptab,
                           pos, prev, active, seeds, seed_len, cap, gen,
                           temp, topk, topp, keys, stop_buf, stop_len,
                           finished, acc_hist, tp_axis=None,
                           view_pages=None):
            rows = jnp.arange(B)
            live = active & ~finished & (pos < cap)
            sampled = temp > 0                   # (B,) sampled-row lane
            # -- draft k tokens with the shallow pass (window position 0
            # is the normal step token; seed positions stay forced).
            # Sampled rows DRAW their draft from the filtered shallow
            # distribution (q must be the actual proposal for the
            # accept/reject rule below); greedy rows keep the argmax.
            wp0 = jnp.clip(pos, 0, cap - 1)
            t0 = jnp.where(pos < seed_len,
                           seeds[rows, wp0], prev).astype(jnp.int32)
            toks, qs, d_tok, d_pos = [t0], [], t0, pos
            for _ in range(k):
                d_valid = live & (d_pos < cap)
                dlogp, caches = _lm_forward_one(
                    d_tok, jnp.clip(d_pos, 0, cap - 1), caches,
                    local_draft, n_view, pe, tp_axis=tp_axis,
                    pages=(ptab, ps), valid=d_valid,
                    view_pages=view_pages)
                d_arg = jnp.argmax(dlogp, axis=-1).astype(jnp.int32)
                # proposal draw keyed by the WRITE position of this
                # drafted token (= d_pos before the increment)
                lq = smp.filter_logits(dlogp, temp, topk, topp)
                gq = jnp.maximum(d_pos - (seed_len - 1), 0)
                dsub = smp.fold_in_rows(
                    keys, smp.DRAW_TAGS * gq + smp.TAG_DRAFT)
                d_smp = jax.vmap(jax.random.categorical)(
                    dsub, lq).astype(jnp.int32)
                qs.append(jax.nn.softmax(lq, axis=-1))
                d_pos = d_pos + 1
                d_draft = jnp.where(sampled, d_smp, d_arg)
                d_tok = jnp.where(
                    d_pos < seed_len,
                    seeds[rows, jnp.clip(d_pos, 0, n_view - 1)],
                    d_draft)
                toks.append(d_tok)
            W = jnp.stack(toks, axis=1)                     # (B, k+1)
            qs = jnp.stack(qs, axis=1)                      # (B, k, V)
            p_idx = pos[:, None] + jnp.arange(k + 1)[None, :]
            valid = live[:, None] & (p_idx < cap[:, None])
            wp = jnp.clip(p_idx, 0, n_view - 1)
            # -- ONE batched verify pass with the full model (overwrites
            # the draft's shallow K/V at the same positions)
            logp, caches = _lm_forward_window(
                W, wp, caches, local_full, pe, (ptab, ps),
                valid=valid, tp_axis=tp_axis, view_pages=view_pages)
            g = jnp.argmax(logp, axis=-1).astype(jnp.int32)  # (B, k+1)
            # -- greedy lane (byte-identity): drafted token j+1 survives
            # iff it equals the verify argmax at position j (seed-forced
            # positions always survive), so the committed stream is
            # EXACTLY the non-speculative greedy stream
            forced = p_idx[:, 1:] < seed_len[:, None]
            # valid-masked so a chance match at a garbage position past
            # the slot's page capacity cannot extend the run (it could
            # never commit — consumed caps at cap - pos — but it would
            # inflate the acceptance telemetry)
            match_g = valid[:, 1:] & (forced | (W[:, 1:] == g[:, :k]))
            # -- sampled lane (Leviathan lossless accept/reject): the
            # target distribution p at every window slot, filtered with
            # the SAME per-row params as the draft's q
            pp = jax.nn.softmax(
                smp.filter_logits(logp, temp, topk, topp), axis=-1)
            ga = jnp.maximum(p_idx[:, :k] - (seed_len - 1)[:, None], 0)
            asub = smp.fold_in_rows(
                jnp.broadcast_to(keys[:, None, :],
                                 (B, k, 2)).reshape(B * k, 2),
                (smp.DRAW_TAGS * ga + smp.TAG_ACCEPT).reshape(B * k))
            u = smp.uniform_rows(asub).reshape(B, k)
            p_x = jnp.take_along_axis(pp[:, :k], W[:, 1:, None],
                                      axis=-1)[..., 0]
            q_x = jnp.take_along_axis(qs, W[:, 1:, None],
                                      axis=-1)[..., 0]
            # division-free min(1, p/q) accept: u * q(x) < p(x)
            match_s = valid[:, 1:] & (forced | (u * q_x < p_x))
            match = jnp.where(sampled[:, None], match_s, match_g)
            acc = jnp.cumprod(match.astype(jnp.int32), axis=1).sum(axis=1)
            consumed = jnp.where(live,
                                 jnp.minimum(acc + 1, cap - pos), 0)
            commit = jnp.arange(k + 1)[None, :] < consumed[:, None]
            # committed tokens: greedy rows commit the verify argmax;
            # sampled rows commit their accepted drafts, with the slot
            # at ``acc`` replaced by the residual draw (rejection) or —
            # at slot k with q = 0 — a fresh draw from p (the bonus
            # token), which keeps the committed marginal exactly p
            qa = jnp.concatenate(
                [qs, jnp.zeros_like(qs[:, :1])],
                axis=1)[rows, jnp.clip(acc, 0, k)]
            pa = pp[rows, jnp.clip(acc, 0, k)]
            gfix = jnp.maximum(pos + acc - (seed_len - 1), 0)
            fsub = smp.fold_in_rows(
                keys, smp.DRAW_TAGS * gfix + smp.TAG_FIX)
            c = jax.vmap(jax.random.categorical)(
                fsub, jnp.log(smp.spec_residual(pa, qa))
            ).astype(jnp.int32)
            S = jnp.concatenate([W[:, 1:], jnp.zeros((B, 1), jnp.int32)],
                                axis=1)
            S = jnp.where(jnp.arange(k + 1)[None, :] == acc[:, None],
                          c[:, None], S)
            C = jnp.where(sampled[:, None], S, g)
            gen = gen.at[rows[:, None],
                         jnp.where(commit, wp, n_view)].set(C)
            # -- stop sequences: scan the freshly committed window slots
            # (backward-looking matches only read already-written gen);
            # the first matching slot truncates the commit run and
            # freezes the row for boundary retirement
            hit = _stop_hit(gen, wp, seed_len, stop_buf,
                            stop_len) & commit
            any_hit = hit.any(axis=1)
            jstar = jnp.argmax(hit, axis=1)
            consumed = jnp.where(any_hit,
                                 jnp.minimum(consumed, jstar + 1),
                                 consumed)
            finished = finished | (any_hit & live)
            prev = jnp.where(consumed > 0,
                             C[rows, jnp.clip(consumed - 1, 0, k)], prev)
            # acceptance telemetry covers PURE decode windows only —
            # every drafted position past the seed.  Seed-forced
            # (chunked-prefill) windows "accept" by construction and
            # would skew the histogram toward k no matter how bad the
            # draft actually is.
            rec = live & (p_idx[:, 1] >= seed_len)
            pos = pos + consumed
            acc_hist = acc_hist + jnp.where(
                rec[:, None],
                jax.nn.one_hot(acc, k + 1, dtype=jnp.int32), 0
            ).sum(axis=0)
            return caches, pos, prev, gen, finished, acc_hist

        def _draft_of(local):
            return local._replace(blocks=local.blocks[:Ld],
                                  block_eps=handles.block_eps[:Ld],
                                  n_layers=Ld)

        # ---- program assembly (single-chip or TP shard_map) ---------------
        pool_shape = ((L, self._pool.n_pages, ps, H, hd) if self.paged
                      else (L, B, n_pos, H, hd))
        #: arrays in the KV-storage pytree: (k, v) pools, plus the two
        #: per-page-row scale arrays under int8 KV quantization
        n_caches = 4 if self.kv_quant == "int8" else 2
        kind = "spec" if k else ("paged" if self.paged else "slab")
        key_tail = ((ps, self.pages_per_slot, self._pool.n_pages, k, Ld,
                     self.kv_quant)
                    if self.paged else ())
        if (NS, LS) != (DEFAULT_STOP_SEQS, DEFAULT_STOP_LEN):
            # non-default stop capacity changes the packed-buffer shapes
            # every program takes; keep the default fn_key unchanged
            key_tail = key_tail + ("stop%dx%d" % (NS, LS),)

        if self.tp > 1:
            # Megatron head/hidden sharding over the mesh's "model"
            # axis: the step body runs inside shard_map on LOCAL weight
            # shards (passed as an argument pytree — constants cannot
            # shard), with the KV pools split on their head dim.
            if H % self.tp:
                raise ValueError(
                    f"tensor parallelism {self.tp} must divide "
                    f"n_heads={H}")
            for li, (_, _, _, lin1, _) in enumerate(handles.blocks):
                hidden = int(lin1["weight"].shape[0])
                if hidden % self.tp:
                    raise ValueError(
                        f"tensor parallelism {self.tp} must divide the "
                        f"FFN hidden dim ({hidden}, block {li})")
            from jax.sharding import NamedSharding
            from jax.sharding import PartitionSpec as P

            ax = "model"
            wspec = _tp_weight_specs(handles, ax)
            # weights pinned to the mesh ONCE, pre-sharded per the spec:
            # passing host arrays each step would re-ship the whole
            # model H2D per decode step
            self._W = jax.device_put(
                {"emb": handles.emb, "blocks": handles.blocks,
                 "ln_f": handles.ln_f, "head": handles.head},
                jax.tree_util.tree_map(
                    lambda sp: NamedSharding(mesh, sp), wspec))
            # head dim: the pools shard their H axis (dim 3 of both the
            # 5-d value pools AND the 4-d per-page-row scale arrays —
            # scales are per-head exactly so they shard with zero
            # cross-shard traffic, quant/kv.py)
            cache = P(None, None, None, ax)
            cspec = (cache,) * n_caches
            rep = P()
            H_local = H // self.tp

            def _local(W):
                return handles._replace(
                    mods=None, emb=W["emb"], blocks=W["blocks"],
                    ln_f=W["ln_f"], head=W["head"], n_heads=H_local)

        else:
            self._W = None

        # ---- step-program cache -------------------------------------------
        # Paged decoders hold ONE step program per (view-horizon bucket,
        # attention-kernel flag state) instead of a single program:
        #
        # * View-horizon buckets (the pure-XLA micro-opt): the gathered
        #   attention view only needs the pages the CURRENT live set can
        #   reach (max in-use ptab run), not every reserved page — but
        #   the gather width is a static shape, so the horizon is
        #   bucketed to a short pow2 ladder ending at the full
        #   reservation and each bucket gets its own program.  All
        #   buckets are warmed at construction (zero-cold-compile).
        # * Attention-kernel flag state: `transformer._PALLAS_PAGED_ATTN`
        #   / `_PALLAS_SPEC_VERIFY` are read at TRACE time, so a flip on
        #   a warm decoder must select a DIFFERENT program — flag state
        #   rides the fn_key and programs for non-default states build
        #   lazily at the first boundary that needs them (exactly the
        #   expected new compiles once, zero on later waves — pinned by
        #   the jit-trap audit in tests/test_paged_attention.py).
        if self.paged:
            # two-point ladder {1, full}: the single-page bucket owns
            # the common low-latency case (short live set on a big
            # reservation) and every bucket costs one warm step compile
            # per decoder, so the ladder stays deliberately short
            self._view_buckets = sorted({1, self.pages_per_slot})
        else:
            self._view_buckets = [None]

        base_key = ("decode_step_" + kind, fp, B, n_pos) + key_tail

        def _build_step(view_w, flag_state):
            key = base_key
            if view_w is not None and view_w != self.pages_per_slot:
                key = key + ("view%d" % view_w,)
            if any(f != "False" for f in flag_state):
                key = key + ("attn:" + "/".join(flag_state),)
            if self.tp > 1:
                if k:
                    def step_tp(W, *st):
                        local = _local(W)
                        return spec_step_body(local, _draft_of(local),
                                              *st, tp_axis=ax,
                                              view_pages=view_w)
                    n_rep_in, n_rep_out = 16, 5
                elif self.paged:
                    def step_tp(W, *st):
                        return paged_step_body(_local(W), *st,
                                               tp_axis=ax,
                                               view_pages=view_w)
                    n_rep_in, n_rep_out = 15, 4
                else:
                    def step_tp(W, *st):
                        return slab_step_body(_local(W), *st, tp_axis=ax)
                    n_rep_in, n_rep_out = 13, 4
                # an INTERPRETED attention kernel cannot pass shard_map's
                # vma check: the Pallas interpreter re-binds the kernel's
                # primitives on the shard's varying operands without the
                # pvary casts tracing inserts.  The compiled (Mosaic)
                # kernel keeps the check — its out_shape carries the
                # operands' vma (ops/pallas_kernels.py _out_struct).
                from bigdl_tpu.ops.pallas_kernels import _interpreted
                interpreted = any(f != "False" and _interpreted(f)
                                  for f in flag_state)
                sharded = jax.shard_map(
                    step_tp, mesh=mesh,
                    in_specs=(wspec, cspec) + (rep,) * n_rep_in,
                    out_specs=(cspec,) + (rep,) * n_rep_out,
                    check_vma=not interpreted)
                return xcache.tracked_jit(
                    sharded, key + ("tp%d" % self.tp,), mesh=mesh)
            if k:
                def step(*st):
                    return spec_step_body(handles, _draft_of(handles),
                                          *st, view_pages=view_w)
            elif self.paged:
                def step(*st):
                    return paged_step_body(handles, *st,
                                           view_pages=view_w)
            else:
                def step(*st):
                    return slab_step_body(handles, *st)
            return xcache.tracked_jit(step, key)

        self._build_step = _build_step
        self._step_programs = {}
        # the full-reservation default-flag program: the flops-ledger
        # anchor for decode_model_flops_util, and the widest warm step
        self._step = self._step_program(self._view_buckets[-1])

        def _admit_sampling(temp, topk, topp, keys, stop_buf, stop_len,
                            finished, slot, t_v, k_v, p_v, key_row,
                            sb_row, sl_row):
            """The per-slot sampling-state half of admission (shared by
            both layouts): load the request's params/key/stop rows and
            clear the stop-finished flag."""
            temp = temp.at[slot].set(t_v)
            topk = topk.at[slot].set(k_v)
            topp = topp.at[slot].set(p_v)
            keys = keys.at[slot].set(key_row)
            stop_buf = stop_buf.at[slot].set(sb_row)
            stop_len = stop_len.at[slot].set(sl_row)
            finished = finished.at[slot].set(False)
            return temp, topk, topp, keys, stop_buf, stop_len, finished

        if self.paged:
            def admit(ptab, pos, active, seeds, seed_len, cap, gen,
                      temp, topk, topp, keys, stop_buf, stop_len,
                      finished, slot, ptab_row, start, seed_row, s_len,
                      capv, t_v, k_v, p_v, key_row, sb_row, sl_row):
                ptab = ptab.at[slot].set(ptab_row)
                pos = pos.at[slot].set(start)
                active = active.at[slot].set(True)
                seeds = seeds.at[slot].set(seed_row)
                seed_len = seed_len.at[slot].set(s_len)
                cap = cap.at[slot].set(capv)
                gen = gen.at[slot].set(0)
                return (ptab, pos, active, seeds, seed_len, cap, gen
                        ) + _admit_sampling(
                            temp, topk, topp, keys, stop_buf, stop_len,
                            finished, slot, t_v, k_v, p_v, key_row,
                            sb_row, sl_row)

            def retire(ptab, active, slot):
                # frozen rows' K/V writes are valid-gated out, so the
                # table reset is hygiene: freed pages stop being
                # gathered into this slot's (masked) attention view
                return ptab.at[slot].set(0), active.at[slot].set(False)
        else:
            def admit(caches, pos, active, seeds, seed_len, gen,
                      temp, topk, topp, keys, stop_buf, stop_len,
                      finished, slot, seed_row, s_len, t_v, k_v, p_v,
                      key_row, sb_row, sl_row):
                kc, vc = caches
                kc = kc.at[:, slot].set(0.0)
                vc = vc.at[:, slot].set(0.0)
                pos = pos.at[slot].set(0)
                active = active.at[slot].set(True)
                seeds = seeds.at[slot].set(seed_row)
                seed_len = seed_len.at[slot].set(s_len)
                gen = gen.at[slot].set(0)
                return ((kc, vc), pos, active, seeds, seed_len, gen
                        ) + _admit_sampling(
                            temp, topk, topp, keys, stop_buf, stop_len,
                            finished, slot, t_v, k_v, p_v, key_row,
                            sb_row, sl_row)

            def retire(active, slot):
                return active.at[slot].set(False)

        if self.tp > 1:
            # admit/retire ride the SAME shard_map layout as the step:
            # mixing plain-jit programs into the carry chain would hand
            # the step differently-placed inputs on some paths and cost
            # a silent recompile per (program, sharding) combination
            cache, rep = P(None, None, None, "model"), P()
            if self.paged:
                admit = jax.shard_map(
                    admit, mesh=mesh, in_specs=(rep,) * 26,
                    out_specs=(rep,) * 14)
                retire = jax.shard_map(
                    retire, mesh=mesh, in_specs=(rep,) * 3,
                    out_specs=(rep, rep))
            else:
                admit = jax.shard_map(
                    admit, mesh=mesh,
                    in_specs=((cache, cache),) + (rep,) * 21,
                    out_specs=((cache, cache),) + (rep,) * 12)
                retire = jax.shard_map(retire, mesh=mesh,
                                       in_specs=(rep, rep),
                                       out_specs=rep)
        self._admit_fn = xcache.tracked_jit(
            admit, ("decode_admit_" + kind, fp, B, n_pos) + key_tail,
            mesh=mesh)
        self._retire_fn = xcache.tracked_jit(
            retire, ("decode_retire_" + kind, fp, B) + key_tail,
            mesh=mesh)

        # page re-admit program (host-tier H2D / shipped-prefill
        # adoption): write one host page payload into pool page ``pid``
        # across every cache array.  ``pid`` is traced, the payload
        # shapes are fixed, so it compiles ONCE at construction and
        # re-admits never cold-compile mid-stream.
        self._readmit_fn = None
        if self.paged and (self._tier is not None or prefill_adopt):
            def readmit(caches, pid, payload):
                return tuple(c.at[:, pid].set(p)
                             for c, p in zip(caches, payload))
            if self.tp > 1:
                cache, rep = P(None, None, None, "model"), P()
                # payload dims mirror a page slice: values (L, ps, H,
                # hd), scales (L, ps, H) — the head dim shards exactly
                # like the pools, so adoption ships zero cross-shard
                pay = tuple(
                    (P(None, None, "model", None) if i < 2
                     else P(None, None, "model"))
                    for i in range(n_caches))
                readmit = jax.shard_map(
                    readmit, mesh=mesh,
                    in_specs=((cache,) * n_caches, rep, pay),
                    out_specs=(cache,) * n_caches)
            self._readmit_fn = xcache.tracked_jit(
                readmit,
                ("decode_readmit_" + kind, fp, B, n_pos) + key_tail,
                mesh=mesh)

        def z(shape, dtype):
            return jnp.zeros(shape, dtype, device=device)

        if self.kv_quant == "int8":
            # int8 pools + per-page-row per-head scale arrays; a fresh
            # page's stale rows are never read before their overwrite
            # (same masked-read argument as the fp pool), so zero-init
            # scales are only ever paired with zero-init values
            sshape = kvq.scale_shape(pool_shape)
            self._caches = (z(pool_shape, jnp.int8),
                            z(pool_shape, jnp.int8),
                            z(sshape, jnp.float32),
                            z(sshape, jnp.float32))
        else:
            self._caches = (z(pool_shape, jnp.float32),
                            z(pool_shape, jnp.float32))
        self._pos = z((B,), jnp.int32)
        self._prev = z((B,), jnp.int32)
        self._active = z((B,), bool)
        self._seeds = z((B, n_view), jnp.int32)
        self._seed_len = z((B,), jnp.int32)
        self._gen = z((B, n_view), jnp.int32)
        # per-slot traced sampling state (zeros = the greedy default:
        # temp 0 selects the argmax lane, stop_len 0 never matches)
        self._temp = z((B,), jnp.float32)
        self._topk = z((B,), jnp.int32)
        self._topp = z((B,), jnp.float32)
        self._keys = z((B, 2), jnp.uint32)
        self._stop_buf = z((B, self.max_stop_seqs, self.max_stop_len),
                           jnp.int32)
        self._stop_len = z((B, self.max_stop_seqs), jnp.int32)
        self._finished = z((B,), bool)
        if self.paged:
            self._ptab = z((B, self.pages_per_slot), jnp.int32)
            # capacity starts at one page so clips/masks stay in range
            # for never-admitted slots; admit sets the real value
            self._cap = jnp.full((B,), ps, jnp.int32, device=device)
        if k:
            self._acc_hist = z((k + 1,), jnp.int32)
            self._acc_seen = np.zeros((k + 1,), np.int64)
            # host-side copy of the acceptance-length counts (warm pass
            # excluded) — stats()/bench read p50 from here without
            # touching the registry
            self._accept_counts = np.zeros((k + 1,), np.int64)

        self._pending: "deque[_DecodeReq]" = deque()
        self._slots: list = [None] * B

        # telemetry: mirrored into the mergeable metrics registry
        # (labelled decoder=<name>) so slot occupancy and throughput
        # show up in the fleet exporter next to the engine numbers
        from bigdl_tpu.obs import metrics as obs_metrics
        # fleet replicas pass an explicit name so per-replica decoder
        # series stay attributable after the child-registry merge
        self.name = name or f"decoder{next(_DECODER_SEQ)}"
        self._flags_cache = None   # decode_flags() memo
        #: optional WeightStore version this decoder serves — set by
        #: whoever snapshotted the weights (a decode replica has no
        #: rollout machinery of its own); the flight recorder notes it
        #: per request so tools/request_replay.py can pin the exact
        #: served weights
        self.weights_version = None
        reg = obs_metrics.get()
        lab = {"decoder": self.name}
        self._m_steps = reg.counter(
            "decode_steps_total", "decode steps driven", **lab)
        self._m_admitted = reg.counter(
            "decode_admitted_total", "requests admitted into slots", **lab)
        self._m_retired = reg.counter(
            "decode_retired_total", "requests retired from slots", **lab)
        self._m_syncs = reg.counter(
            "decode_host_syncs_total", "boundary device->host fetches",
            **lab)
        self._m_slots = reg.gauge(
            "decode_slots_active", "occupied decode slots", **lab)
        self._m_slots_hwm = reg.gauge(
            "decode_slots_hwm", "live-request high-water mark",
            agg="max", **lab)
        #: KV bytes one pooled token costs across all layers (scales
        #: included under int8 KV quant) — the density lever the
        #: quantized pool pulls (docs/observability.md)
        self.kv_bytes_per_token = kvq.bytes_per_token(
            L, H, hd, self.kv_quant)
        reg.gauge("decode_kv_bytes_per_token",
                  "KV bytes per pooled token incl. scales",
                  **lab).set(self.kv_bytes_per_token)
        #: live decode utilization (docs/observability.md "Performance
        #: observatory"): ledger flops of the compiled step program x
        #: step rate over the boundary window / datasheet peak — set
        #: once per sync boundary, never per token
        self._m_util = reg.gauge(
            "decode_model_flops_util",
            "model flops utilization of the decode step over the last "
            "sync-boundary window", agg="max", **lab)
        self._m_toks = reg.gauge(
            "decode_tokens_per_s",
            "committed tokens per second over the last sync-boundary "
            "window", **lab)
        if self.paged:
            self._m_pages = reg.gauge(
                "decode_pages_in_use", "allocated KV pool pages", **lab)
            reg.gauge("decode_pages_total", "KV pool size in pages",
                      **lab).set(self._pool.n_pages)
            self._m_pfx_hit = reg.counter(
                "decode_prefix_hits_total",
                "requests admitted with >=1 cached prefix page", **lab)
            self._m_pfx_miss = reg.counter(
                "decode_prefix_misses_total",
                "requests admitted with no cached prefix page", **lab)
            self._m_pfx_pages = reg.counter(
                "decode_prefix_pages_total",
                "prefill pages served from the prefix cache", **lab)
        if k:
            self._m_accept = reg.histogram(
                "decode_spec_accept_len",
                "accepted draft tokens per speculative window",
                bounds=obs_metrics.SPEC_ACCEPT_BUCKETS, **lab)
        # streaming SLO surface (docs/observability.md "Streaming
        # telemetry"): TTFT on the shared LATENCY_BUCKETS, ITL on the
        # finer ITL_BUCKETS (on-chip inter-token gaps sit well below
        # the 100 µs latency floor) — both fleet-mergeable
        self._m_ttft = reg.histogram(
            "decode_ttft_seconds",
            "submit-to-first-streamed-token latency", **lab)
        self._m_itl = reg.histogram(
            "decode_itl_seconds",
            "inter-token gap of streamed tokens (per-token, averaged "
            "within a boundary)", bounds=obs_metrics.ITL_BUCKETS, **lab)
        self._m_stream_toks = reg.counter(
            "decode_stream_tokens_total",
            "tokens delivered incrementally at sync boundaries", **lab)
        # sampled decode + stop-sequence early retirement
        # (docs/observability.md "Sampled decode")
        self._m_sampled = reg.counter(
            "decode_sampled_total",
            "sampled (temperature > 0) requests admitted", **lab)
        self._m_stop_retired = reg.counter(
            "decode_stop_retired_total",
            "requests retired early on a stop-sequence match", **lab)
        self._m_steps_saved = reg.counter(
            "decode_steps_saved_total",
            "decode step-slots reclaimed by stop-sequence early "
            "retirement", **lab)
        # directly-constructed decoders (the TP-serving entry point)
        # may never see close() — drop the uniquely-labelled series at
        # GC so the process registry cannot grow without bound, and
        # stop the lazily created delivery thread (the box is filled by
        # _ensure_delivery; a finalizer must not reference self)
        self._delivery_box: list = []
        self._drop_series = weakref.finalize(
            self, _decoder_gc_cleanup, reg, self.name,
            self._delivery_box)
        self.steps = 0
        self.host_syncs = 0
        self.admitted = 0
        self.retired = 0
        self.live_hwm = 0
        self.spec_windows = 0
        self.spec_accepted = 0
        self.sampled = 0           # admitted requests with temp > 0
        self.stop_retired = 0      # requests retired on a stop match
        self.steps_saved = 0       # step-slots reclaimed by early retire
        # streaming lifetime aggregates (stats() / emit_decode_event)
        self.streams = 0           # requests that streamed >= 1 token
        self.stream_tokens = 0
        #: DISTINCT sync boundaries that delivered tokens to at least
        #: one stream (per-request boundary counts live on the
        #: `stream` events' timelines)
        self.stream_boundaries = 0
        self._ttft_sum = 0.0
        self._req_seq = itertools.count(1)
        #: lazy dedicated delivery thread — consumer callbacks and
        #: streaming-future resolution run there, never the step loop
        self._delivery = None

        self._warm()

        # cost truth for the utilization gauge: the step program's
        # compile-time ledger capture (its tracked_jit key), plus the
        # KV pool's static HBM tenant entry — both labelled with this
        # decoder's name so close()'s drop_series reclaims them
        from bigdl_tpu.obs import ledger as obs_ledger
        self._step_flops = obs_ledger.get().flops_for(self._step.fn_key)
        # None on the CPU: no utilization gauge there
        self._peak_flops = obs_ledger.device_peak_flops(self.device)
        self._util_t_last = time.perf_counter()
        obs_ledger.note_tenant(
            "kv_pool", sum(obs_ledger.tree_nbytes(c)
                           for c in self._caches),
            decoder=self.name, paged=self.paged, kv_quant=self.kv_quant)

    # -- compiled-program drivers -------------------------------------------
    def _attn_flag_state(self):
        """Current attention-kernel flag state, as the fn_key fragment
        that selects a step program.  Slab decoders never page, so the
        flags cannot affect their program; spec decoders contain both
        the S=1 draft steps and the S=k+1 verify window, so both flags
        select."""
        if not self.paged:
            return ()
        from bigdl_tpu.models import transformer as _tf
        if self.spec_k:
            return (str(_tf._PALLAS_PAGED_ATTN),
                    str(_tf._PALLAS_SPEC_VERIFY))
        return (str(_tf._PALLAS_PAGED_ATTN),)

    def _view_horizon_bucket(self):
        """Smallest warmed view bucket covering every live slot's page
        reservation (the max in-use ptab run).  Idle decoders step at
        the cheapest bucket."""
        live = max((len(r.pages) for r in self._slots if r is not None),
                   default=1)
        for w in self._view_buckets:
            if w >= live:
                return w
        return self._view_buckets[-1]

    def _step_program(self, view_w=None):
        if view_w is None:
            view_w = (self._view_horizon_bucket() if self.paged
                      else self._view_buckets[-1])
        flag_state = self._attn_flag_state()
        sel = (view_w, flag_state)
        prog = self._step_programs.get(sel)
        if prog is None:
            prog = self._build_step(view_w, flag_state)
            self._step_programs[sel] = prog
        return prog

    def _run_step(self, view_w=None):
        if self.paged:
            args = (self._caches, self._ptab, self._pos,
                    self._prev, self._active, self._seeds,
                    self._seed_len, self._cap, self._gen)
        else:
            args = (self._caches, self._pos, self._prev,
                    self._active, self._seeds, self._seed_len, self._gen)
        args = args + (self._temp, self._topk, self._topp, self._keys,
                       self._stop_buf, self._stop_len, self._finished)
        if self.spec_k:
            args = args + (self._acc_hist,)
        if self._W is not None:
            args = (self._W,) + args
        out = self._step_program(view_w)(*args)
        if self.spec_k:
            (self._caches, self._pos, self._prev, self._gen,
             self._finished, self._acc_hist) = out
        else:
            (self._caches, self._pos, self._prev, self._gen,
             self._finished) = out

    def _sampling_rows(self, req):
        """Host-built admit operands for the request's sampling state:
        scalar params, the threefry key row, and the right-aligned
        packed stop buffers (submit() already validated capacity)."""
        p = req.params
        NS, LS = self.max_stop_seqs, self.max_stop_len
        sb_row = np.zeros((NS, LS), np.int32)
        sl_row = np.zeros((NS,), np.int32)
        for j, seq in enumerate(p.stop):
            sb_row[j, LS - len(seq):] = seq
            sl_row[j] = len(seq)
        return (np.float32(p.temperature), np.int32(p.top_k),
                np.float32(p.top_p), smp.key_data(p.seed), sb_row,
                sl_row)

    def _apply_admit(self, slot, req):
        seed_row = np.zeros((self._n_view,), np.int32)
        seed_row[:len(req.seed)] = req.seed
        samp = self._sampling_rows(req)
        state = (self._temp, self._topk, self._topp, self._keys,
                 self._stop_buf, self._stop_len, self._finished)
        if self.paged:
            row = np.zeros((self.pages_per_slot,), np.int32)
            row[:len(req.pages)] = req.pages
            (self._ptab, self._pos, self._active, self._seeds,
             self._seed_len, self._cap, self._gen, self._temp,
             self._topk, self._topp, self._keys, self._stop_buf,
             self._stop_len, self._finished) = self._admit_fn(
                self._ptab, self._pos, self._active, self._seeds,
                self._seed_len, self._cap, self._gen, *state,
                np.int32(slot), row, np.int32(req.start_pos), seed_row,
                np.int32(len(req.seed)),
                np.int32(len(req.pages) * self.page_size), *samp)
        else:
            (self._caches, self._pos, self._active, self._seeds,
             self._seed_len, self._gen, self._temp, self._topk,
             self._topp, self._keys, self._stop_buf, self._stop_len,
             self._finished) = self._admit_fn(
                self._caches, self._pos, self._active, self._seeds,
                self._seed_len, self._gen, *state, np.int32(slot),
                seed_row, np.int32(len(req.seed)), *samp)

    def _apply_retire(self, slot):
        if self.paged:
            self._ptab, self._active = self._retire_fn(
                self._ptab, self._active, np.int32(slot))
        else:
            self._active = self._retire_fn(self._active, np.int32(slot))

    def _warm(self):
        """Pre-compile the step/admit/retire programs at construction so
        admission and decode never hit a cold compile (the serving
        zero-cold-compile property, docs/serving.md).

        The warm pass cycles the REAL state machine once — step on the
        fresh state, admit into slot 0, step on the admit outputs,
        retire, step again — keeping each program's outputs as the live
        state, so every (shape, sharding) combination the serving loop
        will feed each program is compiled here and not mid-stream (jit
        caches per input sharding; under TP the shard_map step and the
        admit/retire programs produce differently-placed carries).  The
        warm admission maps slot 0 at pool page 0 with a one-page
        capacity; whatever K/V it writes there is overwritten
        position-by-position by the page's next real owner before any
        masked-in read."""
        warm = _DecodeReq([0], 1)
        warm.pages = [0] if self.paged else []
        # every view-horizon bucket compiles here (widest first — the
        # fresh host-placed state combo — then the rest on the carried
        # device state, the only placement serving ever feeds them)
        for w in reversed(self._view_buckets):
            self._run_step(view_w=w)
        for _ in range(2):
            # twice: the first admission's carries are the fresh
            # host-placed state, every later admission's are program
            # outputs — both placement combinations must compile now
            self._apply_admit(0, warm)
        self._run_step()
        self._apply_retire(0)
        if self._readmit_fn is not None:
            # the readmit warm writes zeros into page 0 — unallocated at
            # construction, and overwritten position-by-position by its
            # next real owner before any masked-in read (same argument
            # as the warm admission above)
            self._caches = self._readmit_fn(
                self._caches, np.int32(0), self._zero_page_payload())
        self._run_step()
        if self.spec_k:
            # the warm pass ran live speculative windows; exclude them
            # from the acceptance histogram — they judged garbage
            self._acc_seen = np.asarray(self._acc_hist, np.int64)

    # -- host tier + shipped-prefill adoption -------------------------------
    def _page_payload_shape(self, cache) -> tuple:
        """Host payload shape for one pool array's page slice
        (``pool[:, pid]`` — the page dim removed)."""
        return tuple(cache.shape[:1]) + tuple(cache.shape[2:])

    def _zero_page_payload(self) -> tuple:
        return tuple(np.zeros(self._page_payload_shape(c), c.dtype)
                     for c in self._caches)

    def _payload_ok(self, payload) -> bool:
        if len(payload) != len(self._caches):
            return False
        return all(tuple(p.shape) == self._page_payload_shape(c)
                   and p.dtype == c.dtype
                   for c, p in zip(self._caches, payload))

    def _spill_page(self, key, pid):
        """Prefix-cache ``on_evict`` intercept: snapshot the evicted
        page as cheap on-device slices and enqueue them for the tier's
        writer thread (the async-checkpoint pattern — eviction runs on
        the admission path and must not pay a blocking D2H).  The
        slices are functional arrays, so the pool page's next owner can
        never corrupt what was spilled."""
        self._tier.spill(key, tuple(c[:, pid] for c in self._caches))

    def _extend_from_tier(self, seed, shared) -> int:
        """Continue an admission's chain walk past the device cache:
        for each further chain key, prefer a (stranded) device-cache
        entry, else re-admit the host tier's copy H2D through the
        compiled re-admit program and register it back in the prefix
        cache.  Extends ``shared`` in place (every appended page id is
        retained for the slot); returns the number of tier re-admits."""
        ps = self.page_size
        max_pages = max(0, (len(seed) - 1) // ps)
        if len(shared) >= max_pages:
            return 0
        keys = list(chain_keys(seed, max_pages, ps))
        n = 0
        for j in range(len(shared), max_pages):
            pid = self._prefix.lookup(keys[j])   # retained for the slot
            if pid is not None:
                shared.append(pid)
                continue
            payload = self._tier.lookup(keys[j])
            if payload is None:
                break
            t0 = time.perf_counter()
            pids = self._alloc_pages(1)
            if pids is None:
                break
            pid = pids[0]
            self._caches = self._readmit_fn(
                self._caches, np.int32(pid),
                tuple(np.asarray(p) for p in payload))
            self._prefix.adopt(keys[j], pid)     # the cache's reference
            self._pool.retain(pid)               # the slot's reference
            shared.append(pid)
            n += 1
            self._tier.note_readmit(1, time.perf_counter() - t0)
        return n

    def adopt_pages(self, seed, payloads) -> int:
        """Adopt KV pages shipped by a prefill replica
        (``serve/fleet.py``): ``payloads[j]`` is the tuple of host
        arrays for the page holding positions ``j*ps .. (j+1)*ps - 1``
        computed under ``seed`` — the per-array page slices, int8 +
        scales under KV quantization.  Each page lands in the pool
        through the compiled re-admit program and registers in the
        prefix cache under ``seed``'s chain keys, so the request (and
        every later request sharing the prefix) admits with a prefix
        hit instead of a cold prefill.

        Best-effort by design: adoption needs ``prefill_adopt=True``
        (or an attached host tier) and payloads matching this pool's
        page shape/dtype — on any mismatch or pool pressure it adopts
        what it can and returns; the request still decodes correctly
        via colocated prefill.  Returns the number of NEWLY adopted
        pages."""
        if (not self.paged or self._prefix is None
                or self._readmit_fn is None or not payloads):
            return 0
        ps = self.page_size
        n_pages = min(len(payloads), max(0, (len(seed) - 1) // ps))
        adopted = 0
        for key, payload in zip(chain_keys(seed, n_pages, ps), payloads):
            payload = tuple(np.asarray(p) for p in payload)
            if not self._payload_ok(payload):
                logger.warning(
                    "adopt_pages: shipped payload does not match this "
                    "pool's page shape/dtype (prefill kv_quant drift?); "
                    "serving via colocated prefill")
                break
            if self._prefix.has(key):
                continue             # already resident — chain intact
            pids = self._alloc_pages(1)
            if pids is None:
                break                # pool pressure: partial adoption
            self._caches = self._readmit_fn(
                self._caches, np.int32(pids[0]), payload)
            self._prefix.adopt(key, pids[0])
            adopted += 1
        return adopted

    # -- submit -------------------------------------------------------------
    def submit(self, seed_ids, n_words: int, trace=None,
               sampling=None) -> StreamFuture:
        """Queue one request; the future resolves to the full token row
        (seed + up to ``n_words`` generated ids) — exactly
        ``lm_decode``'s greedy output for the same seed by default.  A
        request that cannot ever fit fails ONLY its own future with
        :class:`RequestTooLongError` — other submitted requests are
        untouched.

        ``sampling`` (a :class:`~bigdl_tpu.serve.sampling.SamplingParams`,
        a dict in its ``to_dict`` form, or None for greedy) selects the
        sampled lane: temperature/top-k/top-p draws keyed by the
        request's (resolved) seed, stop token-sequences that retire the
        request early at the boundary after a match — the row then ends
        just past the matched sequence, shorter than ``n_words`` — and
        ``max_tokens`` capping ``n_words``.  A stop list exceeding this
        decoder's packed capacity (``max_stop_seqs`` × ``max_stop_len``)
        fails its own future with ``ValueError``.

        The returned :class:`~bigdl_tpu.serve.streaming.StreamFuture`
        additionally streams: ``on_tokens(cb)`` (or ``request_stream``)
        turns on incremental delivery of the generated tokens at each
        sync boundary, byte-identical to the resolved row's tail.
        ``trace`` (an ``obs.trace.Trace``) gains ``decode_admit`` /
        ``first_token`` / ``retire`` hops as the request moves."""
        seed = np.asarray(seed_ids, np.int32)
        if seed.ndim != 1 or seed.size == 0:
            raise ValueError("seed_ids must be one flat non-empty id row")
        if n_words < 1:
            raise ValueError("n_words must be >= 1")
        params = smp.SamplingParams.of(sampling).resolved()
        if params.max_tokens is not None:
            n_words = min(int(n_words), params.max_tokens)
        req = _DecodeReq(seed.tolist(), n_words, trace=trace,
                         params=params)
        req.rid = next(self._req_seq)
        if trace is not None:
            # flight-recorder identity: everything request_replay needs
            # to rebuild an equivalent decoder for this request (plain
            # host dict merges — the device is never touched)
            obs_recorder.note(
                trace.trace_id, rid=f"{self.name}/{req.rid}",
                decoder=self.name,
                seed_hash=obs_recorder.seed_hash(req.seed),
                seed_len=len(req.seed), n_words=req.n_words,
                flags=self.decode_flags(),
                weights_version=self.weights_version)
            if not params.is_default:
                # the resolved params (seed pinned) — what replay
                # re-submits to redraw the exact token stream
                obs_recorder.note(trace.trace_id,
                                  sampling=params.to_dict())
        if (len(params.stop) > self.max_stop_seqs
                or any(len(s) > self.max_stop_len for s in params.stop)):
            req.future.set_exception(ValueError(
                f"stop list exceeds this decoder's packed capacity "
                f"({self.max_stop_seqs} sequences x "
                f"{self.max_stop_len} tokens); raise max_stop_seqs/"
                f"max_stop_len at construction"))
            return req.future
        too_long = req.steps_needed > self.n_pos
        if self.paged and not too_long:
            too_long = (_pages_needed(req.steps_needed, self.page_size)
                        > self._pool.n_pages)
        if too_long:
            req.future.set_exception(RequestTooLongError(
                f"request needs {req.steps_needed} positions "
                f"(len(seed)={len(req.seed)} + n_words={req.n_words} - 1)"
                f" but this decoder holds n_pos={self.n_pos}"
                + (f" across {self._pool.n_pages} pages of "
                   f"{self.page_size}" if self.paged else "")
                + "; raise n_pos/the pool or split the request"))
            return req.future
        self._pending.append(req)
        return req.future

    # -- drive --------------------------------------------------------------
    def _alloc_pages(self, n):
        """``n`` fresh pool pages, evicting cache-only prefix pages on
        demand (one LRU scan per attempt); None when the pool cannot
        satisfy the request yet."""
        short = n - self._pool.free_count
        if short > 0 and (self._prefix is None
                          or self._prefix.evict(short) < short):
            return None
        return [self._pool.alloc_one() for _ in range(n)]

    def _try_admit_paged(self, req) -> bool:
        shared = (self._prefix.match(req.seed)
                  if self._prefix is not None else [])
        if self._tier is not None:
            # a failed admission leaves tier re-admits in the prefix
            # cache (content already written) — the retry matches them
            self._extend_from_tier(req.seed, shared)
        total = _pages_needed(req.steps_needed, self.page_size)
        fresh = self._alloc_pages(total - len(shared))
        if fresh is None:
            for pid in shared:
                self._pool.release(pid)
            return False
        req.pages = shared + fresh
        req.start_pos = len(shared) * self.page_size
        if self._prefix is not None:
            self._prefix.note_request(len(shared))
            (self._m_pfx_hit if shared else self._m_pfx_miss).inc()
            if shared:
                self._m_pfx_pages.inc(len(shared))
        return True

    def _admit_waiting(self):
        for slot in range(self.B):
            if self._slots[slot] is not None or not self._pending:
                continue
            req = self._pending[0]
            if self.paged and not self._try_admit_paged(req):
                break   # head-of-line: wait for retirements to free pages
            self._pending.popleft()
            req.slot = slot
            self._apply_admit(slot, req)
            self._slots[slot] = req
            req.t_admit = time.perf_counter()
            if req.trace is not None:
                req.trace.stamp("decode_admit", req.t_admit)
                if self.paged:
                    # page/prefix counters at admission (already on the
                    # host — _try_admit_paged computed them)
                    obs_recorder.note(
                        req.trace.trace_id, start_pos=req.start_pos,
                        kv_pages=len(req.pages),
                        prefix_pages=req.start_pos // self.page_size)
            self.admitted += 1
            self._m_admitted.inc()
            if not req.params.greedy:
                self.sampled += 1
                self._m_sampled.inc()
        if self.paged:
            self._m_pages.set(self._pool.in_use)

    def _retire_req(self, req):
        self._apply_retire(req.slot)
        if self.paged:
            donate = 0
            if self._prefix is not None:
                # donate the full pages inside the seed: their K/V is a
                # pure function of the seed prefix, so the next request
                # sharing it skips that much prefill (ownership moves to
                # the cache — no copy; already-shared pages just drop
                # this slot's reference)
                donate = min(len(req.seed) // self.page_size,
                             len(req.pages))
                self._prefix.insert(req.seed, req.pages[:donate])
            for pid in req.pages[donate:]:
                self._pool.release(pid)
            self._m_pages.set(self._pool.in_use)
        self._slots[req.slot] = None
        self.retired += 1
        self._m_retired.inc()

    def _drain_accept_hist(self):
        """Fold the device-accumulated acceptance-length vector into the
        registry histogram (bulk bucket adds — one tiny fetch per
        boundary, never one observation per window)."""
        cur = np.asarray(self._acc_hist, np.int64)
        delta = cur - self._acc_seen
        self._acc_seen = cur
        for a, n in enumerate(delta):
            n = int(n)
            if n > 0:
                self._m_accept.observe_n(float(a), n)
                self._accept_counts[a] += n
                self.spec_windows += n
                self.spec_accepted += n * a

    def outstanding(self) -> int:
        """Queued + live requests — the fleet replica's inflight signal."""
        return (len(self._pending)
                + sum(1 for r in self._slots if r is not None))

    def step_boundary(self) -> int:
        """One admit → ``sync_interval``-step window → retire cycle —
        the unit :meth:`run` loops and a fleet decode replica's driver
        thread calls incrementally (``serve/fleet.py``).  Returns the
        number of slots served this boundary (0 = nothing admissible:
        drained, or — defensively — a stalled queue whose futures were
        just failed)."""
        spec = self.spec_k > 0
        w0, a0 = self.spec_windows, self.spec_accepted
        self._admit_waiting()
        live = [r for r in self._slots if r is not None]
        # stop-sequence rows make completion data-dependent exactly like
        # speculative decode: those boundaries fetch the position row
        # (plus the finished flags) — greedy no-stop streams keep the
        # pre-sampling host-sync count
        has_stop = any(r.params.stop for r in live)
        if not live:
            # idle boundary: restart the utilization window so wait
            # time between submissions is not charged to the next one
            self._util_t_last = time.perf_counter()
            if self._pending:   # pragma: no cover - defensive
                # submit() guarantees every queued request can fit an
                # empty pool, so an empty slab with work pending is a
                # bug — fail the futures loudly instead of dropping them
                for req in self._pending:
                    req.future.set_exception(RuntimeError(
                        "decoder stalled with no admissible request"))
                self._pending.clear()
            return 0
        self.live_hwm = max(self.live_hwm, len(live))
        self._m_slots.set(len(live))
        self._m_slots_hwm.set(self.live_hwm)
        for _ in range(self.sync_interval):
            self._run_step()
        self.steps += self.sync_interval
        self._m_steps.inc(self.sync_interval)
        pos_host = fin_host = None
        if spec or has_stop:
            pos_host = np.asarray(self._pos)
            if has_stop:
                # rides the same boundary fetch — ONE host sync
                fin_host = np.asarray(self._finished)
            self.host_syncs += 1
            self._m_syncs.inc()
            if spec:
                self._drain_accept_hist()
        if not spec:
            for r in live:
                r.steps_run += self.sync_interval
        if pos_host is not None:
            done = [r for r in live
                    if int(pos_host[r.slot]) >= r.steps_needed
                    or (fin_host is not None and bool(fin_host[r.slot]))]
        else:
            done = [r for r in live
                    if r.start_pos + r.steps_run >= r.steps_needed]
        # ONE slab materialization per boundary, shared by streaming
        # delivery AND retirement — streaming never adds a second fetch
        # to a boundary, and a boundary with neither live streams nor
        # retirements still fetches nothing (the pre-streaming count)
        streaming = [r for r in live if r.future.streaming]
        gen_host = None
        if done or streaming:
            gen_host = np.asarray(self._gen)   # the boundary host sync
            if not spec:
                self.host_syncs += 1
                self._m_syncs.inc()
        delivered = False
        if streaming:
            ts = time.perf_counter()
            for r in streaming:
                consumed = (int(pos_host[r.slot])
                            if pos_host is not None
                            else r.start_pos + r.steps_run)
                delivered |= self._feed_stream(r, gen_host, consumed,
                                               ts)
        if done:
            ts = time.perf_counter()
            for r in done:
                s = len(r.seed)
                final, n_gen = r.steps_needed, r.n_words
                if pos_host is not None:
                    # stop-retired rows froze early: the row ends just
                    # past the matched sequence (pos overshoot on
                    # normal rows is clipped back to n_words)
                    final = int(pos_host[r.slot])
                    n_gen = max(1, min(r.n_words, final - (s - 1)))
                toks = gen_host[r.slot, s - 1:s - 1 + n_gen]
                row = r.seed + [int(t) for t in toks]
                if n_gen < r.n_words:
                    # stop-sequence early retirement: the slot + pages
                    # free NOW instead of after the row's remaining
                    # step budget — count the reclaimed step-slots
                    r.stop_retired = True
                    saved = r.steps_needed - final
                    self.stop_retired += 1
                    self.steps_saved += saved
                    self._m_stop_retired.inc()
                    self._m_steps_saved.inc(saved)
                if r.trace is not None:
                    # the committed row — request_replay's oracle.
                    # Reuses the boundary's ONE slab materialization;
                    # no added sync, no per-token host work beyond the
                    # row already built for the future
                    obs_recorder.note(r.trace.trace_id, tokens=row)
                    if r.stop_retired:
                        obs_recorder.note(r.trace.trace_id,
                                          stop_retired=True)
                    if self.spec_k:
                        obs_recorder.note(
                            r.trace.trace_id,
                            spec_windows=self.spec_windows,
                            spec_accepted=self.spec_accepted)
                # retire BEFORE resolving: a serial client waiting on
                # this future may submit again the instant it resolves,
                # and the dispatch decision it triggers (least-loaded /
                # affinity, serve/fleet.py) must see this slot free —
                # resolving first leaves a window where outstanding()
                # still counts the finished request (the fleet drill's
                # old flake)
                self._retire_req(r)
                if r.future.streaming:
                    # catch-up (a consumer registered this boundary),
                    # then the stream epilogue; the resolution rides
                    # the delivery FIFO so the final chunk is always
                    # delivered before result() unblocks.  The catch-up
                    # bound is the row's ACTUAL final consumption — a
                    # stop-retired stream must never over-deliver past
                    # its truncation point
                    delivered |= self._feed_stream(
                        r, gen_host, min(final, r.steps_needed), ts)
                    self._finish_stream(r, ts)
                    self._ensure_delivery().resolve(r.future, row)
                else:
                    r.future.set_result(row)
            self._m_slots.set(sum(1 for r in self._slots
                                  if r is not None))
        if delivered:
            self.stream_boundaries += 1
        if spec:
            # a speculative window commits its accepted drafts plus the
            # verify token — both counters were drained this boundary
            tokens = ((self.spec_windows - w0)
                      + (self.spec_accepted - a0))
        else:
            tokens = len(live) * self.sync_interval
        self._note_util(tokens)
        return len(live)

    # -- streaming delivery -------------------------------------------------
    def _ensure_delivery(self) -> TokenDelivery:
        if self._delivery is None:
            self._delivery = TokenDelivery(name=self.name)
            self._delivery_box.append(self._delivery)
        return self._delivery

    def _feed_stream(self, req, gen_host, consumed: int,
                     ts: float) -> bool:
        """Deliver the tokens that became visible this boundary for one
        streaming request: everything generated past what was already
        delivered, read from the boundary's ONE slab materialization.
        Stamps the request timeline and the TTFT/ITL histograms; the
        actual consumer callbacks run on the delivery thread.
        Idempotent per boundary (``streamed`` only grows); returns
        whether anything was delivered."""
        s = len(req.seed)
        avail = min(int(consumed), req.steps_needed) - (s - 1)
        new = avail - req.streamed
        if new <= 0:
            return False
        toks = [int(t) for t in
                gen_host[req.slot, s - 1 + req.streamed:s - 1 + avail]]
        start = req.streamed
        req.streamed = avail
        if req.first_ts is None:
            req.first_ts = ts
            self.streams += 1
            self._m_ttft.observe(ts - req.t_submit)
            self._ttft_sum += ts - req.t_submit
            if req.trace is not None:
                req.trace.stamp("first_token", ts)
        else:
            # per-token gaps, averaged within the boundary: n tokens
            # landing dt after the previous delivery are n observations
            # of dt/n (co-delivered tokens share the window; the first
            # boundary's tokens belong to TTFT, not ITL)
            gap = ts - req.last_ts
            if gap > 0:
                self._m_itl.observe_n(gap / new, new)
        req.last_ts = ts
        req.timeline.append((ts, new))
        self.stream_tokens += new
        self._m_stream_toks.inc(new)
        self._ensure_delivery().enqueue(req.future, toks, start, ts)
        return True

    def _finish_stream(self, req, ts: float):
        """The per-request stream epilogue at retire: the ``retire``
        trace hop and one ``stream`` obs event carrying the token
        timeline (admit → first token → per-boundary counts → retire)
        — what the obs_report token waterfall renders."""
        if req.trace is not None:
            req.trace.stamp("retire", ts)
        if req.first_ts is None:   # pragma: no cover - n_words >= 1
            return
        from bigdl_tpu.obs import events
        rel = req.t_submit
        events.emit(
            "serve", kind="stream", request=f"{self.name}/{req.rid}",
            decoder=self.name, tokens=req.streamed,
            n_seed=len(req.seed),
            admit_ms=(None if req.t_admit is None
                      else round((req.t_admit - rel) * 1e3, 3)),
            ttft_ms=round((req.first_ts - rel) * 1e3, 3),
            retire_ms=round((ts - rel) * 1e3, 3),
            boundaries=len(req.timeline),
            timeline=[[round((t - rel) * 1e3, 3), n]
                      for t, n in req.timeline])

    def _note_util(self, tokens: int):
        """``decode_model_flops_util`` + ``decode_tokens_per_s``: one
        gauge set per sync boundary (the decode cadence unit — never
        per token or per step).  The window is boundary-entry to
        boundary-entry wall, so asynchronously queued device work
        amortizes across boundaries without forcing an extra host
        sync; flops come from the step program's compile-time ledger
        capture."""
        now = time.perf_counter()
        wall, self._util_t_last = now - self._util_t_last, now
        if wall <= 0:
            return
        self._m_toks.set(tokens / wall)
        if self._step_flops and self._peak_flops:
            self._m_util.set(self._step_flops * self.sync_interval
                             / (wall * self._peak_flops))

    def run(self):
        """Drive the decoder until every submitted request has resolved.
        Admissions and retirements happen only at ``sync_interval``
        step boundaries; the only device->host reads are one
        generated-slab fetch per boundary that retires a request (plus,
        under speculative decode, one (B,)-int position fetch per
        boundary — acceptance lengths make completion data-dependent)."""
        while self._pending or any(r is not None for r in self._slots):
            if self.step_boundary() == 0:
                break
        self.emit_decode_event()
        return self

    def emit_decode_event(self):
        """The lifetime ``decode`` obs event (``run`` emits one per
        drain; a fleet replica emits one at close)."""
        from bigdl_tpu.obs import events
        extra = {}
        if self.paged:
            ps = self._pool.stats()
            extra.update(paged=True, page_size=self.page_size,
                         pages=ps["pages"], pages_hwm=ps["in_use_hwm"],
                         live_hwm=self.live_hwm)
            if self._prefix is not None:
                extra.update(prefix_hits=self._prefix.hits,
                             prefix_misses=self._prefix.misses,
                             prefix_pages=self._prefix.pages_reused)
            if self._tier is not None:
                ts = self._tier.stats()
                extra.update(kv_host_spilled=ts["spilled"],
                             kv_host_readmitted=ts["readmitted"],
                             kv_host_dropped=ts["dropped"],
                             kv_host_bytes=ts["bytes"])
        if self.kv_quant != "off":
            extra.update(kv_quant=self.kv_quant,
                         kv_bytes_per_token=self.kv_bytes_per_token)
        if self.spec_k:
            extra.update(spec_k=self.spec_k,
                         spec_windows=self.spec_windows,
                         accept_mean=(self.spec_accepted
                                      / max(1, self.spec_windows)))
        if self.sampled:
            # sampled-vs-greedy split (greedy = admitted - sampled)
            extra.update(sampled=self.sampled,
                         greedy=self.admitted - self.sampled)
        if self.stop_retired:
            extra.update(stop_retired=self.stop_retired,
                         steps_saved=self.steps_saved)
        if self.streams:
            # required-when-streaming (events schema v4)
            extra.update(streaming=True, streams=self.streams,
                         stream_tokens=self.stream_tokens,
                         stream_boundaries=self.stream_boundaries,
                         first_token_ms=(self._ttft_sum / self.streams
                                         * 1e3))
        events.emit("serve", kind="decode", steps=self.steps,
                    host_syncs=self.host_syncs, admitted=self.admitted,
                    retired=self.retired, slots=self.B, **extra)
        return self

    def close(self):
        """Drop this decoder's series from the process metrics registry
        and release the prefix cache's page holds.  Decoders are
        labelled uniquely (``decoder=<name>``), so a process that
        constructs many short-lived decoders (every
        :func:`continuous_decode` call makes one) would otherwise grow
        the registry — and every snapshot/exposition — without bound.
        The series drop also runs at GC for decoders nobody closes;
        idempotent."""
        if self._delivery is not None:
            # FIFO drain: every pending chunk and streaming resolution
            # lands before the thread stops (then joined — the orphaned
            # daemon-thread-at-teardown lesson, Router.close)
            self._delivery.close()
            self._delivery = None
        if self._prefix is not None:
            self._prefix.drop_all()
        if self._tier is not None and self._tier_owned:
            self._tier.close()
            self._tier = None
        self._drop_series()

    def decode_flags(self) -> dict:
        """The constructor recipe ``tools/request_replay.py`` needs to
        rebuild an equivalent decoder for a recorded request: every
        flag that shapes the committed token stream or the KV layout.
        Built once — the flight recorder notes it per traced request."""
        if self._flags_cache is None:
            self._flags_cache = {
                "max_slots": self.B, "n_pos": self.n_pos,
                "sync_interval": self.sync_interval,
                "paged": self.paged, "page_size": self.page_size,
                "n_pages": (self._pool.n_pages if self.paged
                            else None),
                "prefix_cache": self._prefix is not None,
                "spec_k": self.spec_k,
                "draft_layers": self.draft_layers,
                "kv_quant": self.kv_quant,
                "max_stop_seqs": self.max_stop_seqs,
                "max_stop_len": self.max_stop_len}
        return self._flags_cache

    def stats(self) -> dict:
        out = {"steps": self.steps, "host_syncs": self.host_syncs,
               "admitted": self.admitted, "retired": self.retired,
               "slots": self.B,
               "slots_active": sum(1 for r in self._slots
                                   if r is not None),
               "live_hwm": self.live_hwm,
               "n_pos": self.n_pos, "paged": self.paged,
               "sync_interval": self.sync_interval, "tp": self.tp,
               "name": self.name, "kv_quant": self.kv_quant,
               "kv_bytes_per_token": self.kv_bytes_per_token,
               "sampled": self.sampled,
               "stop_retired": self.stop_retired,
               "steps_saved": self.steps_saved}
        if self.paged:
            out["pool"] = self._pool.stats()
            if self._prefix is not None:
                out["prefix"] = self._prefix.stats()
            if self._tier is not None:
                out["kv_host"] = self._tier.stats()
        if self.streams:
            out["stream"] = {
                "streams": self.streams,
                "tokens": self.stream_tokens,
                "boundaries": self.stream_boundaries,
                "ttft_mean_ms": self._ttft_sum / self.streams * 1e3}
        if self.spec_k:
            counts = self._accept_counts
            total = int(counts.sum())
            p50 = None
            if total:
                p50 = int(np.searchsorted(np.cumsum(counts),
                                          (total + 1) // 2))
            out.update(spec_k=self.spec_k,
                       spec_windows=self.spec_windows,
                       spec_accepted=self.spec_accepted,
                       accept_hist=[int(c) for c in counts],
                       accept_p50=p50,
                       accept_mean=(self.spec_accepted
                                    / max(1, self.spec_windows)))
        return out


def continuous_decode(model, seed_rows, n_words, max_slots: int = 4,
                      n_pos: int | None = None,
                      sync_interval: int | None = None, mesh=None,
                      **decoder_kwargs):
    """Convenience one-shot: decode every seed row with a shared decoder.

    ``n_pos`` defaults to the largest request's need, so a mixed set of
    seed lengths shares one compiled step.  ``mesh`` (with a ``model``
    axis) serves tensor-parallel; extra keyword arguments (``paged``,
    ``page_size``, ``n_pages``, ``prefix_cache``, ``spec_k``, ...) pass
    through to :class:`ContinuousDecoder`.  Returns the extended rows in
    submission order (``lm_decode`` greedy semantics per row)."""
    reqs = [np.asarray(s, np.int32) for s in seed_rows]
    if n_pos is None:
        n_pos = max(int(s.size) + int(n_words) - 1 for s in reqs)
    dec = ContinuousDecoder(model, max_slots=max_slots, n_pos=n_pos,
                            sync_interval=sync_interval, mesh=mesh,
                            **decoder_kwargs)
    try:
        futs = [dec.submit(s, n_words) for s in reqs]
        dec.run()
        return [f.result() for f in futs]
    finally:
        dec.close()   # one-shot decoder: don't leak its registry series
