"""Transformer encoder classifier — the attention-family flagship.

No counterpart in the reference (its sequence model zoo stops at
RNN/LSTM text classifiers, models/textclassifier); this family exists to
exercise the long-context machinery end to end: `nn.MultiHeadSelfAttention`
(ring attention under ``DistriOptimizer(sequence_parallel=True)``),
`nn.LayerNorm` (per-token — no cross-device stats under any sharding),
and optionally `nn.MoE` FFN blocks (expert-parallel under
``expert_parallel=True``).

Structure per block (pre-LN): x + Attn(LN(x)); x + FFN(LN(x)) — the
residuals use the reference's ConcatTable(Identity, branch) + CAddTable
idiom (same as its ResNet shortcut spelling).
"""
from __future__ import annotations

import collections

import bigdl_tpu.nn as nn

# Round-7 Mosaic paged-attention kernels (ops/pallas_kernels.py
# paged_attention / paged_spec_verify): walk the slot→page table
# in-kernel with an online softmax and the int8 dequantize fused into
# the QK/PV loops, instead of materializing the gathered `pool[ptab]`
# view (and a separate dequantize pass) in HBM each decode step.
# `_PALLAS_PAGED_ATTN` gates the S == 1 continuous-decode step,
# `_PALLAS_SPEC_VERIFY` the speculative (k+1)-query verify window.
# PR-2 adoption discipline: no chip verdict yet → both default OFF;
# True adopts on TPU, "interpret" forces the Pallas interpreter
# (CPU equivalence tests and the perf_smoke drill).  The staged A/B is
# `tools/bench_serve.py --decode-sweep --attn-kernel`; the verdicts are
# ROADMAP S6's (paged attention) and C5's (spec verify).
_PALLAS_PAGED_ATTN = False
_PALLAS_SPEC_VERIFY = False


def _residual(branch: nn.Module) -> nn.Module:
    return nn.Sequential(nn.ConcatTable(nn.Identity(), branch),
                         nn.CAddTable())


def _ffn(d_model: int, hidden: int, dropout: float,
         moe_experts: int) -> nn.Module:
    if moe_experts > 0:
        return nn.Sequential(nn.MoE(d_model, hidden, moe_experts),
                             nn.Dropout(dropout))
    return nn.Sequential(
        nn.TimeDistributed(nn.Linear(d_model, hidden)),
        nn.ReLU(True),
        nn.Dropout(dropout),
        nn.TimeDistributed(nn.Linear(hidden, d_model)),
    )


def encoder_block(d_model: int, n_heads: int, hidden: int,
                  dropout: float = 0.1, causal: bool = False,
                  moe_experts: int = 0) -> nn.Module:
    return nn.Sequential(
        _residual(nn.Sequential(
            nn.LayerNorm(d_model),
            nn.MultiHeadSelfAttention(d_model, n_heads, causal=causal),
            nn.Dropout(dropout),
        )),
        _residual(nn.Sequential(
            nn.LayerNorm(d_model),
            _ffn(d_model, hidden, dropout, moe_experts),
        )),
    )


def TransformerLM(vocab_size: int, d_model: int = 128, n_heads: int = 4,
                  n_layers: int = 2, hidden: int = 256,
                  dropout: float = 0.1):
    """Causal word LM over (B, T, vocab) one-hot input -> per-token class
    log-probs — the attention-family counterpart of models/rnn.SimpleRNN
    (ref SimpleRNN.scala:23-38): same input/output contract, so it trains
    with ``TimeDistributedCriterion(ClassNLLCriterion)`` and generates
    with ``models.rnn.generate`` unchanged.  Sequence order comes from
    ``nn.SinusoidalPositionalEncoding`` (attention is permutation-
    equivariant; the RNN's recurrence is replaced, not imitated)."""
    m = nn.Sequential(
        nn.TimeDistributed(nn.Linear(vocab_size, d_model)),
        nn.SinusoidalPositionalEncoding(d_model),
    )
    for _ in range(n_layers):
        m.add(encoder_block(d_model, n_heads, hidden, dropout,
                            causal=True))
    m.add(nn.LayerNorm(d_model))
    m.add(nn.TimeDistributed(nn.Sequential(
        nn.Linear(d_model, vocab_size), nn.LogSoftMax())))
    return m


_LMHandles = collections.namedtuple(
    "_LMHandles", ["mods", "n_layers", "emb", "d_model", "blocks",
                   "block_eps", "n_heads", "hd", "ln_f", "eps_f", "head",
                   "vocab"])


def _lm_handles(model):
    """Structural handle extraction shared by ``lm_decode`` and
    ``lm_beam_search``: walk each block for its LayerNorm/attention/
    Linear instances (count-checked) so refactors of ``encoder_block``'s
    container nesting fail loudly instead of silently diverging through
    stale hard-coded param paths."""
    from bigdl_tpu.nn.attention import (MultiHeadSelfAttention,
                                        SinusoidalPositionalEncoding)
    from bigdl_tpu.nn.linear import Linear
    from bigdl_tpu.nn.moe import MoE
    from bigdl_tpu.nn.normalization import LayerNorm

    def _walk(mod, path=()):
        yield path, mod
        for i, ch in enumerate(getattr(mod, "modules", None) or []):
            yield from _walk(ch, path + (str(i),))

    def _find(mod, cls):
        return [(p, m) for p, m in _walk(mod) if isinstance(m, cls)]

    def _param_at(tree, path):
        for k in path:
            tree = tree[k]
        return tree

    mods = model.modules
    n_layers = len(mods) - 4
    if (n_layers < 1
            or not isinstance(mods[1], SinusoidalPositionalEncoding)):
        raise ValueError("lm_decode expects a TransformerLM-built model "
                         "(embedding, positional encoding, blocks, final "
                         "LayerNorm, head)")
    params = model.params()
    emb_mods = _find(mods[0], Linear)
    if len(emb_mods) != 1:
        raise ValueError("lm_decode: embedding stage must hold exactly "
                         "one Linear")
    emb = _param_at(params["0"], emb_mods[0][0])["~"]  # weight (d, vocab)
    d_model = int(emb["weight"].shape[0])
    blocks, block_eps = [], []
    n_heads = None
    for li in range(n_layers):
        blk, pb = mods[2 + li], params[str(2 + li)]
        if _find(blk, MoE):
            raise NotImplementedError(
                "lm_decode does not support MoE FFN blocks")
        attn = _find(blk, MultiHeadSelfAttention)
        lns = _find(blk, LayerNorm)
        ffn_lins = _find(blk, Linear)
        if len(attn) != 1 or len(lns) != 2 or len(ffn_lins) != 2:
            raise ValueError(
                f"lm_decode: block {li} must hold exactly one attention, "
                f"two LayerNorms and two FFN Linears; found {len(attn)}/"
                f"{len(lns)}/{len(ffn_lins)} — was encoder_block "
                f"restructured?")
        n_heads = attn[0][1].n_heads
        blocks.append((
            _param_at(pb, lns[0][0]),        # attention-branch LN
            _param_at(pb, attn[0][0])["~"],  # MHSA weights
            _param_at(pb, lns[1][0]),        # FFN-branch LN
            _param_at(pb, ffn_lins[0][0])["~"],  # d_model -> hidden
            _param_at(pb, ffn_lins[1][0])["~"],  # hidden -> d_model
        ))
        block_eps.append((lns[0][1].eps, lns[1][1].eps))
    hd = d_model // n_heads
    ln_f = params[str(2 + n_layers)]["~"]
    eps_f = mods[2 + n_layers].eps
    head_mods = _find(mods[3 + n_layers], Linear)
    if len(head_mods) != 1:
        raise ValueError("lm_decode: head stage must hold exactly one "
                         "Linear")
    head = _param_at(params[str(3 + n_layers)],
                     head_mods[0][0])["~"]   # weight (vocab, d)
    vocab = int(head["weight"].shape[0])
    return _LMHandles(mods, n_layers, emb, d_model, blocks, block_eps,
                      n_heads, hd, ln_f, eps_f, head, vocab)


def _lm_forward_window(tok, i, caches, handles, pe, pages, valid=None,
                       tp_axis=None, view_pages=None):
    """Paged multi-position forward: token ids (B, S) at per-row
    positions ``i`` (B, S) against block-paged KV pools.

    ``pages`` is ``(page_table, page_size)``: the pools in ``caches``
    are shaped (layers, n_pages, page_size, H, hd) and ``page_table``
    (B, P) maps each row's logical page ``t // page_size`` to a pool
    page, so a row's attention span is the gathered view
    ``pool[layer][page_table[b]]`` — (P * page_size) positions in
    logical order.  The window's K/V scatter runs BEFORE the gather, so
    window position j attends window positions j' <= j and the
    committed past through one causal mask (``t <= i[b, j]``): this is
    both the speculative-verify batch step (S = k+1 drafted positions
    judged in one pass) and, at S = 1, the paged continuous-decode
    step.

    ``valid`` (B, S) gates the scatter: invalid positions — a frozen
    row, or window positions past the row's page allocation — are
    routed out of bounds, where XLA DROPS the update.  That gate is a
    correctness contract, not hygiene: pages can outlive their request
    through the prefix cache (serve/prefix.py), so a stale write from a
    finished row would corrupt K/V another request later trusts.

    ``caches`` of FOUR arrays — ``(kpool, vpool, kscale, vscale)`` —
    selects int8 KV storage (``BIGDL_SERVE_KV_QUANT``, docs/serving.md
    "Quantized serving"): the pools are int8 and the scale arrays
    ``(layers, n_pages, page_size, H)`` carry one float scale per
    written head-row, pool-indexed exactly like the values (so prefix
    page donation ships scales with pages).  The scatter quantizes
    (``quant/kv.py``: per-head amax/127), the page-gathered attention
    view dequantizes; scales ride the SAME ``phys`` coordinates, so
    invalid lanes drop both writes together.

    ``tp_axis`` has `_lm_forward_one`'s Megatron semantics: handles
    carry LOCAL shards, the pools (and scale arrays) shard on their
    head dim, one psum merges each branch's output projection.

    ``view_pages`` (static int) bounds the attention view to the first
    that many page-table columns — the caller promises every live
    position in this window sits below ``view_pages * page_size``
    (serve/decode.py tracks the fleet-wide live page horizon), so the
    gather, mask and softmax shrink from the full reservation to the
    pages actually in use.  Scatter coordinates are unaffected: a valid
    position's logical page is < ``view_pages`` by the same promise,
    and invalid positions were already routed out of bounds.

    When `_PALLAS_PAGED_ATTN` (S == 1) or `_PALLAS_SPEC_VERIFY`
    (S > 1) is set, the gather + dequantize + attention stack is
    replaced by the fused Mosaic page-walk kernel
    (ops/pallas_kernels.py paged_attention); the K/V scatter is
    unchanged.  Flag value "interpret" forces the Pallas interpreter
    off-TPU."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bigdl_tpu.quant import kv as kvq

    h_ = handles
    ptab, page_size = pages
    if view_pages is not None:
        ptab = ptab[:, :view_pages]
    quantized = len(caches) == 4
    if quantized:
        kpool, vpool, kscale, vscale = caches
    else:
        kpool, vpool = caches
    bsz, S = tok.shape
    n_pool_pages = int(kpool.shape[1])
    n_view = int(ptab.shape[1]) * int(page_size)
    rows = jnp.arange(bsz)[:, None]                      # (B, 1)
    scale = 1.0 / np.sqrt(h_.hd)
    if valid is None:
        valid = jnp.ones(tok.shape, bool)
    # scatter coordinates: logical page -> physical pool page; invalid
    # positions target page id n_pool_pages (out of bounds -> dropped)
    phys = jnp.where(valid, ptab[rows, i // page_size], n_pool_pages)
    off = i % page_size
    use_kernel = _PALLAS_SPEC_VERIFY if S > 1 else _PALLAS_PAGED_ATTN
    if use_kernel:
        from bigdl_tpu.ops import pallas_kernels as pk
        kernel_interp = pk._interpreted(use_kernel)
    mask = (jnp.arange(n_view)[None, None, None, :]
            <= i[:, None, :, None])                      # (B, 1, S, T)

    def layernorm(x, p, eps):
        mean = x.mean(axis=-1, keepdims=True)
        inv = jax.lax.rsqrt(x.var(axis=-1, keepdims=True) + eps)
        return (x - mean) * inv * p["~"]["weight"] + p["~"]["bias"]

    def merge(partial):
        return (partial if tp_axis is None
                else jax.lax.psum(partial, tp_axis))

    x = h_.emb["weight"].T[tok] + h_.emb["bias"] + pe[i]   # (B, S, d)
    for li, (ln1, m, ln2, lin1, lin2) in enumerate(h_.blocks):
        a = layernorm(x, ln1, h_.block_eps[li][0])
        q = (a @ m["wq"] + m["bq"]).reshape(bsz, S, h_.n_heads, h_.hd)
        k = (a @ m["wk"] + m["bk"]).reshape(bsz, S, h_.n_heads, h_.hd)
        v = (a @ m["wv"] + m["bv"]).reshape(bsz, S, h_.n_heads, h_.hd)
        if quantized:
            qk, sk = kvq.quantize_rows(k)
            qv, sv = kvq.quantize_rows(v)
            kpool = kpool.at[li, phys, off].set(qk)
            vpool = vpool.at[li, phys, off].set(qv)
            kscale = kscale.at[li, phys, off].set(sk)
            vscale = vscale.at[li, phys, off].set(sv)
        else:
            kpool = kpool.at[li, phys, off].set(k)
            vpool = vpool.at[li, phys, off].set(v)
        if use_kernel:
            # fused page-walk attention: no gathered view, no HBM
            # dequantize pass — scatter above is unchanged.
            o = pk.paged_attention(
                q, kpool[li], vpool[li], ptab, i,
                kscale[li] if quantized else None,
                vscale[li] if quantized else None,
                interpret=kernel_interp,
            ).reshape(bsz, S, h_.n_heads * h_.hd)
        else:
            if quantized:
                kview = kvq.dequantize_view(kpool[li][ptab],
                                            kscale[li][ptab])
                vview = kvq.dequantize_view(vpool[li][ptab],
                                            vscale[li][ptab])
                kview = kview.reshape(bsz, n_view, h_.n_heads, h_.hd)
                vview = vview.reshape(bsz, n_view, h_.n_heads, h_.hd)
            else:
                kview = kpool[li][ptab].reshape(bsz, n_view, h_.n_heads,
                                                h_.hd)
                vview = vpool[li][ptab].reshape(bsz, n_view, h_.n_heads,
                                                h_.hd)
            s = jnp.einsum("bshd,bthd->bhst", q, kview) * scale
            s = jnp.where(mask, s, -jnp.inf)
            p = jax.nn.softmax(s, axis=-1)
            o = jnp.einsum("bhst,bthd->bshd", p,
                           vview).reshape(bsz, S, h_.n_heads * h_.hd)
        x = x + merge(o @ m["wo"]) + m["bo"]
        a2 = layernorm(x, ln2, h_.block_eps[li][1])
        h = jax.nn.relu(a2 @ lin1["weight"].T + lin1["bias"])
        x = x + merge(h @ lin2["weight"].T) + lin2["bias"]
    xf = ((x - x.mean(axis=-1, keepdims=True))
          * jax.lax.rsqrt(x.var(axis=-1, keepdims=True) + h_.eps_f)
          * h_.ln_f["weight"] + h_.ln_f["bias"])
    logp = jax.nn.log_softmax(xf @ h_.head["weight"].T + h_.head["bias"])
    if quantized:
        return logp, (kpool, vpool, kscale, vscale)
    return logp, (kpool, vpool)


def _lm_forward_one(tok, i, caches, handles, n_pos, pe, tp_axis=None,
                    pages=None, valid=None, view_pages=None):
    """One decode position for all rows: token ids (B,) at position i
    with per-layer KV caches (layers, B, n_pos, H, hd) -> (log-probs
    (B, vocab), updated caches).  The shared inner body of lm_decode,
    lm_beam_search and the continuous-batching decoder.

    ``pages=(page_table, page_size)`` switches the cache layout to the
    block-paged pools of :func:`_lm_forward_window` (gather/scatter
    through the slot→page table, ``valid`` gating the write) — the same
    math at that row's position, storage indirected through pages.  A
    four-array ``caches`` tuple (int8 pools + per-page-row scales,
    ``BIGDL_SERVE_KV_QUANT``) passes through opaquely to the window's
    quantized storage path.

    ``i`` is either a scalar position (every row at the same step — the
    lock-step scans here) or a per-row (B,) vector (``serve/decode.py``
    slots at independent positions): the cache write scatters per row
    and the causal mask compares against each row's own position, so
    the math per row is IDENTICAL to the scalar path at that row's
    position — the bit-parity contract ``tests/test_serve.py`` holds
    the decoder to.

    ``tp_axis`` names a mesh axis when this body runs INSIDE shard_map
    with Megatron-style tensor parallelism (serve/decode.py TP path):
    ``handles`` then carries the LOCAL shard of each block — attention
    heads split over the axis (wq/wk/wv columns, wo rows, and the KV
    caches on their head dim) and the FFN hidden dim likewise (lin1
    rows, lin2 columns).  The only cross-shard communication is one
    psum after each branch's output projection, with the replicated
    bias added after the sum — per-head/per-hidden-unit math is
    untouched, so the TP decode stays token-identical to one device."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    if pages is not None:
        v = None if valid is None else valid[:, None]
        logp, caches = _lm_forward_window(
            tok[:, None], i[:, None], caches, handles, pe, pages,
            valid=v, tp_axis=tp_axis, view_pages=view_pages)
        return logp[:, 0], caches

    h_ = handles
    emb, blocks, block_eps = h_.emb, h_.blocks, h_.block_eps
    n_heads, hd, d_model = h_.n_heads, h_.hd, h_.d_model
    ln_f, eps_f, head = h_.ln_f, h_.eps_f, h_.head
    kcache, vcache = caches
    bsz = tok.shape[0]
    per_row = getattr(i, "ndim", 0) == 1
    rows = jnp.arange(bsz)
    limit = i[:, None, None] if per_row else i
    scale = 1.0 / np.sqrt(hd)

    def layernorm(x, p, eps):
        mean = x.mean(axis=-1, keepdims=True)
        inv = jax.lax.rsqrt(x.var(axis=-1, keepdims=True) + eps)
        return (x - mean) * inv * p["~"]["weight"] + p["~"]["bias"]

    def merge(partial):
        return (partial if tp_axis is None
                else jax.lax.psum(partial, tp_axis))

    x = emb["weight"][:, tok].T + emb["bias"] + pe[i]
    for li, (ln1, m, ln2, lin1, lin2) in enumerate(blocks):
        a = layernorm(x, ln1, block_eps[li][0])
        q = (a @ m["wq"] + m["bq"]).reshape(bsz, n_heads, hd)
        k = (a @ m["wk"] + m["bk"]).reshape(bsz, n_heads, hd)
        v = (a @ m["wv"] + m["bv"]).reshape(bsz, n_heads, hd)
        if per_row:
            kcache = kcache.at[li, rows, i].set(k)
            vcache = vcache.at[li, rows, i].set(v)
        else:
            kcache = kcache.at[li, :, i].set(k)
            vcache = vcache.at[li, :, i].set(v)
        s = jnp.einsum("bhd,bthd->bht", q, kcache[li]) * scale
        s = jnp.where(jnp.arange(n_pos)[None, None, :] <= limit, s,
                      -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bht,bthd->bhd", p,
                       vcache[li]).reshape(bsz, n_heads * hd)
        x = x + merge(o @ m["wo"]) + m["bo"]
        a2 = layernorm(x, ln2, block_eps[li][1])
        h = jax.nn.relu(a2 @ lin1["weight"].T + lin1["bias"])
        x = x + merge(h @ lin2["weight"].T) + lin2["bias"]
    xf = ((x - x.mean(axis=-1, keepdims=True))
          * jax.lax.rsqrt(x.var(axis=-1, keepdims=True) + eps_f)
          * ln_f["weight"] + ln_f["bias"])
    logp = jax.nn.log_softmax(xf @ head["weight"].T + head["bias"])
    return logp, (kcache, vcache)


def lm_decode(model, seed_ids, n_words, greedy: bool = True, key=None,
              temperature: float = 1.0, top_k: int = 0,
              top_p: float = 0.0):
    """KV-cached incremental decoding for a ``TransformerLM`` model.

    Same math as re-forwarding the whole prefix per token
    (``models.rnn.generate``): causal attention at position i reads only
    positions <= i, so the per-layer K/V projections are computed ONCE
    and cached.  The entire decode — seed consumption and generation —
    is a single ``lax.scan`` with static shapes (fixed-size caches
    written via ``.at[i].set``), so it compiles to one TPU program with
    no host round-trip per token; the reference's generation loop
    (rnn/Test.scala:58-90) re-forwards the growing sentence from
    scratch each word.

    ``greedy=True`` takes the argmax; otherwise ``key`` (a JAX PRNG key)
    drives ``jax.random.categorical`` — a different draw stream from
    ``generate``'s host inverse-CDF, same distribution — with optional
    ``temperature`` scaling plus ``top_k`` / ``top_p`` truncation
    through the ONE shared sampler
    (:func:`bigdl_tpu.serve.sampling.sample_tokens` — the served
    continuous decoder filters logits with the same function, so the
    offline and serving paths cannot drift).  Pre-existing
    (temperature, top_k) draws are byte-identical to the historical
    inline math; ``top_p`` in (0, 1) additionally keeps only the
    smallest descending-probability prefix reaching that mass.

    ``seed_ids`` is a flat list of ids (returns the extended flat list)
    or a rectangular batch of B seed rows (returns B extended rows) —
    batched decoding shares ONE scan, with independent draws per row.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bigdl_tpu.serve.sampling import sample_tokens

    if not greedy and key is None:
        raise ValueError("sampling (greedy=False) needs a PRNG key")
    if temperature <= 0:
        raise ValueError("temperature must be > 0")
    if not 0.0 <= top_p <= 1.0:
        raise ValueError("top_p must be in [0, 1] (0 or 1 = off)")
    handles = _lm_handles(model)
    mods, n_layers = handles.mods, handles.n_layers
    n_heads, hd, vocab = handles.n_heads, handles.hd, handles.vocab

    if len(seed_ids) == 0:
        raise ValueError("lm_decode needs at least one seed token")
    try:
        seed_np = np.asarray(seed_ids, np.int32)
    except (ValueError, TypeError) as e:   # ragged rows
        raise ValueError("seed_ids must be a flat id list or a "
                         "RECTANGULAR batch of seed rows") from e
    flat = seed_np.ndim == 1
    seed_np = np.atleast_2d(seed_np)
    if seed_np.ndim != 2 or seed_np.shape[1] == 0:
        raise ValueError("seed_ids must be a flat id list or a "
                         "rectangular batch of non-empty seed rows")
    seed = jnp.asarray(seed_np)
    bsz, n_seed = int(seed.shape[0]), int(seed.shape[1])
    n_pos = n_seed + int(n_words) - 1      # positions fed through
    pe = jnp.asarray(mods[1].table(n_pos))

    def step(carry, i):
        kcache, vcache, tok, k_rng = carry
        tok = jnp.where(i < n_seed, seed[:, jnp.minimum(i, n_seed - 1)],
                        tok)
        logp, (kcache, vcache) = _lm_forward_one(
            tok, i, (kcache, vcache), handles, n_pos, pe)
        if greedy:
            nxt = jnp.argmax(logp, axis=-1).astype(jnp.int32)
        else:
            k_rng, sub = jax.random.split(k_rng)
            nxt = sample_tokens(logp, sub, temperature, top_k,
                                top_p).astype(jnp.int32)
        return (kcache, vcache, nxt, k_rng), nxt

    k0 = jnp.zeros((n_layers, bsz, n_pos, n_heads, hd), jnp.float32)
    rng0 = key if key is not None else jax.random.PRNGKey(0)
    (_, _, _, _), preds = jax.lax.scan(
        step, (k0, jnp.zeros_like(k0),
               jnp.zeros((bsz,), jnp.int32), rng0),
        jnp.arange(n_pos))
    gen = np.asarray(preds[n_seed - 1:])        # (n_words, B)
    rows = [[int(t) for t in seed_np[b]] + [int(t) for t in gen[:, b]]
            for b in range(bsz)]
    return rows[0] if flat else rows


def lm_beam_search(model, seed_ids, n_words, beam_size: int = 4,
                   return_all: bool = False):
    """Beam-search decoding over the same KV-cache scan as ``lm_decode``.

    Two compiled scans, no host round-trip per token: the seed is
    consumed at batch 1 (beams share the prefix, so a K-wide seed pass
    would be K-times redundant), the caches tile to ``beam_size`` rows,
    and the beam scan does a joint top-k over ``beam_size * vocab``
    continuations plus a beam-reordering gather of every layer's KV
    cache per step.  Beams have equal length (``n_words``
    continuations), so the winner is the highest total log-probability;
    ``return_all=True`` additionally returns every beam's token row and
    score, best first.

    The reference has no beam search (its generation loop samples one
    path, rnn/Test.scala:58-90); this extends the attention family's
    decoder the TPU-native way: the beam dimension is just the batch
    dimension of the cached decode, and reordering is a device-side
    gather.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    if beam_size < 1:
        raise ValueError("beam_size must be >= 1")
    seed_np = np.asarray(seed_ids, np.int32)
    if seed_np.ndim != 1 or seed_np.size == 0:
        raise ValueError("lm_beam_search takes one flat non-empty seed "
                         "id list")
    handles = _lm_handles(model)
    mods, n_layers = handles.mods, handles.n_layers
    n_heads, hd, vocab = handles.n_heads, handles.hd, handles.vocab
    K = int(beam_size)
    n_seed = int(seed_np.size)
    n_pos = n_seed + int(n_words) - 1
    pe = jnp.asarray(mods[1].table(n_pos))
    seed = jnp.asarray(seed_np)

    # ---- seed pass at batch 1: all beams share the prefix
    k0 = jnp.zeros((n_layers, 1, n_pos, n_heads, hd), jnp.float32)

    def seed_step(caches, i):
        _, caches = _lm_forward_one(seed[i][None], i, caches, handles,
                                    n_pos, pe)
        return caches, None

    (kc, vc), _ = jax.lax.scan(seed_step, (k0, jnp.zeros_like(k0)),
                               jnp.arange(n_seed - 1))
    kc = jnp.repeat(kc, K, axis=1)
    vc = jnp.repeat(vc, K, axis=1)

    # ---- beam scan over the generated positions
    def step(carry, i):
        kcache, vcache, tok, scores, gen = carry
        logp, (kcache, vcache) = _lm_forward_one(
            tok, i, (kcache, vcache), handles, n_pos, pe)
        total = (scores[:, None] + logp).reshape(-1)
        scores, flat_idx = jax.lax.top_k(total, K)
        beam_idx = flat_idx // vocab
        nxt = (flat_idx % vocab).astype(jnp.int32)
        # reorder every beam-indexed carry to the surviving beams
        kcache = kcache[:, beam_idx]
        vcache = vcache[:, beam_idx]
        gen = gen[beam_idx].at[:, i - (n_seed - 1)].set(nxt)
        return (kcache, vcache, nxt, scores, gen), None

    # only beam 0 is live at the first expansion, else the top-k would
    # pick the same token K times from identical beams
    scores0 = jnp.full((K,), -jnp.inf).at[0].set(0.0)
    gen0 = jnp.zeros((K, int(n_words)), jnp.int32)
    tok0 = jnp.full((K,), seed[-1], jnp.int32)
    (_, _, _, scores, gen), _ = jax.lax.scan(
        step, (kc, vc, tok0, scores0, gen0),
        jnp.arange(n_seed - 1, n_pos))
    order = np.argsort(-np.asarray(scores))
    rows = [[int(t) for t in seed_np] + [int(t) for t in np.asarray(gen)[b]]
            for b in order]
    if return_all:
        return rows, [float(scores[b]) for b in order]
    return rows[0]


def TransformerClassifier(class_num: int, d_model: int = 128,
                          n_heads: int = 4, n_layers: int = 2,
                          hidden: int = 256, dropout: float = 0.1,
                          causal: bool = False, moe_experts: int = 0):
    """(B, T, d_model) embeddings -> class log-probs.

    The head mirrors the Bi-LSTM text classifier's (mean over time ->
    linear -> LogSoftMax), so the two families slot into the same
    training CLIs and datasets.  ``causal=True`` masks attention
    autoregressively in every block.
    """
    m = nn.Sequential()
    for _ in range(n_layers):
        m.add(encoder_block(d_model, n_heads, hidden, dropout,
                            causal=causal, moe_experts=moe_experts))
    m.add(nn.LayerNorm(d_model))
    m.add(nn.Mean(1, n_input_dims=2))
    m.add(nn.Linear(d_model, class_num))
    m.add(nn.LogSoftMax())
    return m
