"""Serving benchmark: throughput-vs-latency curve under an offered-load
sweep, plus the dynamic-batching speedup over the one-request-at-a-time
baseline (docs/serving.md).

Scoring (``--model lenet|inception``): each sweep point submits
``--requests`` single-row requests to a :class:`ServeEngine` at the
offered rate (requests/second; ``inf`` = closed-loop, all at once) and
reports achieved throughput with p50/p95/p99 latency.  The baseline is
the serial loop a naive deployment runs — one row, one forward, one
host sync at a time — at the SAME model/shape, so the headline ratio
isolates exactly what dynamic batching + bucketed AOT executables buy.

Decode (``--model transformer``): serial per-request ``lm_decode``
versus the continuous-batching slot driver at equal token budgets,
reported as tokens/second.

Decode sweep (``--decode-sweep``): the paged-KV concurrency-scaling
story (docs/serving.md "Paged KV + speculative decode").  At a FIXED
pooled-token budget — exactly the HBM a ``--decode-slots``-wide slab of
``--decode-npos`` rows holds — the sweep offers increasing concurrency
and reports tokens/sec/slot for the legacy slab (live requests capped
at the slab width) against the paged pool (live requests capped only by
pooled tokens), asserts paged output token-for-token equal to serial
``lm_decode``, and finishes with a mixed-length SPECULATIVE stream
(``--spec-k``) audited for zero cold compiles after warmup through the
shared executable-cache counter.  Three SAMPLED-decode points follow
(docs/serving.md "Sampled decode"): a uniformly sampled stream
(``--temperature/--top-k/--top-p``), a mixed-param rotation whose
greedy rows must stay byte-identical, and a stop-sequence
early-retirement point (``--stop-len``) whose stops are cut from each
request's own greedy oracle so every row retires early.  Every point
STREAMS its tokens (``StreamFuture.on_tokens``), so rows carry the
client-observed ``ttft_p50``/``ttft_p99``/``itl_p50`` SLO columns next
to throughput.  One JSON row per point (contract pinned by
``tests/test_paged_decode.py``); ``--check`` enforces the acceptance
bar: more live requests than the slab bound, parity (streamed chunks
included), zero cold compiles (sampled and mixed-param streams
included), sampled throughput >= 0.9x the greedy point, a wall-clock
win from stop retirement, and TTFT p50 below the e2e p50 on a
long-generation point.

Traffic (``--traffic``): seeded OPEN-LOOP bursty/diurnal load — Poisson
arrivals whose instantaneous rate follows a declared burst window
(``--burst-factor/--burst-start-s/--burst-len-s``) and an optional
sinusoidal diurnal envelope, mixed priority classes
(``--priority-mix``), and shared-prefix request families when the
target is a decode fleet (``--model transformer``).  The run resolves
every submitted future exactly once (completed + shed + failed ==
accepted — the capstone accounting ``--check`` enforces), splits sheds
into inside/outside the declared overload window, and with
``--autoscale`` closes the loop through ``serve/autoscale.py``
(replica counts + scale actions land in the row).  One JSON row per
run (contract pinned by ``tests/test_autoscale.py``).

Router (``--replicas N``, N > 1): the same offered-load sweep through a
:class:`ReplicaPool` — N engine replicas behind the SLO router — with
per-replica and aggregate rows/s plus the shed rate per point
(``--slo-ms`` arms the deadline/shed policy; 0 = serve everything).
The JSON row contract is pinned by ``tests/test_serve_cluster.py``.

Runs on CPU (small defaults) and on a chip unchanged; emits one JSON
line per sweep point (``bench_serve:`` prefix) plus a summary table.
The acceptance bar — batched throughput >= 2x serial — is asserted with
``--check`` (used by scripts/serve_smoke.sh on the scoring path).
"""
from __future__ import annotations

import argparse
import json
import math
import os as _os
import sys as _sys
import time

import numpy as np

_REPO = _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__)))
_sys.path.insert(0, _REPO)


def _build(name: str):
    from bigdl_tpu.utils.random import set_seed
    set_seed(1)
    if name == "lenet":
        from bigdl_tpu.models.lenet import LeNet5
        return LeNet5(10), (28, 28)
    if name == "inception":
        from bigdl_tpu.models.inception import Inception_v1
        return Inception_v1(1000), (3, 224, 224)
    raise SystemExit(f"unknown scoring model {name!r}")


def serial_baseline(model, rows):
    """One-request-at-a-time: jitted batch-1 forward, full host sync per
    request — the Predictor-loop deployment this engine replaces."""
    import jax

    from bigdl_tpu.nn.module import Context

    p, s = model.params(), model.state()

    @jax.jit
    def fwd(x):
        out, _ = model.apply(p, x, s,
                             Context(training=False,
                                     key=jax.random.PRNGKey(0)))
        return out

    np.asarray(fwd(rows[:1]))          # compile outside the clock
    lats = []
    t0 = time.perf_counter()
    for r in rows:
        t1 = time.perf_counter()
        np.asarray(fwd(r[None]))
        lats.append(time.perf_counter() - t1)
    wall = time.perf_counter() - t0
    return {"mode": "serial", "requests": len(rows), "wall_s": wall,
            "throughput_rps": len(rows) / wall,
            **_quantiles(lats)}


def _quantiles(lats):
    lats = np.asarray(lats, np.float64)
    return {f"p{q}_ms": float(np.percentile(lats, q)) * 1e3
            for q in (50, 95, 99)}


def engine_point(eng, rows, rate):
    """One sweep point: submit at ``rate`` req/s (inf = closed loop).
    Latency is submit->completion, stamped by a done-callback on the
    engine's compute thread (not when the collector happens to look)."""
    gap = 0.0 if np.isinf(rate) else 1.0 / rate
    done_at = [None] * len(rows)

    def _stamp(i):
        def cb(_f):
            done_at[i] = time.perf_counter()
        return cb

    futs = []
    t0 = time.perf_counter()
    for i, r in enumerate(rows):
        if gap:
            delay = t0 + i * gap - time.perf_counter()
            if delay > 0:
                time.sleep(delay)
        t_sub = time.perf_counter()
        f = eng.submit(r)
        f.add_done_callback(_stamp(i))
        futs.append((f, t_sub))
    for f, _ in futs:
        f.result()
    wall = time.perf_counter() - t0
    # result() waiters wake BEFORE done-callbacks run (CPython Future
    # semantics), so give the last stamps a moment to land
    t_spin = time.perf_counter()
    while any(d is None for d in done_at):
        if time.perf_counter() - t_spin > 5.0:
            raise RuntimeError("latency stamps missing after 5s")
        time.sleep(0.001)
    lats = [done - t_sub for (_, t_sub), done in zip(futs, done_at)]
    return {"mode": "engine", "offered_rps": None if np.isinf(rate)
            else rate, "requests": len(rows), "wall_s": wall,
            "throughput_rps": len(rows) / wall, **_quantiles(lats)}


def router_point(pool, rows, rate, slo_ms):
    """One router sweep point: submit at ``rate`` req/s through the
    pool; shed futures count against the shed rate, completions against
    throughput/latency."""
    from bigdl_tpu.serve import SheddedError

    gap = 0.0 if np.isinf(rate) else 1.0 / rate
    done_at = [None] * len(rows)

    def _stamp(i):
        def cb(_f):
            done_at[i] = time.perf_counter()
        return cb

    futs = []
    t0 = time.perf_counter()
    for i, r in enumerate(rows):
        if gap:
            delay = t0 + i * gap - time.perf_counter()
            if delay > 0:
                time.sleep(delay)
        t_sub = time.perf_counter()
        f = pool.submit(r, slo_ms=slo_ms or None)
        f.add_done_callback(_stamp(i))
        futs.append((t_sub, f))
    lats, shed = [], 0
    for i, (t_sub, f) in enumerate(futs):
        try:
            f.result()
        except SheddedError:
            shed += 1
            continue
        # completion stamped by the done-callback (result() waiters wake
        # before callbacks run — engine_point's spin covers the race)
        t_spin = time.perf_counter()
        while done_at[i] is None:
            if time.perf_counter() - t_spin > 5.0:
                raise RuntimeError("latency stamp missing after 5s")
            time.sleep(0.0005)
        lats.append(done_at[i] - t_sub)
    wall = time.perf_counter() - t0
    return {"offered_rps": None if np.isinf(rate) else rate,
            "requests": len(rows), "completed": len(lats), "shed": shed,
            "wall_s": wall, "throughput_rps": len(lats) / wall,
            "shed_rate": shed / len(rows),
            **(_quantiles(lats) if lats
               else {"p50_ms": None, "p95_ms": None, "p99_ms": None})}


def router_row(model_name, replicas, point, replica_stats,
               wall_s, quant="off", kv_quant="off") -> dict:
    """The pinned JSON contract for one ``--replicas`` sweep point:
    aggregate throughput/latency/shed plus a per-replica breakdown and
    the replica weight-quant recipe (``quant``/``kv_quant`` — KV quant
    never applies to the scoring path, the column keeps the row shape
    uniform with the decode sweep).  ``tests/test_serve_cluster.py``
    keeps this shape honest."""
    per_replica = [{"name": s.get("name", f"r{i}"),
                    "completed": s.get("completed", 0),
                    "rps": (s.get("completed", 0) / wall_s
                            if wall_s else 0.0),
                    "shed": s.get("shed", 0),
                    "alive": s.get("alive", True)}
                   for i, s in enumerate(replica_stats)]
    return {"model": model_name, "mode": "router",
            "replicas": replicas, "quant": quant, "kv_quant": kv_quant,
            **point, "per_replica": per_replica}


def bench_router(args):
    from bigdl_tpu.serve import ReplicaPool
    model, shape = _build(args.model)
    rng = np.random.RandomState(0)
    rows = rng.rand(args.requests, *shape).astype(np.float32)

    pool = ReplicaPool(model, n_replicas=args.replicas,
                       max_batch=args.max_batch,
                       max_wait_ms=args.max_wait_ms, input_shape=shape,
                       slo_ms=args.slo_ms or None, quant=args.quant)
    try:
        pool.predict(rows[:args.max_batch])          # warm every bucket
        prev = [r.stats() for r in pool.replicas]
        points = []
        for rate in args.loads:
            t0 = time.perf_counter()
            pt = router_point(pool, rows, rate, args.slo_ms)
            wall = time.perf_counter() - t0
            # per-replica deltas over this point (rate-differenced
            # monotonic counters — the documented stats contract)
            cur = [r.stats() for r in pool.replicas]
            deltas = [{"name": getattr(r, "name", f"r{i}"),
                       "completed": (c.get("completed", 0)
                                     - p.get("completed", 0)),
                       "shed": c.get("shed", 0) - p.get("shed", 0),
                       "alive": r.alive()}
                      for i, (r, p, c) in enumerate(
                          zip(pool.replicas, prev, cur))]
            prev = cur
            row = router_row(args.model, args.replicas, pt, deltas, wall,
                             quant=args.quant)
            points.append(row)
            print(f"bench_serve: {json.dumps(row)}")
        rstats = pool.router.stats()
    finally:
        pool.close()

    print(f"\n{args.model} router x{args.replicas}:")
    for pt in points:
        off = ("closed-loop" if pt["offered_rps"] is None
               else f"{pt['offered_rps']:g} req/s offered")
        per = ", ".join(f"{p['name']} {p['rps']:.0f} r/s"
                        for p in pt["per_replica"])
        p95 = pt["p95_ms"]
        print(f"  {off}: {pt['throughput_rps']:.1f} req/s aggregate "
              f"(shed {pt['shed_rate']:.1%}; "
              f"p95 {p95:.2f} ms; {per})" if p95 is not None else
              f"  {off}: everything shed")
    print(f"  router: accepted {rstats['accepted']}, completed "
          f"{rstats['completed']}, shed {rstats['shed']}, requeued "
          f"{rstats['requeued']}")
    return points


def bench_scoring(args):
    from bigdl_tpu.serve import ServeEngine
    model, shape = _build(args.model)
    rng = np.random.RandomState(0)
    rows = rng.rand(args.requests, *shape).astype(np.float32)

    base = serial_baseline(model, rows)
    print(f"bench_serve: {json.dumps({'model': args.model, **base})}")

    eng = ServeEngine(model, max_batch=args.max_batch,
                      max_wait_ms=args.max_wait_ms, input_shape=shape,
                      quant=args.quant)
    try:
        eng.predict(rows[:eng.max_batch])        # warm every hot bucket
        points = []
        for rate in args.loads:
            pt = engine_point(eng, rows, rate)
            pt["compiles"] = eng.stats()["compiles"]
            pt["quant"] = args.quant
            points.append(pt)
            print(f"bench_serve: {json.dumps({'model': args.model, **pt})}")
        stats = eng.stats()
    finally:
        eng.close()

    best = max(p["throughput_rps"] for p in points)
    ratio = best / base["throughput_rps"]
    print(f"\n{args.model}: serial {base['throughput_rps']:.1f} req/s "
          f"(p95 {base['p95_ms']:.2f} ms)")
    for pt in points:
        off = ("closed-loop" if pt["offered_rps"] is None
               else f"{pt['offered_rps']:g} req/s offered")
        print(f"  engine {off}: {pt['throughput_rps']:.1f} req/s, "
              f"p50 {pt['p50_ms']:.2f} / p95 {pt['p95_ms']:.2f} / "
              f"p99 {pt['p99_ms']:.2f} ms")
    print(f"  batching speedup (best/serial): {ratio:.2f}x; compiles "
          f"{stats['compiles']} (all warmup), bucket hits "
          f"{stats['bucket_hits']}")
    if args.check and ratio < 2.0:
        raise SystemExit(
            f"dynamic batching speedup {ratio:.2f}x < required 2x")
    return ratio


def bench_decode(args):
    from bigdl_tpu.models.transformer import TransformerLM, lm_decode
    from bigdl_tpu.serve.decode import ContinuousDecoder
    from bigdl_tpu.utils.random import set_seed
    set_seed(1)
    model = TransformerLM(vocab_size=128, d_model=64, n_heads=4,
                          n_layers=2, hidden=128)
    rng = np.random.RandomState(0)
    n_words = args.decode_words
    seeds = [rng.randint(1, 128, rng.randint(2, 6)).tolist()
             for _ in range(args.requests)]
    n_pos = max(len(s) for s in seeds) + n_words - 1

    # compile outside the clock — ONCE PER DISTINCT SEED LENGTH: the
    # serial path recompiles its scan for every (n_seed, n_pos) pair,
    # which is exactly the cold-compile tax the bucketed/slotted serving
    # paths exist to avoid; warming all shapes keeps the comparison to
    # steady-state math only
    for length in {len(s) for s in seeds}:
        lm_decode(model, seeds[0][:1] * length, n_words)
    t0 = time.perf_counter()
    for s in seeds:
        lm_decode(model, s, n_words)
    serial_wall = time.perf_counter() - t0
    toks = len(seeds) * n_words
    print(f"bench_serve: {json.dumps({'model': 'transformer', 'mode': 'serial', 'tokens': toks, 'wall_s': serial_wall, 'tok_per_s': toks / serial_wall})}")

    dec = ContinuousDecoder(model, max_slots=args.decode_slots,
                            n_pos=n_pos, sync_interval=args.decode_sync)
    futs = [dec.submit(seeds[0], n_words)]
    dec.run()                                    # compile outside clock
    t0 = time.perf_counter()
    futs = [dec.submit(s, n_words) for s in seeds]
    dec.run()
    cont_wall = time.perf_counter() - t0
    assert all(f.done() for f in futs)
    print(f"bench_serve: {json.dumps({'model': 'transformer', 'mode': 'continuous', 'tokens': toks, 'wall_s': cont_wall, 'tok_per_s': toks / cont_wall, **dec.stats()})}")
    print(f"\ntransformer decode: serial {toks / serial_wall:.1f} tok/s, "
          f"continuous ({args.decode_slots} slots) "
          f"{toks / cont_wall:.1f} tok/s "
          f"({serial_wall / cont_wall:.2f}x), host syncs "
          f"{dec.host_syncs} for {dec.steps} steps")
    return serial_wall / cont_wall


def decode_sweep_row(impl, offered, tokens, wall_s, dec_stats,
                     compiles, stream=None, attn_kernel=None) -> dict:
    """The pinned JSON contract for one ``--decode-sweep`` point:
    throughput per live slot plus the paging/prefix/speculation/quant
    counters that explain it, and the streaming SLO columns
    (``ttft_p50``/``ttft_p99``/``itl_p50``, milliseconds,
    client-observed through ``StreamFuture.on_tokens`` — None when the
    point did not stream, so old parsers keep working).
    ``attn_kernel`` names the Mosaic decode kernel active for the point
    (``--attn-kernel``; None — the default XLA gathered view — keeps
    old parsers working).  ``sampled``/``steps_saved`` surface the
    sampled-decode counters (None on points that used neither, so old
    parsers keep working).  ``tests/test_paged_decode.py`` keeps this
    shape honest."""
    live = dec_stats.get("live_hwm") or dec_stats["slots"]
    pool = dec_stats.get("pool") or {}
    prefix = dec_stats.get("prefix") or {}
    rate = tokens / wall_s if wall_s else 0.0
    pool_tokens = pool["pages"] * pool["page_size"] if pool else None
    bpt = dec_stats.get("kv_bytes_per_token")
    stream = stream or {}
    return {"model": "transformer", "mode": "decode_sweep", "impl": impl,
            "offered": offered, "tokens": tokens, "wall_s": wall_s,
            "tok_per_s": rate,
            "tok_per_s_per_slot": rate / max(1, live),
            "live_max": live, "slots": dec_stats["slots"],
            "pool_tokens": pool_tokens,
            # the quant columns: weight mode (decode serves fp weights),
            # KV-page mode, and the pooled-token HBM budget in BYTES —
            # the quantity held constant across fp-vs-int8 points
            "quant": dec_stats.get("quant", "off"),
            "kv_quant": dec_stats.get("kv_quant", "off"),
            "pool_bytes": (pool_tokens * bpt
                           if pool_tokens is not None and bpt else None),
            "spec_k": dec_stats.get("spec_k", 0),
            "accept_mean": dec_stats.get("accept_mean"),
            "accept_p50": dec_stats.get("accept_p50"),
            "prefix_hits": prefix.get("hits", 0),
            "ttft_p50": stream.get("ttft_p50"),
            "ttft_p99": stream.get("ttft_p99"),
            "itl_p50": stream.get("itl_p50"),
            "e2e_p50": stream.get("e2e_p50"),
            "attn_kernel": attn_kernel,
            "sampled": dec_stats.get("sampled") or None,
            "steps_saved": dec_stats.get("steps_saved") or None,
            "compiles": compiles}


def bench_decode_sweep(args):
    from bigdl_tpu import quant
    from bigdl_tpu.models.transformer import TransformerLM, lm_decode
    from bigdl_tpu.quant import kv as kvq
    from bigdl_tpu.serve import xcache
    from bigdl_tpu.serve.decode import ContinuousDecoder
    from bigdl_tpu.utils.random import set_seed
    set_seed(1)
    model = TransformerLM(vocab_size=128, d_model=64, n_heads=4,
                          n_layers=2, hidden=128)
    rng = np.random.RandomState(0)
    n_words, ps = args.decode_words, args.page_size
    seeds = [rng.randint(1, 128, rng.randint(2, 6)).tolist()
             for _ in range(args.requests)]
    n_pos = max(args.decode_npos,
                max(len(s) for s in seeds) + n_words - 1)
    slab_slots = args.decode_slots
    # the FIXED HBM budget both implementations get: what the slab holds
    pool_pages = slab_slots * (-(-n_pos // ps))
    toks = len(seeds) * n_words
    kv_quant = args.kv_quant

    # serial oracle (and scan warmup per distinct seed length)
    for length in {len(s) for s in seeds}:
        lm_decode(model, [1] * length, n_words)
    oracle = [lm_decode(model, s, n_words) for s in seeds]

    # --attn-kernel: flip the Mosaic decode-kernel flags for the sweep's
    # paged points (interpreter off-TPU, the staged on-chip A/B runs the
    # same command with a chip attached); each row's attn_kernel column
    # records what was ACTIVE for that point, None = XLA gathered view
    from bigdl_tpu.models import transformer as _tf
    from bigdl_tpu.ops import pallas_kernels as _pk
    attn_mode = getattr(args, "attn_kernel", "off")
    _flags_prev = (_tf._PALLAS_PAGED_ATTN, _tf._PALLAS_SPEC_VERIFY)
    if attn_mode != "off":
        on = True if _pk._on_tpu() else "interpret"
        if attn_mode in ("paged", "paged+spec"):
            _tf._PALLAS_PAGED_ATTN = on
        if attn_mode in ("spec", "paged+spec"):
            _tf._PALLAS_SPEC_VERIFY = on

    def _active_attn_kernel(kw):
        parts = []
        if kw.get("page_size") is not None:
            if _tf._PALLAS_PAGED_ATTN:
                parts.append("paged")
            if kw.get("spec_k") and _tf._PALLAS_SPEC_VERIFY:
                parts.append("spec")
        return "+".join(parts) or None

    def run_point(impl, offered, sampling=None, parity_mode="exact",
                  **kw):
        # ``sampling`` is a per-request list of SamplingParams dicts
        # (None entries stay greedy); ``parity_mode`` picks the oracle
        # comparison — "exact" (every row byte-identical), "greedy_rows"
        # (only the greedy rows of a mixed-param stream), "prefix"
        # (stop-retired rows are exact PREFIXES of their oracle rows),
        # or "none" (sampled rows have no greedy oracle — parity=None
        # keeps the --check fp gate out of their way)
        dec = ContinuousDecoder(model, n_pos=n_pos,
                                sync_interval=args.decode_sync, **kw)
        c0 = xcache.get().stats()["compiles"]
        # every point streams: per-request token-arrival stamps give
        # the client-observed TTFT/ITL columns, and the chunk-sum
        # parity check below holds the streamed sequence to the
        # all-at-once result (zero compiled-program cost — delivery is
        # host bookkeeping on the boundary's existing materialization)
        arrivals = [[] for _ in seeds]
        sub_at = [0.0] * len(seeds)
        done_at = [None] * len(seeds)
        t0 = time.perf_counter()
        futs = []
        for i, s in enumerate(seeds):
            sub_at[i] = time.perf_counter()
            f = dec.submit(s, n_words,
                           sampling=sampling[i] if sampling else None)
            f.on_tokens(lambda toks, i=i: arrivals[i].append(
                (time.perf_counter(), len(toks))))
            f.add_done_callback(lambda _f, i=i: done_at.__setitem__(
                i, time.perf_counter()))
            futs.append(f)
        dec.run()
        wall = time.perf_counter() - t0
        rows = [f.result() for f in futs]
        t_spin = time.perf_counter()
        while any(d is None for d in done_at):   # callbacks race result()
            if time.perf_counter() - t_spin > 5.0:
                raise RuntimeError("latency stamps missing after 5s")
            time.sleep(0.001)
        streamed = [f.streamed() for f in futs]
        stream_parity = all(
            st == list(r[len(s):])
            for st, r, s in zip(streamed, rows, seeds))
        ttfts = [a[0][0] - sub_at[i]
                 for i, a in enumerate(arrivals) if a]
        itls = []
        for a in arrivals:
            for (t1, _n1), (t2, n2) in zip(a, a[1:]):
                itls += [(t2 - t1) / n2] * n2
        e2e = [d - s for d, s in zip(done_at, sub_at)]

        def pct(vals, q):
            return (float(np.percentile(np.asarray(vals), q)) * 1e3
                    if vals else None)

        stream = {"ttft_p50": pct(ttfts, 50), "ttft_p99": pct(ttfts, 99),
                  "itl_p50": pct(itls, 50), "e2e_p50": pct(e2e, 50)}
        # per-token agreement with the serial fp oracle over the
        # GENERATED tail (truncated to the replayed row's length, so
        # stop-retired rows compare what they actually generated): 1.0
        # on every fp greedy point (exact parity contract); sampled
        # rows and quantized-KV points may diverge within their budget
        agree = float(np.mean([
            np.mean(np.asarray(r[len(s):])
                    == np.asarray(o[len(s):len(r)]))
            for r, o, s in zip(rows, oracle, seeds)]))
        n_tok = sum(len(r) - len(s) for r, s in zip(rows, seeds))
        row = decode_sweep_row(impl, offered, n_tok, wall, dec.stats(),
                               xcache.get().stats()["compiles"] - c0,
                               stream=stream,
                               attn_kernel=_active_attn_kernel(kw))
        if parity_mode == "exact":
            row["parity"] = rows == oracle
        elif parity_mode == "greedy_rows":
            row["parity"] = all(
                r == o for r, o, sp in zip(rows, oracle, sampling)
                if sp is None)
        elif parity_mode == "prefix":
            row["parity"] = all(
                len(r) <= len(o) and list(r) == list(o[:len(r)])
                for r, o in zip(rows, oracle))
        else:
            row["parity"] = None
        row["stream_parity"] = stream_parity
        row["agreement"] = agree
        dec.close()
        print(f"bench_serve: {json.dumps(row)}")
        return row

    try:
        points = [run_point("slab", slab_slots, max_slots=slab_slots,
                            paged=False)]
        for offered in (slab_slots, 2 * slab_slots, 4 * slab_slots):
            points.append(run_point(
                "paged", offered, max_slots=offered, page_size=ps,
                n_pages=pool_pages, prefix_cache=False))
        spec = run_point("paged+spec", 2 * slab_slots,
                         max_slots=2 * slab_slots, page_size=ps,
                         n_pages=pool_pages, prefix_cache=True,
                         spec_k=args.spec_k)
        points.append(spec)

        # the sampled-decode points ride the SAME paged config as
        # points[1] (offered == slots), so a cold compile here would
        # mean sampling params leaked into the program shape
        samp = run_point(
            "paged+sampled", slab_slots, max_slots=slab_slots,
            page_size=ps, n_pages=pool_pages, prefix_cache=False,
            parity_mode="none",
            sampling=[{"temperature": args.temperature,
                       "top_k": args.top_k, "top_p": args.top_p,
                       "seed": 1000 + i} for i in range(len(seeds))])
        points.append(samp)

        # mixed-param rotation: greedy / temp / temp+top_k / temp+top_p
        # interleave across one stream — one compiled program serves
        # all four, and the greedy rows must stay byte-identical
        def _rot(i):
            j = i % 4
            if j == 0:
                return None
            p = {"temperature": args.temperature, "seed": 2000 + i}
            if j == 2:
                p["top_k"] = args.top_k or 8
            elif j == 3:
                p["top_p"] = args.top_p or 0.9
            return p
        mixed = run_point(
            "paged+mixed", slab_slots, max_slots=slab_slots,
            page_size=ps, n_pages=pool_pages, prefix_cache=False,
            parity_mode="greedy_rows",
            sampling=[_rot(i) for i in range(len(seeds))])
        points.append(mixed)

        # stop-sequence early retirement: each request's stop is cut
        # from its OWN greedy oracle a quarter of the way in, so every
        # row retires early and the point's rows/s beats the full run
        cut = max(1, n_words // 4)
        stop_pt = run_point(
            "paged+stop", slab_slots, max_slots=slab_slots,
            page_size=ps, n_pages=pool_pages, prefix_cache=False,
            max_stop_len=max(8, args.stop_len), parity_mode="prefix",
            sampling=[{"stop": [list(o[len(s):])[
                max(0, cut - args.stop_len):cut]]}
                for s, o in zip(seeds, oracle)])
        points.append(stop_pt)

        qpoints = []
        qspec = None
        if kv_quant != "off":
            # int8 KV points at the SAME pooled-token HBM BUDGET: the
            # fp pool's bytes re-divided by the quantized bytes/token
            # (scales included), so extra live concurrency is pure
            # density win
            from bigdl_tpu.models.transformer import _lm_handles
            h = _lm_handles(model)
            budget_bytes = pool_pages * ps * kvq.bytes_per_token(
                h.n_layers, h.n_heads, h.hd, "off")
            pages_q = budget_bytes // (ps * kvq.bytes_per_token(
                h.n_layers, h.n_heads, h.hd, kv_quant))
            for offered in (2 * slab_slots, 4 * slab_slots,
                            8 * slab_slots):
                qpoints.append(run_point(
                    f"paged[{kv_quant}]", offered, max_slots=offered,
                    page_size=ps, n_pages=pages_q, prefix_cache=False,
                    kv_quant=kv_quant))
            qspec = run_point(f"paged+spec[{kv_quant}]", 4 * slab_slots,
                              max_slots=4 * slab_slots, page_size=ps,
                              n_pages=pages_q, prefix_cache=True,
                              spec_k=args.spec_k, kv_quant=kv_quant)
            qpoints.append(qspec)
            points += qpoints
    finally:
        (_tf._PALLAS_PAGED_ATTN, _tf._PALLAS_SPEC_VERIFY) = _flags_prev

    slab = points[0]
    print(f"\ntransformer decode sweep (pool {pool_pages} pages x {ps} "
          f"tokens = slab {slab_slots} x {n_pos}"
          + (f"; kv_quant={kv_quant}" if kv_quant != "off" else "")
          + "):")
    for pt in points:
        ttft = pt.get("ttft_p50")
        print(f"  {pt['impl']:<12} offered {pt['offered']:>3}: "
              f"{pt['live_max']:>3} live max, "
              f"{pt['tok_per_s']:8.1f} tok/s "
              f"({pt['tok_per_s_per_slot']:.1f}/slot), "
              f"agreement {pt['agreement']:.3f}, "
              f"cold compiles {pt['compiles']}"
              + (f", ttft p50 {ttft:.1f} ms / itl p50 "
                 + (f"{pt['itl_p50']:.2f} ms" if pt["itl_p50"]
                    is not None else "-")
                 if ttft is not None else "")
              + (f", accept mean {pt['accept_mean']:.2f}"
                 if pt["spec_k"] else "")
              + (f", sampled {pt['sampled']}" if pt["sampled"] else "")
              + (f", steps saved {pt['steps_saved']}"
                 if pt["steps_saved"] else ""))
    scaled = [p for p in points if p["impl"] == "paged"
              and p["offered"] > slab_slots]
    best_live = max(p["live_max"] for p in scaled)
    # the fp pool's live bound is only MEASURED when some fp point is
    # pool-bound (live < offered — admission queued on page exhaustion);
    # an offered-limited ladder underestimates it, which would make the
    # quant density ratio below spuriously strict
    fp_saturated = any(p["live_max"] < p["offered"] for p in scaled)
    print(f"  live-concurrency: slab bound {slab['live_max']}, paged "
          f"reaches {best_live} on the same pooled tokens"
          + ("" if fp_saturated else
             " (fp pool never saturated at this offered ladder)"))
    if qpoints:
        best_live_q = max(p["live_max"] for p in qpoints)
        print(f"  {kv_quant} KV at the same HBM budget: {best_live_q} "
              f"live ({best_live_q / max(1, best_live):.2f}x the fp-KV "
              f"bound), agreement >= "
              f"{min(p['agreement'] for p in qpoints):.3f}")
    if args.check:
        fp_points = [p for p in points if p["kv_quant"] == "off"
                     and p["parity"] is not None]
        if not all(p["parity"] for p in fp_points):
            raise SystemExit("decode sweep lost token parity")
        if not all(p["stream_parity"] for p in points):
            raise SystemExit("streamed chunks diverged from the "
                             "all-at-once rows")
        # the streaming SLO point: on a long generation (n_words spans
        # several sync boundaries) the first token must land well
        # before retire — TTFT below the e2e completion latency
        lp = points[1]     # paged @ offered == slots: uncontended
        if (lp["ttft_p50"] is not None and lp["e2e_p50"] is not None
                and lp["ttft_p50"] >= lp["e2e_p50"]):
            raise SystemExit(
                f"streaming ttft p50 {lp['ttft_p50']:.1f} ms did not "
                f"beat the e2e p50 {lp['e2e_p50']:.1f} ms on a "
                f"long-generation point")
        if best_live <= slab["live_max"]:
            raise SystemExit(
                f"paged concurrency {best_live} did not scale past the "
                f"slab bound {slab['live_max']}")
        if spec["compiles"]:
            raise SystemExit(
                f"speculative stream hit {spec['compiles']} cold "
                f"compiles after warmup")
        # sampled decode rides the greedy fast path: same compiled
        # program (zero cold compiles on sampled AND mixed-param
        # streams) at no worse than a 10% throughput haircut
        base = points[1]       # greedy paged @ offered == slots
        for pt in (samp, mixed):
            if pt["compiles"]:
                raise SystemExit(
                    f"{pt['impl']} stream hit {pt['compiles']} cold "
                    f"compiles — sampling params leaked into the "
                    f"program shape")
        if samp["tok_per_s"] < 0.9 * base["tok_per_s"]:
            raise SystemExit(
                f"sampled throughput {samp['tok_per_s']:.1f} tok/s "
                f"fell below 0.9x the greedy point "
                f"{base['tok_per_s']:.1f} tok/s")
        if not stop_pt["steps_saved"]:
            raise SystemExit("stop point retired no request early")
        if stop_pt["wall_s"] >= base["wall_s"]:
            raise SystemExit(
                f"stop-retirement point took {stop_pt['wall_s']:.2f}s "
                f"for the same request count the greedy point "
                f"finished in {base['wall_s']:.2f}s — early "
                f"retirement saved nothing")
        if qpoints:
            if not fp_saturated:
                print("  note: density gate not evaluable — the fp "
                      "pool never saturated at this offered ladder; "
                      "raise --requests or lower --decode-npos to "
                      "measure the fp live bound")
            elif best_live_q < 1.8 * best_live:
                raise SystemExit(
                    f"{kv_quant} KV live-concurrency {best_live_q} < "
                    f"1.8x the fp bound {best_live} at equal HBM")
            worst = min(p["agreement"] for p in qpoints)
            if worst < 1.0 - quant.KV_TOKEN_DRIFT_BUDGET:
                raise SystemExit(
                    f"{kv_quant} KV greedy drift {1 - worst:.3f} "
                    f"exceeds the declared budget "
                    f"{quant.KV_TOKEN_DRIFT_BUDGET}")
            if qspec["compiles"]:
                raise SystemExit(
                    f"quantized speculative stream hit "
                    f"{qspec['compiles']} cold compiles after warmup")
            if (spec["accept_p50"] is not None
                    and qspec["accept_p50"] is not None
                    and abs(spec["accept_p50"]
                            - qspec["accept_p50"]) > 1):
                raise SystemExit(
                    f"quantized spec acceptance p50 "
                    f"{qspec['accept_p50']} drifted more than one "
                    f"bucket from fp {spec['accept_p50']}")
    return points


# ---------------------------------------------------------------------------
# open-loop traffic generator (--traffic; docs/serving.md "Autoscaling")
# ---------------------------------------------------------------------------

def traffic_envelope(t: float, base_rps: float, burst_factor: float = 1.0,
                     burst_start_s: float = 0.0, burst_len_s: float = 0.0,
                     diurnal_amp: float = 0.0,
                     diurnal_period_s: float = 60.0) -> float:
    """Offered rate (req/s) at offset ``t``: the base rate modulated by
    a sinusoidal diurnal envelope (``amp`` in [0, 1) scales the swing)
    and multiplied by ``burst_factor`` inside the declared burst window
    ``[burst_start_s, burst_start_s + burst_len_s)`` — the overload
    window the chaos drill asserts sheds stay inside."""
    rate = base_rps
    if diurnal_amp:
        rate *= 1.0 + diurnal_amp * math.sin(
            2.0 * math.pi * t / max(diurnal_period_s, 1e-9))
    if burst_len_s > 0 and burst_start_s <= t < burst_start_s + burst_len_s:
        rate *= burst_factor
    return max(rate, 1e-9)


def traffic_arrivals(rng, n: int, base_rps: float, **envelope) -> list:
    """``n`` seeded open-loop arrival offsets (seconds from start):
    Poisson arrivals whose instantaneous rate follows
    :func:`traffic_envelope` (each inter-arrival gap drawn at the rate
    in effect at the PREVIOUS arrival — piecewise approximation of the
    inhomogeneous process, deterministic under a seeded ``rng``)."""
    t, out = 0.0, []
    for _ in range(int(n)):
        t += rng.exponential(1.0 / traffic_envelope(t, base_rps,
                                                    **envelope))
        out.append(t)
    return out


def parse_priority_mix(s: str) -> list:
    """``"0:0.2,2:0.8"`` → normalized ``[(class, weight), ...]`` —
    the mixed-priority-class contract of the ``--traffic`` flag."""
    out = []
    for tok in str(s).split(","):
        tok = tok.strip()
        if not tok:
            continue
        cls, w = tok.split(":")
        out.append((int(cls), float(w)))
    if not out:
        raise ValueError(f"empty priority mix: {s!r}")
    total = sum(w for _, w in out)
    if total <= 0:
        raise ValueError(f"priority mix weights sum to {total}: {s!r}")
    return [(c, w / total) for c, w in out]


def traffic_priorities(rng, n: int, mix) -> list:
    """``n`` seeded priority classes drawn from a normalized mix."""
    classes = [c for c, _ in mix]
    probs = [w for _, w in mix]
    return [int(c) for c in rng.choice(classes, size=int(n), p=probs)]


def traffic_row(model_name, spec: dict, outcome: dict,
                autoscale: dict | None = None,
                families: int | None = None) -> dict:
    """The pinned JSON contract for one ``--traffic`` run: the seeded
    traffic spec (replayable), the resolution accounting (accepted ==
    completed + failed + shed — every future resolves exactly once),
    the shed split against the DECLARED overload window, per-priority
    outcomes, and the autoscaler's actions when one ran.
    ``tests/test_autoscale.py::TestBenchTrafficContract`` keeps this
    shape honest."""
    row = {"model": model_name, "mode": "traffic", "families": families,
           **spec, **outcome}
    scale = autoscale or {}
    row.update(autoscale=bool(autoscale),
               scale_ups=scale.get("scale_ups", 0),
               scale_downs=scale.get("scale_downs", 0),
               replicas_start=scale.get("replicas_start"),
               replicas_final=scale.get("replicas_final"))
    return row


def run_traffic(submit, rows, arrivals, priorities, burst_window,
                timeout: float = 300.0) -> dict:
    """Drive one open-loop traffic schedule: ``submit(row, priority)``
    at each arrival offset, resolve every future, and account each
    exactly once (completed / shed / failed — the capstone bar).
    ``burst_window = (t0, t1)`` splits sheds into in-window vs outside
    (the declared-overload assertion)."""
    from bigdl_tpu.serve import SheddedError

    done_at = [None] * len(rows)

    def _stamp(i):
        def cb(_f):
            done_at[i] = time.perf_counter()
        return cb

    futs = []
    t0 = time.perf_counter()
    for i, (r, off, p) in enumerate(zip(rows, arrivals, priorities)):
        delay = t0 + off - time.perf_counter()
        if delay > 0:
            time.sleep(delay)
        t_sub = time.perf_counter()
        f = submit(r, p)
        f.add_done_callback(_stamp(i))
        futs.append((f, t_sub, off))
    lats, shed_in, shed_out = [], 0, 0
    per: dict = {}
    completed = failed = shed = 0
    for i, ((f, t_sub, off), p) in enumerate(zip(futs, priorities)):
        d = per.setdefault(p, {"priority": p, "requests": 0,
                               "completed": 0, "shed": 0, "failed": 0})
        d["requests"] += 1
        try:
            f.result(timeout=timeout)
        except SheddedError:
            shed += 1
            d["shed"] += 1
            if burst_window[0] <= off <= burst_window[1]:
                shed_in += 1
            else:
                shed_out += 1
            continue
        except Exception:
            failed += 1
            d["failed"] += 1
            continue
        completed += 1
        d["completed"] += 1
        t_spin = time.perf_counter()
        while done_at[i] is None:    # callbacks race result()
            if time.perf_counter() - t_spin > 5.0:
                raise RuntimeError("latency stamp missing after 5s")
            time.sleep(0.0005)
        lats.append(done_at[i] - t_sub)
    wall = time.perf_counter() - t0
    n = len(rows)
    return {"requests": n, "wall_s": wall,
            "offered_rps": n / arrivals[-1] if arrivals[-1] else None,
            "accepted": n, "completed": completed, "shed": shed,
            "failed": failed,
            "throughput_rps": completed / wall if wall else 0.0,
            "shed_rate": shed / n if n else 0.0,
            "shed_in_window": shed_in, "shed_outside_window": shed_out,
            **(_quantiles(lats) if lats
               else {"p50_ms": None, "p95_ms": None, "p99_ms": None}),
            "per_priority": [per[k] for k in sorted(per)]}


def bench_traffic(args):
    """``--traffic``: seeded bursty/diurnal open-loop load — mixed
    priority classes, Poisson arrivals, the declared overload window —
    through a ReplicaPool (scoring models) or a DecodeFleet with
    shared-prefix families (``--model transformer``), optionally with
    the SLO-driven autoscaler closed-loop (``--autoscale``)."""
    spec = {"requests": args.requests, "seed": args.traffic_seed,
            "base_rps": args.base_rps, "burst_factor": args.burst_factor,
            "burst_start_s": args.burst_start_s,
            "burst_len_s": args.burst_len_s,
            "diurnal_amp": args.diurnal_amp,
            "diurnal_period_s": args.diurnal_period_s,
            "priority_mix": args.priority_mix}
    envelope = dict(burst_factor=args.burst_factor,
                    burst_start_s=args.burst_start_s,
                    burst_len_s=args.burst_len_s,
                    diurnal_amp=args.diurnal_amp,
                    diurnal_period_s=args.diurnal_period_s)
    rng = np.random.RandomState(args.traffic_seed)
    arrivals = traffic_arrivals(rng, args.requests, args.base_rps,
                                **envelope)
    priorities = traffic_priorities(
        rng, args.requests, parse_priority_mix(args.priority_mix))
    burst_window = (args.burst_start_s,
                    args.burst_start_s + args.burst_len_s
                    + args.burst_margin_s)

    def autoscale_of(target):
        if not args.autoscale:
            return None, None
        scaler = target.start_autoscaler(
            min_replicas=args.min_replicas or args.replicas,
            max_replicas=args.max_replicas,
            interval=args.scale_interval, window_s=args.scale_interval * 4)
        return scaler, len(target.replicas)

    families = None
    if args.model == "transformer":
        from bigdl_tpu.models.transformer import TransformerLM
        from bigdl_tpu.serve.fleet import DecodeFleet
        from bigdl_tpu.utils.random import set_seed
        set_seed(1)
        model = TransformerLM(vocab_size=128, d_model=64, n_heads=4,
                              n_layers=2, hidden=128)
        families = args.families
        seeds, _f = fleet_families(rng, args.families, args.requests,
                                   args.zipf_a, args.prefix_pages,
                                   args.page_size, 128)
        n_pos = max(len(s) for s in seeds) + args.decode_words - 1
        fleet = DecodeFleet(model, n_decode=args.replicas,
                            slo_ms=args.slo_ms or None,
                            max_slots=args.decode_slots, n_pos=n_pos,
                            page_size=args.page_size,
                            sync_interval=args.decode_sync)
        scaler, start = autoscale_of(fleet)
        try:
            outcome = run_traffic(
                lambda s, p: fleet.submit(s, args.decode_words,
                                          priority=p,
                                          slo_ms=args.slo_ms or None),
                seeds, arrivals, priorities, burst_window)
            rstats = fleet.router.stats()
            scale = None if scaler is None else {
                "scale_ups": scaler.scale_ups,
                "scale_downs": scaler.scale_downs,
                "replicas_start": start,
                "replicas_final": len(fleet.replicas)}
        finally:
            fleet.close()
    else:
        from bigdl_tpu.serve import ReplicaPool
        model, shape = _build(args.model)
        rows = rng.rand(args.requests, *shape).astype(np.float32)
        pool = ReplicaPool(model, n_replicas=args.replicas,
                           max_batch=args.max_batch,
                           max_wait_ms=args.max_wait_ms,
                           input_shape=shape,
                           slo_ms=args.slo_ms or None, quant=args.quant)
        scaler, start = autoscale_of(pool)
        try:
            # warm every bucket OUTSIDE the SLO policy (slo_ms=0 = no
            # deadline): a cold-compile warmup burst must not shed
            for f in pool.submit_many(rows[:args.max_batch], slo_ms=0):
                f.result(timeout=300)
            outcome = run_traffic(
                lambda r, p: pool.submit(r, priority=p,
                                         slo_ms=args.slo_ms or None),
                rows, arrivals, priorities, burst_window)
            rstats = pool.router.stats()
            scale = None if scaler is None else {
                "scale_ups": scaler.scale_ups,
                "scale_downs": scaler.scale_downs,
                "replicas_start": start,
                "replicas_final": len(pool.replicas)}
        finally:
            pool.close()

    row = traffic_row(args.model, spec, outcome, autoscale=scale,
                      families=families)
    print(f"bench_serve: {json.dumps(row)}")
    print(f"\n{args.model} traffic ({args.requests} req, base "
          f"{args.base_rps:g} rps, burst x{args.burst_factor:g} @ "
          f"[{args.burst_start_s:g}, "
          f"{args.burst_start_s + args.burst_len_s:g}]s):")
    print(f"  {outcome['throughput_rps']:.1f} req/s served; "
          f"completed {outcome['completed']}, shed {outcome['shed']} "
          f"({outcome['shed_in_window']} in window / "
          f"{outcome['shed_outside_window']} outside), failed "
          f"{outcome['failed']}")
    if outcome["p95_ms"] is not None:
        print(f"  p50 {outcome['p50_ms']:.2f} / p95 "
              f"{outcome['p95_ms']:.2f} / p99 "
              f"{outcome['p99_ms']:.2f} ms")
    if scale:
        print(f"  autoscale: +{scale['scale_ups']}/"
              f"-{scale['scale_downs']} "
              f"({scale['replicas_start']} → "
              f"{scale['replicas_final']} replicas)")
    if args.check:
        total = (outcome["completed"] + outcome["shed"]
                 + outcome["failed"])
        if total != outcome["accepted"]:
            raise SystemExit(
                f"resolution accounting broken: completed+shed+failed "
                f"{total} != accepted {outcome['accepted']}")
        if rstats["failed"] != outcome["failed"]:
            raise SystemExit(
                f"router failed count {rstats['failed']} != observed "
                f"{outcome['failed']}")
    return row


def fleet_families(rng, n_families: int, n_requests: int, zipf_a: float,
                   prefix_pages: int, page_size: int, vocab: int,
                   suffix_max: int = 3):
    """Shared-prefix request families: ``n_families`` fixed prefixes of
    ``prefix_pages`` full pages each, requests drawing their family
    Zipf(``zipf_a``)-distributed (family 0 hottest) with a short random
    suffix — the system-prompt traffic shape affinity routing and the
    host tier exist for.  Returns ``(seeds, family_ids)``."""
    plen = prefix_pages * page_size
    prefixes = [rng.randint(1, vocab, plen).tolist()
                for _ in range(n_families)]
    w = 1.0 / np.power(np.arange(1, n_families + 1), zipf_a)
    w /= w.sum()
    fams = rng.choice(n_families, size=n_requests, p=w)
    seeds = [prefixes[f] + rng.randint(1, vocab,
                                       1 + rng.randint(suffix_max)).tolist()
             for f in fams]
    return seeds, [int(f) for f in fams]


def fleet_row(impl, replicas, prefill_replicas, families, zipf_a,
              requests, tokens, wall_s, router_stats,
              replica_stats, transport: str = "inproc",
              ship_bytes_per_s: float = 0.0) -> dict:
    """The pinned JSON contract for one ``--fleet-sweep`` point:
    fleet-aggregate throughput plus the affinity/prefill/host-tier
    counters that explain it and a per-replica breakdown (role-labelled
    — prefill replicas ride along with their ship counts).
    ``transport`` names the replica wire (inproc/stdio/tcp) and
    ``ship_bytes_per_s`` the prefill→decode KV-page payload rate over
    it (0.0 without prefill replicas) — both default-valued so parsers
    of the pre-transport contract keep working.
    ``tests/test_fleet.py::TestBenchFleetContract`` keeps this shape
    honest."""
    per_replica, hits, misses, readmitted = [], 0, 0, 0
    for s in replica_stats:
        entry = {"name": s.get("name", "?"), "role": s.get("role", "?"),
                 "alive": s.get("alive", True)}
        if s.get("role") == "decode":
            pfx = s.get("prefix") or {}
            entry.update(admitted=s.get("admitted", 0),
                         prefix_hits=pfx.get("hits", 0),
                         prefix_misses=pfx.get("misses", 0))
            hits += pfx.get("hits", 0)
            misses += pfx.get("misses", 0)
            readmitted += (s.get("kv_host") or {}).get("readmitted", 0)
        else:
            entry.update(prefills=s.get("prefills", 0),
                         pages_shipped=s.get("pages_shipped", 0))
        per_replica.append(entry)
    rate = tokens / wall_s if wall_s else 0.0
    return {"model": "transformer", "mode": "fleet_sweep", "impl": impl,
            "replicas": replicas, "prefill_replicas": prefill_replicas,
            "families": families, "zipf_a": zipf_a,
            "requests": requests, "tokens": tokens, "wall_s": wall_s,
            "tok_per_s": rate,
            "hit_rate": hits / max(1, hits + misses),
            "affinity_hits": router_stats.get("affinity_hits", 0),
            "affinity_misses": router_stats.get("affinity_misses", 0),
            "prefill_shipped": router_stats.get("prefill_shipped", 0),
            "prefill_fallback": router_stats.get("prefill_fallback", 0),
            "prefill_skipped": router_stats.get("prefill_skipped", 0),
            "kv_host_readmitted": readmitted,
            "transport": transport,
            "ship_bytes_per_s": float(ship_bytes_per_s),
            "per_replica": per_replica}


def bench_fleet(args):
    """``--fleet-sweep``: the same Zipf shared-prefix family stream
    through a least-loaded fleet and an affinity-routed fleet — the
    per-replica prefix hit-rate recovery (and, with
    ``--prefill-replicas`` / ``--host-mb``, the prefill offload and
    host-tier re-admits) is the headline."""
    from bigdl_tpu.models.transformer import TransformerLM, lm_decode
    from bigdl_tpu.serve.fleet import DecodeFleet
    from bigdl_tpu.utils.random import set_seed
    set_seed(1)
    model = TransformerLM(vocab_size=128, d_model=64, n_heads=4,
                          n_layers=2, hidden=128)
    rng = np.random.RandomState(0)
    ps, n_words = args.page_size, args.decode_words
    seeds, _fams = fleet_families(
        rng, args.families, args.requests, args.zipf_a,
        args.prefix_pages, ps, 128)
    n_pos = max(len(s) for s in seeds) + n_words - 1
    toks = len(seeds) * n_words

    for length in sorted({len(s) for s in seeds}):
        lm_decode(model, [1] * length, n_words)
    oracle = [lm_decode(model, s, n_words) for s in seeds]

    transport = getattr(args, "transport", "inproc")

    def ship_bytes_total():
        from bigdl_tpu.obs import metrics as obs_metrics
        fam = obs_metrics.get().snapshot().get("fleet_ship_bytes_total")
        return sum(r.get("value", 0.0) for r in (fam or {}).get(
            "series", []))

    def run_point(impl, affinity):
        kw = {}
        agents = []
        if transport == "stdio":
            kw["process"] = True
        elif transport == "tcp":
            from bigdl_tpu.serve.remote import spawn_agent
            agents = [spawn_agent(token="bench")
                      for _ in range(args.replicas
                                     + args.prefill_replicas)]
            kw.update(hosts=[a.addr for a in agents], token="bench")
        try:
            fleet = DecodeFleet(
                model, n_decode=args.replicas,
                n_prefill=args.prefill_replicas, affinity=affinity,
                host_mb=args.host_mb or None,
                max_slots=args.decode_slots,
                n_pos=n_pos, page_size=ps,
                sync_interval=args.decode_sync,
                kv_quant=args.kv_quant, **kw)
        except Exception:
            for a in agents:
                a.close()
            raise
        ship0 = ship_bytes_total()
        t0 = time.perf_counter()
        futs = fleet.submit_many(seeds, n_words)
        rows = [f.result(timeout=600) for f in futs]
        wall = time.perf_counter() - t0
        shipped_b = ship_bytes_total() - ship0
        st = fleet.stats()
        row = fleet_row(impl, args.replicas, args.prefill_replicas,
                        args.families, args.zipf_a, len(seeds), toks,
                        wall, st["router"], st["replicas"],
                        transport=transport,
                        ship_bytes_per_s=(shipped_b / wall if wall
                                          else 0.0))
        row["parity"] = rows == oracle if args.kv_quant == "off" else None
        row["agreement"] = float(np.mean([
            np.mean(np.asarray(r[len(s):]) == np.asarray(o[len(s):]))
            for r, o, s in zip(rows, oracle, seeds)]))
        fleet.close()
        for a in agents:
            a.close()
        print(f"bench_serve: {json.dumps(row)}")
        return row

    base = run_point("least_loaded", affinity=False)
    aff = run_point("affinity", affinity=True)

    print(f"\ntransformer fleet sweep ({args.replicas} decode + "
          f"{args.prefill_replicas} prefill over {transport}; "
          f"{args.families} families, "
          f"zipf {args.zipf_a}, {len(seeds)} requests):")
    for pt in (base, aff):
        ship = (f", ship {pt['ship_bytes_per_s'] / 1e6:.2f} MB/s"
                if pt["ship_bytes_per_s"] else "")
        print(f"  {pt['impl']:<13} {pt['tok_per_s']:8.1f} tok/s, "
              f"prefix hit-rate {pt['hit_rate']:.0%}, affinity "
              f"{pt['affinity_hits']}/{pt['affinity_hits'] + pt['affinity_misses']}, "
              f"shipped {pt['prefill_shipped']}, agreement "
              f"{pt['agreement']:.3f}{ship}")
    if args.prefill_replicas:
        # shipped pages equalize the ADMISSION hit rate (every request
        # adopts its chain), so affinity's win shows as prefill work
        # SHED instead: hops skipped because the pick already cached it
        print(f"  affinity skipped {aff['prefill_skipped']} prefill "
              f"hops (least-loaded skipped "
              f"{base['prefill_skipped']})")
    else:
        ratio = (aff["hit_rate"] / base["hit_rate"]
                 if base["hit_rate"] else float("inf"))
        print(f"  affinity recovers {ratio:.2f}x the least-loaded "
              f"prefix hit rate")
    if args.check:
        if args.kv_quant == "off" and not (base["parity"]
                                           and aff["parity"]):
            raise SystemExit("fleet sweep lost token parity")
        if args.prefill_replicas:
            if aff["prefill_skipped"] <= base["prefill_skipped"]:
                raise SystemExit(
                    f"affinity skipped {aff['prefill_skipped']} "
                    f"prefill hops vs least-loaded "
                    f"{base['prefill_skipped']} — no offload win")
        elif aff["hit_rate"] <= base["hit_rate"]:
            raise SystemExit(
                f"affinity hit rate {aff['hit_rate']:.2f} did not beat "
                f"least-loaded {base['hit_rate']:.2f}")
    return [base, aff]


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--model", default="lenet",
                    choices=("lenet", "inception", "transformer"))
    ap.add_argument("--requests", type=int, default=200)
    ap.add_argument("--loads", default="inf,500,100",
                    help="offered loads in req/s (comma list; inf = "
                         "closed loop)")
    ap.add_argument("--max-batch", type=int, default=32)
    ap.add_argument("--max-wait-ms", type=float, default=2.0)
    ap.add_argument("--decode-words", type=int, default=16)
    ap.add_argument("--decode-slots", type=int, default=4)
    ap.add_argument("--decode-sync", type=int, default=8)
    ap.add_argument("--decode-sweep", action="store_true",
                    help="paged-vs-slab concurrency-scaling sweep at a "
                         "fixed pooled-token budget, plus a zero-cold-"
                         "compile speculative stream")
    ap.add_argument("--decode-npos", type=int, default=48,
                    help="per-request position capacity for the sweep "
                         "(slab rows reserve ALL of it)")
    ap.add_argument("--page-size", type=int, default=8,
                    help="KV page size (tokens) for the sweep")
    ap.add_argument("--spec-k", type=int, default=4,
                    help="draft length for the speculative sweep point")
    ap.add_argument("--attn-kernel", default="off",
                    choices=("off", "paged", "spec", "paged+spec"),
                    help="run the sweep's paged points through the "
                         "Mosaic paged-attention / spec-verify kernels "
                         "(transformer._PALLAS_PAGED_ATTN / "
                         "_PALLAS_SPEC_VERIFY; interpreter off-TPU) — "
                         "the rows' attn_kernel column records what "
                         "was active")
    ap.add_argument("--temperature", type=float, default=0.7,
                    help="sampling temperature for the sweep's "
                         "sampled/mixed points")
    ap.add_argument("--top-k", type=int, default=0,
                    help="top-k filter for the sweep's sampled point "
                         "(0 = off)")
    ap.add_argument("--top-p", type=float, default=0.0,
                    help="nucleus filter for the sweep's sampled "
                         "point (0 = off)")
    ap.add_argument("--stop-len", type=int, default=2,
                    help="stop-sequence length for the sweep's "
                         "early-retirement point (cut from each "
                         "request's own greedy oracle)")
    ap.add_argument("--quant", default=None,
                    choices=("off", "int8", "fp8"),
                    help="weight quantization for the scoring/router "
                         "engines (default: BIGDL_SERVE_QUANT)")
    ap.add_argument("--kv-quant", default=None, choices=("off", "int8"),
                    help="KV-page quantization for the decode sweep "
                         "(default: BIGDL_SERVE_KV_QUANT)")
    ap.add_argument("--fleet-sweep", action="store_true",
                    help="shared-prefix family stream through a "
                         "least-loaded vs an affinity-routed decode "
                         "fleet (docs/serving.md 'Disaggregated "
                         "fleet')")
    ap.add_argument("--families", type=int, default=6,
                    help="shared-prefix request families for the fleet "
                         "sweep")
    ap.add_argument("--zipf-a", type=float, default=1.1,
                    help="Zipf exponent over the request families")
    ap.add_argument("--prefix-pages", type=int, default=2,
                    help="full KV pages per family prefix")
    ap.add_argument("--prefill-replicas", type=int, default=0,
                    help="dedicated prefill replicas for the fleet "
                         "sweep")
    ap.add_argument("--host-mb", type=int, default=0,
                    help="per-replica host-RAM KV tier budget (MiB) "
                         "for the fleet sweep (0 = off)")
    ap.add_argument("--transport", default="inproc",
                    choices=("inproc", "stdio", "tcp"),
                    help="fleet replica wire for the fleet sweep: "
                         "in-process threads, stdio subprocess "
                         "workers, or TCP-loopback replica agents "
                         "(docs/serving.md 'Cross-host fleet').  This "
                         "process computes the oracle on its own jax "
                         "runtime, and a chip belongs to one process: "
                         "on a TPU the subprocess transports are "
                         "refused (ReplicaSpawnError) unless "
                         "BIGDL_SERVE_WORKER_PLATFORM sends the "
                         "workers elsewhere")
    ap.add_argument("--traffic", action="store_true",
                    help="open-loop bursty/diurnal traffic run: seeded "
                         "Poisson arrivals with a declared burst "
                         "window, mixed priority classes and (for "
                         "--model transformer) shared-prefix families "
                         "(docs/serving.md 'Autoscaling')")
    ap.add_argument("--base-rps", type=float, default=50.0,
                    help="traffic: baseline offered rate (req/s)")
    ap.add_argument("--burst-factor", type=float, default=8.0,
                    help="traffic: rate multiplier inside the burst "
                         "window")
    ap.add_argument("--burst-start-s", type=float, default=1.0,
                    help="traffic: burst window start offset (s)")
    ap.add_argument("--burst-len-s", type=float, default=1.0,
                    help="traffic: burst window length (s; 0 = none)")
    ap.add_argument("--burst-margin-s", type=float, default=1.0,
                    help="traffic: drain margin appended to the "
                         "declared overload window when splitting "
                         "sheds into in/out of window")
    ap.add_argument("--diurnal-amp", type=float, default=0.0,
                    help="traffic: sinusoidal diurnal amplitude in "
                         "[0, 1) over the base rate")
    ap.add_argument("--diurnal-period-s", type=float, default=60.0,
                    help="traffic: diurnal period (s)")
    ap.add_argument("--priority-mix", default="0:0.2,2:0.8",
                    help="traffic: 'class:weight,...' request mix "
                         "(lower class = more urgent)")
    ap.add_argument("--traffic-seed", type=int, default=0,
                    help="traffic: RNG seed (arrivals, priorities and "
                         "families replay byte-identically)")
    ap.add_argument("--autoscale", action="store_true",
                    help="traffic: arm the SLO-driven autoscaler over "
                         "the pool/fleet (serve/autoscale.py)")
    ap.add_argument("--min-replicas", type=int, default=0,
                    help="autoscale lower bound (0 = --replicas)")
    ap.add_argument("--max-replicas", type=int, default=8,
                    help="autoscale upper bound")
    ap.add_argument("--scale-interval", type=float, default=0.5,
                    help="autoscale cadence seconds for the traffic run")
    ap.add_argument("--replicas", type=int, default=1,
                    help="> 1 sweeps a ReplicaPool behind the SLO "
                         "router instead of one engine (also the fleet "
                         "sweep's decode-replica count)")
    ap.add_argument("--slo-ms", type=float, default=0.0,
                    help="per-request deadline for the router sweep "
                         "(0 = none; arms the shed policy)")
    ap.add_argument("--check", action="store_true",
                    help="fail unless batched >= 2x serial throughput")
    args = ap.parse_args()
    args.loads = [float(tok) for tok in str(args.loads).split(",") if tok]
    from bigdl_tpu.utils.engine import enable_compile_cache
    enable_compile_cache()
    from bigdl_tpu import quant as _quant
    if args.quant is None:
        args.quant = _quant.weight_mode_default()
    if args.kv_quant is None:
        args.kv_quant = _quant.kv_mode_default()

    if args.traffic:
        args.replicas = max(2, args.replicas)
        bench_traffic(args)
    elif args.fleet_sweep:
        args.replicas = max(2, args.replicas)
        bench_fleet(args)
    elif args.decode_sweep:
        bench_decode_sweep(args)
    elif args.model == "transformer":
        bench_decode(args)
    elif args.replicas > 1:
        bench_router(args)
    else:
        bench_scoring(args)


if __name__ == "__main__":
    main()
