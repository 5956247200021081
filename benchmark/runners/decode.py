"""Runner of the serving front door: the window drives
``DecodeFleet(lm, n_decode=1, max_slots=.., n_pos=..)`` through
``fleet.submit(prompt, n_words, on_tokens=cb)`` under a closed loop of
clients, each sending its next request when its last resolved.  Every
latency is taken on the client's clock.

Set-up builds the fleet (which compiles its step programs) and runs the
closed loop until every client has finished one request; the window opens
with all slots in use.  After the window the fleet is closed and the plain
reference is run once over a sample of the requests the window finished,
each prompt with its served tokens.
"""
from __future__ import annotations

import queue
import sys
import time

import numpy as np

from benchmark import stats, traffic
from benchmark.program import build_model, load_reference, to_program_tree


class _Clients:
    """The closed loop: ``n`` clients over one stream of requests, driven
    from one thread.  Callbacks of the fleet only note times and wake the
    loop."""

    def __init__(self, fleet, plan, n):
        self.fleet, self.plan, self.n = fleet, plan, n
        self.records = []               # every request sent, in order
        self.events = queue.Queue()     # clients whose request resolved
        self.finished = [0] * n

    def send(self, client):
        spec = next(self.plan)
        rec = {"client": client, "prompt": spec["prompt"],
               "prompt_len": len(spec["prompt"]), "n_words": spec["n_words"],
               "chunks": [], "tokens": [], "done": None, "error": None,
               "row": None}
        self.records.append(rec)

        def on_tokens(tokens, rec=rec):
            rec["chunks"].append((time.perf_counter(), len(tokens)))
            rec["tokens"].extend(int(t) for t in tokens)

        def on_done(fut, rec=rec, client=client):
            rec["done"] = time.perf_counter()
            try:
                rec["row"] = [int(t) for t in fut.result()]
            except Exception as e:          # a failed or refused request
                rec["error"] = f"{type(e).__name__}: {e}"
            self.events.put(client)

        rec["submit"] = time.perf_counter()
        fut = self.fleet.submit(spec["prompt"], spec["n_words"],
                                on_tokens=on_tokens)
        fut.add_done_callback(on_done)

    def start(self):
        for client in range(self.n):
            self.send(client)

    def pump(self, until=None, stop=lambda: False):
        """Resend for every client whose request resolved, until ``stop()``
        or the clock passes ``until``."""
        while not stop():
            timeout = None if until is None else until - time.perf_counter()
            if timeout is not None and timeout <= 0:
                return
            try:
                client = self.events.get(timeout=timeout)
            except queue.Empty:
                return
            self.finished[client] += 1
            self.send(client)


def run(ctx):
    import jax

    from bigdl_tpu.serve.fleet import DecodeFleet
    from bigdl_tpu.utils.random import set_seed

    ref = load_reference(ctx.config)
    cfg, mix = ctx.config, ctx.traffic
    seed = traffic.seed32(ctx.seed)
    ctx.lap("import")

    p0 = jax.jit(lambda k: ref.init_params(k, cfg))(jax.random.PRNGKey(seed))
    set_seed(seed)
    lm = build_model(cfg)
    names = list(ref.param_shapes(cfg))
    lm.load_params(to_program_tree(lm.params(), p0, names))
    ctx.lap("weights")

    plan = traffic.decode_requests(mix, ctx.seed, cfg["vocab_size"])
    ctx.lap("data")

    fleet = DecodeFleet(lm, n_decode=1, n_prefill=0,
                        max_slots=mix["clients"], n_pos=mix["n_pos"])
    decoder = fleet.replicas[0]
    ctx.lap("fleet_compile")
    try:
        clients = _Clients(fleet, plan, mix["clients"])
        clients.start()
        clients.pump(stop=lambda: min(clients.finished) >= 1)
        ctx.lap("closed_loop_fill")

        counters0 = decoder.stats()
        t_open = ctx.open_window()
        deadline = t_open + ctx.seconds
        clients.pump(until=deadline)
        counters1 = decoder.stats()
        ctx.close_window()
    finally:
        fleet.close(drain=False)
    t_close = deadline

    records = clients.records
    done = [r for r in records
            if r["done"] is not None and t_open <= r["done"] < t_close]
    failed = [r for r in done if r["error"] is not None]
    finished = [r for r in done if r["error"] is None]
    ttfts = stats.ttfts_ms(records, t_open, t_close)
    tpots = stats.tpots_ms(finished, t_open, t_close)
    end_to_end = {
        "decode_tokens_per_s": stats.rate(
            stats.tokens_in_window(records, t_open, t_close), t_open,
            t_close),
        "ttft_p95_ms": stats.percentile(ttfts, 95) or 0.0,
        "tpot_p95_ms": stats.percentile(tpots, 95) or 0.0}
    # the mix shares no prefix: a hit would mean the benchmark repeated a
    # prompt, and that the readers count prompt steps that never ran
    hits = (counters1.get("prefix", {}).get("hits", 0)
            - counters0.get("prefix", {}).get("hits", 0))
    obs = {"requests": records, "t_open": t_open, "t_close": t_close,
           "steps": counters1["steps"] - counters0["steps"],
           "slots": mix["clients"]}
    print(f"decode: {len(finished)} finished, {len(failed)} failed in the "
          f"window; ttft samples {len(ttfts)}, tpot samples {len(tpots)}; "
          f"steps {obs['steps']}, host syncs "
          f"{counters1['host_syncs'] - counters0['host_syncs']}",
          file=sys.stderr)

    # free the program's state, then run the reference over a sample
    del fleet, decoder, lm, clients
    checks, detail = _compare(ctx, ref, cfg, p0, finished, mix, seed)
    checks.append(("prefix_hits_in_window", hits, 0))
    return {"attempted": len(done), "failed": len(failed),
            "end_to_end": end_to_end, "obs": obs, "checks": checks,
            "detail": detail}


def _compare(ctx, ref, cfg, p0, finished, mix, seed):
    """Over a sample of the finished requests, drawn from the seed and
    holding the longest: the widest gap by which a served token's logit
    lies below the reference's best; and that what was streamed is what
    the future resolved to, and as long as asked."""
    import jax.numpy as jnp

    limits = ctx.limits
    mismatched = sum(
        1 for r in finished
        if r["row"] != r["prompt"] + r["tokens"]
        or len(r["tokens"]) != r["n_words"])
    if not finished:
        return [("finished_requests_missing", 1, 0)], {}
    rng = np.random.Generator(np.random.PCG64(seed))
    longest = max(range(len(finished)),
                  key=lambda i: finished[i]["prompt_len"]
                  + finished[i]["n_words"])
    n = min(limits["sample"], len(finished))
    others = [i for i in rng.permutation(len(finished)) if i != longest]
    sample = [longest] + others[:n - 1]
    row_check = ref.make_row_check(cfg, mix["n_pos"], mix["output_len"][1])
    widest, control_widest, tokens = 0.0, 0.0, 0
    for i in sample:
        r = finished[i]
        seq = np.zeros(mix["n_pos"], np.int32)
        seq[:len(r["row"])] = r["row"]
        gap, control_gap = row_check(p0, jnp.asarray(seq),
                                     r["prompt_len"] - 1, r["n_words"])
        widest = max(widest, float(gap.max()))
        control_widest = max(control_widest, float(control_gap.max()))
        tokens += r["n_words"]
    print(f"check detail: {len(sample)} requests, {tokens} served tokens "
          f"compared; the fp8 control's widest gap on the same positions "
          f"{control_widest:.6g}", file=sys.stderr)
    detail = {"compared_requests": len(sample), "compared_tokens": tokens,
              "control_served_logit_gap": control_widest}
    return [("served_logit_gap", widest, limits["served_logit_gap"]),
            ("stream_mismatches", mismatched, 0)], detail
