"""Metric arithmetic: rates over a whole window, percentiles of all
requests, per-request time per output token.  Pure Python on timelines, so
the tests drive it with synthetic ones."""
from __future__ import annotations

import math


def percentile(values, q):
    """The q-th percentile (0-100) by linear interpolation between order
    statistics; None of nothing."""
    xs = sorted(values)
    if not xs:
        return None
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def rate(count, t_open, t_close):
    """Work per second over all the time of the window."""
    return count / (t_close - t_open)


def in_window(t, t_open, t_close):
    return t_open <= t < t_close


def tokens_in_window(requests, t_open, t_close):
    """Generated tokens delivered to clients inside the window.  Each
    request carries ``chunks``: [(time, n_tokens), ...]."""
    return sum(n for r in requests for t, n in r["chunks"]
               if in_window(t, t_open, t_close))


def ttfts_ms(requests, t_open, t_close):
    """Submit -> first streamed token, of every request whose first token
    arrived inside the window."""
    return [(r["chunks"][0][0] - r["submit"]) * 1e3 for r in requests
            if r["chunks"] and in_window(r["chunks"][0][0], t_open, t_close)]


def tpots_ms(requests, t_open, t_close):
    """(last token time - first token time) / (tokens - 1), of every
    request completed inside the window with two tokens or more."""
    out = []
    for r in requests:
        if r.get("done") is None or not in_window(r["done"], t_open, t_close):
            continue
        n = sum(c for _, c in r["chunks"])
        if n >= 2:
            out.append((r["chunks"][-1][0] - r["chunks"][0][0])
                       / (n - 1) * 1e3)
    return out


def spread(values):
    """Interquartile distance over the median, as the driver takes it."""
    import statistics
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def positions_done(request, t):
    """How many of a request's positions have had their step by time t,
    read from the client's side: the prompt's positions fall evenly
    between submit and the first chunk, the generated ones between the
    chunks (a request of P prompt tokens that has streamed n tokens has
    run P - 1 + n positions)."""
    knots = [(request["submit"], 0.0)]
    done = request["prompt_len"] - 1
    for when, n in request["chunks"]:
        done += n
        knots.append((when, float(done)))
    if t <= knots[0][0]:
        return 0.0
    for (t0, p0), (t1, p1) in zip(knots, knots[1:]):
        if t <= t1:
            return p0 + (p1 - p0) * (t - t0) / max(t1 - t0, 1e-9)
    return knots[-1][1]


def position_spans(requests, t_open, t_close):
    """[(first position, last position)] that each request ran inside the
    window, fractional at the window's edges."""
    spans = []
    for r in requests:
        a, b = positions_done(r, t_open), positions_done(r, t_close)
        if b > a:
            spans.append((a, b))
    return spans
