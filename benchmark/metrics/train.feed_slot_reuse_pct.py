"""Share of the window's batches that the feed assembled into a recycled
host slot (``feed/slot-wait``'s count over ``data-load/fetch``'s), in
percent: under 100 where batches fell back to a fresh allocation (a partial
tail, rows that drift in shape or dtype).  What the producer waited for a
free slot, milliseconds per batch, goes to standard error."""
import sys

from benchmark import spanread

SLOT_WAIT = "feed/slot-wait"


def read(obs):
    spans = obs.get("spans") or {}
    draws = spans.get(spanread.FETCH, (0.0, 0))[1]
    if SLOT_WAIT not in spans or not draws:
        return None
    seconds, recycled = spans[SLOT_WAIT]
    print(f"feed slot wait: {seconds / draws * 1e3:.3f} ms/batch",
          file=sys.stderr)
    return 100.0 * recycled / draws
