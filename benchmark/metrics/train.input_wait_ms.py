"""Host milliseconds a step waited for its input: the trainer's
``data-load`` and ``h2d`` spans over the window, per step.  (The
``prefetch_stall`` events lie inside ``data-load``.)"""


def read(obs):
    spans, steps = obs.get("spans"), obs.get("steps")
    if not spans or not steps:
        return None
    total = sum(spans.get(p, (0.0, 0))[0] for p in ("data-load", "h2d"))
    return total / steps * 1e3
