"""Share of the traced window in which the device was idle, the loop
waited in ``data-load`` and an ``h2d/prefetch`` transfer was open."""
from benchmark import spanread


def read(obs):
    parts = spanread.idle_partition(obs)
    return None if parts is None else parts["h2d"]
