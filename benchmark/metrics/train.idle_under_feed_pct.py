"""Share of the traced window in which the device was idle, the loop
waited in ``data-load``, no transfer was open and the producer was inside a
``data-load/fetch``: idle time that the producer's work accounts for."""
from benchmark import spanread


def read(obs):
    parts = spanread.idle_partition(obs)
    return None if parts is None else parts["feed"]
