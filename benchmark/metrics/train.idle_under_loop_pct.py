"""Share of the traced window in which the device was idle while the
loop's thread was in ``dispatch``, ``host-wait``, ``flush`` or
``bookkeep``."""
from benchmark import spanread


def read(obs):
    parts = spanread.idle_partition(obs)
    return None if parts is None else parts["loop"]
