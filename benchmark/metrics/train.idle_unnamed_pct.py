"""Share of the traced window in which the device was idle under none of
``train.idle_under_feed_pct``, ``..._h2d_pct`` and ``..._loop_pct``: the
four sum to ``train.device_idle_pct``."""
from benchmark import spanread


def read(obs):
    parts = spanread.idle_partition(obs)
    return None if parts is None else parts["unnamed"]
