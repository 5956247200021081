"""Host milliseconds per step of the loop's own work: its ``dispatch``,
``host-wait``, ``flush`` and ``bookkeep`` spans.  What sets the pace once
the feed and the device are off the critical path."""
from benchmark import spanread


def read(obs):
    return spanread.ms_per_step(obs, spanread.LOOP_WORK)
