"""The largest self time among the links of the producer's chain (the
source and every transformer stage under ``data-load/fetch``), milliseconds
per batch: the stage that a faster feed has to shorten.  Which one it is,
and the others, go to standard error."""
import sys

from benchmark import spanread


def read(obs):
    stages = spanread.stage_self_ms(obs)
    if not stages:
        return None
    for name, ms in sorted(stages.items(), key=lambda kv: -kv[1]):
        print(f"feed stage {name}: {ms:.3f} ms/batch", file=sys.stderr)
    return max(stages.values())
