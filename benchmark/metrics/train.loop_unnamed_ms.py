"""Host milliseconds per step of an iteration that lie under no span: the
``loop`` counter (an iteration's wall) minus every span of the loop's
thread."""
from benchmark import spanread


def read(obs):
    return spanread.loop_unnamed_ms(obs)
