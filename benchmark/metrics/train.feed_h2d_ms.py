"""Milliseconds the transfer thread works on one batch: the wall of a
``to_device`` call, the ``h2d/prefetch`` span."""
from benchmark import spanread


def read(obs):
    return spanread.ms_per_count(obs, spanread.H2D)
