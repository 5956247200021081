"""Collective primitives over mesh axes.

The TPU-native equivalent of the reference's hand-built collective on Spark
BlockManager (parameters/AllReduceParameter.scala, SURVEY.md §2.5): its
putGradients+aggregrateGradientPartition = reduce-scatter, its
sendWeightPartition+getWeights = all-gather.  Here each is one XLA op over
ICI.  For use inside ``shard_map``-ped functions.
"""
from __future__ import annotations

import jax
from jax import lax


def all_reduce(x, axis_name: str = "data"):
    """Sum across the axis (= the reference's full AllReduceParameter cycle)."""
    return lax.psum(x, axis_name)


def all_reduce_mean(x, axis_name: str = "data"):
    return lax.pmean(x, axis_name)


def reduce_scatter(x, axis_name: str = "data", scatter_dimension: int = 0,
                   tiled: bool = True):
    """Sum + shard: each participant keeps its slice
    (= putGradients + aggregrateGradientPartition, AllReduceParameter.scala:202/162)."""
    return lax.psum_scatter(x, axis_name, scatter_dimension=scatter_dimension,
                            tiled=tiled)


def all_gather(x, axis_name: str = "data", axis: int = 0, tiled: bool = True):
    """Collect every participant's slice
    (= sendWeightPartition + getWeights, AllReduceParameter.scala:218/135)."""
    return lax.all_gather(x, axis_name, axis=axis, tiled=tiled)


def ppermute(x, axis_name: str, perm):
    """Point-to-point ring shifts (building block of ring attention)."""
    return lax.ppermute(x, axis_name, perm)


def pvary(x, axis_names):
    """Mark ``x`` device-varying over ``axis_names`` (shard_map scan carries
    must keep a consistent varying type)."""
    return lax.pcast(x, axis_names, to="varying")


def ring_shift(x, axis_name: str, shift: int = 1):
    """Shift values around the axis ring by ``shift`` positions."""
    n = lax.axis_size(axis_name)
    perm = [(i, (i + shift) % n) for i in range(n)]
    return lax.ppermute(x, axis_name, perm)


def all_to_all(x, axis_name: str, split_axis: int, concat_axis: int,
               tiled: bool = True):
    """Ulysses-style sequence<->head reshard primitive."""
    return lax.all_to_all(x, axis_name, split_axis, concat_axis, tiled=tiled)


def axis_index(axis_name: str):
    return lax.axis_index(axis_name)


def axis_size(axis_name: str):
    return lax.axis_size(axis_name)
