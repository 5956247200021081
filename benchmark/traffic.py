"""The one generator of inputs.  A traffic mix is the ``traffic`` object of
a cell's file (``benchmark/workloads/<cell>.json``): parameters only.  The
same seed gives the same inputs; every seed gives the same set of sizes, in
another order."""
from __future__ import annotations

import numpy as np


def seed32(seed):
    """Any whole number -> a seed every generator here accepts."""
    return int(seed) % (2 ** 31 - 1)


def image_records(traffic, seed, shape, classes):
    """``records`` labelled images: unit-normal pixels plus a class-dependent
    offset, float32 (records, *shape); labels 1-based float32 (records,)."""
    rng = np.random.Generator(np.random.PCG64(seed32(seed)))
    n = int(traffic["records"])
    labels = rng.integers(1, classes + 1, n)
    images = rng.standard_normal((n, *shape), dtype=np.float32)
    images += (traffic["class_offset"] * (labels % 7)
               ).astype(np.float32)[:, None, None, None]
    return images, labels.astype(np.float32)


def _log_uniform_sizes(lo, hi, n):
    """n sizes spread evenly in log space over [lo, hi]: the same multiset
    for every seed."""
    if n == 1:
        return [int(round((lo * hi) ** 0.5))]
    return [int(round(np.exp(np.log(lo) + (np.log(hi) - np.log(lo))
                             * i / (n - 1)))) for i in range(n)]


def decode_requests(traffic, seed, vocab):
    """The endless request stream of a decode mix, the same for the same
    seed: lengths cycle through ``pool`` pairs of the fixed log-uniform
    grids, paired and ordered by the seed; every request sent draws its own
    prompt, token ids uniform over the vocabulary, so no prompt is ever sent
    twice and none shares a prefix (a repeat would be served from the
    program's prefix cache, and the mix would measure that)."""
    rng = np.random.Generator(np.random.PCG64(seed32(seed)))
    n = int(traffic["pool"])
    prompts = _log_uniform_sizes(*traffic["prompt_len"], n)
    outputs = _log_uniform_sizes(*traffic["output_len"], n)
    rng.shuffle(prompts)
    rng.shuffle(outputs)
    while True:
        for p, o in zip(prompts, outputs):
            yield {"prompt": rng.integers(0, vocab, p).tolist(),
                   "n_words": int(o)}
