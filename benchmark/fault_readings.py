"""Readings of the output check under the reference's planted faults,
several seeds in one process (``python -m benchmark.fault_readings
--workload <cell> --seeds 1,2 --faults taps_reversed,no_rotary``): the
reference computed with each ``fault`` of its own put in the program's
place and held to the cell's limits, as ``benchmark.readings --what
control`` does for the precision below; every one has to come out not
correct.  The sound reference is computed once a seed.  Training cells of
the ``train_lm`` runner.  Not part of a benchmark run."""
from __future__ import annotations

import argparse
import json
import sys


def fault_numbers(cell, config, seed, faults, sizes=None):
    """{fault: [(name, value, limit)]}: the check's numbers of the reference
    with each fault against the sound reference; same weights and sequences
    as a run of ``seed``, the rows the first batches in storage order (as
    ``train_lm.variant_numbers``)."""
    import jax
    import numpy as np

    from benchmark import traffic
    from benchmark.harness import apply_sizes
    from benchmark.program import load_reference
    from benchmark.runners import train_lm
    mix, cfg, limits = apply_sizes(cell, config, sizes)
    ref = load_reference(cfg)
    batch = mix["sequences_per_step"]
    p0_host = train_lm._host(jax.jit(lambda k: ref.init_params(k, cfg))(
        jax.random.PRNGKey(traffic.seed32(seed))))
    tokens = train_lm.token_records(mix, seed, cfg["vocab_size"])
    rows = [np.arange(k * batch, (k + 1) * batch) for k in range(3)]
    args = (ref, cfg, p0_host, rows, tokens, mix)
    sound = train_lm.reference_steps(*args)
    return {fault: train_lm.compare_numbers(
        limits, p0_host, sound, train_lm.reference_steps(*args, fault=fault))
        for fault in faults}


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True)
    parser.add_argument("--faults", required=True)
    args = parser.parse_args(argv)

    from benchmark import check, harness
    manifest = harness.load_manifest()
    entry, config_entry = harness.find_cell(manifest, args.workload)
    from bigdl_tpu.utils.engine import enable_compile_cache
    enable_compile_cache()
    harness.require_chip(entry["chips"])
    cell = harness.load_json("benchmark", "workloads",
                             args.workload + ".json")
    config = harness.load_json(config_entry["file"])
    for seed in (int(s) for s in args.seeds.split(",")):
        for fault, numbers in fault_numbers(
                cell, config, seed, args.faults.split(",")).items():
            correct, table = check.verdict(numbers)
            print(json.dumps(dict(
                {"fault": fault, "seed": seed, "correct": correct},
                **{k: v["value"] for k, v in table.items()})), flush=True)
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    import os
    os._exit(code)
