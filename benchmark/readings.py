"""Readings for the limits of the output check, several seeds in one
process (``python -m benchmark.readings --workload <cell> --seeds 1,2,3
--what program|control|half_batch``).  ``program`` makes whole runs (with a
window of ``--seconds``, 0 for a training cell) and prints each run's
numbers; ``control`` and ``half_batch`` put the reference in the program's
place (training cells) and hold its numbers to the cell's limits, as a run
is held: both have to come out not correct.  Not part of a benchmark run."""
from __future__ import annotations

import argparse
import importlib
import json
import sys


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True)
    parser.add_argument("--what", default="program")
    parser.add_argument("--seconds", type=float, default=0.0)
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
        if args.what == "program":
            result = harness.run_cell(args.workload, seed, args.seconds,
                                      False, manifest=manifest)
            numbers = {k: v["value"] for k, v in result["check"].items()}
            numbers["correct"] = result["correct"]
        else:
            runner = importlib.import_module(
                "benchmark.runners." + cell["runner"])
            correct, table = check.verdict(
                runner.variant_numbers(cell, config, seed, args.what))
            numbers = {k: v["value"] for k, v in table.items()}
            numbers["limits"] = {k: v["limit"] for k, v in table.items()}
            numbers["correct"] = correct
        print(json.dumps({"what": args.what, "seed": seed, **numbers}),
              flush=True)
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    import os
    os._exit(code)
