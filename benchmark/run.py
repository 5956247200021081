"""``python -m benchmark.run --workload <cell> --seed <n> --seconds <s>
--trace <0|1>``: one run of one cell on the machine it is started on.  The
last line of standard output is the result object; without a TPU (or with
fewer chips than the cell asks for) it prints none and exits non-zero."""
from __future__ import annotations

import time

T0 = time.perf_counter()       # set-up is counted from here

import argparse                # noqa: E402
import json                    # noqa: E402
import os                      # noqa: E402
import sys                     # noqa: E402


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)

    from benchmark import harness
    try:
        manifest = harness.load_manifest()
        entry, _ = harness.find_cell(manifest, args.workload)
        from bigdl_tpu.utils.engine import enable_compile_cache
        enable_compile_cache()      # <checkout>/.xla_cache, a fixed path
        harness.require_chip(entry["chips"])
    except (harness.BenchmarkError, ImportError, OSError) as e:
        print(f"benchmark: {type(e).__name__}: {e}", file=sys.stderr)
        return 2
    result = harness.run_cell(args.workload, args.seed, args.seconds,
                              bool(args.trace), t0=T0, manifest=manifest)
    sys.stdout.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    sys.stderr.flush()
    # daemon threads of the program (prefetch, decode drivers) must not
    # hold the process open after the result is out
    os._exit(code)
