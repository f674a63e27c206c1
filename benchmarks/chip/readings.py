"""Readings that the limits of ``limits/<cell>.json`` are set from: the
numbers the comparison reads, for many seeds in one process, from

* ``program``: sound runs of the system under test (the lower readings);
* ``control``: the reference in the configuration's next lower precision
  put in the program's place (``CONTROL`` of the config module);
* ``half_batch``: half of every batch left out, planted under the timed
  path (a state left unchanged, the other fault, reads 1 by the change's
  measure and needs no run).

    python3 benchmarks/chip/readings.py --workload <name> \
        --mode program --seeds 1,2,3

Training needs no measured window: each reading builds the cell, drives
its ``check_steps`` units, frees it and runs the reference.  One JSON line
per seed on standard output.  The benchmark's own runs never run this.
"""
import argparse
import gc
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE, os.path.join(os.path.dirname(os.path.dirname(HERE)),
                                   "src")]


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--mode", required=True,
                    choices=["program", "control", "half_batch"])
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds")
    args = ap.parse_args(argv)

    import bench
    from record import compare
    spec = bench.cell_spec(bench.benchmark(), args.workload)
    bench.use_cache()
    mod = bench.load_module(spec["module"], "cfg_readings")
    fault = "half_batch" if args.mode == "half_batch" else None
    for seed in (int(s) for s in args.seeds.split(",")):
        t = time.time()
        cell = mod.Cell(spec["cfg"], spec["traffic"], seed, bench.Spans(),
                        fault=fault)
        if args.mode == "control":
            prog = cell.reference(dtype=mod.CONTROL)
        else:
            cell.setup()
            prog = cell.record
            cell.release()
        gc.collect()
        numbers = compare(prog, cell.reference())
        del cell, prog
        gc.collect()
        print(json.dumps({"workload": args.workload, "mode": args.mode,
                          "seed": seed, "seconds": time.time() - t,
                          **{k: v[0] for k, v in numbers.items()},
                          "detail": {k: v[1] for k, v in numbers.items()}}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
