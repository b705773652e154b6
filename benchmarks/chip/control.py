#!/usr/bin/env python3
"""Readings that the correctness limit of a cell is set from.

    python benchmarks/chip/control.py --workload qwen3-4b.docqa-chat \\
        --seeds 1,2,3,...  --control-seeds 1,2,3 --seconds 10

In one process, one short run of the cell per seed at the cell's own load
(long enough to finish the mix's longest requests; the same comparison as
a benchmark run, on as many requests). For each seed it prints the widest
logit gap the program's served tokens read against the float32 reference
and, on the control seeds, the widest gap of the fp8 control's own argmax
at the same positions, with the control put in the program's place and
judged by the run's own ``verdict`` (``control_correct``, which has to
come out false). The limit goes above the largest program reading and
below the smallest control reading. The benchmark's own runs do not run
the control.
"""
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parents[1] / "src")]


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    import jax
    jax.config.update("jax_compilation_cache_dir",
                      str(HERE.parents[1] / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    import cell
    ctrl = {int(s) for s in args.control_seeds.split(",") if s}
    for seed in [int(s) for s in args.seeds.split(",")]:
        res = cell.run(args.workload, seed, args.seconds, False,
                       control=seed in ctrl)
        ex = res["extra"]
        print(json.dumps({
            "workload": args.workload, "seed": seed,
            "correct": res["correct"],
            "gap": max(ex["gaps"]) if ex["gaps"] else None,
            "control_gap": (max(ex["control_gaps"]) if ex["control_gaps"]
                            else None),
            "control_correct": ex.get("control_correct"),
            "gaps": ex["gaps"], "control_gaps": ex["control_gaps"],
            "checks": res["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
