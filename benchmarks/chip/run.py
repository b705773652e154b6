#!/usr/bin/env python3
"""Run one cell of the on-chip benchmark once.

    python benchmarks/chip/run.py --workload <config>.<mix> --seed <n> \\
        --seconds <s> --trace <0|1>

One process holds the chip: it builds Echo's engine for the cell's
configuration with weights drawn from ``--seed``, warms up the cell's
shapes and runs 5 s of its traffic (set-up), measures ``--seconds`` of
open-loop traffic, and checks what was served against the plain
reference. ``--trace 0`` reports the end-to-end metrics, ``--trace 1`` the
per-layer metrics (host counters over the window, a device trace of a few
seconds in its middle) with ``busy_s``/``window_s`` and a breakdown.

The last line of stdout is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` (and ``breakdown`` when traced), with
the compared numbers and their limits under ``checks``, last. Progress
and the same checks go to stderr. Without a TPU, or with fewer chips than
the cell asks for, it exits non-zero and prints no result.

JAX's persistent compilation cache lives in ``.jax_cache/`` at the root of
the checkout, so only a cell's first run in a checkout compiles.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path[:0] = [str(HERE), str(ROOT / "src")]

CACHE_DIR = ROOT / ".jax_cache"


def _finite(x):
    """JSON has no infinity: an infinite reading (a request that never
    answered) is written as 1e12."""
    if isinstance(x, float) and not math.isfinite(x):
        return 1e12 if x > 0 else (-1e12 if x < 0 else None)
    if isinstance(x, dict):
        return {k: _finite(v) for k, v in x.items()}
    if isinstance(x, list):
        return [_finite(v) for v in x]
    return x


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    import cell

    result = cell.run(args.workload, args.seed, args.seconds,
                      bool(args.trace), t_process=T_PROCESS)
    result.pop("extra", None)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(_finite(result)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
