"""Run one cell of the chip benchmark once, in this process.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json`` ``workloads``) names a configuration
(``bench/configs/<config>.json``) and a traffic mix
(``bench/traffic/<mix>.json``), which names its driver
(``bench/drivers/<kind>.py``) and its generator
(``bench/traffic/<generator>.py``); the cell's limits for ``correct`` are
in ``bench/limits/<cell>.json`` and each per-layer metric has a reader in
``bench/metrics/<metric>.py``. With ``--trace 0`` the result carries the
cell's end-to-end metrics, with ``--trace 1`` its per-layer metrics read
from a profiler trace of the window.

The last line of stdout is the result as one JSON object; the numbers
compared for ``correct`` are the last lines of stderr. Without an
accelerator (or with fewer chips than the cell asks for) the run exits
nonzero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None, *, root: str = ROOT, require_chip: bool = True,
         fault=None, t_start: float = None) -> dict:
    """One run. ``require_chip=False`` and ``fault`` exist for the tests:
    the first lets a CPU run go on past the chip check (its result names
    the CPU and it still exits nonzero), the second plants a fault in the
    timed path."""
    args = parse(argv)
    from bench import harness
    cell = harness.load_cell(root, args.workload, seed=args.seed,
                             seconds=args.seconds, trace=bool(args.trace),
                             fault=fault)
    chips = cell.workload["chips"]
    if require_chip:
        devices = harness.chip_or_exit(chips)
    else:
        import jax
        devices = jax.devices()
    harness.log(f"[bench] {cell.name} seed {cell.seed} on {len(devices)} x "
                f"{devices[0].device_kind}; compile cache "
                f"{harness.enable_compile_cache(root)}")
    counter = harness.CompileCounter()
    result, checks = harness.driver(cell).run(cell, devices,
                                              t_start or T_START, counter)
    harness.emit(result, checks)
    return result


if __name__ == "__main__":
    main()
