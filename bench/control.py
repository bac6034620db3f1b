"""The program's readings over many seeds, and the control and the faults
that set each limit's upper reading.

    python3 bench/control.py --workload <name> --seeds 1,2,3

For each seed, in one process: for a training cell, the program's set-up
as the cell's run makes it (its first steps through the timed trainer)
and the float32 reference over the same batches; then the reference put
in the program's place one precision step down (float8, ``low=True``)
and, for a training cell, with half of each batch left out and the mean
taken over the rest. Each is compared with the float32 reference by the
cell's own numbers and limits (``harness.compared``). Prints one JSON
line a seed, each reading beside its limit and whether it passed. The
benchmark's own runs never run this; its test runs it at a tiny size.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (ROOT, os.path.join(ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def _judged(cell, numbers: dict, **extra) -> dict:
    from bench import harness
    c = harness.compared(numbers, cell.limits)
    return {"correct": c["ok"],
            "checks": {k: [r["value"], r["limit"]] for k, r in c["rows"].items()},
            "numbers": numbers, **extra}


def train_readings(cell, device) -> dict:
    from bench import harness
    from bench.reference import check
    drv, mcfg = harness.driver(cell), harness.model_config(cell)
    w = drv.warm_up(cell, mcfg, device)
    warm, prog = w.warm_batches, w.prog
    w.trainer.state = None
    w.trainer = None
    gc.collect()
    ref = drv.follow_reference(cell, mcfg, warm)
    out = {"program": _judged(cell, check.train_numbers(prog, ref),
                              by_step=check.later_loss_gaps(prog, ref))}
    rows = cell.config["train"]["rows_per_step"]
    runs = {"control_fp8": {"low": True},
            "half_batch": {"rows_kept": rows // 2}}
    for name, kw in runs.items():
        got = drv.follow_reference(cell, mcfg, warm, **kw)
        out[name] = _judged(cell, check.train_numbers(got, ref),
                            by_step=check.later_loss_gaps(got, ref))
    return out


def serve_readings(cell) -> dict:
    import numpy as np
    from bench import harness, weights
    from bench.reference import check
    mcfg, gen = harness.model_config(cell), harness.generator(cell)
    _, reqs = gen.requests(cell.mix, mcfg.vocab_size, cell.seconds, cell.seed)
    idx = check.sample_requests(list(range(len(reqs))),
                                cell.mix["check_requests"], cell.seed,
                                key=lambda i: gen.context_tokens(reqs[i]))
    params = weights.make_params(mcfg, cell.seed)
    cfg, n = harness.ref_config(cell), cell.mix["check_row_tokens"]
    ref = check.make_serve_ref(cfg, mcfg.window)
    low = check.make_serve_ref(cfg, mcfg.window, low=True)
    gap = 0.0
    for i in idx:
        a = ref(params, reqs[i], n)
        b = low(params, reqs[i], n)
        gap = max(gap, float(np.max(np.abs(a - b))))
    return {"control_fp8": _judged(cell, {"score_gap": gap,
                                          "unanswered": 0.0})}


def readings(root: str, workload: str, seed: int, seconds: float = 10.0,
             device=None) -> dict:
    """Every reading of one seed."""
    import jax
    from bench import harness
    cell = harness.load_cell(root, workload, seed=seed, seconds=seconds,
                             trace=False)
    harness.enable_compile_cache(root)
    device = device or jax.devices()[0]
    if cell.mix["kind"] == "train":
        return train_readings(cell, device)
    return serve_readings(cell)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    from bench import harness
    spec = harness.load_json(ROOT, "BENCHMARK.json")
    devices = harness.chip_or_exit(harness.find_cell(spec, args.workload)["chips"])
    for s in args.seeds.split(","):
        r = readings(ROOT, args.workload, int(s), args.seconds, devices[0])
        print(json.dumps({"workload": args.workload, "seed": int(s),
                          "device": devices[0].device_kind, **r}), flush=True)


if __name__ == "__main__":
    main()
