"""Measure the knee of a serving mix on a configuration: the highest fixed
arrival rate at which the queue of requests waiting for a slot is no
longer at the end of a window than at its start.

    python3 bench/sweep_knee.py --config qwen2-1.5b-lora8 \
        --traffic ranking-open-rate80 --seconds 10 --rates 10,15,20,25,30

One process, one scheduler built as a cell of that configuration builds
it; each rate, in rising order up to the first that the scheduler does
not sustain, runs the mix for ``--seconds`` at that rate, then drains.
Prints one JSON line a rate, and writes the knee with every rate's
readings to the file the mix names (``bench/traffic/<knee>.knee.json``,
or ``--out``). A serving mix runs at its ``load`` times that knee, and is
refused while the file is missing; the benchmark's own runs never search
for a rate.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (ROOT, os.path.join(ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def knee(rows) -> float:
    """The highest rate whose window ended with no longer a queue than it
    began with, below the first rate that did not."""
    best = None
    for r in sorted(rows, key=lambda r: r["rate"]):
        if r["queue_end"] > r["queue_start"]:
            break
        best = r["rate"]
    if best is None:
        raise RuntimeError("no rate swept was sustained")
    return best


def sweep(root: str, config: str, traffic: str, rates, seconds: float,
          seed: int, devices) -> list:
    import numpy as np
    from bench import harness, weights

    cell = harness.Cell(
        root, {}, {"name": f"{config}.{traffic}", "chips": 1},
        harness.load_json(root, "bench", "configs", config + ".json"),
        harness.load_json(root, "bench", "traffic", traffic + ".json"),
        {}, seed, seconds, False, "")
    mcfg = harness.model_config(cell)
    serve, gen = harness.driver(cell), harness.generator(cell)
    sched = serve.make_scheduler(cell, weights.make_params(mcfg, seed), mcfg)
    sched.warmup()
    rows = []
    for k, rate in enumerate(sorted(rates)):
        _, reqs = gen.requests(cell.mix, mcfg.vocab_size, seconds, seed + k,
                               rate_per_s=rate)
        q0 = len(sched._queue)
        sched.reset_stats()
        sub, rid_of, t0, t1, queued, n = serve.open_loop(sched, reqs, seconds)
        tel = sched.telemetry()
        res = sched.run()
        done = {rid_of[r]: sub[rid_of[r]] + v.latency_s for r, v in res.items()
                if r in rid_of}
        tts = np.asarray([done[i] - t0 - reqs[i]["due"] for i in range(n)])
        late = np.asarray([s - t0 - reqs[i]["due"] for i, s in enumerate(sub)])
        fin = sum(1 for i in range(n) if done[i] <= t1)
        row = {
            "rate": rate, "submitted": n, "finished_inside": fin,
            "queue_start": q0, "queue_end": queued,
            "tts_p50_ms": float(np.percentile(tts, 50) * 1e3),
            "tts_p95_ms": float(np.percentile(tts, 95) * 1e3),
            "cand_per_s": fin * cell.mix["k"] / (t1 - t0),
            "late_mean_ms": float(late.mean() * 1e3),
            "late_max_ms": float(late.max() * 1e3),
            "steps": tel["steps"], "prefix_hit": tel["prefix_hit_rate"]}
        print(json.dumps(row), flush=True)
        rows.append(row)
        if queued > q0:                 # past the knee: higher rates add nothing
            break
        time.sleep(0.5)
    return rows, cell, gen


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    from bench import harness
    devices = harness.chip_or_exit(1)
    harness.enable_compile_cache(ROOT)
    rows, cell, gen = sweep(ROOT, args.config, args.traffic,
                            [float(r) for r in args.rates.split(",")],
                            args.seconds, args.seed, devices)
    out = {"knee_req_per_s": knee(rows), "config": args.config,
           "seconds": args.seconds, "seed": args.seed,
           "device": devices[0].device_kind, "sweep": rows}
    path = args.out or gen.knee_path(cell.mix)
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps({"knee_req_per_s": out["knee_req_per_s"],
                      "written": path}), flush=True)


if __name__ == "__main__":
    main()
