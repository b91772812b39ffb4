#!/usr/bin/env python3
"""Find a serving cell's knee once, on the chip: the highest arrival rate
at which the backlog does not grow over the window.

    python3 bench/knee.py --workload <cell> --seconds <s> --seed <n> \
        --rates 0.5,1,1.5,2

One process builds the server and engine once (the cell's set-up), then
serves the cell's traffic at each rate in turn, rising: a pre-roll, a
window of ``--seconds``, and a drain.  For each rate it prints the
completed tokens/s, the 95th percentiles, and the backlog (requests queued
or in a decode slot) over the window's first and last quarters.  The rate
the cell runs at is written by hand into its traffic file.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    import os
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", str(ROOT / ".jax_cache"))
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(ROOT))
    import jax
    import numpy as np

    from bench import blocks, traffic_gen
    from bench.drivers import serve
    from bench.drivers.common import RunSpec
    from bench.flops import peaks
    from bench.run import load_json

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    bench = load_json(ROOT / "BENCHMARK.json")
    cell = {w["name"]: w for w in bench["workloads"]}[args.workload]
    cfg = load_json(BENCH / "configs" / f"{cell['config']}.json")
    mix = load_json(BENCH / "traffic" / f"{cell['traffic']}.json")
    devices = jax.devices()[:cell["chips"]]
    if devices[0].platform != "tpu":
        print("knee.py: a TPU is required", file=sys.stderr)
        return 3
    spec = RunSpec(cell=cell, cfg=cfg, block=blocks.named(cfg), mix=mix,
                   limits={}, seed=args.seed, seconds=args.seconds,
                   trace=False, devices=devices,
                   peak=peaks(devices[0].device_kind), process_age=lambda: 0.0)
    engine = serve.build(spec)
    for k, rate in enumerate(float(r) for r in args.rates.split(",")):
        m = dict(mix, rate_rps=rate)
        sched = traffic_gen.serve_schedule(
            m, cfg["vocab_size"], args.seed + k,
            (m["preroll_s"], args.seconds, args.seconds))
        sv = serve.serve_window(engine, spec, sched, m["preroll_s"])
        e2e, by_req, win_due, failed, _, _ = serve.measure(sv, sched, cfg,
                                                           spec.block)
        q = args.seconds / 4
        first = [b for t, b in sv.backlog if t < q]
        last = [b for t, b in sv.backlog if t >= 3 * q]
        print(json.dumps({
            "rate_rps": rate, "due": len(win_due), "failed": failed,
            "serve_tok_s": e2e["serve_tok_s"],
            "ttft_p50_ms": e2e["ttft_p50_ms"], "itl_p95_ms": e2e["itl_p95_ms"],
            "backlog_first_quarter": float(np.mean(first)) if first else None,
            "backlog_last_quarter": float(np.mean(last)) if last else None,
            "compiles_in_window": sv.counters["compiles_in_window"]}),
            flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
