#!/usr/bin/env python3
"""Readings of a cell's correctness numbers under the control and under
planted faults, on the chip at the cell's own size.  The benchmark's own
runs never run this; its readings set the upper end of each limit
(``bench/limits/<cell>.json``, see PERF.md).

    python3 bench/control.py --workload <cell> --seconds <s> --seeds a,b,c \
        [--runs control_fp8,fault_half_batch] [--out readings.jsonl]

Serving cells run the cell as the benchmark does and then hold, at every
position of the same prompts and served tokens, the gap of the token that
the fp8 reference puts first (the control) to the cell's limits.
Training cells read the numbers of the reference put in the program's
place: in fp8 (the control), with half of the batch left out, and (more
than one chip) with the exchange between chips left out.  A state left
unchanged reads 1 on ``change_gap`` by construction and needs no run.
Each seed's readings are one JSON line, with ``correct`` as the cell's
limits judge each run; the control has to come out not correct.
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
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--runs", default="control_fp8,fault_half_batch,"
                    "fault_no_exchange",
                    help="training cells: which of the control and faults")
    ap.add_argument("--out", help="also append each seed's line here")
    args = ap.parse_args(argv)
    import os
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", str(ROOT / ".jax_cache"))
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(ROOT))
    import jax

    from bench import blocks
    from bench.drivers.common import RunSpec
    from bench.flops import peaks
    from bench.run import load_json

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    bench = load_json(ROOT / "BENCHMARK.json")
    cell = {w["name"]: w for w in bench["workloads"]}[args.workload]
    cfg = load_json(BENCH / "configs" / f"{cell['config']}.json")
    mix = load_json(BENCH / "traffic" / f"{cell['traffic']}.json")
    limits = load_json(BENCH / "limits" / f"{cell['name']}.json")
    block = blocks.named(cfg)
    devices = jax.devices()[:cell["chips"]]
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        print("control.py: the cell's chips are required", file=sys.stderr)
        return 3

    def judged(values: dict) -> bool:
        return all(values[n] <= lim for n, lim in limits.items())

    for seed in (int(s) for s in args.seeds.split(",")):
        out = {"seed": seed}
        if mix["driver"] == "serve":
            from bench.drivers import serve
            spec = RunSpec(cell=cell, cfg=cfg, block=block, mix=mix,
                           limits=limits, seed=seed, seconds=args.seconds,
                           trace=False,
                           devices=devices, peak=peaks(devices[0].device_kind),
                           process_age=lambda: 0.0)
            res = serve.run(spec, control=True)
            out.update({f"control_fp8.{c['name']}": c["value"]
                        for c in res.checks})
            out["control_fp8.correct"] = all(c["value"] <= c["limit"]
                                             for c in res.checks)
        else:
            from bench.drivers.train import compare, reference_readings
            ref = reference_readings(block, cfg, mix, seed, devices)
            runs = {"control_fp8": {"precision": "fp8"},
                    "fault_half_batch": {"fault": "half_batch"},
                    "fault_no_exchange": {"fault": "no_exchange"}}
            for tag in args.runs.split(","):
                if tag == "fault_no_exchange" and len(devices) == 1:
                    continue
                got = compare(reference_readings(block, cfg, mix, seed,
                                                 devices, **runs[tag]), ref)
                out.update({f"{tag}.{n}": v for n, v in got.items()})
                out[f"{tag}.correct"] = judged(got)
        line = json.dumps(out)
        print(line, flush=True)
        if args.out:
            with open(args.out, "a") as f:
                print(line, file=f)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
