#!/usr/bin/env python3
"""Run one benchmark cell once and print its result as the last line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything is found by name from ``BENCHMARK.json``: the cell names its
configuration (``bench/configs/<config>.json``) and its traffic mix
(``bench/traffic/<mix>.json``); the configuration names its block
(``bench/blocks/<block>.py``: the layer equations, weight layout and
counts); the mix names its driver (``bench/drivers/<driver>.py``); each
per-layer metric is read by ``bench/metrics/<metric>.py``; the limits of
the correctness comparison are in ``bench/limits/<cell>.json``.  Adding an
architecture, a configuration, a mix, a metric or a cell is adding files
and entries.

With ``--trace 0`` the metrics are the cell's end-to-end metrics; with
``--trace 1`` a device trace of the window is taken and the metrics are the
cell's per-layer metrics.  Only a TPU backend with as many chips as the
cell asks for is accepted.
"""
from __future__ import annotations

import argparse
import importlib
import importlib.util
import json
import os
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def process_age_s() -> float:
    """Seconds since this process started (Linux), else since import."""
    try:
        with open("/proc/self/stat") as f:
            start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return uptime - start_ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return time.perf_counter() - _IMPORTED


_IMPORTED = time.perf_counter()


def fail(msg: str, code: int = 2) -> int:
    print(f"bench/run.py: {msg}", file=sys.stderr)
    return code


def load_json(path: Path) -> dict:
    return json.loads(path.read_text())


def cell_metrics(bench: dict, cell: str, section: str) -> list:
    """The entries of ``section`` that this cell reports."""
    e2e_here = {m["name"] for m in bench["end_to_end"]
                if cell in m.get("workloads", [cell])}
    out = []
    for m in bench[section]:
        if "workloads" in m:
            if cell in m["workloads"]:
                out.append(m)
        elif section == "end_to_end" or m["moves"] in e2e_here:
            out.append(m)
    return out


def read_layer_metric(name: str, record):
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_')}", BENCH / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(record)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        return fail("--seed must be a non-negative whole number")

    bench_file = ROOT / "BENCHMARK.json"
    if not bench_file.is_file() or not (ROOT / "src" / "repro").is_dir():
        return fail(f"no BENCHMARK.json or no program under {ROOT / 'src'}")
    bench = load_json(bench_file)
    cells = {w["name"]: w for w in bench["workloads"]}
    if args.workload not in cells:
        return fail(f"unknown workload {args.workload!r}")
    cell = cells[args.workload]
    cfg = load_json(BENCH / "configs" / f"{cell['config']}.json")
    mix = load_json(BENCH / "traffic" / f"{cell['traffic']}.json")
    limits = load_json(BENCH / "limits" / f"{cell['name']}.json")

    # the compile cache lives at one fixed path inside the checkout, so that
    # only a cell's first run in a checkout compiles
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(ROOT))
    from bench import blocks
    try:
        block = blocks.named(cfg)
    except ValueError as e:
        return fail(str(e))
    import jax
    jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    try:
        devices = jax.devices()
    except RuntimeError as e:
        return fail(f"JAX found no accelerator: {e}", 3)
    if devices[0].platform != "tpu":
        return fail(f"a TPU is required; JAX found {devices[0].platform}", 3)
    if len(devices) < cell["chips"]:
        return fail(f"{cell['chips']} chips asked, {len(devices)} found", 3)

    from bench.drivers.common import RunSpec
    from bench.flops import peaks
    driver = importlib.import_module(f"bench.drivers.{mix['driver']}")
    spec = RunSpec(cell=cell, cfg=cfg, block=block, mix=mix, limits=limits,
                   seed=args.seed, seconds=args.seconds,
                   trace=bool(args.trace),
                   devices=devices[:cell["chips"]],
                   peak=peaks(devices[0].device_kind),
                   process_age=process_age_s)
    res = driver.run(spec)
    return report(bench, spec, res)


def report(bench: dict, spec, res) -> int:
    """Print the checks (stderr, last lines) and the result line."""
    cell = spec.cell["name"]
    if spec.trace:
        metrics = {}
        for m in cell_metrics(bench, cell, "per_layer"):
            v = read_layer_metric(m["name"], res.record)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    else:
        metrics = {m["name"]: {"value": float(res.e2e[m["name"]]),
                               "unit": m["unit"]}
                   for m in cell_metrics(bench, cell, "end_to_end")}
    checks = {c["name"]: {"value": c["value"], "limit": c["limit"]}
              for c in res.checks}
    correct = bool(res.checks) and all(
        c["value"] <= c["limit"] for c in res.checks)
    dev = spec.devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(spec.devices),
              "memory_peak_bytes": int(res.memory_peak_bytes)}
    line = {"correct": correct, "attempted": int(res.attempted),
            "failed": int(res.failed), "metrics": metrics, "device": device}
    if spec.trace and res.trace is not None:
        device["busy_s"] = res.trace.busy_s
        device["window_s"] = res.trace.window_s
        line["breakdown"] = res.trace.breakdown
    line["checks"] = checks
    for name, c in checks.items():
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(f"correct {correct}", file=sys.stderr)
    sys.stdout.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
