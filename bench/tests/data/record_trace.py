#!/usr/bin/env python3
"""Record a small device trace on the chip and print what it holds.

    python bench/tests/data/record_trace.py [outdir]

Runs one MoE serving layer (Pallas kernels, gpt2-moe widths) and one
training step of a one-layer transformer-xl-moe under the JAX profiler,
then lists the trace's planes, lines and the device events with the most
time.  The trace written here is the fixture that
``bench/tests/test_bench_trace_reduce.py`` reads; looking at its event
names by hand is how the kernel-name lists in ``bench/metrics`` were set.
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[3]
sys.path.insert(0, str(ROOT / "src"))


def main() -> int:
    out = Path(sys.argv[1] if len(sys.argv) > 1 else "chiprun_out/trace_probe")
    out.mkdir(parents=True, exist_ok=True)
    import jax
    import jax.numpy as jnp
    import numpy as np

    print("env", {k: os.environ.get(k) for k in
                  ("JAX_COMPILATION_CACHE_DIR", "LIBTPU_INIT_ARGS", "HOME",
                   "TMPDIR", "XDG_CACHE_HOME")})
    devs = jax.devices()
    print("devices", devs[0].platform, devs[0].device_kind, len(devs))
    if devs[0].platform != "tpu":
        print("no TPU", file=sys.stderr)
        return 1
    print("prng big seed", jax.random.key_data(jax.random.PRNGKey(2**31 + 12345)))
    from repro.configs import get_config
    from repro.core import PlanArrays, init_moe_params, plan_placement
    from repro.core.serving import serve_moe_layer
    from repro.data import DataConfig
    from repro.optim.adamw import AdamWConfig
    from repro.runtime import Trainer, TrainerConfig

    cfg = get_config("gpt2-moe")
    params = init_moe_params(jax.random.PRNGKey(0), 768, 3072, 16, "gelu",
                             dtype=jnp.bfloat16)
    pop = np.random.RandomState(0).dirichlet(np.ones(16) * 0.5)
    plan = plan_placement(pop, 16, max_pack=4)
    pa = PlanArrays.from_plan(plan)
    fns = {}
    for t in (8, 512):
        x = jax.random.normal(jax.random.PRNGKey(t), (t, 768)).astype(jnp.bfloat16)
        f = jax.jit(lambda x, p, pl: serve_moe_layer(
            None, x, p, cfg.moe, pl, ffn_type="gelu", top_k=1,
            min_replicas=int(plan.n_replicas.min()))[0])
        jax.block_until_ready(f(x, params, pa))
        fns[t] = (f, x)
    txl = dataclasses.replace(get_config("transformer-xl-moe"), n_layers=1)
    tr = Trainer(txl, DataConfig(vocab_size=txl.vocab_size, seq_len=512,
                                 global_batch=8, seed=0),
                 AdamWConfig(lr=1e-3, total_steps=10, warmup_steps=1),
                 TrainerConfig(steps=2, ckpt_dir=None, schedule="priority+partition"))
    st = tr.init_state()
    batch = {k: jnp.asarray(v) for k, v in tr.dataset.batch(0).items()}
    p, o, m = tr.step_fn(st["params"], st["opt_state"], batch)
    jax.block_until_ready(m)
    jax.profiler.start_trace(str(out))
    for t, (f, x) in fns.items():
        with jax.profiler.TraceAnnotation(f"serve_layer_T{t}"):
            jax.block_until_ready(f(x, params, pa))
    with jax.profiler.TraceAnnotation("train_step"):
        p, o, m = tr.step_fn(p, o, batch)
        jax.block_until_ready(m)
    jax.profiler.stop_trace()
    files = glob.glob(str(out / "plugins/profile/*/*.xplane.pb"))
    print("xplane", files, [os.path.getsize(f) for f in files])
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(files[0])
    for plane in pd.planes:
        lines = list(plane.lines)
        print("PLANE", repr(plane.name), "lines",
              [(ln.name, sum(1 for _ in ln.events)) for ln in lines][:12])
        if not plane.name.startswith("/device:TPU:0"):
            continue
        for ln in lines:
            tot = collections.Counter()
            cnt = collections.Counter()
            first = None
            for ev in ln.events:
                tot[ev.name] += ev.duration_ns
                cnt[ev.name] += 1
                if first is None:
                    first = ev
            print("  LINE", repr(ln.name))
            if first is not None:
                print("    first event", first.name, first.start_ns,
                      first.duration_ns, dict(list(first.stats)[:12]))
            for name, ns in tot.most_common(40):
                print(f"    {ns / 1e3:10.1f} us  x{cnt[name]:4d}  {name[:140]}")
    print("memory_stats", devs[0].memory_stats() and
          {k: v for k, v in devs[0].memory_stats().items() if "peak" in k or "limit" in k})
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
