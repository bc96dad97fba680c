"""How far the long train cell's rate lies apart from seed to seed, in ONE
process on the chip: the cell's driver is built and warmed once, then for
each seed the engine takes that seed's weights, a fresh optimizer state and
that seed's batches, steps once over every distinct batch and is timed over
whole cycles of them.  Prints a line a seed (tokens/s, the held experts'
pairs a step, the busiest held expert's over the mean) and the quartile
spread of the rates as the driver's check reckons it.

    chiprun --chips 1 --timeout 1500 -- python3 scripts/laguna_seed_spread.py \
        --seeds 2147530001,2147531112,2147532223 --cycles 5
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

CELL = "train-long-expert.laguna-xs.2"


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--seeds", required=True, help="comma-separated")
    p.add_argument("--cycles", type=int, default=5)
    args = p.parse_args()
    seeds = [int(s) for s in args.seeds.split(",")]

    from benchmark import run as bench
    from benchmark.lib import lengths
    from benchmark.lib.compile_clock import CompileClock

    spec = bench.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell, config, traffic = bench.resolve_cell(spec, CELL)
    from areal_tpu.base.compile_cache import setup_compile_cache

    setup_compile_cache()
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    dev = jax.devices()[0]
    work_dir = os.path.join(bench.OUT_DIR, "work")
    os.makedirs(work_dir, exist_ok=True)
    ctx = bench.RunContext(
        cell=cell, config=config, traffic=traffic, seed=seeds[0], seconds=0.0,
        traced=False, device_kind=dev.device_kind, n_devices=1, peaks={},
        clock=CompileClock(), work_dir=work_dir,
    )
    driver = bench.load_module("drivers", traffic["driver"]).build(ctx)
    driver.warm()
    engine = driver.model.engine
    rates = []
    for seed in seeds:
        engine.params = engine.opt_state = None
        gc.collect()
        ctx.seed = seed
        params = driver._init_params()
        engine.params, engine.opt_state = params, jax.jit(engine.tx.init)(params)
        del params
        driver.batches = [
            lengths.train_batch(traffic, seed, driver.vocab, k)
            for k in range(traffic["distinct_batches"])
        ]
        for b in driver.batches:
            driver._step(b)
        steps = []
        t0 = time.perf_counter()
        for _ in range(args.cycles):
            for b in driver.batches:
                tik = time.perf_counter()
                s = driver._step(b)
                s["seconds"] = time.perf_counter() - tik
                steps.append(s)
        elapsed = time.perf_counter() - t0
        tokens = args.cycles * sum(sum(b["seqlens"]) for b in driver.batches)
        held = sum(s["moe_held_pairs"] for s in steps)
        rates.append(tokens / elapsed)
        print(
            json.dumps(
                {
                    "seed": seed,
                    "train_tok_per_s": tokens / elapsed,
                    "step_seconds": sorted(round(s["seconds"], 4) for s in steps),
                    "held_pairs_a_step": held / len(steps),
                    "load_max_over_mean": sum(s["moe_busiest_pairs"] for s in steps)
                    * driver.cfg.n_held_experts / held,
                    "loss": steps[-1]["loss"],
                }
            ),
            flush=True,
        )
    if len(rates) >= 3:
        q = statistics.quantiles(rates, n=4)
        print(
            json.dumps(
                {
                    "median": statistics.median(rates),
                    "quartile_spread_share": (q[2] - q[0]) / statistics.median(rates),
                    "min": min(rates), "max": max(rates),
                }
            ),
            flush=True,
        )
    driver.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
