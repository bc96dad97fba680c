"""Time the engine's per-step phases on the real chip: dispatch (jit call
returns), harvest (device_get), admit, misc host work.  Identifies whether
dispatch is truly async and where the per-chunk overhead beyond device
time goes."""

import json
import time

import numpy as np


def main():
    from areal_tpu.base.compile_cache import setup_compile_cache

    setup_compile_cache()
    import jax

    from areal_tpu.api.model_api import (
        APIGenerateInput,
        GenerationHyperparameters,
    )
    from areal_tpu.engine.inference_server import ContinuousBatchingEngine
    from scripts.profile_decode import bench_cfg
    from areal_tpu.models import transformer
    import jax.numpy as jnp

    cfg = bench_cfg()
    params = jax.tree.map(
        lambda x: x.astype(jnp.bfloat16),
        transformer.init_params(cfg, jax.random.PRNGKey(0)),
    )

    for B, chunk in ((32, 128), (32, 256), (64, 128), (64, 256)):
        eng = ContinuousBatchingEngine(
            cfg, params, max_batch=B, kv_cache_len=2048, chunk_size=chunk
        )
        rng = np.random.default_rng(1)
        gcfg = GenerationHyperparameters(max_new_tokens=512, temperature=1.0)

        def submit_all(tag):
            for i in range(B):
                ids = rng.integers(0, cfg.vocab_size, (512,)).tolist()
                eng.submit(APIGenerateInput(
                    qid=f"{tag}{i}", prompt_ids=ids, input_ids=ids,
                    gconfig=gcfg))

        # warmup drain: compiles every bucket the timed run will touch
        submit_all("w")
        while eng.has_work:
            eng.step()
        eng.drain_results()
        submit_all("t")

        t_dispatch = t_harvest = t_admit = 0.0
        n_steps = 0
        # monkeypatch instrumentation
        orig_dispatch = eng._dispatch_chunk
        orig_harvest = eng._harvest_oldest
        orig_admit = eng._admit

        def dispatch():
            nonlocal t_dispatch
            t0 = time.perf_counter()
            orig_dispatch()
            t_dispatch += time.perf_counter() - t0

        def harvest():
            nonlocal t_harvest
            t0 = time.perf_counter()
            n = orig_harvest()
            t_harvest += time.perf_counter() - t0
            return n

        def admit():
            nonlocal t_admit
            t0 = time.perf_counter()
            orig_admit()
            t_admit += time.perf_counter() - t0

        eng._dispatch_chunk = dispatch
        eng._harvest_oldest = harvest
        eng._admit = admit

        t0 = time.perf_counter()
        n_tok = 0
        while eng.has_work:
            n_tok += eng.step()
            n_steps += 1
        dt = time.perf_counter() - t0
        print(json.dumps({
            "B": B, "chunk": chunk,
            "tok_s": round(n_tok / dt, 1),
            "total_s": round(dt, 2),
            "steps": n_steps,
            "dispatch_s": round(t_dispatch, 2),
            "harvest_s": round(t_harvest, 2),
            "admit_s": round(t_admit, 2),
            "other_s": round(dt - t_dispatch - t_harvest - t_admit, 2),
        }), flush=True)


if __name__ == "__main__":
    main()
