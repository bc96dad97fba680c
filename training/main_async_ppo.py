"""Asynchronous PPO training entry point (reference: training/main_async_ppo.py).

Runs the decoupled pipeline: generation servers + gserver manager + rollout
workers (agent/env loops) + trainer (master + model workers fed by the
trajectory push stream), with post-train weight publication hot-swapping the
generation servers.

Usage:
  python training/main_async_ppo.py --config training/configs/async_ppo.yaml \
      actor.args.path=/path/to/hf-ckpt dataset.args.dataset_path=math.jsonl \
      n_gen_servers=2 max_head_offpolicyness=4
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from areal_tpu.api.cli_args import dump_config, parse_cli
from areal_tpu.apps.local_runner import register_impls, run_experiment_local
from areal_tpu.base import constants, logging_
from areal_tpu.base.compile_cache import setup_compile_cache
from areal_tpu.experiments.async_ppo_exp import AsyncPPOMathExperiment

logger = logging_.getLogger("main_async_ppo")


def main():
    setup_compile_cache()
    register_impls()
    exp: AsyncPPOMathExperiment = parse_cli(AsyncPPOMathExperiment)
    exp.apply_device_overrides()
    cfg = exp.initial_setup()
    constants.set_experiment_trial_names(cfg.experiment_name, cfg.trial_name)
    dump_config(exp, os.path.join(constants.get_log_path(), "config.yaml"))
    logger.info(
        "starting async PPO %s/%s: trainer graph=%s, %d gen server(s), "
        "%d rollout worker(s), offpolicyness<=%d",
        cfg.experiment_name,
        cfg.trial_name,
        [r.name for r in cfg.master.model_rpcs],
        len(cfg.gen_servers),
        len(cfg.rollout_workers),
        exp.max_head_offpolicyness,
    )
    master = run_experiment_local(cfg)
    logger.info("finished: final stats %s", master.stats)


if __name__ == "__main__":
    main()
