"""Synchronous PPO training entry point (reference: training/main_sync_ppo.py).

Usage:
  python training/main_sync_ppo.py --config training/configs/sync_ppo.yaml \
      actor.args.path=/path/to/hf-ckpt dataset.args.dataset_path=math.jsonl \
      ppo.gen.max_new_tokens=1024 train_bs_n_seqs=512
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from areal_tpu.api.cli_args import dump_config, parse_cli
from areal_tpu.apps.local_runner import register_impls, run_experiment_local
from areal_tpu.base import constants, logging_
from areal_tpu.base.compile_cache import setup_compile_cache
from areal_tpu.experiments.ppo_math_exp import PPOMathExperiment

logger = logging_.getLogger("main_sync_ppo")


def main():
    setup_compile_cache()
    register_impls()
    exp: PPOMathExperiment = parse_cli(PPOMathExperiment)
    exp.apply_device_overrides()
    cfg = exp.initial_setup()
    constants.set_experiment_trial_names(cfg.experiment_name, cfg.trial_name)
    dump_config(exp, os.path.join(constants.get_log_path(), "config.yaml"))
    logger.info(
        "starting sync PPO %s/%s: graph=%s",
        cfg.experiment_name,
        cfg.trial_name,
        [r.name for r in cfg.master.model_rpcs],
    )
    master = run_experiment_local(cfg)
    logger.info("finished: final stats %s", master.stats)


if __name__ == "__main__":
    main()
