"""SFT training entry point (reference: training/main_sft.py).

Usage:
  python training/main_sft.py --config training/configs/sft.yaml \
      model.args.path=/path/to/hf-ckpt dataset.args.dataset_path=data.jsonl \
      train_bs_n_seqs=32
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from areal_tpu.api.cli_args import dump_config, parse_cli
from areal_tpu.apps.local_runner import register_impls, run_experiment_local
from areal_tpu.base import constants, logging_
from areal_tpu.base.compile_cache import setup_compile_cache
from areal_tpu.experiments.sft_exp import SFTExperiment

logger = logging_.getLogger("main_sft")


def main():
    setup_compile_cache()
    register_impls()
    exp: SFTExperiment = parse_cli(SFTExperiment)
    exp.apply_device_overrides()
    cfg = exp.initial_setup()
    constants.set_experiment_trial_names(cfg.experiment_name, cfg.trial_name)
    dump_config(exp, os.path.join(constants.get_log_path(), "config.yaml"))
    logger.info(
        "starting SFT experiment %s/%s: %d worker(s), mesh %s",
        cfg.experiment_name,
        cfg.trial_name,
        len(cfg.model_workers),
        exp.mesh_spec,
    )
    master = run_experiment_local(cfg)
    logger.info("finished: final stats %s", master.stats)


if __name__ == "__main__":
    main()
