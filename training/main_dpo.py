"""DPO training entry point (preference pairs -> sigmoid preference loss;
the reference ships the DPO math in realhf/impl/model/utils/dpo_functional.py
without a CLI — this wires its ReaLHF-era quickstart shape).

Usage:
  python training/main_dpo.py --config training/configs/dpo.yaml \
      actor.args.path=/path/to/hf-ckpt dataset.args.dataset_path=pairs.jsonl \
      beta=0.1 train_bs_n_seqs=32
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from areal_tpu.api.cli_args import dump_config, parse_cli
from areal_tpu.apps.local_runner import register_impls, run_experiment_local
from areal_tpu.base import constants, logging_
from areal_tpu.base.compile_cache import setup_compile_cache
from areal_tpu.experiments.dpo_exp import DPOExperiment

logger = logging_.getLogger("main_dpo")


def main():
    setup_compile_cache()
    register_impls()
    exp: DPOExperiment = parse_cli(DPOExperiment)
    exp.apply_device_overrides()
    cfg = exp.initial_setup()
    constants.set_experiment_trial_names(cfg.experiment_name, cfg.trial_name)
    dump_config(exp, os.path.join(constants.get_log_path(), "config.yaml"))
    logger.info(
        "starting DPO experiment %s/%s: %d worker(s), mesh %s",
        cfg.experiment_name,
        cfg.trial_name,
        len(cfg.model_workers),
        exp.mesh_spec,
    )
    master = run_experiment_local(cfg)
    logger.info("finished: final stats %s", master.stats)


if __name__ == "__main__":
    main()
