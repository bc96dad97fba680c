"""Quickstart CLI: launch any registered experiment by name.

Rebuild of the reference's quickstart entrypoint (reference:
realhf/apps/quickstart.py + api/quickstart/entrypoint.py — hydra-backed
per-experiment subcommands over the experiment registry).  Ours resolves
the experiment class from the registry, parses ``--config``/dotted
overrides with the in-repo config system (api/cli_args.py), and launches
either in-process (threads, debug) or through the multi-process launcher
(apps/main.py).

Usage::

    python -m areal_tpu.apps.quickstart list
    python -m areal_tpu.apps.quickstart ppo_math --config cfg.yaml \
        trial_name=run0 actor.args.path=/ckpts/qwen2-1.5b
    python -m areal_tpu.apps.quickstart async_ppo_math --mode processes ...
"""

from __future__ import annotations

import os
import sys

from areal_tpu.api import system_api
from areal_tpu.api.cli_args import dump_config, parse_cli
from areal_tpu.base import constants, logging_
from areal_tpu.base.compile_cache import setup_compile_cache

logger = logging_.getLogger("quickstart")


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    from areal_tpu.apps.local_runner import register_impls

    register_impls()

    if not argv or argv[0] in ("-h", "--help"):
        print(__doc__)
        print("registered experiments:")
        for name in system_api.list_experiments():
            print(f"  {name}")
        return 0
    cmd = argv.pop(0)
    if cmd == "list":
        for name in system_api.list_experiments():
            print(name)
        return 0

    mode = "threads"
    if "--mode" in argv:
        i = argv.index("--mode")
        mode = argv[i + 1]
        del argv[i : i + 2]

    setup_compile_cache()
    cls = system_api.experiment_cls(cmd)
    exp = parse_cli(cls, argv=argv)
    exp.apply_device_overrides()
    cfg = exp.initial_setup()
    constants.set_experiment_trial_names(cfg.experiment_name, cfg.trial_name)
    dump_config(exp, os.path.join(constants.get_log_path(), "config.yaml"))
    logger.info(
        "quickstart %s (%s/%s): %d model worker(s), %d gen server(s), "
        "%d rollout worker(s)%s",
        cmd,
        cfg.experiment_name,
        cfg.trial_name,
        len(cfg.model_workers),
        len(cfg.gen_servers),
        len(cfg.rollout_workers),
        ", gateway" if getattr(cfg, "gateway", None) is not None else "",
    )
    if mode == "threads":
        from areal_tpu.apps.local_runner import run_experiment_local

        master = run_experiment_local(cfg)
        logger.info("finished: final stats %s", master.stats)
    else:
        from areal_tpu.apps.main import launch_experiment

        launch_experiment(cfg, mode="local" if mode == "processes" else mode)
    return 0


if __name__ == "__main__":
    sys.exit(main())
