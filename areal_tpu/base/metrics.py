"""Experiment metrics sinks: JSONL + tensorboard + optional wandb/swanlab.

Rebuild of the reference's observability fan-out (reference:
realhf/system/master_worker.py:291-350 initializes wandb / swanlab /
tensorboard and realhf/base/logging.py ``log_swanlab_wandb_tensorboard``
writes every scalar to all three).  Differences by design: a JSONL sink is
always on (it is the machine-readable artifact tests and the offline
evaluator consume), tensorboard event files are written with the
``tensorboard`` package's own record writer and protos (torch's
``SummaryWriter`` imports torch AND, where it is installed, tensorflow:
15 s a process to log a few scalars), and wandb/swanlab are optional
imports that degrade to no-ops when the package
or the opt-in env (``AREAL_WANDB=1`` / ``AREAL_SWANLAB=1``) is absent.
"""

from __future__ import annotations

import json
import os
import time
from typing import Any, Dict, Optional

from areal_tpu.base import logging_

logger = logging_.getLogger("metrics")


class _ScalarEvents:
    """A tensorboard event file of scalars: length-prefixed, checksummed
    ``Event`` records, the first one the file version."""

    def __init__(self, log_dir: str):
        import socket

        from tensorboard.compat.proto import event_pb2, summary_pb2
        from tensorboard.summary.writer.record_writer import RecordWriter

        self._event, self._summary = event_pb2.Event, summary_pb2.Summary
        os.makedirs(log_dir, exist_ok=True)
        self._file = open(
            os.path.join(
                log_dir,
                f"events.out.tfevents.{int(time.time()):010d}."
                f"{socket.gethostname()}.{os.getpid()}",
            ),
            "wb",
        )
        self._records = RecordWriter(self._file)
        self._write(self._event(file_version="brain.Event:2"))

    def _write(self, event):
        event.wall_time = time.time()
        self._records.write(event.SerializeToString())
        self._file.flush()

    def add_scalar(self, tag: str, value: float, global_step: int):
        self._write(
            self._event(
                step=global_step,
                summary=self._summary(
                    value=[self._summary.Value(tag=tag, simple_value=value)]
                ),
            )
        )

    def close(self):
        self._file.close()


class MetricsLogger:
    """Fan-out scalar logger keyed by global step."""

    def __init__(
        self,
        log_dir: str,
        experiment_name: str = "",
        trial_name: str = "",
        enable_tensorboard: bool = True,
    ):
        os.makedirs(log_dir, exist_ok=True)
        self.log_dir = log_dir
        self._jsonl_path = os.path.join(log_dir, "stats.jsonl")
        self._jsonl = open(self._jsonl_path, "a", buffering=1)
        self._tb = None
        if enable_tensorboard:
            try:
                self._tb = _ScalarEvents(os.path.join(log_dir, "tensorboard"))
            except Exception:  # noqa: BLE001 - tb is best-effort
                logger.warning("tensorboard unavailable; skipping")
        self._wandb = None
        if os.environ.get("AREAL_WANDB") == "1":
            try:
                import wandb

                self._wandb = wandb
                wandb.init(
                    project=experiment_name or "areal_tpu",
                    name=trial_name or None,
                    dir=log_dir,
                    mode=os.environ.get("WANDB_MODE", "online"),
                )
            except Exception:  # noqa: BLE001
                logger.warning("wandb requested but unavailable")
                self._wandb = None
        self._swanlab = None
        if os.environ.get("AREAL_SWANLAB") == "1":
            try:
                import swanlab

                self._swanlab = swanlab
                swanlab.init(
                    project=experiment_name or "areal_tpu",
                    experiment_name=trial_name or None,
                    logdir=log_dir,
                )
            except Exception:  # noqa: BLE001
                logger.warning("swanlab requested but unavailable")
                self._swanlab = None

    def log(self, stats: Dict[str, Any], step: int):
        """Write one step's scalars to every sink."""
        scalars = {
            k: float(v)
            for k, v in stats.items()
            if isinstance(v, (int, float)) and not isinstance(v, bool)
        }
        rec = {"step": step, "time": time.time(), **scalars}
        self._jsonl.write(json.dumps(rec) + "\n")
        if self._tb is not None:
            for k, v in scalars.items():
                self._tb.add_scalar(k, v, global_step=step)
        if self._wandb is not None:
            self._wandb.log(scalars, step=step)
        if self._swanlab is not None:
            self._swanlab.log(scalars, step=step)

    def close(self):
        self._jsonl.close()
        if self._tb is not None:
            self._tb.close()
        if self._wandb is not None:
            self._wandb.finish()
        if self._swanlab is not None:
            self._swanlab.finish()
