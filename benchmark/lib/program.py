"""The one place where the benchmark's files become the program's objects."""

from __future__ import annotations

import dataclasses
import os


def model_config(config: dict, role: str):
    """The program's ``TransformerConfig`` for a configuration file's
    published ``config.json`` keys (through the program's own HuggingFace
    adapter, as a checkpoint's config would come), at the depth the file's
    ``roles`` give this role."""
    import areal_tpu.models.hf  # noqa: F401 - registers the families
    from areal_tpu.models.hf.registry import family_from_architecture

    hf = config["hf_config"]
    cfg = family_from_architecture(hf["architectures"][0]).config_from_hf(hf)
    role_cfg = config["roles"][role]
    return dataclasses.replace(
        cfg,
        n_layers=role_cfg["num_hidden_layers"],
        dtype=hf["torch_dtype"],
        **role_cfg.get("model_overrides", {}),
    )


def point_roots_at(work_dir: str):
    """The program's logs, saves and caches live under ``work_dir``."""
    for var, sub in (
        ("AREAL_LOG_ROOT", "logs"),
        ("AREAL_SAVE_ROOT", "save"),
        ("AREAL_CACHE_ROOT", "cache"),
    ):
        os.environ[var] = os.path.join(work_dir, sub)
