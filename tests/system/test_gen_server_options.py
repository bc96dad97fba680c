"""The generation server hands its engine what ``GenServerConfig`` says:
``GenerationServerWorker._configure`` runs with the engine class replaced
by one that records its keyword arguments and stops there (no socket, no
jit, no device program), once for each config field that names an engine
option.  The fields are read off the dataclass and the constructor's
signature, so a field added to both without a line of hand-over fails
here by its name."""

import dataclasses
import inspect
import types

import pytest

from areal_tpu.api.config import ModelAbstraction
from areal_tpu.api.system_api import GenServerConfig
from areal_tpu.engine import backend, inference_server
from areal_tpu.models.config import tiny_config
from areal_tpu.system.generation_server import GenerationServerWorker

ENGINE_DEFAULTS = {
    name: p.default
    for name, p in inspect.signature(
        inference_server.ContinuousBatchingEngine.__init__
    ).parameters.items()
}
CONFIG_DEFAULTS = {
    f.name: f.default for f in dataclasses.fields(GenServerConfig)
}

#: config field -> where the engine takes it, for the fields the engine
#: knows under another name (``sampling.x``: a field of its SamplingParams)
RENAMED = {
    "max_concurrent_batch": "max_batch",
    "prefix_cache_min_match_tokens": "prefix_cache_min_tokens",
    "worker_name": "server_name",
    "temperature": "sampling.temperature",
    "greedy": "sampling.greedy",
}
HANDED = {
    name: RENAMED.get(name, name)
    for name in CONFIG_DEFAULTS
    if name in RENAMED or name in ENGINE_DEFAULTS
}

#: a value for each field that is neither the dataclass's default nor the
#: engine's, and no other field's value here (a bool takes both values)
VALUES = {
    "worker_name": "gen_server_options_7",
    "max_concurrent_batch": 6,
    "kv_cache_len": 192,
    "chunk_size": 5,
    "temperature": 0.625,
    "cache_mode": "paged",
    "page_size": 48,
    "kv_pool_tokens": 1536,
    "kv_window_pool_tokens": 768,
    "kv_cache_dtype": "int8",
    "serving_weight_dtype": "int8",
    "prefill_chunk_tokens": 96,
    "pipeline_depth": 3,
    "prefix_cache_capacity_frac": 0.375,
    "prefix_cache_min_match_tokens": 24,
    "prefix_cache_host_bytes": 3 << 20,
    "prefix_pull_min_tokens": 384,
    "keep_routed_experts": 11,
    "keep_chosen_sets": 13,
}


class _Handed(Exception):
    """The recording engine's constructor ends ``_configure`` here."""


def _handed_for(monkeypatch, **fields):
    """What ``_configure`` passes the engine's constructor under a config
    with ``fields`` set, by where the engine takes it."""
    taken = {}

    def recording_engine(cfg, params, **kw):
        taken.update(kw)
        raise _Handed

    monkeypatch.setattr(
        inference_server, "ContinuousBatchingEngine", recording_engine
    )
    monkeypatch.setattr(
        backend, "make_model",
        lambda *a, **kw: types.SimpleNamespace(
            model_cfg=tiny_config(), init_params={}
        ),
    )
    fields.setdefault("worker_name", "gen_server_0")
    config = GenServerConfig(model=ModelAbstraction("random", {}), **fields)
    with pytest.raises(_Handed):
        GenerationServerWorker()._configure(config)
    for name, value in vars(taken.pop("sampling")).items():
        taken[f"sampling.{name}"] = value
    return taken


@pytest.mark.parametrize("field", sorted(HANDED))
def test_the_server_hands_the_engine_what_the_config_says(monkeypatch, field):
    where = HANDED[field]
    if isinstance(CONFIG_DEFAULTS[field], bool):
        values = [True, False]
    else:
        values = [VALUES[field]]
        assert values[0] != CONFIG_DEFAULTS[field]
        assert values[0] != ENGINE_DEFAULTS.get(where)
    for value in values:
        taken = _handed_for(monkeypatch, **{field: value})
        assert where in taken, f"{field}: the engine is not given {where}"
        assert taken[where] == value, (
            f"{field}={value!r} reached the engine as {where}={taken[where]!r}"
        )
