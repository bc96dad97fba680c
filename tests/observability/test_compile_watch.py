"""CompileWatch: cache-poll compile counting against REAL jitted
functions, the steady-state recompile sentinel's fire-once/re-arm
episode discipline, and the jax.monitoring duration signal."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from areal_tpu.observability.compile_watch import (
    CompileWatch,
    _on_jax_event_duration,
)
from areal_tpu.observability.registry import MetricsRegistry
from areal_tpu.observability.tracing import TraceConfig, Tracer


def _watch(**kw):
    reg = MetricsRegistry()
    # sample_rate=0: force() must still record compiles
    trc = Tracer(TraceConfig(sample_rate=0.0), worker="w0")
    kw.setdefault("monitoring", False)
    return CompileWatch(registry=reg, tracer=trc, **kw), reg, trc


def _jitted():
    @jax.jit
    def f(x):
        return x * 2

    return f


def test_poll_counts_fresh_compiles_per_fn():
    w, reg, trc = _watch()
    f = _jitted()
    assert w.watch("decode_chunk", f)
    assert w.poll() == {}  # nothing ran yet
    f(jnp.zeros((2,), jnp.float32))
    assert w.poll() == {"decode_chunk": 1}
    assert (
        reg.counter("areal_xla_compiles_total").value(fn="decode_chunk")
        == 1.0
    )
    # same signature again: cache hit, no compile
    f(jnp.ones((2,), jnp.float32))
    assert w.poll() == {}
    # new shape: one more compile
    f(jnp.zeros((3,), jnp.float32))
    assert w.poll() == {"decode_chunk": 1}
    assert w.stats()["xla_compiles/decode_chunk"] == 2.0


def test_compile_records_forced_trace_span_with_signature():
    w, reg, trc = _watch()
    f = _jitted()
    w.watch("fill_chunk", f, signature=lambda: "bs=2 f32")
    f(jnp.zeros((2,), jnp.float32))
    w.poll()
    events = trc.snapshot(0)["events"]
    spans = [e for e in events if e["name"] == "xla.compile"]
    assert spans  # recorded despite sample_rate=0 (forced root)
    assert spans[0]["attrs"]["fn"] == "fill_chunk"
    assert spans[0]["attrs"]["signature"] == "bs=2 f32"


def test_watch_refuses_fn_without_cache():
    w, _, _ = _watch()
    assert not w.watch("plain", lambda x: x)


def test_sentinel_fires_once_per_episode_and_rearms():
    fired = []
    w, reg, _ = _watch(
        quiet_after_steps=5, on_steady_compile=fired.append
    )
    f = _jitted()
    w.watch("decode_chunk", f)
    stalls = reg.counter("areal_trace_stall_total")

    # before the quiet threshold: compiles count but never alarm
    f(jnp.zeros((2,), jnp.float32))
    w.note_step(1)
    w.poll()
    assert stalls.value(kind="recompile") == 0.0
    assert not w.armed

    # cross the threshold -> armed
    w.note_step(5)
    assert w.armed

    # a steady-state compile burst = ONE fire, with the fns attributed
    f(jnp.zeros((3,), jnp.float32))
    f(jnp.zeros((4,), jnp.float32))
    assert w.poll() == {"decode_chunk": 2}
    assert stalls.value(kind="recompile") == 1.0
    assert fired == [["decode_chunk"]]
    assert w.stats()["xla_sentinel_fires_total"] == 1.0
    assert w.stats()["xla_steady_compiles_total"] == 2.0

    # more compiles in the SAME episode (no clean poll between): no
    # second alarm
    f(jnp.zeros((5,), jnp.float32))
    w.poll()
    assert stalls.value(kind="recompile") == 1.0

    # a clean poll re-arms; the next compile is a NEW episode
    assert w.poll() == {}
    assert w.armed
    f(jnp.zeros((6,), jnp.float32))
    w.poll()
    assert stalls.value(kind="recompile") == 2.0
    assert w.stats()["xla_sentinel_fires_total"] == 2.0


def test_quiet_after_steps_zero_never_arms():
    w, reg, _ = _watch(quiet_after_steps=0)
    f = _jitted()
    w.watch("decode_chunk", f)
    w.note_step(10_000)
    assert not w.steady
    f(jnp.zeros((2,), jnp.float32))
    w.poll()
    assert (
        reg.counter("areal_trace_stall_total").value(kind="recompile")
        == 0.0
    )


def test_backend_compile_duration_signal():
    w, reg, _ = _watch()
    w._note_backend_compile(1.25)
    assert (
        reg.counter("areal_xla_compiles_total").value(fn="backend") == 1.0
    )
    total, count = reg.histogram("areal_xla_compile_seconds").snapshot()
    assert total == pytest.approx(1.25)
    assert count == 1


def test_monitoring_dispatch_filters_event_names():
    w, reg, _ = _watch(monitoring=True)
    try:
        assert w.monitoring_active  # real jax.monitoring registered
        _on_jax_event_duration("/jax/backend_compile", 0.5)
        _on_jax_event_duration("/jax/unrelated_event", 9.9)
        assert (
            reg.counter("areal_xla_compiles_total").value(fn="backend")
            == 1.0
        )
    finally:
        w.close()


def test_on_steady_compile_exception_does_not_break_poll():
    def boom(fns):
        raise RuntimeError("callback bug")

    w, reg, _ = _watch(quiet_after_steps=1, on_steady_compile=boom)
    f = _jitted()
    w.watch("decode_chunk", f)
    w.note_step(1)
    f(jnp.zeros((2,), jnp.float32))
    assert w.poll() == {"decode_chunk": 1}  # swallowed, still counted
    assert (
        reg.counter("areal_trace_stall_total").value(kind="recompile")
        == 1.0
    )


def test_sentinel_on_the_engine_silent_when_steady_fires_on_bucket_change():
    """The sentinel on the serving engine's own jits (the entry points
    the generation server watches on the dense path): armed after a warm
    wave, it sees no compile over further waves of the same shapes, and a
    second engine with another KV bucket makes it fire exactly once, with
    the burst counted."""
    from areal_tpu.api.model_api import (
        APIGenerateInput,
        GenerationHyperparameters,
    )
    from areal_tpu.engine import inference_server as eng_mod
    from areal_tpu.models import transformer
    from areal_tpu.models.config import tiny_config

    # a vocabulary no other test uses: the watched jits are module-level,
    # and a shape another test compiled first would be a cache hit here
    cfg = tiny_config(vocab_size=72, max_position_embeddings=256)
    params = transformer.init_params(cfg, jax.random.PRNGKey(0))

    def engine(kv_cache_len):
        return eng_mod.ContinuousBatchingEngine(
            cfg, params, max_batch=2, kv_cache_len=kv_cache_len,
            chunk_size=4, cache_mode="dense",
        )

    def wave(eng, tag):
        for i in range(2):
            ids = [7 + i, 8, 9, 10]
            eng.submit(
                APIGenerateInput(
                    qid=f"{tag}{i}", prompt_ids=ids, input_ids=ids,
                    gconfig=GenerationHyperparameters(
                        max_new_tokens=8, greedy=True
                    ),
                )
            )
        for _ in range(200):
            if not eng.has_work:
                break
            eng.step()
        assert len(eng.drain_results()) == 2

    w, reg, _ = _watch(quiet_after_steps=1)
    for name in ("decode_chunk", "admit_rows", "sample_rows"):
        assert w.watch(name, getattr(eng_mod, "_" + name))
    stalls = reg.counter("areal_trace_stall_total")

    eng = engine(64)
    wave(eng, "warm")
    assert sum(w.poll().values()) >= 1  # the warm wave's own compiles
    w.note_step(1)
    assert w.armed
    wave(eng, "steady-a")
    wave(eng, "steady-b")
    assert w.poll() == {}
    assert stalls.value(kind="recompile") == 0.0

    wave(engine(128), "forced")
    burst = w.poll()
    assert sum(burst.values()) >= 1, burst
    assert stalls.value(kind="recompile") == 1.0
    assert w.stats()["xla_sentinel_fires_total"] == 1.0
    assert w.stats()["xla_steady_compiles_total"] == sum(burst.values())
