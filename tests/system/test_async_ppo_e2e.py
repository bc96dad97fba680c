"""End-to-end async PPO: rollout cluster (gen server + gserver manager +
rollout workers) feeding a decoupled trainer via the push stream, with
post-train weight publication hot-swapping the generation servers
(the reference's boba asynchronous pipeline, SURVEY.md §3.1/3.2)."""

import numpy as np
import pytest

from tests.fixtures import (  # noqa: F401
    dataset,
    dataset_path,
    mixed_dataset_path,
    save_path,
    tokenizer,
    tokenizer_path,
)


def test_async_ppo_e2e(dataset_path, tokenizer_path, tmp_path, monkeypatch):
    monkeypatch.setenv("AREAL_LOG_ROOT", str(tmp_path / "logs"))
    monkeypatch.setenv("AREAL_SAVE_ROOT", str(tmp_path / "save"))

    from areal_tpu.apps.local_runner import run_experiment_local
    from tests.system.exp_factories import make_async_ppo_exp

    exp = make_async_ppo_exp(dataset_path, tokenizer_path)
    cfg = exp.initial_setup()
    names_ = [r.name for r in cfg.master.model_rpcs]
    assert "actor_gen" not in names_ and "rew_inf" not in names_
    assert "actor_train" in names_ and "actor_inf" in names_
    assert cfg.gserver_manager is not None
    assert len(cfg.gen_servers) == 1 and len(cfg.rollout_workers) == 1
    # this fleet serves the model's own dtype: no int8 tree beside each
    # publish (tests/system/test_weight_publish.py holds that path)
    for w in cfg.model_workers:
        w.publish_quantized_int8 = False

    master = run_experiment_local(cfg, timeout=600)

    assert len(master.stats_history) >= 2
    s = master.stats_history[-1]
    assert np.isfinite(s["actor_train/loss"])
    # trajectories carried behavioral logprobs + version stamps through the
    # stream; decoupled loss ran (prox_logp recomputed by actor_inf)
    assert "actor_train/kl" in s


@pytest.mark.slow  # ~37s full e2e; tier-1 keeps test_async_ppo_e2e as the
# launch-path smoke and tests/verifiers/test_code_verify.py as the
# sandboxed-verifier smoke
def test_async_ppo_mixed_math_code(
    mixed_dataset_path, tokenizer_path, tmp_path, monkeypatch
):
    """Async PPO over a mixed math+code dataset: code rewards come from the
    sandboxed verifier actually executing the (random-model) answers, math
    rewards from the hardened parser — the full multi-task dispatch path."""
    monkeypatch.setenv("AREAL_LOG_ROOT", str(tmp_path / "logs"))
    monkeypatch.setenv("AREAL_SAVE_ROOT", str(tmp_path / "save"))

    from areal_tpu.apps.local_runner import run_experiment_local
    from tests.system.exp_factories import make_async_ppo_exp

    exp = make_async_ppo_exp(
        mixed_dataset_path,
        tokenizer_path,
        trial_name="e2e-mixed",
    )
    cfg = exp.initial_setup()
    master = run_experiment_local(cfg, timeout=600)
    assert len(master.stats_history) >= 2
    assert np.isfinite(master.stats_history[-1]["actor_train/loss"])


@pytest.mark.slow  # ~63s full e2e (tripped the 60s runtime guard);
# tier-1 keeps test_async_ppo_e2e as the launch-path smoke and
# tests/agents/test_math_multi_turn_agent.py as the multi-turn smoke
def test_async_ppo_multi_turn_agent(
    dataset_path, tokenizer_path, tmp_path, monkeypatch
):
    """Async PPO with the MULTI-TURN agent: each rollout is a
    retry-with-feedback chain, every turn becomes its own trajectory with
    turn-discounted reward-to-go, and training consumes them through the
    same stream (reference: math_multi_turn_agent + AsyncRLOptions)."""
    monkeypatch.setenv("AREAL_LOG_ROOT", str(tmp_path / "logs"))
    monkeypatch.setenv("AREAL_SAVE_ROOT", str(tmp_path / "save"))

    from areal_tpu.apps.local_runner import run_experiment_local
    from tests.system.exp_factories import make_async_ppo_exp

    exp = make_async_ppo_exp(
        dataset_path,
        tokenizer_path,
        trial_name="e2e-multiturn",
        agent_type="math-multi-turn",
        num_turns=2,
        turn_level_discount=0.5,
        group_size=2,
    )
    cfg = exp.initial_setup()
    # staleness accounting switched to the per-turn minimum yield (1), NOT
    # the group size (2) — counting group_size seqs per rollout deadlocks
    assert cfg.gserver_manager.group_size == 1
    agent = cfg.rollout_workers[0].agent
    assert agent.type_ == "math-multi-turn"
    assert agent.args["num_turns"] == 2

    master = run_experiment_local(cfg, timeout=600)
    assert len(master.stats_history) >= 2
    assert np.isfinite(master.stats_history[-1]["actor_train/loss"])
