"""CPU rehearsal of chip_smoke.py: its phase functions called with a tiny
config, so paths, arguments and control flow are guarded by the suite.
What only a chip can show (the compiled kernels, MFU against a known
peak, HBM) is asserted in ``chip_smoke.main()`` and checked on the chip.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import chip_smoke

TINY = dict(
    n_layers=1, hidden_dim=64, n_q_heads=4, n_kv_heads=2, head_dim=16,
    intermediate_dim=128, vocab_size=512, max_position_embeddings=4096,
    use_attention_bias=True, tied_embedding=True, dtype="float32",
)


def test_main_refuses_a_cpu_backend(tmp_path):
    """No TPU -> non-zero exit before any phase, and no result line."""
    proc = subprocess.run(
        [sys.executable, chip_smoke.__file__],
        capture_output=True, text=True, timeout=120, cwd=tmp_path,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode != 0
    assert "needs a TPU" in proc.stderr
    assert '"ok"' not in proc.stdout and '"phase"' not in proc.stdout


def test_cli_has_no_cpu_mode_and_no_skip_option(capsys):
    with pytest.raises(SystemExit):
        chip_smoke.main(["--help"])
    usage = capsys.readouterr().out
    assert "--chips" in usage and "--seed" in usage
    for word in ("cpu", "skip", "platform", "no-"):
        assert word not in usage.lower().replace("--chips", "")


def test_fit_layers_cuts_depth_only_and_says_why():
    fit = chip_smoke.fit_layers(chip_smoke.QWEN25_1P5B, 16 * 2**30)
    assert 1 <= fit["layers"] < 28 and fit["of"] == 28
    assert "HBM" in fit["why"]
    # the whole published depth fits when nothing else shares the chip
    big = chip_smoke.fit_layers(chip_smoke.QWEN25_1P5B, 10**12)
    assert big["layers"] == 28
    with pytest.raises(RuntimeError, match="not even one layer"):
        chip_smoke.fit_layers(chip_smoke.QWEN25_7B, 2**30)


def test_param_count_matches_init_params():
    import jax

    from areal_tpu.models.config import TransformerConfig
    from areal_tpu.models.transformer import init_params

    for cfg in (TINY, dict(TINY, tied_embedding=False, n_layers=2)):
        shapes = jax.eval_shape(
            lambda: init_params(TransformerConfig(**cfg), jax.random.PRNGKey(0))
        )
        n = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
        assert chip_smoke.param_count(cfg, cfg["n_layers"]) == n


# The two rehearsals run once each in module-scoped fixtures: several
# tests read one report.


@pytest.fixture(scope="module")
def serve_report(tmp_path_factory):
    return chip_smoke.phase_serve(
        TINY, 0, str(tmp_path_factory.mktemp("serve") / "work"),
        prompt_lens=(300, 40), group_prompt_len=50, group_size=2,
        max_new_tokens=16, kv_cache_len=2048, max_batch=4, page_size=256,
        prefill_chunk_tokens=256, chunk_size=8, n_reference=3,
        clock=chip_smoke.CompileClock(),
    )


@pytest.fixture(scope="module")
def async_report(tmp_path_factory):
    return chip_smoke.phase_async_ppo(
        TINY, 0, str(tmp_path_factory.mktemp("async") / "work"),
        n_layers=1, train_steps=2, max_new_tokens=16, train_bs_n_seqs=2,
        group_size=2, max_tokens_per_mb=256, gen_kv_cache_len=2048,
        gen_max_batch=4, page_size=256, prefill_chunk_tokens=256,
        gen_chunk_size=8, timeout=300,
    )


def test_phase_serve_rehearsal(serve_report):
    r = serve_report
    json.dumps(r)
    assert r["paged"] and r["requests"] == 4
    assert r["tokens_generated"] == 4 * 16
    assert max(r["prompt_lens"]) > r["prefill_chunk_tokens"]
    # float32 tiny model on the CPU: the server and the plain forward agree
    # to rounding (the chip's bf16 tolerance is in chip_smoke.py)
    assert r["reference"]["max_abs_diff"] < 1e-3, r["reference"]
    assert len(r["reference"]["sequences"]) == 3
    assert r["compile_seconds"] > 0


def test_serve_chip_only_checks_live_in_main(serve_report):
    # chip-only facts are reported, not asserted, off-chip ...
    assert serve_report["use_paged_kernel"] is False
    assert serve_report["kernel_interpret"] is True
    # ... and main()'s check refuses a reference path: never a fallback
    with pytest.raises(AssertionError, match="COMPILED paged kernel"):
        chip_smoke.check_serve(serve_report)


def test_phase_async_ppo_rehearsal(async_report):
    r = async_report
    json.dumps(r)
    assert r["train_steps"] == 2 and r["trainer_version"] == 2
    assert all(np.isfinite(r["losses"])) and all(l != 0 for l in r["losses"])
    assert all(g > 0 for g in r["grad_norms"])
    assert r["server_paged"] and r["server_tokens_generated"] > 0
    assert r["new_dense_fallback_warnings"] == []


def test_async_chip_only_checks_live_in_main(async_report):
    # no published peak on the CPU: MFU is skipped, and main() would fail
    assert async_report["areal_train_mfu"] == 0
    with pytest.raises(AssertionError, match="peak was not known"):
        chip_smoke.check_async(async_report, 2)


@pytest.mark.slow  # ~90 s: the four-device phase and both comparisons
def test_phase_sharded_rehearsal_on_virtual_devices(tmp_path):
    cfg = dict(TINY, n_layers=2, tied_embedding=False)
    r = chip_smoke.phase_sharded(
        cfg, 0, str(tmp_path / "work"), n_layers=2, train_steps=2,
        max_new_tokens=16, train_bs_n_seqs=2, group_size=2,
        max_tokens_per_mb=256, gen_kv_cache_len=2048, gen_max_batch=4,
        page_size=256, prefill_chunk_tokens=256, gen_chunk_size=8,
        timeout=600,
    )
    a = r["async_ppo"]
    assert a["trainer_mesh"] == {"fsdp": 2}
    assert a["inspect"]["trainer_devices"] == [0, 1]
    assert a["inspect"]["server_devices"] == [2, 3]
    assert r["fsdp_vs_one_chip"]["rel_diff"] < 1e-4
    for row in r["tp_server_vs_one_chip"]["requests"]:
        assert row["agree_prefix"] == row["tokens"]
