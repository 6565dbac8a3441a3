"""End-to-end system tests: trainer, serving, checkpoints, dry-run.

All multi-device behaviour runs in subprocesses with 8 simulated host
devices (see testing/subproc.py for why).
"""
import pytest

from repro.testing.subproc import run_checks


@pytest.mark.slow
def test_trainer_group():
    run_checks([
        "check_trainer_loss_decreases",
        "check_trainer_zeropp_tracks_baseline",
    ], n_devices=8, timeout=900)


@pytest.mark.slow
def test_trainer_accum():
    run_checks(["check_trainer_grad_accumulation"], n_devices=8, timeout=900)


@pytest.mark.slow
def test_checkpoint_elastic():
    run_checks(["check_checkpoint_elastic_restart"], n_devices=8,
               timeout=900)


@pytest.mark.slow
def test_serve_consistency_dense():
    run_checks(["check_serve_prefill_decode_consistency"], n_devices=4,
               timeout=900)


@pytest.mark.slow
def test_serve_consistency_families():
    run_checks([
        "check_serve_consistency_ssm",
        "check_serve_consistency_hybrid",
        "check_serve_consistency_moe",
    ], n_devices=4, timeout=1200)


@pytest.mark.slow
def test_dryrun_machinery():
    run_checks(["check_dryrun_smoke_cell"], n_devices=8, timeout=900)


def test_compile_cache_dir(monkeypatch):
    """$JAX_COMPILATION_CACHE_DIR wins and nothing is set in code;
    otherwise the cache goes to one fixed directory in the checkout."""
    import os

    import jax
    from repro.launch import train
    was = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/from/env")
    assert train.enable_compile_cache() == "/from/env"
    assert jax.config.jax_compilation_cache_dir == was
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    try:
        path = train.enable_compile_cache()
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert path == os.path.join(repo, ".jax_cache")


def test_overlap_flags_never_put_tpu_flags_in_xla_flags(monkeypatch):
    """jaxlib aborts on an --xla_tpu_* name in XLA_FLAGS; the flags the
    launcher adds parse on every platform, once."""
    from repro.launch.xla_flags import enable_overlap_flags
    monkeypatch.setenv("XLA_FLAGS", "--xla_force_host_platform_device_count=2")
    flags = enable_overlap_flags().split()
    assert flags[0] == "--xla_force_host_platform_device_count=2"
    assert not any(f.startswith("--xla_tpu_") for f in flags)
    assert enable_overlap_flags().split() == flags
