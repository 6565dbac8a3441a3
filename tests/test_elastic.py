"""Elastic fault-tolerant runtime tests (train/elastic.py, testing/faults.py).

Fast tests run world=1 in the main pytest process: async writer overlap /
backpressure / abandon semantics, the save retry-with-backoff path, stale
staging sweeps, and the crash-before-manifest invariant.  The full fault
suite (worker death + bit-exact resume, live 8->4->8 resharding, REAL
SIGKILL/SIGTERM subprocess scenarios) runs on 8 simulated devices via
testing/subproc.py — same groups as ``make fault-smoke``.
"""
import os
import time

import numpy as np
import pytest

from repro.testing.subproc import run_checks


def _tiny_state():
    import jax
    from repro.configs import get_config
    from repro.launch.mesh import make_mesh
    from repro.models.model import Model
    from repro.optim.adamw import AdamWConfig
    from repro.train.policy import make_policy
    from repro.train.state import ZeroState

    mesh = make_mesh((1, 1), ("data", "model"))
    arch = get_config("gpt-350m").reduced()
    pol = make_policy(arch, tuple(mesh.axis_names))
    model = Model(arch, pol.zcfg, world=1)
    opt_cfg = AdamWConfig()
    st = ZeroState(model, mesh, opt_cfg).init(jax.random.PRNGKey(0))
    return mesh, model, opt_cfg, st


# ---------------------------------------------------------------------------
# fast: async writer semantics
# ---------------------------------------------------------------------------

def test_async_writer_overlap_and_snapshot_isolation(tmp_path):
    """The write happens on the background thread (steps keep running:
    steps_overlapped counts them) and commits a checkpoint identical to
    the submitted state — the on-device snapshot means later mutation of
    the live buffers cannot leak into the file."""
    import jax
    from repro.testing.faults import SlowIO
    from repro.train.elastic import AsyncCheckpointWriter
    from repro.train.state import ZeroState, read_manifest

    mesh, model, opt_cfg, st = _tiny_state()
    p_host = jax.device_get(st.params)
    w = AsyncCheckpointWriter(model, mesh, opt_cfg, str(tmp_path),
                              io_hooks=SlowIO(0.3))
    w.submit(1, st.params, st.opt, {"world": 1})
    while w.in_flight():              # the "train loop" keeps stepping
        w.note_step()
        time.sleep(0.02)
    path = w.drain()
    w.close()
    assert w.stats.completed == 1 and w.stats.failed == 0
    assert w.stats.steps_overlapped > 0
    man = read_manifest(path)
    assert man["step"] == 1 and man["checksums"]
    st2 = ZeroState.restore(model, mesh, opt_cfg, str(tmp_path))
    for k, v in st2.params.items():
        np.testing.assert_array_equal(np.asarray(jax.device_get(v)),
                                      np.asarray(p_host[k]))


def test_async_writer_backpressure_single_flight(tmp_path):
    """Never more than one write in flight: a second submit blocks until
    the first (slowed) write commits."""
    from repro.testing.faults import SlowIO
    from repro.train.elastic import AsyncCheckpointWriter

    mesh, model, opt_cfg, st = _tiny_state()
    w = AsyncCheckpointWriter(model, mesh, opt_cfg, str(tmp_path),
                              io_hooks=SlowIO(0.6))
    w.submit(1, st.params, st.opt)
    t0 = time.monotonic()
    w.submit(2, st.params, st.opt)    # must wait out write #1
    assert time.monotonic() - t0 > 0.4
    w.drain()
    w.close()
    assert w.stats.submitted == 2 and w.stats.completed == 2


def test_async_writer_abandon_publishes_nothing(tmp_path):
    """Abandoning an in-flight write (grace expired) cancels it before
    the manifest commit: no checkpoint is ever published."""
    from repro.testing.faults import SlowIO
    from repro.train.elastic import AsyncCheckpointWriter
    from repro.train.state import latest_checkpoint

    mesh, model, opt_cfg, st = _tiny_state()
    w = AsyncCheckpointWriter(model, mesh, opt_cfg, str(tmp_path),
                              io_hooks=SlowIO(1.0))
    w.submit(1, st.params, st.opt)
    assert w.abandon() is True
    w.close()
    assert w.stats.abandoned == 1 and w.stats.completed == 0
    assert latest_checkpoint(str(tmp_path)) is None
    assert not [n for n in os.listdir(tmp_path) if n.endswith(".tmp")]
    # abandoning while idle is a no-op
    assert w.abandon() is False


# ---------------------------------------------------------------------------
# fast: save retry / staging hygiene
# ---------------------------------------------------------------------------

def test_save_retries_transient_errors(tmp_path):
    from repro.testing.faults import FlakyIO
    from repro.train.state import latest_checkpoint, read_manifest

    mesh, model, opt_cfg, st = _tiny_state()
    flaky = FlakyIO(2)
    path = st.save(str(tmp_path), 1, io_hooks=flaky, retries=3,
                   backoff=0.01)
    assert flaky.calls == 3 and flaky.remaining == 0   # 2 fails + 1 ok
    assert os.path.basename(latest_checkpoint(str(tmp_path))) == "ckpt_1"
    assert read_manifest(path)["checksums"]


def test_save_retry_exhaustion_raises_and_sweeps(tmp_path):
    from repro.testing.faults import FlakyIO
    from repro.train.state import CheckpointError, latest_checkpoint

    mesh, model, opt_cfg, st = _tiny_state()
    st.save(str(tmp_path), 1)                       # a good one to keep
    with pytest.raises(CheckpointError, match="injected transient"):
        st.save(str(tmp_path), 2, io_hooks=FlakyIO(5), retries=1,
                backoff=0.01)
    # the failed attempt left no debris and the good checkpoint survives
    assert not [n for n in os.listdir(tmp_path) if n.endswith(".tmp")]
    assert os.path.basename(latest_checkpoint(str(tmp_path))) == "ckpt_1"


def test_save_sweeps_stale_staging(tmp_path):
    """A crash-left staging dir for the SAME step must not poison the
    re-save after resume."""
    from repro.testing.faults import make_stale_staging
    from repro.train.state import latest_checkpoint, read_manifest

    mesh, model, opt_cfg, st = _tiny_state()
    staging = make_stale_staging(str(tmp_path), 5)
    assert os.path.isdir(staging)
    path = st.save(str(tmp_path), 5)
    assert not os.path.isdir(staging)
    assert os.path.basename(latest_checkpoint(str(tmp_path))) == "ckpt_5"
    assert read_manifest(path)["step"] == 5


def test_crash_before_manifest_never_selectable(tmp_path):
    """The commit-protocol invariant from the I/O side: failing between
    the shard write and the manifest commit publishes nothing."""
    from repro.testing.faults import CrashBeforeManifest
    from repro.train.state import CheckpointError, latest_checkpoint

    mesh, model, opt_cfg, st = _tiny_state()
    with pytest.raises(CheckpointError):
        st.save(str(tmp_path), 3, io_hooks=CrashBeforeManifest())
    assert latest_checkpoint(str(tmp_path)) is None


# ---------------------------------------------------------------------------
# slow: the 8-device fault suite (same groups as `make fault-smoke`)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_elastic_overlap_kill_flaky():
    run_checks(["check_elastic_async_overlap", "check_elastic_kill_resume",
                "check_elastic_flaky_io_retry"], n_devices=8, timeout=1800)


@pytest.mark.slow
def test_elastic_reshard_and_corrupt():
    run_checks(["check_elastic_live_reshard",
                "check_elastic_corrupt_fallback"], n_devices=8,
               timeout=1800)


@pytest.mark.slow
def test_elastic_real_signals():
    run_checks(["check_elastic_crash_during_write",
                "check_elastic_sigterm_grace"], n_devices=8, timeout=1800)
