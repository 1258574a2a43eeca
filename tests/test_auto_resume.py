"""Elastic recovery (--auto-resume) + fault injection (--fault-inject).

The reference's only failure recovery is a manual restart with
--model-load (ref /root/reference/train.py:190-199). This framework adds
in-process recovery from transient backend failures — back off, restore
the newest checkpoint, continue — plus a fault injector so the recovery
path is exercised in CI rather than discovered during a real outage.
"""

import os

import pytest

from real_time_helmet_detection_tpu.config import Config
from real_time_helmet_detection_tpu.data import make_synthetic_voc
from real_time_helmet_detection_tpu.train import (
    FaultInjector, InjectedBackendError, is_transient_backend_error)


@pytest.fixture(scope="module")
def fixture_root(tmp_path_factory):
    root = tmp_path_factory.mktemp("voc_resume")
    return make_synthetic_voc(str(root), num_train=6, num_test=2,
                              imsize=(96, 72), seed=3)


def _cfg(fixture_root, save, **kw):
    base = dict(train_flag=True, num_stack=1, hourglass_inch=16, num_cls=2,
                imsize=64, batch_size=2, end_epoch=3, ckpt_interval=1,
                print_interval=1, num_workers=0, data=fixture_root,
                save_path=save, hang_warn_seconds=0,
                # injected faults need no real-transport pause; the backoff
                # path itself is still exercised
                resume_backoff_s=0.2)
    base.update(kw)
    return Config(**base)


def test_fault_injector_fires_once_at_target():
    inj = FaultInjector("1:2")
    inj.maybe_fire(0, 2)
    inj.maybe_fire(1, 1)
    with pytest.raises(InjectedBackendError):
        inj.maybe_fire(1, 2)
    inj.maybe_fire(1, 2)  # consumed: never fires twice


def test_transient_error_classifier():
    assert is_transient_backend_error(InjectedBackendError("boom"))
    assert is_transient_backend_error(RuntimeError("UNAVAILABLE: socket"))
    assert not is_transient_backend_error(RuntimeError("shape mismatch"))
    assert not is_transient_backend_error(ValueError("UNAVAILABLE"))


def test_transient_error_classifier_requires_status_prefix():
    """Bare substrings must not classify (round-2 advisor finding): a
    programming error mentioning 'connection' or 'INTERNAL' in prose is not
    backend evidence."""
    assert not is_transient_backend_error(
        RuntimeError("bad data-loader connection string: tcp://x"))
    assert not is_transient_backend_error(
        RuntimeError("INTERNAL: assertion failed in user code"))
    from jax.errors import JaxRuntimeError
    assert is_transient_backend_error(
        JaxRuntimeError("UNAVAILABLE: TPU backend setup error"))


def test_compile_failure_is_permanent():
    """A kernel the compiler refuses is reported under XLA's INTERNAL
    status; retrying it (--auto-resume, the engine's requeue, the job
    supervisor's backoff) would recompile it to the same refusal."""
    from jax.errors import JaxRuntimeError

    from real_time_helmet_detection_tpu.runtime import (classify_error_text,
                                                        classify_exception)
    mosaic = ("INTERNAL: Mosaic failed to compile TPU kernel: Not "
              "implemented: unsupported block shape")
    assert not is_transient_backend_error(JaxRuntimeError(mosaic))
    assert classify_exception(JaxRuntimeError(mosaic)) == "permanent"
    assert classify_error_text(mosaic) == "permanent"


def test_fault_injector_rejects_malformed_spec():
    for bad in ("5", "1:2:3", "a:b"):
        with pytest.raises(ValueError):
            FaultInjector(bad)


@pytest.mark.slow
def test_auto_resume_recovers_after_checkpoint(fixture_root, tmp_path,
                                               capsys):
    """Fault in epoch 1 -> recovery restores epoch-0's checkpoint and the
    run still completes all epochs with full checkpoint coverage."""
    from real_time_helmet_detection_tpu.train import train

    save = str(tmp_path / "w")
    cfg = _cfg(fixture_root, save, auto_resume=2, fault_inject="1:0")
    state = train(cfg)
    out = capsys.readouterr().out
    # recovery took the restore path (not a from-scratch restart)
    assert "auto-resumed from" in out and "check_point_1" in out
    steps_per_epoch = 6 // 2
    assert int(state.step) == 3 * steps_per_epoch
    for n in (1, 2, 3):
        assert os.path.isdir(os.path.join(save, "check_point_%d" % n))


@pytest.mark.slow
def test_auto_resume_with_donated_state(fixture_root, tmp_path, capsys):
    """Fault MID-epoch (iter 1): by then iter 0's jitted step has DONATED
    the state object train() still holds, so its buffers are deleted. The
    restore template must come from avals, not buffers — this is the shape
    of a real backend failure (which strikes mid-step, not at iter 0)."""
    from real_time_helmet_detection_tpu.train import train

    save = str(tmp_path / "w")
    cfg = _cfg(fixture_root, save, auto_resume=2, fault_inject="1:1")
    state = train(cfg)
    out = capsys.readouterr().out
    assert "auto-resumed from" in out and "check_point_1" in out
    assert int(state.step) == 3 * (6 // 2)
    assert os.path.isdir(os.path.join(save, "check_point_3"))


@pytest.mark.slow
def test_auto_resume_restarts_when_no_checkpoint_yet(fixture_root, tmp_path,
                                                     capsys):
    """Fault at the very first step (no save yet) -> fresh restart."""
    from real_time_helmet_detection_tpu.train import train

    save = str(tmp_path / "w")
    cfg = _cfg(fixture_root, save, auto_resume=1, fault_inject="0:0",
               end_epoch=2)
    state = train(cfg)
    out = capsys.readouterr().out
    assert "auto-restarting" in out
    assert int(state.step) == 2 * (6 // 2)
    assert os.path.isdir(os.path.join(save, "check_point_2"))


@pytest.mark.slow
def test_fault_without_auto_resume_propagates(fixture_root, tmp_path):
    from real_time_helmet_detection_tpu.train import train

    cfg = _cfg(fixture_root, str(tmp_path / "w"), fault_inject="0:0",
               end_epoch=1)
    with pytest.raises(InjectedBackendError):
        train(cfg)


@pytest.mark.slow
def test_keep_ckpt_retention_with_recovery(fixture_root, tmp_path, capsys):
    """--keep-ckpt 1: only the newest checkpoint of this run survives; a
    fault AFTER retention pruned older saves must recover from the still-
    existing newest one (check_point_1 is deleted by then, so restoring it
    would crash)."""
    from real_time_helmet_detection_tpu.train import train

    save = str(tmp_path / "w")
    cfg = _cfg(fixture_root, save, keep_ckpt=1, auto_resume=1,
               fault_inject="2:0")
    state = train(cfg)
    out = capsys.readouterr().out
    assert "retention: removed" in out
    assert "auto-resumed from" in out and "check_point_2" in out
    assert int(state.step) == 3 * (6 // 2)
    kept = sorted(d for d in os.listdir(save) if d.startswith("check_point"))
    assert kept == ["check_point_3"]
