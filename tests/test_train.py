"""Training-runtime tests: step mechanics, LR schedule, grad accumulation,
DP gradient equality, checkpoint round-trip, loss decrease.

Encodes SURVEY.md §4's implicit invariants (3) loss on fixed synthetic
batches and (5) DP-vs-single-device gradient equality on the fake 8-device
CPU backend.
"""

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from real_time_helmet_detection_tpu.config import Config
from real_time_helmet_detection_tpu.models import build_model
from real_time_helmet_detection_tpu.optim import build_optimizer, make_lr_schedule
from real_time_helmet_detection_tpu.parallel import make_mesh, shard_batch
from real_time_helmet_detection_tpu.train import (
    TrainState, create_train_state, load_checkpoint, loss_fn, make_train_step,
    restore_params_only, save_checkpoint)
from real_time_helmet_detection_tpu.ops.loss import LossLog

IMSIZE = 64


def tiny_cfg(**kw):
    base = dict(num_stack=1, hourglass_inch=16, num_cls=2, batch_size=4,
                lr=1e-3)
    base.update(kw)
    return Config(**base)


def synthetic_batch(b=4, seed=0):
    from real_time_helmet_detection_tpu.data import synthetic_target_batch
    return synthetic_target_batch(b, IMSIZE, seed=seed)


def make_state(cfg, steps_per_epoch=10):
    model = build_model(cfg)
    tx = build_optimizer(cfg, steps_per_epoch)
    state = create_train_state(model, cfg, jax.random.key(0), IMSIZE, tx)
    return model, tx, state


def test_lr_schedule_multistep():
    cfg = tiny_cfg(lr=1.0, lr_milestone=[2, 4], lr_gamma=0.1)
    sched = make_lr_schedule(cfg, steps_per_epoch=10)
    assert sched(0) == pytest.approx(1.0)
    assert sched(19) == pytest.approx(1.0)
    assert sched(20) == pytest.approx(0.1)
    assert sched(40) == pytest.approx(0.01)


def test_train_step_runs_and_updates():
    cfg = tiny_cfg()
    model, tx, state = make_state(cfg)
    mesh = make_mesh(1)
    step = make_train_step(model, tx, cfg, mesh)
    batch = shard_batch(mesh, synthetic_batch(), spatial_dims=[1] * 5)
    p0 = jax.device_get(jax.tree.leaves(state.params)[0]).copy()
    state, losses = step(state, *batch)
    assert int(state.step) == 1
    assert np.isfinite(float(losses["total"]))
    p1 = jax.device_get(jax.tree.leaves(state.params)[0])
    assert not np.allclose(p0, p1)


def test_loss_decreases_over_steps():
    cfg = tiny_cfg(lr=5e-3)
    model, tx, state = make_state(cfg)
    mesh = make_mesh(1)
    step = make_train_step(model, tx, cfg, mesh)
    batch = shard_batch(mesh, synthetic_batch(), spatial_dims=[1] * 5)
    first = last = None
    for i in range(8):
        state, losses = step(state, *batch)
        v = float(losses["total"])
        first = v if first is None else first
        last = v
    assert last < first


def test_dp_gradients_match_single_device():
    """SURVEY §4 invariant (5): same global batch, 1-device vs 8-device DP
    meshes produce identical losses and updated params."""
    cfg = tiny_cfg(batch_size=8)
    model, tx, state = make_state(cfg)
    batch_np = synthetic_batch(b=8, seed=3)

    results = []
    for ndev in (1, 8):
        mesh = make_mesh(ndev)
        step = make_train_step(model, tx, cfg, mesh)
        st = jax.tree.map(lambda x: jnp.array(np.asarray(x)), state)
        batch = shard_batch(mesh, batch_np, spatial_dims=[1] * 5)
        st, losses = step(st, *batch)
        results.append((jax.device_get(losses),
                        jax.device_get(jax.tree.leaves(st.params)[0])))
    (l1, p1), (l8, p8) = results
    assert l1["total"] == pytest.approx(l8["total"], rel=1e-4)
    np.testing.assert_allclose(p1, p8, rtol=1e-4, atol=1e-6)


@pytest.mark.parametrize("stem_s2d", [False, True])
def test_spatial_sharding_matches_pure_dp(stem_s2d):
    """(data=4, spatial=2) must be numerically equivalent to (8, 1) — with
    both stem formulations (--stem-s2d's H reshape must compose with the
    spatial sharding of H)."""
    cfg = tiny_cfg(batch_size=8, stem_s2d=stem_s2d)
    model, tx, state = make_state(cfg)
    batch_np = synthetic_batch(b=8, seed=5)

    results = []
    for spatial in (1, 2):
        mesh = make_mesh(8, spatial=spatial)
        step = make_train_step(model, tx, cfg, mesh)
        st = jax.tree.map(lambda x: jnp.array(np.asarray(x)), state)
        batch = shard_batch(mesh, batch_np, spatial_dims=[1] * 5)
        st, losses = step(st, *batch)
        results.append(jax.device_get(losses))
    assert results[0]["total"] == pytest.approx(results[1]["total"], rel=1e-4)


def test_gradient_accumulation_semantics():
    """MultiSteps(k=2): params only change every 2nd step (ref
    train.py:124-139 sub-divisions)."""
    cfg = tiny_cfg(sub_divisions=2)
    model, tx, state = make_state(cfg)
    mesh = make_mesh(1)
    step = make_train_step(model, tx, cfg, mesh)
    batch = shard_batch(mesh, synthetic_batch(), spatial_dims=[1] * 5)

    p0 = jax.device_get(jax.tree.leaves(state.params)[0]).copy()
    state, _ = step(state, *batch)
    p_mid = jax.device_get(jax.tree.leaves(state.params)[0])
    np.testing.assert_allclose(p0, p_mid)  # accumulated, not applied
    state, _ = step(state, *batch)
    p_end = jax.device_get(jax.tree.leaves(state.params)[0])
    assert not np.allclose(p0, p_end)


def test_grad_accumulation_matches_reference_sum():
    """The reference accumulates micro-batch gradients by repeated
    backward() with no division (ref train.py:128-136), i.e. the optimizer
    steps on the *sum*. Two accumulate steps with sub_divisions=2 must equal
    one hand-rolled step on g1+g2. SGD makes the sum-vs-mean distinction
    observable (Adam is gradient-scale-invariant)."""
    cfg = tiny_cfg(sub_divisions=2, optim="sgd", lr=1e-2)
    model, tx, state = make_state(cfg)
    mesh = make_mesh(1)
    step = make_train_step(model, tx, cfg, mesh)
    b1 = synthetic_batch(seed=11)
    b2 = synthetic_batch(seed=12)

    copy = lambda st: jax.tree.map(lambda x: jnp.array(np.asarray(x)), st)
    st = copy(state)
    st, _ = step(st, *shard_batch(mesh, b1, spatial_dims=[1] * 5))
    st, _ = step(st, *shard_batch(mesh, b2, spatial_dims=[1] * 5))

    # hand-rolled: summed grads through the plain (sub_divisions=1) optimizer
    import optax as _optax
    from real_time_helmet_detection_tpu.ops.loss import detection_loss  # noqa: F401
    plain_cfg = tiny_cfg(sub_divisions=1, optim="sgd", lr=1e-2)
    plain_tx = build_optimizer(plain_cfg, 10)
    grad_fn = jax.grad(loss_fn, has_aux=True)
    g1, (bs1, _) = grad_fn(state.params, state.batch_stats, model,
                           *[jnp.asarray(a) for a in b1], cfg)
    g2, (bs2, _) = grad_fn(state.params, bs1, model,
                           *[jnp.asarray(a) for a in b2], cfg)
    summed = jax.tree.map(lambda a, b: a + b, g1, g2)
    updates, _ = plain_tx.update(summed, plain_tx.init(state.params),
                                 state.params)
    manual = _optax.apply_updates(state.params, updates)

    np.testing.assert_allclose(
        jax.device_get(jax.tree.leaves(st.params)[0]),
        jax.device_get(jax.tree.leaves(manual)[0]), rtol=1e-5, atol=1e-7)


def test_epoch_end_accumulation_flush_matches_reference():
    """The reference steps the optimizer at the epoch's LAST iteration even
    mid-window (ref train.py:124: `... or (iteration == len(dataloader))`),
    applying the partial micro-grad SUM. Three micro-steps at k=2 (emit
    after 2, flush the trailing 1) must equal the hand-rolled sequence
    p0 -SGD-> p0 - lr*(g1+g2) -SGD-> that - lr*g3. SGD+momentum makes both
    the sum-vs-mean and the missing-flush errors observable."""
    import optax as _optax
    from real_time_helmet_detection_tpu.train import make_state_accum_flush

    cfg = tiny_cfg(sub_divisions=2, optim="sgd", lr=1e-2)
    model, tx, state = make_state(cfg)
    mesh = make_mesh(1)
    step = make_train_step(model, tx, cfg, mesh)
    batches = [synthetic_batch(seed=s) for s in (21, 22, 23)]

    st = jax.tree.map(lambda x: jnp.array(np.asarray(x)), state)
    for b in batches:
        st, _ = step(st, *shard_batch(mesh, b, spatial_dims=[1] * 5))
    assert int(jax.device_get(st.opt_state.mini_step)) == 1  # trailing grad
    flush = make_state_accum_flush(cfg, steps_per_epoch=3)
    st = flush(st)
    assert int(jax.device_get(st.opt_state.mini_step)) == 0
    assert int(jax.device_get(st.opt_state.gradient_step)) == 2

    # hand-rolled reference semantics through the plain optimizer
    plain_cfg = tiny_cfg(sub_divisions=1, optim="sgd", lr=1e-2)
    plain_tx = build_optimizer(plain_cfg, 2)
    grad_fn = jax.grad(loss_fn, has_aux=True)
    params, bs = state.params, state.batch_stats
    opt = plain_tx.init(params)
    g1, (bs, _) = grad_fn(params, bs, model,
                          *[jnp.asarray(a) for a in batches[0]], cfg)
    g2, (bs, _) = grad_fn(params, bs, model,
                          *[jnp.asarray(a) for a in batches[1]], cfg)
    summed = jax.tree.map(lambda a, b: a + b, g1, g2)
    updates, opt = plain_tx.update(summed, opt, params)
    params = _optax.apply_updates(params, updates)
    g3, (bs, _) = grad_fn(params, bs, model,
                          *[jnp.asarray(a) for a in batches[2]], cfg)
    updates, opt = plain_tx.update(g3, opt, params)
    params = _optax.apply_updates(params, updates)

    np.testing.assert_allclose(
        jax.device_get(jax.tree.leaves(st.params)[0]),
        jax.device_get(jax.tree.leaves(params)[0]), rtol=1e-5, atol=1e-7)


def test_checkpoint_roundtrip(tmp_path):
    cfg = tiny_cfg()
    model, tx, state = make_state(cfg)
    mesh = make_mesh(1)
    step = make_train_step(model, tx, cfg, mesh)
    batch = shard_batch(mesh, synthetic_batch(), spatial_dims=[1] * 5)
    state, losses = step(state, *batch)

    log = LossLog()
    log.append({k: float(v) for k, v in jax.device_get(losses).items()})
    path = save_checkpoint(str(tmp_path), 4, state, log)
    assert os.path.basename(path) == "check_point_5"  # ref naming: epoch+1

    _, _, fresh = make_state(cfg)
    restored, epoch, rlog = load_checkpoint(path, fresh)
    assert epoch == 4
    assert rlog.log["total"] == log.log["total"]
    np.testing.assert_allclose(
        jax.device_get(jax.tree.leaves(restored.params)[0]),
        jax.device_get(jax.tree.leaves(state.params)[0]))

    _, _, fresh2 = make_state(cfg)
    evald = restore_params_only(path, fresh2)
    np.testing.assert_allclose(
        jax.device_get(jax.tree.leaves(evald.params)[0]),
        jax.device_get(jax.tree.leaves(state.params)[0]))
    # optimizer state NOT restored on the params-only path
    assert jax.tree.structure(evald.opt_state) == jax.tree.structure(fresh2.opt_state)

    # Regression (the slow-tier test_auto_resume SIGABRT): a restored
    # state goes straight into the DONATING train step on resume. Before
    # load_checkpoint's XLA:CPU deep copy, donating the orbax-restored
    # (tensorstore-backed) buffers corrupted the glibc heap —
    # "malloc_consolidate(): invalid chunk size" at the next allocation.
    # Two donating steps + a fetch exercise exactly that path.
    stepped, losses2 = step(restored, *batch)
    stepped, losses3 = step(stepped, *batch)
    assert np.isfinite(float(jax.device_get(losses3["total"])))
    assert int(jax.device_get(stepped.step)) == 3  # 1 saved + 2 resumed


def test_eval_restore_ignores_optimizer_config(tmp_path):
    """Regression: a checkpoint trained with --sub-divisions 2 (MultiSteps
    wraps the opt state) must be loadable for eval with the default
    optimizer config."""
    cfg = tiny_cfg(sub_divisions=2)
    model, tx, state = make_state(cfg)
    mesh = make_mesh(1)
    step = make_train_step(model, tx, cfg, mesh)
    batch = shard_batch(mesh, synthetic_batch(), spatial_dims=[1] * 5)
    state, losses = step(state, *batch)
    path = save_checkpoint(str(tmp_path), 0, state, LossLog())

    eval_cfg = tiny_cfg()  # sub_divisions back at 1
    _, _, fresh = make_state(eval_cfg)
    restored = restore_params_only(path, fresh)
    np.testing.assert_allclose(
        jax.device_get(jax.tree.leaves(restored.params)[0]),
        jax.device_get(jax.tree.leaves(state.params)[0]))


def test_resume_multisteps_state_exact(tmp_path):
    """Regression (advisor r1): orbax's structure-free restore returns
    namedtuples as alphabetically-keyed dicts, so a flat-leaf-order refit
    scrambles optax.MultiStepsState (field order mini_step/gradient_step/
    inner_opt_state/acc_grads/skip_state is not alphabetical). Resume with
    --sub-divisions 2 mid-accumulation must restore every optimizer leaf
    exactly and continue identically to the un-checkpointed run."""
    cfg = tiny_cfg(sub_divisions=2)
    model, tx, state = make_state(cfg)
    mesh = make_mesh(1)
    step = make_train_step(model, tx, cfg, mesh)
    batch = shard_batch(mesh, synthetic_batch(), spatial_dims=[1] * 5)
    # one step: mini_step=1, acc_grads nonzero — the states that get
    # scrambled by an order-based refit
    state, _ = step(state, *batch)
    path = save_checkpoint(str(tmp_path), 0, state, LossLog())

    _, _, fresh = make_state(cfg)
    restored, _, _ = load_checkpoint(path, fresh)
    assert int(restored.opt_state.mini_step) == 1
    for a, b in zip(jax.tree.leaves(state.opt_state),
                    jax.tree.leaves(restored.opt_state)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b))
    # continuing from the restored state reproduces the direct run
    copy = lambda st: jax.tree.map(lambda x: jnp.array(np.asarray(x)), st)
    cont, _ = step(copy(state), *batch)
    res, _ = step(copy(restored), *batch)
    np.testing.assert_allclose(
        jax.device_get(jax.tree.leaves(cont.params)[0]),
        jax.device_get(jax.tree.leaves(res.params)[0]), rtol=1e-6)


def test_resume_mismatched_optimizer_raises(tmp_path):
    """Full resume with a different optimizer config must fail loudly."""
    cfg = tiny_cfg(sub_divisions=2)
    model, tx, state = make_state(cfg)
    path = save_checkpoint(str(tmp_path), 0, state, LossLog())
    _, _, fresh = make_state(tiny_cfg())  # plain adam structure
    with pytest.raises(ValueError, match="sub-divisions"):
        load_checkpoint(path, fresh)


def test_bool_flags_negatable():
    """Regression: default-True bools must be switchable off on the CLI."""
    from real_time_helmet_detection_tpu.config import parse_args
    assert parse_args([]).use_pallas is True
    assert parse_args(["--no-use-pallas"]).use_pallas is False
    assert parse_args(["--train-flag"]).train_flag is True


def test_device_augment_runner_trains():
    """Fused on-device augment+encode+train path: losses finite and params
    update, with the raw-canvas batch format."""
    from real_time_helmet_detection_tpu.data.pipeline import Batch
    from real_time_helmet_detection_tpu.train import make_step_runner

    cfg = tiny_cfg(device_augment=True, multiscale=[64, 64, 64],
                   multiscale_flag=False, batch_size=2)
    model, tx, state = make_state(cfg)
    mesh = make_mesh(2)
    runner = make_step_runner(cfg, mesh, model, tx)

    rng = np.random.default_rng(0)
    n = 8
    boxes = np.zeros((2, n, 4), np.float32)
    labels = np.zeros((2, n), np.int32)
    valid = np.zeros((2, n), bool)
    boxes[:, 0] = [8, 8, 40, 40]
    valid[:, 0] = True
    empty = np.zeros((2, 0, 0, 0), np.float32)
    batch = Batch(image=rng.uniform(0, 255, (2, 64, 64, 3)
                                    ).astype(np.float32),
                  heatmap=empty, offset=empty, wh=empty, mask=empty,
                  boxes=boxes, labels=labels, valid=valid, infos=[{}, {}])

    p0 = jax.device_get(jax.tree.leaves(state.params)[0]).copy()
    state, losses = runner(state, batch, 0)
    assert np.isfinite(float(losses["total"]))
    state, losses2 = runner(state, batch, 1)
    assert np.isfinite(float(losses2["total"]))
    p1 = jax.device_get(jax.tree.leaves(state.params)[0])
    assert not np.allclose(p0, p1)


def test_bf16_policy_step_runs():
    """--amp selects bf16 compute; step must run and return finite fp32 loss."""
    cfg = tiny_cfg(amp=True)
    model = build_model(cfg, dtype=jnp.bfloat16)
    tx = build_optimizer(cfg, 10)
    state = create_train_state(model, cfg, jax.random.key(0), IMSIZE, tx)
    # params stay fp32 under the policy
    assert jax.tree.leaves(state.params)[0].dtype == jnp.float32
    mesh = make_mesh(1)
    step = make_train_step(model, tx, cfg, mesh)
    batch = shard_batch(mesh, synthetic_batch(), spatial_dims=[1] * 5)
    state, losses = step(state, *batch)
    assert losses["total"].dtype == jnp.float32
    assert np.isfinite(float(losses["total"]))


@pytest.mark.slow  # 21 s at r15 --durations: scan-vs-sequential
# equivalence (perf-harness hygiene) — re-tiered (ISSUE 13 satellite)
def test_scanned_train_fn_matches_sequential_steps():
    """The bench/scaling timing harness (`make_scanned_train_fn`) must run
    the EXACT production step: N scanned steps == N sequential
    `make_train_step_body` calls (same final step counter, same last loss,
    same params)."""
    from real_time_helmet_detection_tpu.train import (make_scanned_train_fn,
                                                      make_train_step_body)

    cfg = tiny_cfg()
    model, tx, state = make_state(cfg)
    body = make_train_step_body(model, tx, cfg)
    batch = tuple(jnp.asarray(a) for a in synthetic_batch())

    seq_state = state
    seq_losses = []
    for _ in range(3):
        seq_state, losses = jax.jit(body)(seq_state, *batch)
        seq_losses.append(float(losses["total"]))

    scanned = jax.jit(make_scanned_train_fn(body, 3))
    final_state, last_total = scanned(state, *batch)
    assert int(final_state.step) == int(seq_state.step) == 3
    # one fused scan program vs three separate programs: XLA reassociates
    # float reductions differently, so equality is semantic, not bitwise
    assert float(last_total) == pytest.approx(seq_losses[-1], rel=1e-3)
    np.testing.assert_allclose(
        jax.device_get(jax.tree.leaves(final_state.params)[0]),
        jax.device_get(jax.tree.leaves(seq_state.params)[0]),
        rtol=1e-4, atol=1e-6)


@pytest.mark.slow  # 15 s at r15 --durations: donation-warning pin
# (the trace-audit donation rule covers the aval law in-tier) —
# re-tiered (ISSUE 13 satellite)
def test_scanned_train_fn_donation_emits_no_warning():
    """The timing harness donates its state (the production memory regime,
    bench.py/scaling.py) and returns the final state so every donated
    buffer has an aliasing target — jitting + running it must not emit
    XLA's 'Some donated buffers were not usable' warning (visible in
    BENCH_r05.json's tail before this contract)."""
    import warnings

    from real_time_helmet_detection_tpu.train import (make_scanned_train_fn,
                                                      make_train_step_body)

    cfg = tiny_cfg()
    model, tx, state = make_state(cfg)
    body = make_train_step_body(model, tx, cfg)
    batch = tuple(jnp.asarray(a) for a in synthetic_batch())
    scanned = jax.jit(make_scanned_train_fn(body, 2), donate_argnums=(0,))
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        compiled = scanned.lower(state, *batch).compile()
        float(compiled(state, *batch)[1])  # fetch only the scalar loss
    donation_warnings = [w for w in caught
                         if "donated buffers" in str(w.message)]
    assert not donation_warnings, [str(w.message) for w in donation_warnings]


def test_ckpt_interval(tmp_path):
    """--ckpt-interval N saves every Nth epoch plus the final one."""
    from real_time_helmet_detection_tpu.data import make_synthetic_voc
    from real_time_helmet_detection_tpu.train import train

    root = str(tmp_path / "voc")
    make_synthetic_voc(root, num_train=4, num_test=2, imsize=(64, 64), seed=0)
    save = str(tmp_path / "w")
    os.makedirs(os.path.join(save, "training_log"), exist_ok=True)
    cfg = tiny_cfg(train_flag=True, data=root, save_path=save, batch_size=2,
                   end_epoch=5, ckpt_interval=2, num_workers=1,
                   multiscale_flag=True, multiscale=[64, 128, 64],
                   print_interval=100)
    train(cfg)
    ckpts = sorted(d for d in os.listdir(save)
                   if d.startswith("check_point_"))
    assert ckpts == ["check_point_2", "check_point_4", "check_point_5"]


def test_hang_watchdog_warns_and_recovers(capsys):
    """The failure detector fires after `warn_seconds` without a beat,
    includes the last-progress label, and re-arms after a new beat."""
    import time as _time

    from real_time_helmet_detection_tpu.train import HangWatchdog

    wd = HangWatchdog(0.2)
    try:
        wd.beat("epoch 0 iter 7")
        _time.sleep(0.6)
        out = capsys.readouterr().out
        assert "WATCHDOG" in out and "epoch 0 iter 7" in out
        assert out.count("WATCHDOG") == 1  # warns once per stall
        wd.beat("epoch 0 iter 8")
        _time.sleep(0.6)
        assert "iter 8" in capsys.readouterr().out  # re-armed
    finally:
        wd.stop()


def test_hang_watchdog_disabled():
    from real_time_helmet_detection_tpu.train import HangWatchdog
    wd = HangWatchdog(0.0)
    assert wd._thread is None
    wd.stop()


def test_hang_watchdog_pause_suppresses(capsys):
    import time as _time

    from real_time_helmet_detection_tpu.train import HangWatchdog

    wd = HangWatchdog(0.2)
    try:
        wd.pause("checkpoint")
        _time.sleep(0.6)
        assert "WATCHDOG" not in capsys.readouterr().out
        wd.resume("done")
        _time.sleep(0.6)
        assert "WATCHDOG" in capsys.readouterr().out  # detection re-armed
    finally:
        wd.stop()


def test_async_checkpoint_roundtrip(tmp_path):
    """--async-ckpt saves must be restorable and equal to the saved state,
    including the deferred loss-log sidecar."""
    from real_time_helmet_detection_tpu.train import CheckpointWriter

    cfg = tiny_cfg()
    model, tx, state = make_state(cfg)
    mesh = make_mesh(1)
    step = make_train_step(model, tx, cfg, mesh)
    batch = shard_batch(mesh, synthetic_batch(), spatial_dims=[1] * 5)
    state, losses = step(state, *batch)

    log = LossLog()
    log.append({k: float(v) for k, v in jax.device_get(losses).items()})
    writer = CheckpointWriter(async_save=True)
    expected_p0 = jax.device_get(jax.tree.leaves(state.params)[0]).copy()
    path = writer.save(str(tmp_path), 0, state, log)
    # mutate state AFTER handing it to the async writer (simulates the
    # next donated train step invalidating the buffers)
    state2, _ = step(state, *batch)
    writer.finalize()
    assert os.path.exists(os.path.join(path, "loss_log.json"))

    _, _, fresh = make_state(cfg)
    restored, epoch, rlog = load_checkpoint(path, fresh)
    assert epoch == 0
    assert rlog.state_dict() == log.state_dict()
    # restored equals the state at save time, not the mutated one
    np.testing.assert_allclose(
        jax.device_get(jax.tree.leaves(restored.params)[0]), expected_p0)
    assert not np.allclose(
        expected_p0, jax.device_get(jax.tree.leaves(state2.params)[0]))


def test_train_driver_async_ckpt(tmp_path):
    from real_time_helmet_detection_tpu.data import make_synthetic_voc
    from real_time_helmet_detection_tpu.train import train

    root = str(tmp_path / "voc")
    make_synthetic_voc(root, num_train=4, num_test=2, imsize=(64, 64), seed=0)
    save = str(tmp_path / "w")
    os.makedirs(os.path.join(save, "training_log"), exist_ok=True)
    cfg = tiny_cfg(train_flag=True, data=root, save_path=save, batch_size=2,
                   end_epoch=2, async_ckpt=True, num_workers=1,
                   multiscale_flag=True, multiscale=[64, 128, 64],
                   print_interval=100)
    train(cfg)
    for e in (1, 2):
        d = os.path.join(save, "check_point_%d" % e)
        assert os.path.isdir(d)
        assert os.path.exists(os.path.join(d, "loss_log.json"))


def test_fit_data_mesh_sizing():
    """Shared train/eval mesh sizing: clamp to visible devices, trim the
    data axis to divide the batch, respect the spatial factor."""
    from real_time_helmet_detection_tpu.parallel import fit_data_mesh
    ndev = len(jax.devices())  # 8 virtual CPU devices under conftest
    assert fit_data_mesh(8) == ndev
    assert fit_data_mesh(6) == 6          # largest divisor of 6 <= 8
    assert fit_data_mesh(7) == 7
    assert fit_data_mesh(1) == 1
    assert fit_data_mesh(8, num_devices=4) == 4
    assert fit_data_mesh(8, num_devices=100) == ndev  # clamped to visible
    assert fit_data_mesh(8, spatial=2) == 8           # (data=4, spatial=2)
    assert fit_data_mesh(3, spatial=2) == 6           # data trims 4->3


def test_fit_data_mesh_rejects_unfit_spatial():
    from real_time_helmet_detection_tpu.parallel import fit_data_mesh
    with pytest.raises(ValueError, match="spatial"):
        fit_data_mesh(8, num_devices=1, spatial=2)  # 1 usable < spatial
    with pytest.raises(ValueError, match="spatial"):
        fit_data_mesh(8, spatial=3)  # 3 does not divide 8 visible


def _grads_of(cfg, batch):
    """Per-config loss value + gradient of the PRODUCTION loss_fn (the
    function every train-step body differentiates), params shared across
    configs via the fixed init seed."""
    model, _, state = make_state(cfg)
    images, heat, off, wh, mask = (jnp.asarray(a) for a in batch)

    def f(params):
        total, _ = loss_fn(params, state.batch_stats, model, images, heat,
                           off, wh, mask, cfg)
        return total

    return jax.value_and_grad(f)(state.params)


@pytest.mark.parametrize("mode", ["stacks", "full"])
@pytest.mark.slow  # 13+10 s at r15 --durations: gradient-equality
# pins (numerics hygiene; test_model's remat pin stays smoke via the
# full-suite slow tier) — re-tiered (ISSUE 13 satellite)
def test_remat_gradient_equality_vs_none(mode):
    """--remat {stacks,full} recompute activations in backward; loss and
    gradients must match --remat none semantically (recompute reassociates
    float reductions, so tolerance is scaled, not bitwise)."""
    batch = synthetic_batch()
    l0, g0 = _grads_of(tiny_cfg(num_stack=2, remat="none"), batch)
    l1, g1 = _grads_of(tiny_cfg(num_stack=2, remat=mode), batch)
    assert float(l0) == pytest.approx(float(l1), rel=1e-6)
    flat0 = jnp.concatenate([jnp.ravel(x) for x in jax.tree.leaves(g0)])
    flat1 = jnp.concatenate([jnp.ravel(x) for x in jax.tree.leaves(g1)])
    scale = float(jnp.max(jnp.abs(flat0)))
    np.testing.assert_allclose(np.asarray(flat1), np.asarray(flat0),
                               atol=scale * 1e-5, rtol=1e-4)


@pytest.mark.slow  # 9 s at r15 --durations — re-tiered with its
# single-device twin (ISSUE 13 satellite)
def test_remat_gradient_equality_on_mesh():
    """--remat stacks vs none through the PRODUCTION sharded train step on
    the virtual 8-device mesh (the ISSUE-2 acceptance pairing): one step
    from identical states must produce matching params."""
    batch = synthetic_batch(b=8)
    results = {}
    for mode in ("none", "stacks"):
        cfg = tiny_cfg(batch_size=8, remat=mode)
        model, tx, state = make_state(cfg)
        mesh = make_mesh(8)
        step = make_train_step(model, tx, cfg, mesh)
        arrays = shard_batch(mesh, batch, spatial_dims=[1] * 5)
        state, losses = step(state, *arrays)
        results[mode] = (float(losses["total"]),
                         jax.device_get(jax.tree.leaves(state.params)[0]))
    l_none, p_none = results["none"]
    l_stacks, p_stacks = results["stacks"]
    assert l_none == pytest.approx(l_stacks, rel=1e-5)
    np.testing.assert_allclose(p_stacks, p_none,
                               atol=np.abs(p_none).max() * 1e-5, rtol=1e-4)


def test_loss_kernel_fused_matches_xla_in_loss_fn():
    """--loss-kernel fused (Pallas, interpret off-TPU) vs xla through the
    production loss_fn: value and gradient parity at train shapes."""
    batch = synthetic_batch()
    l_x, g_x = _grads_of(tiny_cfg(loss_kernel="xla"), batch)
    l_f, g_f = _grads_of(tiny_cfg(loss_kernel="fused"), batch)
    assert float(l_x) == pytest.approx(float(l_f), rel=1e-5)
    flat_x = jnp.concatenate([jnp.ravel(x) for x in jax.tree.leaves(g_x)])
    flat_f = jnp.concatenate([jnp.ravel(x) for x in jax.tree.leaves(g_f)])
    scale = float(jnp.max(jnp.abs(flat_x)))
    np.testing.assert_allclose(np.asarray(flat_f), np.asarray(flat_x),
                               atol=scale * 1e-5, rtol=1e-3)


def test_remat_bool_coercion_and_validation():
    assert Config(remat=True).remat == "stacks"
    assert Config(remat=False).remat == "none"
    assert Config(remat="full").remat == "full"
    with pytest.raises(ValueError, match="remat"):
        Config(remat="everything")
    with pytest.raises(ValueError, match="loss-kernel"):
        Config(loss_kernel="pallas")
