"""chip_smoke.py off the chip: its refusal, its parity stage and the kernel
partitioning it relies on, at toy size on the CPU mesh with the Pallas
kernels in interpret mode. (The reference has no smoke test; its entry
point, ref main.py:9-17, is what chip_smoke.py drives.)

What only the chip can say — that Mosaic compiles the kernels, that the
flagship step fits and runs — is `python chip_smoke.py` itself. What the
CPU can say is that the stages' control flow, checks and bookkeeping are
right, so a chip run is not spent finding a typo.

The full train -> checkpoint -> serve pass is the slow tier's (like
tests/test_cli.py's): the tier-1 command runs serially under a fixed
window, and two toy-size model compiles would cost later files their turn.
"""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402

TOY = dict(imsize=64, width=32, batch=8, steps_per_epoch=2, epochs=2,
           num_test=8, amp=True, interpret=True)


def test_entry_point_refuses_the_cpu(capsys):
    """On a machine without a TPU the entry point exits non-zero and
    prints no result line (this suite's platform is cpu)."""
    with pytest.raises(SystemExit) as ei:
        chip_smoke.main()
    assert ei.value.code not in (0, None)
    out = capsys.readouterr().out
    assert '"ok"' not in out and "{" not in out


def test_parity_checks_at_toy_size_in_interpret_mode():
    """Every family's kernel-vs-XLA check runs, and passes, off the chip:
    peak bit-identical, loss / epilogue / residual forward and gradient
    inside the stated tolerance (the slow tier's full pass adds bf16)."""
    chip_smoke.parity_peak(2, 8, interpret=True)
    chip_smoke.parity_loss(2, 8, interpret=True)
    chip_smoke.parity_bn_kernels((2, 8, 8, 32), jnp.float32, "ReLU",
                                 interpret=True)


def test_parity_check_fails_on_a_wrong_kernel(monkeypatch):
    """The check is a check: a kernel that is off by a bf16 ulp's worth of
    scale is outside the f32 tolerance and ends the stage."""
    from real_time_helmet_detection_tpu.ops.pallas import epilogue
    real = epilogue._act_fwd
    monkeypatch.setattr(epilogue, "_act_fwd",
                        lambda z, act: real(z, act) * (1.0 + 2.0 ** -8))
    epilogue._make_fused_train.cache_clear()
    try:
        with pytest.raises(AssertionError, match="outside tolerance"):
            chip_smoke.parity_bn_kernels((2, 8, 8, 32), jnp.float32, "ReLU",
                                         interpret=True)
    finally:
        epilogue._make_fused_train.cache_clear()


def test_kernels_run_per_batch_shard_under_a_mesh():
    """ops/pallas/partition.py: inside a mesh-sharded jit the fused BN
    tail runs under shard_map over `data` — and BatchNorm's statistics
    stay those of the GLOBAL batch (per-sample partials, summed by XLA
    across shards), so the sharded result equals the unsharded one."""
    from real_time_helmet_detection_tpu.ops.pallas.epilogue import \
        fused_bn_act_train
    from real_time_helmet_detection_tpu.parallel import (
        batch_sharding, make_mesh, replicated, under_kernel_mesh)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((4, 4, 4, 16)).astype(np.float32))
    gamma = jnp.asarray(rng.uniform(0.5, 1.5, (16,)).astype(np.float32))
    beta = jnp.zeros((16,), jnp.float32)

    def f(x, gamma, beta):
        def loss(x, gamma, beta):
            out, mean, var = fused_bn_act_train(x, gamma, beta,
                                                activation="ReLU",
                                                interpret=True)
            return jnp.sum(out * out), (mean, var)
        return jax.value_and_grad(loss, argnums=(0, 1, 2),
                                  has_aux=True)(x, gamma, beta)

    mesh = make_mesh(4)
    sharded = jax.jit(under_kernel_mesh(f, mesh),
                      in_shardings=(batch_sharding(mesh, 4),
                                    replicated(mesh), replicated(mesh)))
    hlo = sharded.lower(x, gamma, beta).compile().as_text()
    assert "all-reduce" in hlo and "all-gather" not in hlo
    for got, ref in zip(jax.tree.leaves(sharded(x, gamma, beta)),
                        jax.tree.leaves(jax.jit(f)(x, gamma, beta))):
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-4, atol=1e-5)


@pytest.mark.slow
def test_stages_at_toy_size_on_the_cpu_mesh(tmp_path, monkeypatch):
    """The smoke's own stages, data -> train -> serve -> demo -> placement
    -> parity, at toy size on the 8-device CPU mesh: the loss and peak
    kernels in Pallas interpret mode, the BN tails on their jnp twins
    (forty interpret-mode sites per step take minutes per step)."""
    import real_time_helmet_detection_tpu.predict as predict
    from real_time_helmet_detection_tpu.obs.telemetry import \
        install_recompile_counter
    monkeypatch.setattr(predict, "kernel_plan", lambda cfg: {"peak": "fused"})
    size = chip_smoke.Size(
        extra_flags=("--loss-kernel", "fused", "--epilogue", "fused",
                     "--block-fuse", "fused", "--num-workers", "2"), **TOY)
    chip_smoke.run(size, str(tmp_path), install_recompile_counter())
    assert os.path.isdir(tmp_path / "w" / "check_point_2")
    assert os.path.exists(tmp_path / "out" / "image.png")


@pytest.mark.slow
def test_script_exits_nonzero_on_cpu_in_a_fresh_process():
    r = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")],
                       capture_output=True, text=True, timeout=300,
                       env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    assert "no TPU" in r.stderr
