"""Unit tests for the measurement harness the driver depends on.

bench.py is the artifact the judge's driver runs every round and
scripts/tpu_sweep.py produced the README's throughput table — their helper
logic (dispatch-overhead subtraction, cost-analysis FLOPs, resume merge)
deserves the same pinning as the framework ops. All tests run on the CPU
backend conftest configures; nothing here touches a device claim.
"""

import importlib.util
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import bench  # noqa: E402


def _load_sweep():
    spec = importlib.util.spec_from_file_location(
        "tpu_sweep", os.path.join(REPO, "scripts", "tpu_sweep.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_measure_dispatch_overhead_small_and_positive():
    ov = bench.measure_dispatch_overhead()
    assert 0 < ov < 1.0  # CPU dispatch is microseconds; 1 s = badly broken


def test_timed_fetch_subtracts_overhead_and_stays_positive():
    f = jax.jit(lambda x: jnp.sum(x * 2.0))
    x = jnp.ones((256, 256))
    float(f(x))  # compile
    dt = bench.timed_fetch(f, (x,), overhead=0.0)
    assert dt > 0
    # an overhead larger than the measurement must clamp, not go negative
    dt_clamped = bench.timed_fetch(f, (x,), overhead=1e9)
    assert dt_clamped == 1e-9


def test_flops_of_matmul_matches_analytic():
    n = 128
    f = jax.jit(lambda a, b: a @ b)
    a = jnp.ones((n, n), jnp.float32)
    compiled = f.lower(a, a).compile()
    fl = bench.flops_of(compiled)
    assert fl is not None
    # XLA counts 2*n^3 (fused multiply-add = 2 flops); allow slack for
    # version differences in how the epilogue is counted
    assert 0.5 * 2 * n**3 <= fl <= 2 * 2 * n**3


def test_sweep_merge_prior_keeps_only_unrerun_sections():
    sweep = _load_sweep()
    fresh = {"platform": "tpu", "inference_batch_sweep": [],
             "train_batch_sweep": [], "num_stack2": {}, "remat": [],
             "stack4_768": [], "step_grid": []}
    # prior predates the stack4_768/step_grid sections (an r3-era
    # sweep.json): the merge must fall back to the fresh empty section,
    # not crash
    prior = {"platform": "tpu",
             "inference_batch_sweep": [{"batch": 8, "img_per_sec": 1.0}],
             "train_batch_sweep": [{"batch": 16, "img_per_sec_chip": 2.0}],
             "num_stack2": {"train": {"batch": 16}}, "remat": []}
    out = sweep.merge_prior(dict(fresh), prior, only={"train"})
    # rerun section starts empty; others carried over
    assert out["train_batch_sweep"] == []
    assert out["inference_batch_sweep"] == prior["inference_batch_sweep"]
    assert out["num_stack2"] == prior["num_stack2"]
    assert out["stack4_768"] == []
    assert out["step_grid"] == []


def test_sweep_merge_prior_carries_step_grid_selected():
    sweep = _load_sweep()
    fresh = {"platform": "tpu", "inference_batch_sweep": [],
             "train_batch_sweep": [], "num_stack2": {}, "remat": [],
             "stack4_768": [], "step_grid": []}
    sel = {"batch": 32, "remat": "stacks", "loss_kernel": "fused"}
    prior = {"platform": "tpu", "step_grid": [sel],
             "step_grid_selected": sel}
    out = sweep.merge_prior(dict(fresh), prior, only={"train"})
    # the derived pick travels with its (un-rerun) section...
    assert out["step_grid"] == [sel]
    assert out["step_grid_selected"] == sel
    # ...and is dropped when the section is being rerun
    out2 = sweep.merge_prior(dict(fresh), prior, only={"step_grid"})
    assert out2["step_grid"] == []
    assert "step_grid_selected" not in out2


def test_sweep_merge_prior_rejects_other_platform():
    # A platform-mismatched merge must be refused loudly: silently dropping
    # the prior records let a `--cpu --only X` rerun clobber merged TPU data
    # (round-2 advisor finding); main() diverts such runs to a
    # platform-suffixed file instead of calling merge_prior at all.
    import pytest
    sweep = _load_sweep()
    fresh = {"platform": "tpu", "inference_batch_sweep": [],
             "train_batch_sweep": [], "num_stack2": {}, "remat": [],
             "stack4_768": []}
    prior = {"platform": "cpu",
             "inference_batch_sweep": [{"batch": 1, "img_per_sec": 9.0}]}
    with pytest.raises(ValueError, match="platform mismatch"):
        sweep.merge_prior(dict(fresh), prior, only={"train"})


def _write_bench_artifact(root, round_name, rec, fname=None):
    d = os.path.join(root, "artifacts", round_name)
    os.makedirs(d, exist_ok=True)
    path = os.path.join(d, fname or ("BENCH_%s_local.json" % round_name))
    import json
    with open(path, "w") as f:
        f.write(json.dumps(rec) + "\n")
    return path


def test_find_last_tpu_result_picks_newest_tpu_line(tmp_path):
    root = str(tmp_path)
    _write_bench_artifact(root, "r03", {
        "platform": "tpu", "metric": "inference_fps_512", "value": 1100.0,
        "mfu_train": 0.47})
    newest = _write_bench_artifact(root, "r04", {
        "platform": "tpu", "metric": "inference_fps_512", "value": 1207.7,
        "vs_baseline": 12.077, "train_img_per_sec_chip": 435.1,
        "mfu_train": 0.5278, "latency_ms_b1": 1.477})
    # adversarial mtimes: the OLDER round gets the NEWER mtime (fresh-clone
    # checkout order is arbitrary); round number must win, not mtime
    now = os.path.getmtime(newest)
    os.utime(os.path.join(root, "artifacts", "r03",
                          "BENCH_r03_local.json"), (now + 60, now + 60))
    got = bench.find_last_tpu_result(root)
    assert got is not None
    assert got["value"] == 1207.7
    assert got["mfu_train"] == 0.5278
    assert got["train_img_per_sec_chip"] == 435.1
    assert got["path"].endswith("r04/BENCH_r04_local.json")
    # these tmp artifacts are not in git: no commit provenance claimed
    assert got["committed_at"] is None
    assert "NOT yet committed" in got["note"]
    assert got["file_mtime_utc"]


def test_find_last_tpu_result_skips_cpu_and_malformed(tmp_path):
    root = str(tmp_path)
    # a CPU fallback line must never be surfaced as on-chip evidence
    _write_bench_artifact(root, "r02", {"platform": "cpu", "value": 18.3})
    bad = _write_bench_artifact(root, "r03", {"platform": "tpu"})
    with open(bad, "w") as f:
        f.write("{not json")
    assert bench.find_last_tpu_result(root) is None
    # and an empty tree returns None rather than raising
    assert bench.find_last_tpu_result(str(tmp_path / "nowhere")) is None


def test_find_last_tpu_result_real_repo_picks_highest_round():
    # the repo's own committed artifacts must be discoverable, and the
    # SELECTED one must be the highest-round on-chip line present (r02 also
    # clears any static value floor, so pin the round, not a threshold)
    import glob
    import json
    import re
    got = bench.find_last_tpu_result(REPO)
    assert got is not None
    assert got["value"] >= 1000.0  # r4: 1207.7 img/s @512^2
    rounds = []
    for p in glob.glob(os.path.join(REPO, "artifacts", "*",
                                    "BENCH_*_local.json")):
        try:
            with open(p) as f:
                rec = json.loads(f.read().strip().splitlines()[-1])
        except (OSError, json.JSONDecodeError, IndexError):
            continue
        if rec.get("platform") == "tpu":
            m = re.search(r"r(\d+)", os.path.basename(os.path.dirname(p)))
            rounds.append(int(m.group(1)) if m else -1)
    want = max(rounds)
    m = re.search(r"r(\d+)", got["path"])
    assert m and int(m.group(1)) == want, (got["path"], rounds)
    # committed artifacts carry git provenance (the working tree may also
    # hold a not-yet-committed newer one; both labels are legitimate here)
    assert got["committed_at"] or "NOT yet committed" in got["note"]


def test_sweep_section_keys_cover_all_result_lists():
    sweep = _load_sweep()
    assert set(sweep.SECTION_KEYS.values()) == {
        "inference_batch_sweep", "train_batch_sweep", "num_stack2", "remat",
        "stack4_768", "step_grid", "int8_inference", "serve_buckets",
        "arch_grid"}


def test_find_last_tpu_result_carries_int8_fields(tmp_path):
    """ISSUE 5 satellite: the JSON line's new infer_dtype/int8 keys must
    survive find_last_tpu_result, and existing consumers see the same
    core fields as before (value/mfu/latency untouched)."""
    root = str(tmp_path)
    _write_bench_artifact(root, "r08", {
        "platform": "tpu", "metric": "inference_fps_512", "value": 1250.0,
        "mfu_train": 0.53, "latency_ms_b1": 1.4, "infer_dtype": "int8",
        "int8_fps": 2100.0, "int8_vs_bf16": 1.68})
    got = bench.find_last_tpu_result(root)
    assert got["infer_dtype"] == "int8"
    assert got["int8_fps"] == 2100.0
    assert got["int8_vs_bf16"] == 1.68
    # pre-existing consumer contract unchanged
    assert got["value"] == 1250.0
    assert got["mfu_train"] == 0.53
    assert got["latency_ms_b1"] == 1.4


def test_find_last_tpu_result_carries_topology_fields(tmp_path):
    """ISSUE 11 satellite: the JSON line's device_count/mesh_shape keys
    survive find_last_tpu_result (a chip line from a pod slice must say
    what the timed programs actually spanned), and the pre-existing
    consumer contract is unchanged."""
    root = str(tmp_path)
    _write_bench_artifact(root, "r13", {
        "platform": "tpu", "metric": "inference_fps_512", "value": 1250.0,
        "mfu_train": 0.53, "device_count": 4,
        "mesh_shape": {"data": 1, "spatial": 1}})
    got = bench.find_last_tpu_result(root)
    assert got["device_count"] == 4
    assert got["mesh_shape"] == {"data": 1, "spatial": 1}
    assert got["value"] == 1250.0 and got["mfu_train"] == 0.53
    # pre-ISSUE-11 lines (no topology fields) still read fine
    _write_bench_artifact(root, "r14", {
        "platform": "tpu", "metric": "inference_fps_512", "value": 1260.0})
    got = bench.find_last_tpu_result(root)
    assert got["value"] == 1260.0 and "device_count" not in got


def test_find_last_tpu_result_carries_obs_fields(tmp_path):
    """ISSUE 6 satellite: the JSON line's flight-recorder keys
    (recompile_count, loadavg) survive find_last_tpu_result; span_log is a
    diagnostic pointer and deliberately does NOT ride (it names a file on
    the box that produced the line, meaningless to later consumers)."""
    root = str(tmp_path)
    _write_bench_artifact(root, "r09", {
        "platform": "tpu", "metric": "inference_fps_512", "value": 1250.0,
        "mfu_train": 0.53, "recompile_count": 7,
        "loadavg": [1.1, 1.4, 1.9], "span_log": "/tmp/spans.jsonl"})
    got = bench.find_last_tpu_result(root)
    assert got["recompile_count"] == 7
    assert got["loadavg"] == [1.1, 1.4, 1.9]
    assert "span_log" not in got
    # pre-existing consumer contract unchanged
    assert got["value"] == 1250.0
    assert got["mfu_train"] == 0.53


def test_find_last_tpu_result_old_lines_lack_obs_keys(tmp_path):
    """A pre-flight-recorder artifact resolves exactly as before."""
    root = str(tmp_path)
    _write_bench_artifact(root, "r05", {
        "platform": "tpu", "metric": "inference_fps_512", "value": 1100.0})
    got = bench.find_last_tpu_result(root)
    assert got["value"] == 1100.0
    assert "recompile_count" not in got
    assert "loadavg" not in got


def test_find_last_tpu_result_old_lines_unaffected_by_int8_keys(tmp_path):
    """A pre-int8 artifact (no infer_dtype key) must still resolve with
    the same fields as before — consumers never see a surprise key."""
    root = str(tmp_path)
    _write_bench_artifact(root, "r04", {
        "platform": "tpu", "metric": "inference_fps_512", "value": 1207.7,
        "mfu_train": 0.5278})
    got = bench.find_last_tpu_result(root)
    assert got["value"] == 1207.7
    assert "infer_dtype" not in got
    assert "int8_fps" not in got


def test_bytes_of_reports_cost_analysis_bytes():
    f = jax.jit(lambda a: jnp.sum(a * 2.0))
    a = jnp.ones((256, 256), jnp.float32)
    compiled = f.lower(a).compile()
    by = bench.bytes_of(compiled)
    # CPU XLA reports 'bytes accessed'; at minimum the input must be read
    assert by is None or by >= a.size * 4


def test_predict_chain_donation_emits_no_warning():
    """The eval/predict chain donates its image batch and returns the
    final carry as the aliasing target (ISSUE-2 satellite: it was the one
    bench program left holding a second input-sized buffer). Lowering +
    running it must not emit XLA's 'Some donated buffers were not usable'
    warning, and `chain_timed_fetch` must thread the returned carry so
    repeats never touch a donated-away buffer."""
    import warnings

    from jax import lax

    def predict_like(images):  # stand-in for the fused predict program
        return jnp.tanh(jnp.sum(images))

    def prog(scale, images):
        def body(imgs, _):
            eps = (predict_like(imgs) * 1e-12).astype(imgs.dtype)
            return imgs + eps * scale, ()
        final, _ = lax.scan(body, images, None, length=2)
        return final, jnp.sum(final[0, 0])

    chain = jax.jit(prog, donate_argnums=(1,))
    images = jnp.ones((2, 16, 16, 3), jnp.float32)
    scale = jnp.float32(1.0)
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        compiled = chain.lower(scale, images).compile()
        images, s = compiled(scale, images)  # donates; carry returned
        np.asarray(s)
        dt = bench.chain_timed_fetch(compiled, scale, images, overhead=0.0)
    assert dt > 0
    donation_warnings = [w for w in caught
                         if "donated buffers" in str(w.message)]
    assert not donation_warnings, [str(w.message) for w in donation_warnings]


def test_bench_error_path_still_prints_one_json_line(monkeypatch, capsys):
    """ISSUE 3 satellite: a backend failure must yield THE one JSON line
    (with error + error_class) and the transient exit code — never a raw
    traceback the driver/supervisor has to log-scrape."""
    import json

    import pytest

    def boom(out, hb):
        out["platform"] = "tpu"  # partial results ride along
        raise RuntimeError("UNAVAILABLE: TPU backend setup/compile error")

    monkeypatch.setattr(bench, "_bench", boom)
    with pytest.raises(SystemExit) as ei:
        bench.main()
    assert ei.value.code == 75  # EXIT_TRANSIENT
    rec = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rec["error_class"] == "transient"
    assert "UNAVAILABLE" in rec["error"]
    assert rec["platform"] == "tpu"  # the partial field survived


def test_bench_error_path_permanent_classification(monkeypatch, capsys):
    import json

    import pytest

    def boom(out, hb):
        raise ValueError("shape mismatch in user code")

    monkeypatch.setattr(bench, "_bench", boom)
    with pytest.raises(SystemExit) as ei:
        bench.main()
    assert ei.value.code == 1
    rec = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rec["error_class"] == "permanent"
    assert rec["value"] is None


def test_save_json_and_pickle_are_atomic(tmp_path):
    """tmp + os.replace: the write leaves either the OLD complete file or
    the NEW complete file, and no tmp residue (ISSUE 3 satellite)."""
    import json

    from real_time_helmet_detection_tpu.utils import (load_pickle,
                                                      save_json,
                                                      save_pickle)

    jpath = str(tmp_path / "artifact.json")
    save_json(jpath, {"a": 1}, indent=1)
    save_json(jpath, {"a": 2}, indent=1)  # overwrite goes through replace
    with open(jpath) as f:
        assert json.load(f) == {"a": 2}

    ppath = str(tmp_path / "artifact.pickle")
    save_pickle(ppath, {"b": [1, 2, 3]})
    assert load_pickle(ppath) == {"b": [1, 2, 3]}

    leftovers = [n for n in os.listdir(str(tmp_path)) if ".tmp." in n]
    assert leftovers == []


def test_find_last_tpu_result_carries_step_policy_fields(tmp_path):
    """ISSUE 7 satellite: param_policy/epilogue ride find_last_tpu_result
    (the A/B labels without which a carried-forward train number is
    uninterpretable); convert_bytes_pct is per-run attribution and
    deliberately does NOT ride."""
    root = str(tmp_path)
    _write_bench_artifact(root, "r09", {
        "platform": "tpu", "metric": "inference_fps_512", "value": 1250.0,
        "mfu_train": 0.61, "param_policy": "bf16-compute",
        "epilogue": "fused", "convert_bytes_pct": 4.2})
    got = bench.find_last_tpu_result(root)
    assert got["param_policy"] == "bf16-compute"
    assert got["epilogue"] == "fused"
    assert "convert_bytes_pct" not in got
    assert got["value"] == 1250.0


def test_find_last_tpu_result_old_lines_lack_policy_keys(tmp_path):
    root = str(tmp_path)
    _write_bench_artifact(root, "r05", {
        "platform": "tpu", "metric": "inference_fps_512", "value": 1100.0})
    got = bench.find_last_tpu_result(root)
    assert "param_policy" not in got and "epilogue" not in got


def test_sweep_step_grid_cell_identity_fields():
    """The step_grid per-cell resume keys on (batch, remat, loss_kernel,
    param_policy, epilogue); a prior record missing the new fields (a
    pre-ISSUE-7 sweep.json) must default to the fp32/xla baseline cell
    rather than colliding with a lever cell."""
    rec_old = {"batch": 16, "remat": "none", "loss_kernel": "xla",
               "img_per_sec_chip": 400.0}
    key = (rec_old.get("batch"), rec_old.get("remat"),
           rec_old.get("loss_kernel"), rec_old.get("param_policy", "fp32"),
           rec_old.get("epilogue", "xla"))
    assert key == (16, "none", "xla", "fp32", "xla")


def test_find_last_tpu_result_carries_serve_fields(tmp_path):
    """ISSUE 8 satellite: the --serve closed-loop headline
    (serve_p50_ms/serve_p99_ms/serve_goodput) rides find_last_tpu_result;
    old lines without the keys are unaffected."""
    root = str(tmp_path)
    _write_bench_artifact(root, "r10", {
        "platform": "tpu", "metric": "inference_fps_512", "value": 1250.0,
        "mfu_train": 0.61, "serve_p50_ms": 18.5, "serve_p99_ms": 41.2,
        "serve_goodput": 1180.0})
    got = bench.find_last_tpu_result(root)
    assert got["serve_p50_ms"] == 18.5
    assert got["serve_p99_ms"] == 41.2
    assert got["serve_goodput"] == 1180.0
    # pre-existing consumer contract unchanged
    assert got["value"] == 1250.0 and got["mfu_train"] == 0.61


def test_find_last_tpu_result_old_lines_lack_serve_keys(tmp_path):
    root = str(tmp_path)
    _write_bench_artifact(root, "r09", {
        "platform": "tpu", "metric": "inference_fps_512", "value": 1100.0})
    got = bench.find_last_tpu_result(root)
    assert "serve_p50_ms" not in got and "serve_goodput" not in got


def test_find_last_tpu_result_carries_sentinel_fields(tmp_path):
    """ISSUE 9 satellite: the JSON line's sentinel (on/off) and
    skipped_steps keys survive find_last_tpu_result; the pre-existing
    consumer contract is untouched."""
    root = str(tmp_path)
    _write_bench_artifact(root, "r11", {
        "platform": "tpu", "metric": "inference_fps_512", "value": 1250.0,
        "mfu_train": 0.61, "sentinel": "on", "skipped_steps": 0})
    got = bench.find_last_tpu_result(root)
    assert got["sentinel"] == "on"
    assert got["skipped_steps"] == 0
    assert got["value"] == 1250.0 and got["mfu_train"] == 0.61


def test_find_last_tpu_result_old_lines_lack_sentinel_keys(tmp_path):
    """A pre-sentinel artifact resolves exactly as before."""
    root = str(tmp_path)
    _write_bench_artifact(root, "r10", {
        "platform": "tpu", "metric": "inference_fps_512", "value": 1100.0})
    got = bench.find_last_tpu_result(root)
    assert "sentinel" not in got and "skipped_steps" not in got
    assert got["value"] == 1100.0


def test_find_last_tpu_result_carries_step_percentile_fields(tmp_path):
    """ISSUE 10 satellite: step_p50_ms/step_p99_ms (the live metrics
    histogram's digest of the chained timed dispatches) ride
    find_last_tpu_result; the pre-existing contract is untouched and
    old lines without the keys resolve as before."""
    root = str(tmp_path)
    _write_bench_artifact(root, "r12", {
        "platform": "tpu", "metric": "inference_fps_512", "value": 1250.0,
        "mfu_train": 0.61, "train_step_ms": 36.2, "step_p50_ms": 36.9,
        "step_p99_ms": 39.4})
    got = bench.find_last_tpu_result(root)
    assert got["step_p50_ms"] == 36.9
    assert got["step_p99_ms"] == 39.4
    assert got["value"] == 1250.0 and got["mfu_train"] == 0.61


def test_find_last_tpu_result_old_lines_lack_step_percentiles(tmp_path):
    root = str(tmp_path)
    _write_bench_artifact(root, "r11", {
        "platform": "tpu", "metric": "inference_fps_512", "value": 1100.0})
    got = bench.find_last_tpu_result(root)
    assert "step_p50_ms" not in got and "step_p99_ms" not in got
    assert got["value"] == 1100.0


def test_chained_scan_step_samples_threads_donated_state():
    """The bench train-timing helper (ISSUE 10): each dispatch's
    returned state feeds the next donated input (no deleted-buffer
    touch), per-dispatch samples are positive with the overhead
    subtracted and clamped, and the chained program really ran
    (state advanced chunks times)."""
    def prog(state, x):
        new = state + jnp.sum(x) * 0 + 1.0
        return new, jnp.sum(new)

    compiled = jax.jit(prog, donate_argnums=(0,)).lower(
        jnp.float32(0.0), jnp.ones((8, 8))).compile()
    samples, final = bench.chained_scan_step_samples(
        compiled, jnp.float32(0.0), (jnp.ones((8, 8)),), overhead=0.0,
        chunks=3)
    assert len(samples) == 3 and all(s > 0 for s in samples)
    assert float(np.asarray(final)) == 3.0  # state threaded, not rebuilt
    clamped, _ = bench.chained_scan_step_samples(
        compiled, final, (jnp.ones((8, 8)),), overhead=1e9, chunks=1)
    assert clamped == [1e-9]


def test_find_last_tpu_result_carries_stream_fields(tmp_path):
    """ISSUE 17 satellite: the BENCH_STREAM JSON-line fields
    (stream/tile_skip_rate/stream_fps) ride find_last_tpu_result, and
    bench_stream_of hands a consumer the full triple."""
    root = str(tmp_path)
    _write_bench_artifact(root, "r17", {
        "platform": "tpu", "metric": "inference_fps_512", "value": 1300.0,
        "stream": True, "tile_skip_rate": 0.62, "stream_fps": 210.5})
    got = bench.find_last_tpu_result(root)
    assert got["stream"] is True
    assert got["tile_skip_rate"] == 0.62
    assert got["stream_fps"] == 210.5
    # pre-existing consumer contract unchanged
    assert got["value"] == 1300.0
    assert bench.bench_stream_of(got) == {
        "stream": True, "tile_skip_rate": 0.62, "stream_fps": 210.5}


def test_find_last_tpu_result_old_lines_lack_stream_keys(tmp_path):
    """Pre-stream lines carry no stream keys and parse as stream-off
    through bench_stream_of (the back-compat contract)."""
    root = str(tmp_path)
    _write_bench_artifact(root, "r09", {
        "platform": "tpu", "metric": "inference_fps_512", "value": 1100.0})
    got = bench.find_last_tpu_result(root)
    assert "stream" not in got and "stream_fps" not in got
    assert bench.bench_stream_of(got) == {
        "stream": False, "tile_skip_rate": None, "stream_fps": None}


def test_find_last_tpu_result_carries_audit_fields(tmp_path):
    """ISSUE 19 satellite: the hygiene self-reports (donation_ok,
    lock_audit_clean, transfer_audit_ok) ride find_last_tpu_result so a
    surfaced on-chip number keeps its audit verdicts attached; old lines
    without the keys are unaffected."""
    root = str(tmp_path)
    _write_bench_artifact(root, "r19", {
        "platform": "tpu", "metric": "inference_fps_512", "value": 1250.0,
        "mfu_train": 0.61, "donation_ok": True, "lock_audit_clean": True,
        "transfer_audit_ok": True})
    got = bench.find_last_tpu_result(root)
    assert got["donation_ok"] is True
    assert got["lock_audit_clean"] is True
    assert got["transfer_audit_ok"] is True
    assert got["value"] == 1250.0 and got["mfu_train"] == 0.61


def test_find_last_tpu_result_old_lines_lack_audit_keys(tmp_path):
    root = str(tmp_path)
    _write_bench_artifact(root, "r18", {
        "platform": "tpu", "metric": "inference_fps_512", "value": 1100.0})
    got = bench.find_last_tpu_result(root)
    assert "transfer_audit_ok" not in got and "donation_ok" not in got


def test_find_last_tpu_result_carries_block_fuse_fields(tmp_path):
    """ISSUE 20 satellite: block_fuse/fwd_dtype ride find_last_tpu_result
    (the A/B labels for the step-compression levers), and
    bench_block_fuse_of hands a consumer the resolved pair."""
    root = str(tmp_path)
    _write_bench_artifact(root, "r18", {
        "platform": "tpu", "metric": "inference_fps_512", "value": 1320.0,
        "mfu_train": 0.60, "block_fuse": "fused", "fwd_dtype": "int8"})
    got = bench.find_last_tpu_result(root)
    assert got["block_fuse"] == "fused"
    assert got["fwd_dtype"] == "int8"
    assert got["value"] == 1320.0
    assert bench.bench_block_fuse_of(got) == {
        "block_fuse": "fused", "fwd_dtype": "int8"}


def test_find_last_tpu_result_old_lines_lack_block_fuse_keys(tmp_path):
    """Pre-ISSUE-20 lines carry neither key and parse as the unfused
    bf16 step through bench_block_fuse_of (the back-compat contract,
    same shape as the tier/cascade/stream field defaults)."""
    root = str(tmp_path)
    _write_bench_artifact(root, "r09", {
        "platform": "tpu", "metric": "inference_fps_512", "value": 1100.0})
    got = bench.find_last_tpu_result(root)
    assert "block_fuse" not in got and "fwd_dtype" not in got
    assert bench.bench_block_fuse_of(got) == {
        "block_fuse": "xla", "fwd_dtype": "bf16"}
    assert bench.STEP_FUSE_DEFAULTS == {
        "block_fuse": "xla", "fwd_dtype": "bf16"}


def test_sweep_step_grid_block_fuse_cell_identity():
    """The grown step_grid resume key: a pre-ISSUE-20 record missing the
    new fields must default to the (xla, bf16) baseline cell rather than
    colliding with a lever cell."""
    rec_old = {"batch": 16, "remat": "none", "loss_kernel": "xla",
               "img_per_sec_chip": 400.0}
    key = (rec_old.get("batch"), rec_old.get("remat"),
           rec_old.get("loss_kernel"), rec_old.get("param_policy", "fp32"),
           rec_old.get("epilogue", "xla"),
           rec_old.get("block_fuse", "xla"),
           rec_old.get("fwd_dtype", "bf16"))
    assert key == (16, "none", "xla", "fp32", "xla", "xla", "bf16")


def test_bench_without_a_chip_exits_nonzero_and_prints_no_line(monkeypatch,
                                                               capsys):
    """No chip, no number: on the CPU backend (what this suite runs on)
    bench.py neither re-runs itself on the CPU nor prints a result line —
    not even an error line, there being nothing measured to report."""
    import pytest
    monkeypatch.setattr(sys, "argv", ["bench.py"])
    with pytest.raises(SystemExit) as ei:
        bench.main()
    assert ei.value.code == 1
    assert capsys.readouterr().out == ""


def test_acquire_backend_cpu_is_a_request_not_a_fallback(monkeypatch):
    """`--cpu` in argv is how the count-only scripts ASK for the CPU
    backend; without it the same helper refuses the CPU."""
    import pytest
    monkeypatch.setattr(sys, "argv", ["roofline.py", "--cpu"])
    _, devs = bench.acquire_backend()
    assert devs[0].platform == "cpu"
    monkeypatch.setattr(sys, "argv", ["tpu_sweep.py"])
    with pytest.raises(SystemExit) as ei:
        bench.acquire_backend()
    assert ei.value.code == 1


def test_chip_peaks_known_kind_or_error():
    """A device_kind missing from the table is an error, never a default
    (an assumed peak is how an MFU gets the wrong denominator)."""
    import pytest
    assert bench.chip_peaks("TPU v5 lite") == (1.97e14, 819e9)
    assert bench.chip_peaks(bench.TARGET_CHIP) == (1.97e14, 819e9)
    with pytest.raises(ValueError, match="not in bench.PEAK_BF16"):
        bench.chip_peaks("TPU v9 imaginary")
    with pytest.raises(ValueError):
        bench.chip_peaks("cpu")
