"""Config system tests: parse, snapshot round-trip, eval-time arch override
(ref /root/reference/config.py:139-179 semantics)."""

import dataclasses
import os

from real_time_helmet_detection_tpu.config import (
    ARCHITECTURE_FIELDS, Config, get_config, load_config, parse_args,
    save_config, update_config_for_eval)


def test_defaults_match_reference():
    cfg = parse_args([])
    # spot-check the reference's defaults (ref config.py:24-128)
    assert cfg.batch_size == 16
    assert cfg.lr == 5e-4
    assert cfg.lr_milestone == [50, 90]
    assert cfg.lr_gamma == 0.1
    assert cfg.end_epoch == 100
    assert cfg.topk == 100
    assert cfg.conf_th == 0.0
    assert cfg.nms_th == 0.5
    assert cfg.num_cls == 2
    assert cfg.num_stack == 1
    assert cfg.hourglass_inch == 128
    assert cfg.multiscale == [320, 512, 64]
    assert cfg.pretrained == "imagenet"
    assert not cfg.train_flag and not cfg.multiscale_flag


def test_flag_parsing_and_aliases():
    cfg = parse_args(["--train-flag", "--batch-size", "4", "--num-stack", "2",
                      "--multiscale", "256", "384", "64", "--multiscale_flag",
                      "--scale_factor", "4"])
    assert cfg.train_flag and cfg.batch_size == 4 and cfg.num_stack == 2
    assert cfg.multiscale == [256, 384, 64] and cfg.multiscale_flag
    assert cfg.scale_factor == 4


def test_snapshot_roundtrip(tmp_path):
    cfg = parse_args(["--num-stack", "3", "--activation", "Mish"])
    save_config(cfg, str(tmp_path))
    assert os.path.exists(tmp_path / "argument.txt")
    loaded = load_config(str(tmp_path / "argument.json"))
    assert loaded == cfg


def test_eval_override_restores_architecture():
    trained = dataclasses.replace(Config(), num_stack=4, activation="Mish",
                                  hourglass_inch=64, normalized_coord=True)
    cli = dataclasses.replace(Config(), imsize=512, conf_th=0.25)
    merged = update_config_for_eval(cli, trained)
    for k in ARCHITECTURE_FIELDS:
        assert getattr(merged, k) == getattr(trained, k)
    # non-architecture CLI choices survive
    assert merged.imsize == 512 and merged.conf_th == 0.25


def test_get_config_eval_reads_checkpoint_snapshot(tmp_path):
    ckpt_dir = tmp_path / "run1"
    train_cfg = parse_args(["--num-stack", "2", "--activation", "Mish",
                            "--save-path", str(ckpt_dir)])
    save_config(train_cfg, str(ckpt_dir))
    eval_cfg = get_config(["--model-load", str(ckpt_dir / "ckpt_1.msgpack"),
                           "--imsize", "512",
                           "--save-path", str(tmp_path / "eval")])
    assert eval_cfg.num_stack == 2 and eval_cfg.activation == "Mish"
    assert eval_cfg.imsize == 512


def test_infer_dtype_flags_parse_and_validate():
    """ISSUE 5: the inference-compression knobs exist as generated CLI
    flags and validate loudly."""
    import pytest

    cfg = parse_args(["--infer-dtype", "int8", "--quant-scales",
                      "/tmp/s.json", "--calib-batches", "2",
                      "--calib-percentile", "99.9", "--nms", "maxpool"])
    assert cfg.infer_dtype == "int8"
    assert cfg.quant_scales == "/tmp/s.json"
    assert cfg.calib_batches == 2
    assert cfg.calib_percentile == 99.9
    assert cfg.nms == "maxpool"
    assert parse_args([]).infer_dtype == "bf16"  # default stays float
    with pytest.raises(ValueError, match="infer-dtype"):
        Config(infer_dtype="fp8")
    with pytest.raises(ValueError, match="calib-batches"):
        Config(calib_batches=0)
    with pytest.raises(ValueError, match="calib-percentile"):
        Config(calib_percentile=0.0)


def test_scale_factor_must_be_four():
    """The stem's 4x downsample is structural; the reference silently
    mis-decodes for other values (SURVEY §5 dead flags) — here it fails
    loudly at config construction."""
    import pytest

    from real_time_helmet_detection_tpu.config import Config
    with pytest.raises(ValueError, match="structural"):
        Config(scale_factor=8)
    Config(scale_factor=4)  # default passes


def test_param_policy_and_epilogue_flags_parse_and_validate():
    """ISSUE 7: the step-compression knobs exist as generated CLI flags
    and validate loudly (bf16-compute's --amp / --sub-divisions
    requirements included)."""
    import pytest

    cfg = parse_args(["--param-policy", "bf16-compute", "--amp",
                      "--epilogue", "fused"])
    assert cfg.param_policy == "bf16-compute"
    assert cfg.epilogue == "fused"
    assert parse_args([]).param_policy == "fp32"   # defaults off
    assert parse_args([]).epilogue == "auto"       # fused on TPU only
    import pytest
    with pytest.raises(ValueError, match="param-policy"):
        Config(param_policy="fp8")
    with pytest.raises(ValueError, match="epilogue"):
        Config(epilogue="pallas")
    with pytest.raises(ValueError, match="requires --amp"):
        Config(param_policy="bf16-compute")
    with pytest.raises(ValueError, match="sub-divisions"):
        Config(param_policy="bf16-compute", amp=True, sub_divisions=4)


def test_serve_flags_parse_and_validate():
    """ISSUE 8: the serving-engine knobs exist as generated CLI flags and
    validate loudly."""
    import pytest

    cfg = parse_args(["--serve-buckets", "1", "4", "8",
                      "--serve-max-wait-ms", "2.5", "--serve-depth", "3",
                      "--serve-queue", "64", "--export-serve"])
    assert cfg.serve_buckets == [1, 4, 8]
    assert cfg.serve_max_wait_ms == 2.5
    assert cfg.serve_depth == 3
    assert cfg.serve_queue == 64
    assert cfg.export_serve is True
    d = parse_args([])
    assert d.serve_buckets == [1, 2, 4, 8, 16]  # engine/export/audit set
    assert d.export_serve is False
    with pytest.raises(ValueError, match="serve-buckets"):
        Config(serve_buckets=[0])
    with pytest.raises(ValueError, match="serve-max-wait-ms"):
        Config(serve_max_wait_ms=-1.0)
    with pytest.raises(ValueError, match="serve-depth"):
        Config(serve_depth=0)
    with pytest.raises(ValueError, match="serve-queue"):
        Config(serve_queue=0)
    # ISSUE 9: the in-flight recovery knobs
    cfg = parse_args(["--serve-max-retries", "4",
                      "--serve-hang-timeout-ms", "750"])
    assert cfg.serve_max_retries == 4
    assert cfg.serve_hang_timeout_ms == 750.0
    assert parse_args([]).serve_max_retries == 2
    assert parse_args([]).serve_hang_timeout_ms == 0.0  # watchdog off
    with pytest.raises(ValueError, match="serve-max-retries"):
        Config(serve_max_retries=-1)
    with pytest.raises(ValueError, match="serve-hang-timeout-ms"):
        Config(serve_hang_timeout_ms=-5.0)
