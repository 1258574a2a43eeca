"""CLI entry point.

Capability parity with the reference entry (/root/reference/main.py:9-17):
dispatch on `--train-flag` to training or evaluation and print the total
wall time. Additionally, if `--data` points at a single image file the demo
path runs (the reference exposes that via `python evaluate.py` __main__,
ref evaluate.py:245).

Usage:
  python main.py --train-flag --data ./DATA/VOC2028 --batch-size 16 --amp
  python main.py --model-load ./WEIGHTS/check_point_100 --data ./DATA/VOC2028 --imsize 512
  python main.py --model-load ./WEIGHTS/check_point_100 --data image.jpg --imsize 512
  python main.py --model-load ./WEIGHTS/check_point_100 --export-flag --imsize 512
"""

import os
import time

from real_time_helmet_detection_tpu.config import get_config
from real_time_helmet_detection_tpu.runtime import use_compile_cache


def main() -> None:
    use_compile_cache()
    cfg = get_config()
    tic = time.time()
    if cfg.train_flag:
        from real_time_helmet_detection_tpu.train import train
        train(cfg)
    elif cfg.export_flag:
        from real_time_helmet_detection_tpu.export import export_predict
        paths = export_predict(cfg)
        print("exported:", *paths)
    elif cfg.data is not None and os.path.isfile(cfg.data):
        from real_time_helmet_detection_tpu.evaluate import demo
        demo(cfg)
    else:
        from real_time_helmet_detection_tpu.evaluate import evaluate
        evaluate(cfg)
    print("%s: total run time: %.2fs" % (time.ctime(), time.time() - tic),
          flush=True)


if __name__ == "__main__":
    main()
