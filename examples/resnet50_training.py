"""ResNet-50 training — the reference's flagship CNN config
(dl4j-examples / zoo ResNet50; the BASELINE.json north-star model).

Runs the ComputationGraph train step (whole step = one XLA executable)
on synthetic ImageNet-shaped data in bf16. For real data, pair
ImageRecordReader (datavec/image.py) + batch_resize_normalize (native
preprocessor) + AsyncDataSetIterator — see tests/test_datavec.py for
each piece in isolation.

Run: python examples/resnet50_training.py [--steps 20] [--batch 64]
"""

from __future__ import annotations

import argparse
import time

import numpy as np


def synthetic_batches(batch: int, n_batches: int, num_classes: int):
    rng = np.random.default_rng(0)
    for _ in range(n_batches):
        x = rng.normal(0, 1, (batch, 224, 224, 3)).astype(np.float32)
        y = np.eye(num_classes, dtype=np.float32)[
            rng.integers(0, num_classes, batch)]
        yield x, y


def main(steps: int = 20, batch: int = 64, num_classes: int = 100):
    from deeplearning4j_tpu.learning import Nesterovs
    from deeplearning4j_tpu.nn.graph.graph import ComputationGraph
    from deeplearning4j_tpu.zoo.resnet50 import ResNet50

    model = ResNet50(num_classes=num_classes,
                     updater=Nesterovs(learning_rate=0.1, momentum=0.9))
    conf = model.conf()
    conf.dtype = "bfloat16"          # params+compute on the MXU in bf16
    net = ComputationGraph(conf).init()

    t0 = time.perf_counter()
    seen = 0
    for x, y in synthetic_batches(batch, steps, num_classes):
        net.fit([x], [y])
        seen += batch
        if seen == batch:            # first step includes compile
            print(f"compile+step1: {time.perf_counter() - t0:.1f}s")
            t0 = time.perf_counter()
    dt = time.perf_counter() - t0
    rate = (seen - batch) / dt if dt > 0 else float("nan")
    print(f"trained {steps} steps, {rate:.0f} img/s steady-state, "
          f"score={net.score():.3f}")
    return net.score()


if __name__ == "__main__":
    from deeplearning4j_tpu.common.environment import (
        configure_compile_cache,
    )

    configure_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=64)
    a = ap.parse_args()
    main(a.steps, a.batch)
