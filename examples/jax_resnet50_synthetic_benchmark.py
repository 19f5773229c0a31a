"""ResNet-50 synthetic benchmark, compiled mode.

The analogue of the reference's ``examples/tensorflow2_synthetic_benchmark.py``:
a model on random data, warm-up, img/sec averaged over timed iterations,
optional fp16 compression and Adasum; the whole step is one XLA program over
the device mesh. The repository's measured numbers come from
``benchmark/run.py`` (docs/benchmarks.md), not from this script.
Usage: python examples/jax_resnet50_synthetic_benchmark.py [--batch-size 32]
"""

import os as _os
import sys as _sys

try:  # allow running from a source checkout without installation
    import horovod_tpu  # noqa: F401
except ImportError:
    _sys.path.insert(0, _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))))

import argparse
import timeit

import numpy as np
import jax
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

import horovod_tpu.jax as hvd
from horovod_tpu.models import get_model


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--fp16-allreduce", action="store_true")
    ap.add_argument("--model", default="resnet50")
    ap.add_argument("--batch-size", type=int, default=32, help="per chip")
    ap.add_argument("--image-size", type=int, default=224)
    ap.add_argument("--num-warmup-batches", type=int, default=10)
    ap.add_argument("--num-batches-per-iter", type=int, default=10)
    ap.add_argument("--num-iters", type=int, default=10)
    ap.add_argument("--use-adasum", action="store_true")
    args = ap.parse_args()
    mesh = hvd.build_mesh()
    n = mesh.devices.size
    model = get_model(args.model, num_classes=1000)
    rng = np.random.RandomState(0)
    size, total = args.image_size, args.batch_size * n
    images = rng.randn(total, size, size, 3).astype(np.float32)
    labels = rng.randint(0, 1000, (total,)).astype(np.int32)
    variables = model.init(jax.random.PRNGKey(0), images[:2], train=False)
    rest = {k: v for k, v in variables.items() if k != "params"}

    def loss_fn(p, batch):
        # Train-mode BatchNorm normalizes with the batch's own statistics;
        # the running averages are not read by a train step.
        logits, _ = model.apply({"params": p, **rest}, batch[0], train=True,
                                mutable=list(rest),
                                rngs={"dropout": jax.random.PRNGKey(1)})
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, batch[1]).mean()

    tx = hvd.DistributedOptimizer(
        optax.sgd(0.01, momentum=0.9),
        op=hvd.Adasum if args.use_adasum else hvd.Average,
        compression=(hvd.Compression.fp16 if args.fp16_allreduce
                     else hvd.Compression.none))
    step = hvd.make_train_step(loss_fn, tx, mesh)
    params = hvd.broadcast_variables(variables["params"], mesh)
    state = hvd.broadcast_variables(tx.init(params), mesh)
    batch = jax.device_put((images, labels), NamedSharding(mesh, P("data")))
    def run(k):
        nonlocal params, state
        for _ in range(k):
            params, state, loss = step(params, state, batch)
        float(loss)  # the last step has finished before the clock stops

    print(f"Model: {args.model}\nBatch size: {args.batch_size}\n"
          f"Number of chips: {n}", flush=True)
    run(args.num_warmup_batches)
    img_secs = []
    for i in range(args.num_iters):
        t = timeit.timeit(lambda: run(args.num_batches_per_iter), number=1)
        img_secs.append(args.batch_size * args.num_batches_per_iter / t)
        print(f"Iter #{i}: {img_secs[-1]:.1f} img/sec per chip", flush=True)
    mean, conf = np.mean(img_secs), 1.96 * np.std(img_secs)
    print(f"Img/sec per chip: {mean:.1f} +-{conf:.1f}")
    print(f"Total img/sec on {n} chip(s): {n * mean:.1f} +-{n * conf:.1f}")


if __name__ == "__main__":
    main()
