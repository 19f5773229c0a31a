"""TF2 synthetic image benchmark — config-parity with the reference
``examples/tensorflow2_synthetic_benchmark.py`` (Keras applications model
on random data, ``DistributedGradientTape``, img/sec averaged over timed
iterations, optional fp16 compression and Adasum).

The recommended high-throughput path on TPU is the JAX compiled mode
(see ``examples/jax_resnet50_synthetic_benchmark.py``);
this script exists for reference-CLI parity and TF-binding validation.

Run:  python -m horovod_tpu.run -np 2 python \
          examples/tensorflow2_synthetic_benchmark.py --image-size 64
"""

import argparse
import timeit

import numpy as np
import tensorflow as tf

import horovod_tpu.tensorflow as hvd

parser = argparse.ArgumentParser(
    description="TensorFlow Synthetic Benchmark",
    formatter_class=argparse.ArgumentDefaultsHelpFormatter,
)
parser.add_argument("--fp16-allreduce", action="store_true", default=False,
                    help="use fp16 compression during allreduce")
parser.add_argument("--model", type=str, default="ResNet50",
                    help="model to benchmark (tf.keras.applications name)")
parser.add_argument("--batch-size", type=int, default=32,
                    help="input batch size")
parser.add_argument("--num-warmup-batches", type=int, default=10,
                    help="number of warm-up batches")
parser.add_argument("--num-batches-per-iter", type=int, default=10,
                    help="number of batches per benchmark iteration")
parser.add_argument("--num-iters", type=int, default=10,
                    help="number of benchmark iterations")
parser.add_argument("--use-adasum", action="store_true", default=False,
                    help="use the Adasum reducer")
parser.add_argument("--image-size", type=int, default=224,
                    help="synthetic image side (TPU-build extension for "
                         "quick smoke runs)")
args = parser.parse_args()

hvd.init()

data = tf.random.uniform([args.batch_size, args.image_size,
                          args.image_size, 3])
target = tf.random.uniform([args.batch_size, 1], minval=0, maxval=999,
                           dtype=tf.int64)

model = getattr(tf.keras.applications, args.model)(
    weights=None, input_shape=(args.image_size, args.image_size, 3)
)
opt = tf.keras.optimizers.SGD(learning_rate=0.01)
compression = (hvd.Compression.fp16 if args.fp16_allreduce
               else hvd.Compression.none)
loss_fn = tf.keras.losses.SparseCategoricalCrossentropy()


@tf.function
def benchmark_step(first_batch):
    with tf.GradientTape() as tape:
        probs = model(data, training=True)
        loss = loss_fn(target, probs)
    tape = hvd.DistributedGradientTape(
        tape, compression=compression,
        op=hvd.Adasum if args.use_adasum else hvd.Average,
    )
    grads = tape.gradient(loss, model.trainable_variables)
    opt.apply_gradients(zip(grads, model.trainable_variables))
    if first_batch:
        hvd.broadcast_variables(model.variables, root_rank=0)
        hvd.broadcast_variables(opt.variables, root_rank=0)


def log(s):
    if hvd.rank() == 0:
        print(s, flush=True)


log(f"Model: {args.model}")
log(f"Batch size: {args.batch_size}")
log(f"Number of workers: {hvd.size()}")

benchmark_step(first_batch=True)
for _ in range(args.num_warmup_batches - 1):
    benchmark_step(first_batch=False)

img_secs = []
for x in range(args.num_iters):
    time = timeit.timeit(lambda: benchmark_step(first_batch=False),
                         number=args.num_batches_per_iter)
    img_sec = args.batch_size * args.num_batches_per_iter / time
    log(f"Iter #{x}: {img_sec:.1f} img/sec per worker")
    img_secs.append(img_sec)

img_sec_mean = np.mean(img_secs)
img_sec_conf = 1.96 * np.std(img_secs)
log(f"Img/sec per worker: {img_sec_mean:.1f} +-{img_sec_conf:.1f}")
log(f"Total img/sec on {hvd.size()} worker(s): "
    f"{hvd.size() * img_sec_mean:.1f} +-{hvd.size() * img_sec_conf:.1f}")
