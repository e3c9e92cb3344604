"""Input-pipeline microbenchmark: real on-disk JPEG folder through
DatasetFolder + DataLoader, comparing native libjpeg decode
(runtime/cxx/image_ops.cpp) vs PIL, and in-process vs process workers
(shared-memory transport). Plus a synthetic INPUT-BOUND training
workload comparing the synchronous feed (host batch + per-step
float(loss)) against io.DeviceLoader + LossBuffer (async sharded
prefetch, batched loss syncs) — printed as one
{"metric": ...} JSON line.

Run:  JAX_PLATFORMS=cpu PYTHONPATH=. python examples/bench_dataloader.py
      (only the device-feed half: ... bench_dataloader.py --device-feed-only)

Representative result (this machine — ONE cpu core, so worker overlap
cannot exceed 1x; on a multi-core host the worker rows scale with cores):

    decode only : native 1713 imgs/s vs PIL 1126 imgs/s  -> 1.52x
    pipeline w0 : native  601 imgs/s vs PIL  361 imgs/s  -> 1.66x
    pipeline w2 : native  367 imgs/s (1-core worker overhead; see
                  docs/performance.md)
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import tempfile
import time

import numpy as np

import paddle_tpu  # noqa: F401  (registers runtime paths)
from paddle_tpu.io import DataLoader
from paddle_tpu.runtime import image as rimage
from paddle_tpu.vision import transforms as T
from paddle_tpu.vision.datasets import DatasetFolder, _load_image


def make_folder(root, n_per_class=64, size=224):
    from PIL import Image
    rng = np.random.RandomState(0)
    for cls in ("a", "b"):
        d = os.path.join(root, cls)
        os.makedirs(d, exist_ok=True)
        for i in range(n_per_class):
            arr = rng.randint(0, 255, (size, size, 3), dtype=np.uint8)
            Image.fromarray(arr).save(os.path.join(d, f"{i:03d}.jpg"),
                                      quality=90)


def pil_loader(path):
    from PIL import Image
    return np.asarray(Image.open(path).convert("RGB"))


def bench_decode(ds, label, n=128):
    t0 = time.perf_counter()
    for i in range(n):
        ds.loader(ds.samples[i % len(ds)][0])
    dt = time.perf_counter() - t0
    print(f"decode only [{label}]: {n / dt:7.0f} imgs/s")
    return n / dt


def bench_loader(ds, label, workers, epochs=2):
    loader = DataLoader(ds, batch_size=32, shuffle=False,
                        num_workers=workers)
    for _ in loader:        # warm (worker spin-up, first batches)
        break
    n = 0
    t0 = time.perf_counter()
    for _ in range(epochs):
        for imgs, labels in loader:
            n += imgs.shape[0]
    dt = time.perf_counter() - t0
    print(f"pipeline [{label}, workers={workers}]: {n / dt:7.0f} imgs/s")
    return n / dt


def bench_device_feed(steps=60, batch=64, dim=512, hidden=2048, classes=10,
                      io_wait_ms=7.0):
    """Synchronous feed vs DeviceLoader on an INPUT-BOUND synthetic
    workload. Each batch costs `io_wait_ms` of off-GIL input wait (the
    stand-in for disk reads, native libjpeg decode, shm transport from
    worker processes — everything a real pipeline waits on outside the
    interpreter) plus numpy assembly. The synchronous loop serializes
    that wait with the compiled step; DeviceLoader hides it behind step
    N's compute. Prints ONE JSON line.

    (On this CPU mesh the "device" step also burns host cores, so
    CPU-bound host transforms can't overlap — that half of the story
    only shows on a real chip; the I/O half shows anywhere.)"""
    import json

    import paddle_tpu as paddle
    from paddle_tpu.distributed import LossBuffer, build_mesh
    from paddle_tpu.distributed.trainer import Trainer
    from paddle_tpu.io import DeviceLoader

    build_mesh()
    paddle.seed(0)
    model = paddle.nn.Sequential(
        paddle.nn.Linear(dim, hidden), paddle.nn.ReLU(),
        paddle.nn.Linear(hidden, classes))
    model.train()
    opt = paddle.optimizer.SGD(learning_rate=0.01)

    def loss_fn(m, b):
        return paddle.nn.functional.cross_entropy(
            m(paddle.to_tensor(b["x"])), paddle.to_tensor(b["y"]))

    trainer = Trainer(model, opt, loss_fn)
    labels = (np.arange(batch) % classes).astype(np.int32)

    def gen(n, seed=0):
        rng = np.random.RandomState(seed)
        for _ in range(n):
            time.sleep(io_wait_ms / 1e3)   # off-GIL input wait
            x = rng.randn(batch, dim).astype(np.float32)
            yield {"x": x, "y": labels}

    float(trainer.step(next(gen(1))))    # compile outside both timed loops

    t0 = time.perf_counter()             # sync: host feed + per-step fetch
    for b in gen(steps):
        float(trainer.step(b))
    sync_sps = steps / (time.perf_counter() - t0)

    loader = DeviceLoader(gen(steps), depth=2)
    losses = LossBuffer(drain_every=steps)
    t0 = time.perf_counter()             # async: prefetch + batched syncs
    for b in loader:
        losses.append(trainer.step(b))
    losses.drain()
    async_sps = steps / (time.perf_counter() - t0)

    print(json.dumps({
        "metric": "input_bound_steps_per_sec",
        "value": round(async_sps, 2), "unit": "steps/s",
        "sync_steps_per_sec": round(sync_sps, 2),
        "speedup": round(async_sps / sync_sps, 2),
        "pipeline": loader.stats.snapshot()}), flush=True)
    return async_sps, sync_sps


def main():
    if "--device-feed-only" in sys.argv:
        bench_device_feed()
        return
    root = tempfile.mkdtemp(prefix="bench_imgs_")
    make_folder(root)
    print(f"native decoder available: {rimage.native_available()}")
    tf = T.Compose([T.Resize(160), T.CenterCrop(128),
                    T.Normalize(mean=[127.5] * 3, std=[127.5] * 3,
                                data_format="HWC")])
    native_ds = DatasetFolder(root, transform=tf)          # native default
    pil_ds = DatasetFolder(root, loader=pil_loader, transform=tf)

    r = {}
    r["dec_native"] = bench_decode(DatasetFolder(root), "native")
    r["dec_pil"] = bench_decode(DatasetFolder(root, loader=pil_loader), "PIL")
    print(f"native decode speedup: {r['dec_native'] / r['dec_pil']:.2f}x")
    for label, ds in (("native", native_ds), ("PIL", pil_ds)):
        for w in (0, 2):
            r[f"{label}_w{w}"] = bench_loader(ds, label, w)
    print(f"end-to-end native vs PIL (w0): "
          f"{r['native_w0'] / r['PIL_w0']:.2f}x")
    bench_device_feed()


if __name__ == "__main__":
    main()
