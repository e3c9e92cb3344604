"""Two-process CPU gloo A/B for the chunked collective-matmul overlap.

Launch under the PR-4 harness (one host, two ranks, gloo):

    python -m paddle_tpu.distributed.launch --nnodes 1 \
        --nproc_per_node 2 --cpu_devices_per_rank 1 \
        examples/bench_overlap_ab.py out.json

Rank 0 measures, on the REAL two-process mesh, the four wall clocks the
overlap story is made of:

  compute_ms  the row-parallel matmul alone (no collective)
  wire_ms     the bulk psum alone (same payload, gloo loopback)
  bulk_ms     matmul + bulk psum (impl="bulk" — the serialized twin)
  ring_ms     matmul + chunked ring (impl="ring", n_chunks tiles)

plus the per-permute dispatch floor (a tiny ppermute round), and banks
a JSON metric line with the two predictions bracketing them:
serial_pred = compute + wire (nothing hides, dispatch free) and
overlap_pred = the cost model's chunked-overlap leg at the same
n_chunks with the MEASURED per-chunk launch overhead.  The committed
line in docs/performance.md pins `closer_to == "overlap"`: the
measured chunked step sits strictly closer to the overlap-aware
prediction than to the serial sum — the chunked leg prices what the
decomposed schedule actually costs.  (On this harness the box has one
core and gloo dispatch costs milliseconds, so the ring pays its chunk
overhead without concurrent silicon to buy it back — the bulk twin
stays the faster CPU path, and the JSON records that honestly too.
The hiding itself is the TPU story the schedule manifest pins.)
"""
import json
import sys
import time

import numpy as np

import paddle_tpu.distributed as dist

dist.init_parallel_env()

import jax                                             # noqa: E402
import jax.numpy as jnp                                # noqa: E402
from jax.sharding import PartitionSpec as P            # noqa: E402

from paddle_tpu.cost_model import chunked_overlap_time  # noqa: E402
from paddle_tpu.distributed.mesh import build_mesh     # noqa: E402
from paddle_tpu.ops.overlap import (                    # noqa: E402
    chunked_matmul_all_reduce)

M, K_LOCAL, N = 128, 512, 4096      # per-device dot [M,K] @ [K,N]
N_CHUNKS = 4
WARMUP, ITERS = 3, 15


def _median_ms(fn, *args):
    for _ in range(WARMUP):
        jax.block_until_ready(fn(*args))
    times = []
    for _ in range(ITERS):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append((time.perf_counter() - t0) * 1e3)
    return float(np.median(times))


def main():
    out_path = sys.argv[1] if len(sys.argv) > 1 else None
    p = jax.device_count()
    mesh = build_mesh(tp=p)
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(M, p * K_LOCAL) * 0.1, jnp.float32)
    w = jnp.asarray(rng.randn(p * K_LOCAL, N) * 0.1, jnp.float32)
    y = jnp.asarray(rng.randn(M, N) * 0.1, jnp.float32)

    def sm(body, n_in):
        return jax.jit(jax.shard_map(
            body, mesh=mesh,
            in_specs=(P(None, "tp"), P("tp", None))[:n_in] or (P(),),
            out_specs=P(), axis_names={"tp"}, check_vma=False))

    compute = sm(lambda xs, ws: xs @ ws, 2)
    wire = jax.jit(jax.shard_map(
        lambda ys: jax.lax.psum(ys, "tp"), mesh=mesh, in_specs=(P(),),
        out_specs=P(), axis_names={"tp"}, check_vma=False))
    bulk = sm(lambda xs, ws: chunked_matmul_all_reduce(
        xs, ws, "tp", impl="bulk"), 2)
    ring = sm(lambda xs, ws: chunked_matmul_all_reduce(
        xs, ws, "tp", n_chunks=N_CHUNKS, impl="ring"), 2)
    # per-permute dispatch floor: one tiny single-hop round on the
    # same gloo wire — the measured value of the cost model's
    # CHUNK_LAUNCH_OVERHEAD_S knob on this transport
    tiny = jax.jit(jax.shard_map(
        lambda v: jax.lax.ppermute(
            v, "tp", [(i, (i + 1) % p) for i in range(p)]),
        mesh=mesh, in_specs=(P(),), out_specs=P(None), axis_names={"tp"},
        check_vma=False))

    # twin discipline holds over the real gloo wire too
    assert np.asarray(ring(x, w)).tobytes() == \
        np.asarray(bulk(x, w)).tobytes(), "ring != bulk over gloo"

    compute_ms = _median_ms(compute, x, w)
    wire_ms = _median_ms(wire, y)
    bulk_ms = _median_ms(bulk, x, w)
    ring_ms = _median_ms(ring, x, w)
    permute_ms = _median_ms(tiny, jnp.zeros((8,), jnp.float32))

    if jax.process_index() != 0:
        return
    serial_pred = compute_ms + wire_ms
    # divisible-path ring at p participants: p-1 reduce-scatter hops +
    # p-1 all-gather hops per chunk
    chunk_overhead_ms = 2 * (p - 1) * permute_ms
    ct = chunked_overlap_time(compute_ms / 1e3, wire_ms / 1e3,
                              n_chunks=N_CHUNKS,
                              launch_overhead_s=chunk_overhead_ms / 1e3)
    overlap_pred = ct.step_s * 1e3
    closer = ("overlap"
              if abs(ring_ms - overlap_pred) < abs(ring_ms - serial_pred)
              else "serial")
    metric = {
        "bench": "overlap_ab_two_process_gloo",
        "mesh": {"processes": jax.process_count(), "tp": p},
        "shape": {"m": M, "k_local": K_LOCAL, "n": N,
                  "dtype": "float32", "n_chunks": N_CHUNKS},
        "compute_ms": round(compute_ms, 3),
        "wire_ms": round(wire_ms, 3),
        "permute_dispatch_ms": round(permute_ms, 3),
        "bulk_ms": round(bulk_ms, 3),
        "ring_ms": round(ring_ms, 3),
        "serial_pred_ms": round(serial_pred, 3),
        "overlap_pred_ms": round(overlap_pred, 3),
        "closer_to": closer,
    }
    line = json.dumps(metric, sort_keys=True)
    print(line)
    if out_path:
        with open(out_path, "w") as f:
            f.write(line + "\n")


if __name__ == "__main__":
    main()
