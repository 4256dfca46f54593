"""Microbenchmark: per-ibatch multihost broadcast — generic pickled-object
path vs the raw-bytes batch fast path (parallel/multihost.py).

Two jax.distributed CPU processes broadcast a realistic ibatch (int32
token tensors + f32 masks + object-dtype non-tensors) both ways and print
median seconds per broadcast. Run:

    python tools/bench_broadcast.py            # parent: spawns 2 workers
"""

from __future__ import annotations

import os
import socket
import subprocess
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

ROWS = int(os.environ.get("BCAST_ROWS", "512"))
SEQ = int(os.environ.get("BCAST_SEQ", "4096"))
REPS = int(os.environ.get("BCAST_REPS", "20"))


def worker(coord: str, pid: int) -> None:
    import jax

    jax.distributed.initialize(coord, num_processes=2, process_id=pid)
    import numpy as np

    from polyrl_tpu.data.batch import TensorBatch
    from polyrl_tpu.parallel import multihost

    rng = np.random.default_rng(0)
    tb = TensorBatch(
        tensors={
            "input_ids": rng.integers(0, 150000, (ROWS, SEQ)).astype(np.int32),
            "responses": rng.integers(0, 150000, (ROWS, SEQ // 4)).astype(np.int32),
            "response_mask": np.ones((ROWS, SEQ // 4), np.float32),
            "old_log_probs": rng.normal(size=(ROWS, SEQ // 4)).astype(np.float32),
        },
        non_tensors={"ground_truth": np.array(["42"] * ROWS, object)},
        meta_info={"step": 1},
    )
    nbytes = sum(v.nbytes for v in tb.tensors.values())

    def timed(fn) -> float:
        ts = []
        for _ in range(REPS):
            t0 = time.monotonic()
            fn()
            ts.append(time.monotonic() - t0)
        ts.sort()
        return ts[len(ts) // 2]

    main = multihost.is_main()
    obj_s = timed(lambda: multihost.broadcast_obj(
        ("batch", tb) if main else None))
    raw_s = timed(lambda: multihost.broadcast_batch(
        ("batch", tb) if main else None))
    if main:
        print(f"ibatch {nbytes / 1e6:.1f} MB tensors x{REPS}: "
              f"pickled-object {obj_s * 1e3:.1f} ms/bcast, "
              f"raw-bytes {raw_s * 1e3:.1f} ms/bcast, "
              f"speedup {obj_s / max(raw_s, 1e-9):.2f}x", flush=True)


def main() -> None:
    if len(sys.argv) > 1:
        worker(sys.argv[1], int(sys.argv[2]))
        return
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    procs = [subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), f"127.0.0.1:{port}",
         str(pid)], env=env) for pid in (0, 1)]
    try:
        rc = [p.wait(timeout=900) for p in procs]
    except subprocess.TimeoutExpired:
        # one worker dying leaves its peer blocked in the collective — kill
        # both so no jax process outlives the bench (single-core VM rule)
        for p in procs:
            p.kill()
        for p in procs:
            p.wait()
        sys.exit(1)
    sys.exit(1 if any(rc) else 0)  # negative rc = signal-killed worker


if __name__ == "__main__":
    main()
