"""Records ``data/tiny_account_tpu.xplane.pb`` on a TPU, for
``test_account.py``: what ``make_tiny_scoped_trace.py`` records (left as
it is: its file is what a program from before this account looks like)
and what ``lib/account.py`` partitions besides. A jitted ``step`` whose
scan body holds ``attn_core``, ``mlp`` with ``mlp_dense`` inside it (the
innermost wins) and one operation under NO scope (a cumulative sum, which
fuses with nothing); a second module, ``jit_upload``, run between two
steps (busy time outside the decode programs); and, from a second thread,
the engine's own stamps: a real ``EngineLoopProfiler`` is told of each
dispatch and lands it after the fetch, so every ``engine/landed`` event
carries the statistics the program writes. The Python tracer is off so
that the file stays small. (As recorded on a v5e, PR 53: five of the six
programs lie wholly inside the window on the device's clock; the ``tanh``
under ``attn_core`` fused into a neighbour, a fusion being its root's.)
Run on the chip:
``python3 benchmark/tests/make_tiny_account_trace.py <out_dir>``."""

import glob
import os
import queue
import shutil
import sys
import threading
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

STEPS_A_PROGRAM = 2


def main(out_dir: str) -> None:
    import jax
    import jax.numpy as jnp

    from polyrl_tpu.obs.engine_profile import EngineLoopProfiler

    assert jax.devices()[0].platform == "tpu"

    def layer(x, _):
        with jax.named_scope("attn_core"):
            x = x + jnp.tanh(x) * 1e-6
        with jax.named_scope("mlp"):
            with jax.named_scope("mlp_dense"):
                w = x.reshape(1024, 1024).astype(jnp.bfloat16)
                y = (w @ w).reshape(-1).astype(jnp.float32)
            x = x + y * 1e-6
        return x + jnp.cumsum(x) * 1e-9, None     # under no scope

    def step(x):
        return jax.lax.scan(layer, x, None, length=STEPS_A_PROGRAM)[0]

    def upload(x):
        return jnp.sort(x)[::2]

    step_j, upload_j = jax.jit(step), jax.jit(upload)
    x = jnp.ones((1024 * 1024,), jnp.float32)
    step_j(x).block_until_ready()
    upload_j(x).block_until_ready()
    prof = EngineLoopProfiler()
    todo: queue.Queue = queue.Queue()

    def fetcher():
        while (y := todo.get()) is not None:
            with prof.fetch():
                y.block_until_ready()
            prof.on_landed(1)

    thread = threading.Thread(target=fetcher, name="fetcher", daemon=True)
    thread.start()
    tmp = os.path.join(out_dir, "tmp_trace")
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(tmp, profiler_options=options)
    with jax.profiler.TraceAnnotation("bench/window"):
        y = x
        for i in range(6):
            with prof.phase("decode_dispatch_device"):
                prof.on_dispatch("step", steps=STEPS_A_PROGRAM, rows=4)
                y = step_j(y)
            todo.put(y)
            if i == 2:
                upload_j(y)
            with prof.phase("idle"):
                time.sleep(0.002)
        todo.put(None)
        thread.join(timeout=30.0)
    jax.profiler.stop_trace()
    found = glob.glob(os.path.join(tmp, "plugins", "profile", "*",
                                   "*.xplane.pb"))[0]
    os.makedirs(out_dir, exist_ok=True)
    shutil.copy(found, os.path.join(out_dir, "tiny_account_tpu.xplane.pb"))
    shutil.rmtree(tmp)


if __name__ == "__main__":
    main(sys.argv[1])
