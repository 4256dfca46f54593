"""Records ``data/tiny_scoped_tpu.xplane.pb`` on a TPU, for
``test_xspans.py``: a jitted ``step`` whose scan body holds two named
scopes (``attn_core`` with a named Pallas kernel in it, ``mlp``) and a
jitted ``prefill_one`` with the scope ``mlp`` alone, run under the
``bench/window`` span; the main thread waits inside ``engine/sample_fetch``
and ``engine/idle`` annotations, a second thread inside ``engine/fetch``.
The Python tracer is off so that the file stays small. Run on the chip:
``python3 benchmark/tests/make_tiny_scoped_trace.py <out_dir>``."""

import glob
import os
import shutil
import sys
import threading
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(out_dir: str) -> None:
    import jax
    import jax.numpy as jnp

    from polyrl_tpu.ops.paged_attention import paged_attention_pallas

    assert jax.devices()[0].platform == "tpu"
    hkv, pages, page, d, s = 2, 8, 16, 128, 4
    pool = jnp.ones((hkv, pages, page, d), jnp.bfloat16)
    table = jnp.tile(jnp.arange(1, 5, dtype=jnp.int32), (s, 1))
    lens = jnp.full((s,), 3 * page, jnp.int32)

    def layer(x, _):
        with jax.named_scope("attn_core"):
            q = (x[:s * 4 * d].reshape(s, 4, d)).astype(jnp.bfloat16)
            o = paged_attention_pallas(q, pool, pool, table, lens)
            x = x + jnp.sum(o.astype(jnp.float32)) * 1e-6
        with jax.named_scope("mlp"):
            w = x.reshape(1024, 1024).astype(jnp.bfloat16)
            x = x + (w @ w).reshape(-1).astype(jnp.float32) * 1e-6
        return x, None

    def step(x):
        return jax.lax.scan(layer, x, None, length=2)[0]

    def prefill_one(x):
        with jax.named_scope("mlp"):
            w = x.reshape(1024, 1024).astype(jnp.bfloat16)
            return (w @ w).sum()

    step_j, prefill_j = jax.jit(step), jax.jit(prefill_one)
    x = jnp.ones((1024 * 1024,), jnp.float32)
    step_j(x).block_until_ready()
    prefill_j(x).block_until_ready()
    stop = threading.Event()

    def fetcher():
        while not stop.is_set():
            with jax.profiler.TraceAnnotation("engine/fetch"):
                time.sleep(0.001)
            time.sleep(0.001)

    thread = threading.Thread(target=fetcher, name="fetcher", daemon=True)
    thread.start()
    tmp = os.path.join(out_dir, "tmp_trace")
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(tmp, profiler_options=options)
    with jax.profiler.TraceAnnotation("bench/window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("engine/decode_dispatch_device"):
                y = step_j(x)
            with jax.profiler.TraceAnnotation("engine/sample_fetch"):
                y.block_until_ready()
            with jax.profiler.TraceAnnotation("engine/idle"):
                time.sleep(0.002)
        prefill_j(x).block_until_ready()
    jax.profiler.stop_trace()
    stop.set()
    thread.join(timeout=5.0)
    found = glob.glob(os.path.join(tmp, "plugins", "profile", "*",
                                   "*.xplane.pb"))[0]
    os.makedirs(out_dir, exist_ok=True)
    shutil.copy(found, os.path.join(out_dir, "tiny_scoped_tpu.xplane.pb"))
    shutil.rmtree(tmp)


if __name__ == "__main__":
    main(sys.argv[1])
