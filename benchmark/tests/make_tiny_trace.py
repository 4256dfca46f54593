"""Records ``data/tiny_tpu.xplane.pb`` on a TPU: a few matmuls under the
``bench/window`` span with a host sleep between them. Run on the chip:
``python3 benchmark/tests/make_tiny_trace.py <out_dir>``."""

import glob
import os
import shutil
import sys
import time


def main(out_dir: str) -> None:
    import jax
    import jax.numpy as jnp

    assert jax.devices()[0].platform == "tpu"
    step = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((1024, 1024), jnp.bfloat16)
    step(x).block_until_ready()
    tmp = os.path.join(out_dir, "tmp_trace")
    jax.profiler.start_trace(tmp)
    with jax.profiler.TraceAnnotation("bench/window"):
        for _ in range(3):
            step(x).block_until_ready()
            time.sleep(0.002)
    jax.profiler.stop_trace()
    found = glob.glob(os.path.join(tmp, "plugins", "profile", "*",
                                   "*.xplane.pb"))[0]
    shutil.copy(found, os.path.join(out_dir, "tiny_tpu.xplane.pb"))
    shutil.rmtree(tmp)


if __name__ == "__main__":
    main(sys.argv[1])
