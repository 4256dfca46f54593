"""``correct`` has to be able to come out false. Two walks of a whole run
at the rehearsal size on the CPU (so the look for a chip is skipped, and
nothing else), each in a process of its own with one thing changed under
the harness:

- the timed path broken where it is produced: the engine's paged attention
  leaves the newest token of every context out;
- the control: the engine serves in bfloat16 what the rehearsal's
  configuration states as float32 (the nearest precision below), against
  the float32 reference and the float32 limits.

Both have to print ``rehearsal: failed`` through the reference's
comparison alone: every request still succeeds."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

BROKEN_ATTENTION = """
import jax.numpy as jnp
import polyrl_tpu.ops.paged_attention as pa
real = pa.paged_attention
def short_sighted(q, k_pool, v_pool, page_table, seq_lens, scale=None):
    return real(q, k_pool, v_pool, page_table, jnp.maximum(seq_lens - 1, 1),
                scale)
pa.paged_attention = short_sighted
"""

BFLOAT16_ENGINE = """
from benchmark.lib import harness
real = harness.rehearsal
def in_bfloat16(config, mix):
    tiny, small = real(config, mix)
    return dict(tiny, dtype="bfloat16"), small
harness.rehearsal = in_bfloat16
"""

SOUND = ""


def walk(patch: str, cell: str):
    code = (f"import sys\nsys.path.insert(0, {ROOT!r})\n{patch}\n"
            "from benchmark import run\n"
            f"sys.exit(run.main(['--workload', {cell!r}, '--seed', '77', "
            "'--seconds', '0.5', '--trace', '0', '--rehearse-cpu']))\n")
    p = subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                       env=dict(os.environ, JAX_PLATFORMS="cpu"),
                       capture_output=True, text=True, timeout=600)
    return p.returncode, json.loads(p.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("patch, passes", [
    (SOUND, True), (BROKEN_ATTENTION, False), (BFLOAT16_ENGINE, False)],
    ids=["sound", "attention-drops-a-token", "bfloat16-for-float32"])
def test_the_reference_tells_a_broken_or_coarser_engine(patch, passes):
    rc, line = walk(patch, "qwen2.5-7b.rollout-short")
    ref = line["checks"]["reference"]
    assert line["failed"] == 0 and line["checks"]["admitted"] == 5
    assert (rc == 0) == passes
    assert line["rehearsal"] == ("passed" if passes else "failed")
    assert ref["ok"] == passes and ref["positions"] == 32
    # with room on both sides of the limits (1e-5 and 5e-5 nats): sound
    # walks read 1e-7 and 5e-7, bfloat16 5e-4 and 2e-3, a dropped token more
    if passes:
        assert ref["logprob_mean_abs_diff"] < 1e-6
        assert ref["logprob_max_abs_diff"] < 5e-6
    else:
        assert ref["logprob_mean_abs_diff"] > 1e-4
        assert ref["logprob_max_abs_diff"] > 5e-4
