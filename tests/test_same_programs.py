"""``tools/same_programs.py`` at tiny presets of three families: an edit
to one family's mixer shows in that family's program, and the other
families' programs, and every family's draw of the weights, are the
same."""

import os
import shutil
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOOL = os.path.join(ROOT, "tools", "same_programs.py")


def _run(other):
    return subprocess.run(
        [sys.executable, TOOL, other, "hybrid-tiny", "cca-tiny",
         "sambay-tiny"],
        capture_output=True, text=True, cwd=ROOT)


def test_an_edited_program_shows(tmp_path):
    shutil.copytree(os.path.join(ROOT, "polyrl_tpu"),
                    tmp_path / "polyrl_tpu",
                    ignore=shutil.ignore_patterns("__pycache__"))
    path = tmp_path / "polyrl_tpu" / "models" / "mixers" / "kda.py"
    text = path.read_text()
    # the KDA recurrence with a delta rule twice as strong
    old = "    u = beta[..., None] * (v - pred)\n"
    assert text.count(old) == 1
    path.write_text(text.replace(
        old, "    u = 2 * beta[..., None] * (v - pred)\n"))
    ran = _run(str(tmp_path))
    assert ran.returncode == 1, ran.stderr[-2000:]
    verdicts = dict(l.split(": ")[:2] for l in (
        x.rsplit(" ", 2)[0] + ": " for x in ran.stdout.strip().splitlines()))
    assert "DIFFERENT" in verdicts["hybrid-tiny step"]
    # (prefill runs the chunked form, which the edit leaves alone)
    assert "same" in verdicts["hybrid-tiny prefill"]
    assert "same" in verdicts["hybrid-tiny init"]
    for preset in ("cca-tiny", "sambay-tiny"):
        for program in ("step", "prefill", "init"):
            assert "same" in verdicts[f"{preset} {program}"]


def test_readable_shows_a_kernel_as_text_and_its_colours():
    """``readable`` (what ``--keep`` leaves to ``diff``): the GQA
    attention kernel lowered for a TPU reads as its Mosaic module's text
    without locations and a config that keeps the pools' HBM colours, with
    no base64 body."""
    import importlib.util

    import jax
    import jax.numpy as jnp

    from polyrl_tpu.ops import paged_attention as pa

    spec = importlib.util.spec_from_file_location("same_programs", TOOL)
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    arg = jax.ShapeDtypeStruct
    pool = arg((2, 9, 64, 128), jnp.bfloat16)

    text = jax.jit(lambda *a: pa.paged_attention_pallas(*a)).trace(
        arg((3, 4, 128), jnp.bfloat16), pool, pool,
        arg((3, 4), jnp.int32), arg((3,), jnp.int32)).lower(
            lowering_platforms=("tpu",)).as_text()
    assert "tpu.enqueue_dma" not in text
    got = tool.readable(text)
    assert 'input_memory_space_colors\\22: [{\\22operand_index\\22:4' in got
    assert "\\22body\\22: \\22<below>\\22" in got
    assert "tpu.enqueue_dma" in got and "tpu.matmul" in got
    assert "loc(" not in got
