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
