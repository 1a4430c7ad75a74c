"""The quickstart example must stay executable — it is the first thing
a new user runs (train -> checkpoint -> export -> serve -> query in one
file; docs/user_guide.md section 1)."""

import os
import pathlib
import subprocess
import sys
import pytest

REPO = pathlib.Path(__file__).parents[1]


def test_quickstart_end_to_end():
    env = dict(
        os.environ,
        PYTHONPATH=str(REPO),
    )
    env.pop("JAX_PLATFORMS", None)       # the script pins cpu itself
    env.pop("KFT_QUICKSTART_TPU", None)  # never grab a host's real chip
    proc = subprocess.run(
        [sys.executable, str(REPO / "examples" / "quickstart.py")],
        capture_output=True, text=True, timeout=280, env=env,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "quickstart OK" in proc.stdout
    # All four stages reported.
    for stage in ("[1]", "[2]", "[3]", "[4]"):
        assert stage in proc.stdout, proc.stdout


@pytest.mark.slow  # ~30s subprocess sweep of every parallelism family
def test_parallelism_tour_runs_every_family():
    """examples/parallelism.py: the SAME flagship model trains through
    dp/fsdp/tp/sp/ep/pp — the one-file proof of the mesh story the
    reference spread across three job kinds."""
    env = dict(os.environ, PYTHONPATH=str(REPO))
    env.pop("JAX_PLATFORMS", None)        # the script pins cpu itself
    env.pop("KFT_PARALLELISM_TPU", None)  # never grab a host's chip
    proc = subprocess.run(
        [sys.executable, str(REPO / "examples" / "parallelism.py")],
        capture_output=True, text=True, timeout=580, env=env,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "tour complete" in proc.stdout
    for family in ("data-parallel", "fsdp", "tensor-parallel",
                   "sequence-parallel", "expert-parallel",
                   "pipeline-parallel"):
        assert family in proc.stdout, proc.stdout
