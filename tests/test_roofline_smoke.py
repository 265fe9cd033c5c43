"""profile_roofline.py's plumbing (row-buffer build, chained kernel jit,
readback) pinned via the --interpret-smoke flag, so a latent bug does not
wait for a chip run to show. The smoke fails loudly if any probe is
skipped; without a chip and without the flag the probe exits non-zero."""

import json
import os
import subprocess
import sys


def test_roofline_interpret_smoke_runs_clean():
    out = subprocess.run(
        [sys.executable,
         os.path.join(os.path.dirname(os.path.dirname(
             os.path.abspath(__file__))), "profile_roofline.py"),
         "--interpret-smoke"],
        capture_output=True, text=True, timeout=540)
    assert out.returncode == 0, out.stderr[-800:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["smoke"] is True and rec["backend"] == "cpu"
    assert len(rec["probes"]) == 2
    assert all("skipped" not in p for p in rec["probes"])


def test_roofline_without_a_chip_exits_nonzero():
    out = subprocess.run(
        [sys.executable, "-m", "automerge_tpu.perf", "roofline"],
        capture_output=True, text=True, timeout=300,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode != 0
    assert "needs the TPU" in out.stderr
    assert not out.stdout.strip(), "no result may be printed without a chip"


def test_hbm_peak_is_keyed_by_device_kind():
    import pytest

    from automerge_tpu.perf import roofline
    assert roofline.hbm_peak_gb_s("TPU v5 lite") == 819
    with pytest.raises(LookupError, match="no HBM peak on record"):
        roofline.hbm_peak_gb_s("TPU v9 imaginary")
