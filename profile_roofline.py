"""Shim: the roofline probe lives in `automerge_tpu.perf.roofline` (run
`python -m automerge_tpu.perf roofline`; this script stays for muscle
memory). Flags, the `--interpret-smoke` contract pinned by
tests/test_roofline_smoke.py and the ROOFLINE.json output are the
module's."""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)) or ".")

from automerge_tpu.perf.roofline import main  # noqa: E402

if __name__ == "__main__":
    main()
