#!/usr/bin/env python
"""Compatibility shim (ISSUE 13): the mesh-axis lint now lives in
``dist_dqn_tpu/analysis/plugins/mesh_axis.py``, registered with
``scripts/dqnlint.py`` as the ``mesh-axis`` check. This entry point
keeps the original verdict contract — ``python scripts/check_mesh_axis.py``
prints ``check_mesh_axis: OK``/``FAIL`` with the same exit code — and
re-exports the historical module surface for external references.
"""
from __future__ import annotations

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from dist_dqn_tpu.analysis.plugins.mesh_axis import (AXIS_IN_CALL,  # noqa: F401,E402
                                                     RATIONALE,
                                                     SCAN_ROOTS, scan)
from dist_dqn_tpu.analysis.runner import legacy_main  # noqa: E402


def main() -> int:
    """The historical module-level entry point."""
    return legacy_main("mesh-axis", "check_mesh_axis")


if __name__ == "__main__":
    sys.exit(main())
