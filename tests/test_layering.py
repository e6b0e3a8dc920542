"""The package graph points one way: ``ops/``, ``models/`` and
``storage/`` sit under ``workflow/`` and import nothing from it (the
chaos harness they fire sites through is the leaf
``predictionio_tpu/faults.py``)."""

from __future__ import annotations

import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]


@pytest.mark.parametrize("module", [
    "ops.retrieval", "ops.pipeline", "ops.ann", "models.als",
    "storage.journal", "storage.backup",
])
def test_lower_layer_imports_nothing_from_workflow(module):
    """In a fresh interpreter, importing the module alone leaves no
    ``predictionio_tpu.workflow*`` module loaded: the top-k kernel does
    not pull in the train, deploy and streaming stack."""
    code = (
        "import sys\n"
        f"import predictionio_tpu.{module}\n"
        "up = sorted(m for m in sys.modules\n"
        "            if m.startswith('predictionio_tpu.workflow'))\n"
        "assert not up, up\n"
        "print('ok')\n")
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=120, cwd=REPO)
    assert p.returncode == 0 and p.stdout.strip() == "ok", p.stderr[-2000:]
