"""``chip_smoke.py`` rehearsed on the CPU: the script the driver runs
on the chip must stay green end to end at a small size, must refuse to
report a chip run from a chip-less host, and must keep its own process
off jax (the server child is the one process that may hold the chip).
"""

import json
import os
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
SMALL = ["--rows", "20000", "--peers", "8", "--entities", "2000"]


def run_smoke(*extra, timeout=240):   # ~50 s alone, slower under xdist
    return subprocess.run(
        [sys.executable, str(ROOT / "chip_smoke.py"), *SMALL, *extra],
        cwd=ROOT, capture_output=True, text=True, timeout=timeout,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )


def test_cpu_rehearsal_is_green_and_says_cpu(native_lib):
    proc = run_smoke("--allow-cpu")
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    lines = proc.stdout.strip().splitlines()
    assert json.loads(lines[-1]) == {
        "ok": True,
        "device": {"platform": "cpu", "kind": "cpu", "count": 1},
    }
    assert any("missing 0, extra 0, delivered twice 0" in ln for ln in lines)
    assert any("native legs live" in ln for ln in lines)
    assert any("deltas refused 0, gaps 0" in ln for ln in lines)


def test_refuses_to_report_a_chip_run_from_a_cpu_host(native_lib):
    proc = run_smoke()
    assert proc.returncode != 0
    assert "platform 'cpu'" in proc.stderr
    assert '"ok"' not in proc.stdout


def test_parent_module_imports_without_jax():
    code = (
        "import sys, chip_smoke; "
        "assert 'jax' not in sys.modules, 'chip_smoke imported jax'"
    )
    subprocess.run([sys.executable, "-c", code], cwd=ROOT, check=True,
                   timeout=60)
