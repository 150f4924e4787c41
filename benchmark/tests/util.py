import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmark import harness  # noqa: E402


def tiny_cell(name: str, seed: int):
    """A cell at its rehearsal sizes, with its seeded deployment."""
    cell = harness.Cell(name, rehearsal=True)
    return cell, cell.deployment(seed)
