"""Tests of the benchmark itself, run by path:

    JAX_PLATFORMS=cpu python -m pytest -q benchmarks/chip/tests
"""
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(HERE), str(HERE.parents[1] / "src")]

PENDING = Path(__file__).resolve().parent / "pending.json"


def with_pending(bench):
    """``bench`` with the cells that are built but not yet admitted
    (``pending.json``), so that their files are tested all the same."""
    import json
    pending = json.loads(PENDING.read_text())
    return {k: v + pending.get(k, []) if isinstance(v, list) and k in pending
            else v for k, v in bench.items()}
