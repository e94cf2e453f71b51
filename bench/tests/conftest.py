import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

DATA = Path(__file__).resolve().parent / "data"


def tiny_cell(name="tiny", loop="sweep"):
    """(cell, config, mix, metrics) of the test-only tiny fleet, run
    by the harness on the CPU."""
    config = json.loads((DATA / "tiny-fleet.json").read_text())
    mix = json.loads((DATA / "tiny.json").read_text())
    mix["loop"] = loop
    cell = {"name": name, "config": "tiny-fleet", "traffic": "tiny",
            "chips": 1}
    metrics = {"end_to_end": [{"name": "answers_per_s",
                               "unit": "answers/s"},
                              {"name": "setup_s", "unit": "s"}],
               "per_layer": []}
    return cell, config, mix, metrics


def run_tiny(seed, *, control=False, seconds=1.0, loop="sweep"):
    import time

    from bench import cell
    return cell.run("tiny", seed, seconds, False, t_start=time.time(),
                    require_tpu=False, control=control, cache=False,
                    cell_data=tiny_cell(loop=loop))


@pytest.fixture
def tiny():
    return run_tiny
