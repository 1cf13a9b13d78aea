import copy
import os
import sys

import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def small(name, jobs=2, history=10):
    """A cell of BENCHMARK.json at a size a CPU test run can hold: the
    configuration's space and modes, with a short GPHP chain, 128 anchors,
    2 refinement steps, at most ``jobs`` jobs and a short history."""
    from bench import spec

    cell = spec.workload(name)
    cfg = copy.deepcopy(spec.config(cell["config"]))
    mix = copy.deepcopy(spec.traffic(cell["traffic"]))
    cfg["engine"]["slice_config"] = {"num_samples": 8, "burn_in": 4,
                                     "thin": 2}
    cfg["engine"]["acq"].update(num_anchors=128, refine_steps=2)
    cfg["jobs"] = min(cfg["jobs"], jobs)
    mix.update(history=history, row_cap=16, warmup_steps=1,
               trace_seconds=0.5)
    return cell, cfg, mix


@pytest.fixture
def small_cell():
    """``small`` as a fixture."""
    return small
