"""``chip_smoke.py``'s phases, run small on the CPU backend.

The script itself refuses to run without a TPU; its phase functions
take sizes, so the same code paths (run_cells_batched with a spawned
msa worker, run_fifo_batch cold and warm, the oracle diffs) are checked
here at a size the CPU runs in seconds.
"""

from __future__ import annotations

import importlib.util
from pathlib import Path

import pytest

pytest.importorskip("jax")

SCRIPT = Path(__file__).resolve().parents[1] / "chip_smoke.py"


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_registered_phase(smoke):
    info = smoke.phase_registered(seeds=range(2), oracle_seeds=(0, 1),
                                  quick=True)
    assert info["cells"] == 7 * 2 + 1
    assert info["max_abs_diff"] <= smoke.TOL
    assert set(info["batches"]) == {"dense_dp", "dsv3_ep64", "fb_shuffle",
                                    "mixed", "mixed_oversub_3to1", "moe_ep",
                                    "pipe_serve"}
    assert all(b["events"] > 0 for b in info["batches"].values())


def test_cluster_phase(smoke):
    info = smoke.phase_cluster(n_jobs=10, n_lanes=2)
    assert info["max_abs_diff"] <= smoke.TOL
    assert info["J"] == 10 and info["events"] > 0


def test_diff_rejects_a_miss(smoke):
    ref = {"j0": 1.0}
    with pytest.raises(AssertionError, match="ΔJCT/CCT"):
        smoke._diff({"j0": 1.0 + 1e-3}, ref, ref, ref, "lane")
    with pytest.raises(AssertionError, match="job set"):
        smoke._diff({"j1": 1.0}, ref, ref, ref, "lane")
