"""The benchmark's bucketed cells, replayed from their traffic files by
``run-scripts/replay_pad_buckets.py``: what the table rule gives them."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# cell -> (buckets its configuration asks for, the worst-case bucket as it has
# been since the cell was added (MACE's since PR 42 sized it at batch 4), the
# padded share ISSUE 41 holds the table to (MACE's: PR 42's replay, 35.991)
CELLS = {
    "mace_mlip_mptrj.fill": (3, [1784, 113792, 5, 0], 38.0),
    "egnn_mlip_mptrj.fill": (4, [7112, 227456, 17, 0], 19.0),
    "schnet_mlip_oc20.fill": (3, [4504, 225024, 21, 0], 13.0),
    "dimenetpp_mlip_oc20.fill": (2, [456, 22528, 3, 1126400], 36.0),
    "gps_egnn_mlip_oc20.fill": (2, [1808, 21632, 9, 0], 27.0),  # PR 46's replay, 25.536
}


@pytest.fixture(scope="module")
def replayed():
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "run-scripts", "replay_pad_buckets.py")],
        capture_output=True, text=True, check=True, cwd=ROOT).stdout
    return json.loads(out.strip().splitlines()[-1])


def test_the_replay_finds_the_bucketed_cells_and_no_other(replayed):
    assert set(replayed) == set(CELLS)  # PaiNN's configuration sets no buckets


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_a_cells_table_keeps_its_length_and_worst_case_and_pads_less(replayed, cell):
    n_buckets, worst, share = CELLS[cell]
    got = replayed[cell]
    assert len(got["table"]) == n_buckets and got["table"][-1] == worst
    edges = [row[1] for row in got["table"]]
    assert edges == sorted(set(edges))
    assert got["padded_edge_share"] <= share
    slots = sum(e * n for e, n in zip(edges, got["steps"])) / sum(got["steps"])
    assert slots == pytest.approx(got["mean_edge_slots"])
    # the worst case is the rare step now, not a fifth of them
    assert got["steps"][-1] <= 0.15 * sum(got["steps"])
