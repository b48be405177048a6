"""The four-chip reclaim cell, added as data, end to end at the reduced
configuration on four virtual CPU devices, with the reclaim at three
points of the window: the warm-up reaches every program the reclaim
dispatches (none compiles inside the window), the migrated requests are
attached from the store, and the served tokens match the reference
across the move."""

import pytest

import _paths  # noqa: F401
from _added import RECLAIM, RECLAIM_CELL, RECLAIM_METRICS, rehearse_added_cell


@pytest.mark.parametrize("frac", [0.2, 0.333, 0.6])
def test_four_chip_reclaim_cell_lands_as_data(tmp_path, frac):
    res, err = rehearse_added_cell(
        tmp_path, RECLAIM, {**RECLAIM_CELL, "reclaim_at_frac": frac},
        devices=4, per_layer=RECLAIM_METRICS, end_to_end=["ttft_p90_ms"])
    assert res["device"]["count"] == 4
    assert "reclaimed chip1" in err
    assert res["checks"]["window_compiles"]["value"] == 0, err[-3000:]
    assert res["checks"]["moved_without_token"]["value"] == 0
    assert res["correct"] is True, err[-3000:]
    assert res["metrics"]["kv_attach_pct.reclaim"]["value"] > 0
    assert res["metrics"]["migration_gap_p90_ms.reclaim"]["value"] > 0
