"""The migration layer's readers and the reclaim warm-up's block counts
on made-up requests, and ``correct`` coming out false on the reclaim
cell's CPU rehearsal when the exchange between chips is left out: the
requests the reclaim moves never reach the replicas that survive it."""

import types

import numpy as np
import pytest

import _paths  # noqa: F401
from _added import RECLAIM, RECLAIM_CELL, rehearse_added_cell

from chipbench.readings import Reading, reader
from chipbench.serve import Tracked, migration_block_counts
from chipbench.traffic import Req


def _tracked(migrations, stamps):
    t = Tracked(Req(arrival_s=0.0, prompt=None, max_new=1))
    t.sreq = types.SimpleNamespace(migrations=migrations)
    t.stamps = list(stamps)
    return t


def _reading(tracked, kv_imports):
    return Reading(dims=None, family=None, peaks={}, max_batch=1,
                   window_s=10.0, steps=[], tracked=tracked,
                   counters={"kv_imports": float(kv_imports)})


def test_migration_readers_known_answers():
    moved = [_tracked(1, [0.0, 0.1, 0.1 + 0.1 * (i + 1)])
             for i in range(11)]          # longest gaps 0.1, 0.2 .. 1.1 s
    stayed = [_tracked(0, [0.0, 5.0]), _tracked(0, [])]
    unsent = Tracked(Req(arrival_s=1.0, prompt=None, max_new=1))
    r = _reading(moved + stayed + [unsent], kv_imports=8)
    assert reader("migration_gap_p90_ms.reclaim")(r) == pytest.approx(1000.0)
    assert reader("kv_attach_pct.reclaim")(r) == pytest.approx(800 / 11)


def test_migration_readers_read_nothing_without_a_move():
    r = _reading([_tracked(0, [0.0, 0.5]), _tracked(0, [])], kv_imports=0)
    for name in ("migration_gap_p90_ms.reclaim", "kv_attach_pct.reclaim"):
        assert reader(name)(r) is None


def test_block_counts_of_a_reclaim():
    def req(arrival_s, prompt, max_new):
        return Req(arrival_s=arrival_s, prompt=np.zeros(prompt, np.int32),
                   max_new=max_new)
    reqs = [req(0.0, 16, 3),       # holds 16-17 tokens: blocks 1-2
            req(1.0, 40, 40),      # 40-78 tokens: blocks 3-5
            req(-2.0, 100, 1),     # done at its first token: never live
            req(5.0, 300, 10)]     # arrives after the reclaim
    assert migration_block_counts(reqs, 5.0, 16, 4096) == [1, 2, 3, 4, 5]
    assert migration_block_counts(reqs, 5.0, 16, 64) == [1, 2, 3, 4]


def test_handover_dropped_is_not_correct(tmp_path):
    res, err = rehearse_added_cell(
        tmp_path, RECLAIM, RECLAIM_CELL, devices=4,
        args=("--seconds", "3", "--trace", "1", "--fault",
              "handover_dropped"))
    assert "reclaimed chip1" in err
    assert res["checks"]["window_compiles"]["value"] == 0
    assert res["checks"]["moved_without_token"]["value"] > 0
    assert res["correct"] is False
