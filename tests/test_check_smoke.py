"""The CI bench gate itself: baseline trend tracking must pass on the
committed baseline and demonstrably fail on a synthetic regression, and
the routing floor must bite."""

import pathlib
import re
import sys

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

from benchmarks.check_smoke import (TRACKED, check_baseline,  # noqa: E402
                                    check_kernels, check_routing,
                                    derived_floats, parse_rows)

BASELINE_CSV = ROOT / "benchmarks" / "baselines.csv"

SYNTH = """name,us_per_call,derived
kv_paging/capacity,0.0,contig=4 paged=8 ratio=2.00x
kv_paging/lazy_capacity,0.0,upfront=8 lazy=12 ratio=1.50x identical=1
prefix_share/capacity,0.0,noshare=14 share=24 ratio=1.71x
prefix_share/identity,0.0,identical=1 reduction=0.450
routing/cost,0.0,ratio=0.400 identical=1
kernels/chunk_dispatch,0.0,direct=9 scatter=2 reduction=1.22x identical=1
cluster_sim/contention,0.0,ratio=1.429x base_s=140.0 des_s=200.0 wait_s=60.0
cluster_sim/frontier,0.0,points=12 front=8 saving=1.238x
"""


def _perturb(text: str, row: str, key: str, factor: float) -> str:
    """Scale one derived value of one row by ``factor``."""
    out = []
    for line in text.splitlines():
        if line.startswith(row + ","):
            m = re.search(rf"{key}=([-+0-9.eE]+)", line)
            val = float(m.group(1)) * factor
            line = (line[:m.start()] + f"{key}={val:.4f}"
                    + line[m.end():])
        out.append(line)
    return "\n".join(out)


def test_baseline_self_comparison_passes():
    rows = parse_rows(SYNTH)
    assert check_baseline(rows, rows) == []


def test_synthetic_25pct_regression_fails_each_tracked_row():
    base = parse_rows(SYNTH)
    for name, key, direction in TRACKED:
        factor = 0.75 if direction == "higher" else 1.25
        bad = parse_rows(_perturb(SYNTH, name, key, factor))
        fails = check_baseline(bad, base)
        assert fails and name in fails[0], (name, fails)


def test_15pct_drift_within_tolerance():
    base = parse_rows(SYNTH)
    for name, key, direction in TRACKED:
        factor = 0.85 if direction == "higher" else 1.15
        drift = parse_rows(_perturb(SYNTH, name, key, factor))
        assert check_baseline(drift, base) == [], name


def test_improvement_never_fails():
    base = parse_rows(SYNTH)
    for name, key, direction in TRACKED:
        factor = 2.0 if direction == "higher" else 0.5
        better = parse_rows(_perturb(SYNTH, name, key, factor))
        assert check_baseline(better, base) == [], name


def test_tracked_row_vanishing_fails():
    base = parse_rows(SYNTH)
    gone = [r for r in base if r[0] != "routing/cost"]
    fails = check_baseline(gone, base)
    assert any("routing/cost" in f and "missing" in f for f in fails)


def test_row_absent_from_baseline_is_skipped():
    """A newly-tracked metric must not fail until a baseline commits it."""
    base = [r for r in parse_rows(SYNTH) if r[0] != "routing/cost"]
    assert check_baseline(parse_rows(SYNTH), base) == []


def test_committed_baseline_is_complete_and_self_consistent():
    """The file CI compares against carries every TRACKED metric and
    passes against itself (a re-baseline can never break the gate)."""
    rows = parse_rows(BASELINE_CSV.read_text())
    by_name = {n: d for n, _, d in rows}
    for name, key, _ in TRACKED:
        assert name in by_name, f"baseline missing tracked row {name}"
        assert key in derived_floats(by_name[name]), (name, key)
    assert check_baseline(rows, rows) == []


def test_kernels_floor_bites():
    ok_rows = (
        "kernels/chunk/jnp,1300.0,tok_s=95000\n"
        "kernels/chunk/pallas,8400.0,tok_s=15000 speedup=0.16x interp=1\n"
        "kernels/decode/jnp,260.0,tok_s=7600\n"
        "kernels/decode/pallas,4500.0,tok_s=440 speedup=0.06x interp=1\n"
        "kernels/chunk_dispatch,0.0,direct=9 scatter=2 contig_ops=11 "
        "paged_ops=9 reduction=1.22x identical=1\n")
    assert check_kernels(parse_rows(ok_rows)) == []
    # interpret mode exempts the speedup floor; a real accelerator doesn't
    on_dev = ok_rows.replace("speedup=0.16x interp=1",
                             "speedup=0.16x interp=0")
    assert any("speedup" in f for f in check_kernels(parse_rows(on_dev)))
    fast_dev = ok_rows.replace("speedup=0.16x interp=1",
                               "speedup=2.40x interp=0")
    assert check_kernels(parse_rows(fast_dev)) == []
    slow = ok_rows.replace("tok_s=95000", "tok_s=4000")
    assert any("floor" in f for f in check_kernels(parse_rows(slow)))
    diverged = ok_rows.replace("identical=1", "identical=0")
    assert any("diverged" in f for f in check_kernels(parse_rows(diverged)))
    no_gain = ok_rows.replace("reduction=1.22x", "reduction=1.00x")
    assert any("reduction" in f for f in check_kernels(parse_rows(no_gain)))
    assert any("chunk_dispatch" in f
               for f in check_kernels(parse_rows(ok_rows.rsplit(
                   "kernels/chunk_dispatch", 1)[0])))


def test_routing_floor_bites():
    ok = parse_rows(
        "routing/cost,0.0,ratio=0.500 identical=1\n"
        "routing/placement_mix,0.0,short_picks_low=1 mixed_picks_high=1\n")
    assert check_routing(ok) == []
    slow = parse_rows(
        "routing/cost,0.0,ratio=0.900 identical=1\n"
        "routing/placement_mix,0.0,short_picks_low=1 mixed_picks_high=1\n")
    assert any("0.85" in f for f in check_routing(slow))
    diverged = parse_rows(
        "routing/cost,0.0,ratio=0.500 identical=0\n"
        "routing/placement_mix,0.0,short_picks_low=1 mixed_picks_high=1\n")
    assert any("diverged" in f for f in check_routing(diverged))
    wrong_mix = parse_rows(
        "routing/cost,0.0,ratio=0.500 identical=1\n"
        "routing/placement_mix,0.0,short_picks_low=0 mixed_picks_high=1\n")
    assert any("mix" in f for f in check_routing(wrong_mix))
    assert check_routing([]) == ["no routing/cost row found"]


def test_bench_run_exits_nonzero_when_a_suite_errors(monkeypatch, capsys):
    import types
    from benchmarks import run

    def boom(rows):
        raise RuntimeError("suite failed")
    monkeypatch.setitem(sys.modules, "benchmarks.bench_calibration",
                        types.SimpleNamespace(run=boom))
    monkeypatch.setattr(sys, "argv", ["run", "calibration"])
    assert run.main() == 1
    assert "calibration/ERROR" in capsys.readouterr().out
