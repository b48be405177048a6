"""The readers of the program's request stamps: known answers on made-up
requests, nothing to read from a program without the stamps, and both
metrics present and positive in the chat cell's CPU rehearsal."""

import types

import _paths  # noqa: F401
from _added import last_json

import pytest

from chipbench import run
from chipbench.readings import Reading, reader
from chipbench.serve import Tracked
from chipbench.traffic import Req

MS = 1_000_000


def _tracked(arrival_s, **stamps):
    t = Tracked(Req(arrival_s=arrival_s, prompt=None, max_new=1))
    t.sreq = types.SimpleNamespace(**stamps)
    return t


def _reading(tracked, window_s=10.0):
    return Reading(dims=None, family=None, peaks={}, max_batch=1,
                   window_s=window_s, steps=[], tracked=tracked, counters={})


def test_stamp_readers_known_answers():
    base = 10**18
    tracked = [_tracked(1.0 * i, submit_ns=base, admit_ns=base + i * MS,
                        first_token_ns=base + i * MS + 100 * i * MS)
               for i in range(11)]                  # 0..10 ms, 0..1000 ms
    tracked.append(_tracked(-1.0, submit_ns=base, admit_ns=base + 999 * MS,
                            first_token_ns=base + 10**6 * MS))   # pre-roll
    tracked.append(_tracked(12.0, submit_ns=base, admit_ns=base + 999 * MS,
                            first_token_ns=base + 10**6 * MS))   # after
    tracked.append(_tracked(2.5, submit_ns=base, admit_ns=0,
                            first_token_ns=0))      # not yet admitted
    r = _reading(tracked, window_s=11.0)
    assert reader("admit_wait_p90_ms.chat")(r) == pytest.approx(9.0)
    assert reader("prefill_latency_p90_ms.chat")(r) == pytest.approx(900.0)


def test_stamp_readers_read_nothing_without_stamps():
    """A program that sets no stamps (its requests lack the attributes)
    leaves both metrics out of the result line."""
    tracked = [_tracked(0.5), _tracked(1.5)]
    tracked.append(Tracked(Req(arrival_s=2.0, prompt=None, max_new=1)))
    r = _reading(tracked)
    for name in ("admit_wait_p90_ms.chat", "prefill_latency_p90_ms.chat"):
        assert reader(name)(r) is None


def test_chat_rehearsal_reports_the_stamp_metrics(capsys):
    rc = run.main(["--workload", "internlm2-chat", "--seed", str(2 ** 35 + 3),
                   "--seconds", "2", "--trace", "1"], rehearse=True)
    res = last_json(capsys.readouterr().out)
    assert rc == 0 and res["correct"] is True
    for name in ("admit_wait_p90_ms.chat", "prefill_latency_p90_ms.chat"):
        assert res["metrics"][name]["value"] > 0, name
        assert res["metrics"][name]["unit"] == "ms"
