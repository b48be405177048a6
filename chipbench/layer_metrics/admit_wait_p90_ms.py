"""Scheduler: 90th percentile, over the requests that arrived in the
window, of the program's stamps ``admit_ns - submit_ns``: from
``GlobalServer.submit`` to the ``Engine.admit_many`` that took the
request into a bucketed prefill or a chunk group."""

from chipbench.stamps import gaps_ms
from chipbench.stats import percentile


def read(r):
    return percentile(gaps_ms(r.tracked, r.window_s, "submit_ns",
                              "admit_ns"), 90)
