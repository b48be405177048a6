"""Model step: 90th percentile, over the requests that arrived in the
window, of the program's stamps ``first_token_ns - admit_ns``: from the
admission that took the request to its first token on the host, through
every chunk of a chunked prompt and the decode steps between them."""

from chipbench.stamps import gaps_ms
from chipbench.stats import percentile


def read(r):
    return percentile(gaps_ms(r.tracked, r.window_s, "admit_ns",
                              "first_token_ns"), 90)
