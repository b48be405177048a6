"""How ``correct`` is decided: the served tokens of a sample of requests,
against the plain float32 reference run over each prompt with its served
tokens, after the window has closed and the program's state is freed.

The reference is the forward of the configuration's model family, the
module ``chipbench/families/<family>.py`` that the caller passes in.

Two numbers are compared, each against its own limit in the cell file.
A token's gap is how far the served token's reference logit lies below
the reference's best logit at that position, in standard deviations of
the reference's logits there. ``widest_gap_sd`` is the widest gap over
every compared token, ``mean_gap_sd`` their mean. Greedy decoding in the
served precision puts both near 0, the mean far below the widest (a
near-tie flips now and then); a token altered where it is produced puts
both near the spread of the whole row; a lower precision throughout
moves the mean a hundredfold.

With the control (``quant``), the lower-precision reference takes the
program's place: its own first tokens at the same positions are judged
by the same numbers and limits, and must come out not correct.
"""

from __future__ import annotations

from types import ModuleType
from typing import Dict, List, Optional

import numpy as np


def pick_sample(tracked: List, n: int, chunk: int, seed: int) -> List:
    """``n`` requests that were served at least one token, drawn from the
    seed: the one with the most served tokens, then one of each path the
    cell took (a KV cache migrated by a reclaim and decoded on, bucketed
    prefill, chunked prefill, shared-prefix suffix), then the rest at
    random."""
    served = [t for t in tracked if t.sreq is not None and t.sreq.generated]
    if not served:
        return []
    rng = np.random.default_rng(seed ^ 0x5A17)
    order = [served[i] for i in rng.permutation(len(served))]
    out = [max(order, key=lambda t: (len(t.sreq.generated),
                                     len(t.req.prompt)))]
    paths = [lambda t: 0 < t.tokens_at_reclaim < len(t.sreq.generated),
             lambda t: t.shared > 0,
             lambda t: t.shared == 0 and len(t.req.prompt) > chunk,
             lambda t: t.shared == 0 and len(t.req.prompt) <= chunk]
    for path in paths:
        hit = next((t for t in order if path(t) and t not in out), None)
        if hit is not None and len(out) < n:
            out.append(hit)
    for t in order:
        if len(out) >= n:
            break
        if t not in out:
            out.append(t)
    return out


NOT_FINITE = 1e9       # a NaN or infinite logit reads as this gap


def _widest(gaps: np.ndarray) -> float:
    if not np.all(np.isfinite(gaps)):
        return NOT_FINITE
    return float(np.max(gaps))


def compare(family: ModuleType, weights, dims, sample: List,
            quant: Optional[str] = None) -> Dict[str, float]:
    """Widest gap (and, with ``quant``, the control's widest gap) over
    every served token of the sample, by ``family``'s reference."""
    allg: List[np.ndarray] = []
    allc: List[np.ndarray] = []
    for t in sample:
        g = family.gaps(weights, dims, t.req.prompt.tolist(),
                        list(t.sreq.generated), quant)
        allg.append(g["gap_sd"])
        allc.append(g["control_gap_sd"])
    g = np.concatenate(allg) if allg else np.zeros(0)
    out = {"widest_gap_sd": _widest(g) if len(g) else 0.0,
           "mean_gap_sd": _mean(g), "compared_tokens": int(len(g)),
           "reference_argmax_agree": int(np.sum(g == 0))}
    if quant:
        c = np.concatenate(allc) if allc else np.zeros(0)
        out["control_widest_gap_sd"] = _widest(c) if len(c) else 0.0
        out["control_mean_gap_sd"] = _mean(c)
        out["control_argmax_agree"] = int(np.sum(c == 0))
    return out


LIMIT_KEYS = {"widest_gap_sd": "gap_limit_sd",
              "mean_gap_sd": "mean_gap_limit_sd"}


def judged(got: Dict[str, float], limits: Dict, control: bool
           ) -> Dict[str, Dict[str, float]]:
    """Each number compared beside its limit (from the cell file's
    ``check``): the program's numbers, or the control's in its place."""
    pre = "control_" if control else ""
    return {k: {"value": got[pre + k], "limit": float(limits[lk])}
            for k, lk in LIMIT_KEYS.items()}


def _mean(gaps: np.ndarray) -> float:
    if len(gaps) == 0:
        return 0.0
    if not np.all(np.isfinite(gaps)):
        return NOT_FINITE
    return float(np.mean(gaps))
