"""The dense decoder family with sliding-window attention, as
h2o-danube3's registry entry runs it: a query at position ``p`` sees the
keys at positions ``p - window + 1 .. p``. Only ``Dims`` differs from
``dense``: it carries the window and answers which keys a query sees;
the forward, the weights and the counts of work are ``dense``'s, which
ask it.

No cell uses this family. ``test_chipbench_families.py`` writes it as a
new file, ``chipbench/families/windowed.py``, into a copy of the
benchmark, to show that a family lands without an edit to a file the
benchmark has.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List

import jax

from chipbench.families import dense


@dataclasses.dataclass(frozen=True)
class Dims(dense.Dims):
    window: int = 0

    @classmethod
    def from_config(cls, c: Dict) -> "Dims":
        return dataclasses.replace(super().from_config(c),
                                   window=c["sliding_window"])

    @classmethod
    def from_program(cls, cfg) -> "Dims":
        return dataclasses.replace(super().from_program(cfg),
                                   window=cfg.swa_window)

    def visible(self, pos: jax.Array) -> jax.Array:
        back = pos[:, None] - pos[None, :]
        return (back >= 0) & (back < self.window)

    def keys_seen(self, q0: int, nq: int) -> float:
        return sum(min(p + 1, self.window) for p in range(q0, q0 + nq))

    def keys_read(self, q0: int, nq: int) -> int:
        return q0 + nq - max(0, q0 - self.window + 1)


def check_program(cfg, config: Dict) -> List[str]:
    differ = dense.check_program(cfg, config)
    if cfg.swa_window != config["sliding_window"]:
        differ.append("swa_window")
    return differ


make_weights = dense.make_weights
gaps = dense.gaps
kv_bytes_per_token = dense.kv_bytes_per_token
decode_attn = dense.decode_attn
prefill_attn = dense.prefill_attn
step_work = dense.step_work
