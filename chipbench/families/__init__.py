"""Model families: ``chipbench/families/<family>.py``, one module for
each, named by a configuration file's ``"family"`` key (``dense`` where
the key is absent).

A family module supplies:

- ``Dims``, with ``from_config(config)`` (the configuration file) and
  ``from_program(cfg)`` (a program registry entry: the rehearsal's
  reduced one);
- ``check_program(cfg, config)``: the keys where the program's registry
  entry differs from the configuration file;
- ``make_weights(dims, key)``: the seed's weights, in the program's
  parameter layout;
- ``gaps(weights, dims, prompt, served, quant)``: the plain float32
  forward under ``jax.default_matmul_precision("highest")``, judged by
  ``reference.served_gaps``, with the float8 control;
- the counts of useful work the readers take: ``step_work(dims, ctxs,
  pieces, logit_rows)``, ``decode_attn(dims, ctxs)``,
  ``prefill_attn(dims, pieces)`` and ``kv_bytes_per_token(dims)``.
"""

from __future__ import annotations

import importlib
import os
import re
from types import ModuleType
from typing import Dict

HERE = os.path.dirname(os.path.abspath(__file__))
NAME = re.compile(r"[a-z][a-z0-9_]*")


def load(config: Dict) -> ModuleType:
    """The family module the configuration names; LookupError if there is
    none."""
    name = config.get("family", "dense")
    if not (isinstance(name, str) and NAME.fullmatch(name)
            and os.path.exists(os.path.join(HERE, name + ".py"))):
        raise LookupError(f"no family module {name!r} in {HERE}")
    return importlib.import_module(f"{__name__}.{name}")

