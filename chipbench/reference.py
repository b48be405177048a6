"""What every model family's plain reference shares (the families are
``chipbench/families/<family>.py``), kept with the benchmark and
independent of the program under test.

The weights are defined by the seed: one key per tensor, split from the
seed's key (``param_key``) in the order of the sorted tensor names of the
family's weight tree, ``normal(0, 1) * 0.02`` rounded to the served dtype
for matrices, ones for norms, zeros for biases (``init_weights``). The
benchmark makes the served model from the same key; the family computes
its own copy.

Every matrix product of a reference runs in float32 (``mm``) under
``jax.default_matmul_precision("highest")``. ``quant="fp8"`` is the
benchmark's control: every operand is rounded to float8 e4m3 with one
scale (``fp8``), the precision step below the served bfloat16.

``served_gaps`` turns a family's forward into the numbers ``check.py``
compares, and ``dtype_bytes`` and ``least_time`` serve the families'
counts of useful work and the readers' rooflines.
"""

from __future__ import annotations

from typing import Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

MASK32 = 0xFFFFFFFF


def param_key(seed: int) -> jax.Array:
    """The key of ``--seed``: all of its bits count, not only the low 32."""
    key = jax.random.PRNGKey(seed & MASK32)
    return jax.random.fold_in(key, (seed >> 32) & MASK32)


def _leaves(tree: Dict, path=()):
    for k in sorted(tree):
        v = tree[k]
        if isinstance(v, dict):
            yield from _leaves(v, path + (k,))
        else:
            yield path + (k,), v


def init_weights(tree: Dict, dtype: str, key: jax.Array) -> Dict:
    """The seed's weights of a nested dict of ``(shape, init)`` leaves, in
    ``dtype`` (call under ``jax.jit``)."""
    dtype = jnp.dtype(dtype)
    leaves = list(_leaves(tree))
    keys = jax.random.split(key, len(leaves))
    out: Dict = {}
    for (path, (shape, init)), k in zip(leaves, keys):
        if init == "normal":
            x = (jax.random.normal(k, shape, jnp.float32) * 0.02).astype(dtype)
        else:
            x = (jnp.ones if init == "ones" else jnp.zeros)(shape, dtype)
        node = out
        for p in path[:-1]:
            node = node.setdefault(p, {})
        node[path[-1]] = x
    return out


def fp8(x: jax.Array) -> jax.Array:
    """Round to float8 e4m3 with one scale for the whole operand."""
    amax = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    s = 448.0 / amax
    return (x * s).astype(jnp.float8_e4m3fn).astype(jnp.float32) / s


def mm(eq: str, a, b, quant: Optional[str]):
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    if quant == "fp8":
        a, b = fp8(a), fp8(b)
    return jnp.einsum(eq, a, b)


ROW_BLOCK = 256
SEQ_BUCKET = 512


def served_gaps(hidden: Callable, logits: Callable, w: Dict, dm,
                prompt: Sequence[int], served: Sequence[int],
                quant: Optional[str] = None) -> Dict[str, np.ndarray]:
    """Reference logits at every position that produced a served token,
    from a family's ``hidden(w, tokens, dm, quant)`` (final-norm hidden
    states of one causal sequence) and ``logits(w, h, dm, quant)``.

    Returns per served token ``gap_sd``: how far the served token's
    reference logit lies below the reference's best, in standard
    deviations of that reference row; with ``quant``, also
    ``control_gap_sd``: the same for the token the lower-precision
    forward puts first.
    """
    seq = list(prompt) + list(served[:-1])
    n = len(served)
    s_pad = -(-len(seq) // SEQ_BUCKET) * SEQ_BUCKET
    toks = np.zeros(s_pad, np.int32)
    toks[:len(seq)] = seq
    rows = np.arange(len(prompt) - 1, len(prompt) - 1 + n)
    h = hidden(w, jnp.asarray(toks), dm)
    hq = hidden(w, jnp.asarray(toks), dm, quant) if quant else None
    out: Dict[str, List[np.ndarray]] = {"gap_sd": [], "control_gap_sd": []}
    for i in range(0, n, ROW_BLOCK):
        idx = np.zeros(ROW_BLOCK, np.int32)
        blk = rows[i:i + ROW_BLOCK]
        idx[:len(blk)] = blk
        ref = np.asarray(logits(w, h[idx], dm))[:len(blk)]
        best = ref.max(axis=1)
        sd = ref.std(axis=1)
        tok = np.asarray(served[i:i + len(blk)])
        out["gap_sd"].append((best - ref[np.arange(len(blk)), tok]) / sd)
        if quant:
            ctl = np.asarray(logits(w, hq[idx], dm, quant))[:len(blk)]
            pick = ctl.argmax(axis=1)
            out["control_gap_sd"].append(
                (best - ref[np.arange(len(blk)), pick]) / sd)
    return {k: np.concatenate(v) if v else np.zeros(0)
            for k, v in out.items()}


def dtype_bytes(dm) -> int:
    """Bytes of one element of the served dtype ``dm.dtype``."""
    return {"bfloat16": 2, "float16": 2, "float32": 4}[dm.dtype]


def least_time(flops: float, nbytes: float, peaks: Dict[str, float]
               ) -> float:
    """The least time the chip could take: compute or HBM, the larger."""
    return max(flops / peaks["flops_bf16"], nbytes / peaks["hbm_bytes_s"])
