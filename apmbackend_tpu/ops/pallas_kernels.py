"""Pallas TPU kernels for the hot ops (SURVEY.md §7.3).

Multi-rank selection: the stats tick needs exactly two order statistics per
row (p75/p95 with the reference's neighbor-interpolation,
util_methods.js:112-142) out of a ``[S, W*CAP]`` window — but the XLA
baseline pays for a full per-row sort (O(N log^2 N) bitonic passes, each
moving the whole row through VMEM). This kernel computes EXACT order
statistics with no sort:

1. bitcast each f32 to its order-preserving uint32 key (sign-magnitude to
   biased-int transform; NaN keys sort past +inf, matching jnp.sort's
   NaN-to-end behavior),
2. binary-search the k-th smallest KEY VALUE bit by bit — 32 fixed
   iterations, each a masked compare+popcount over the row (pure VPU work on
   VMEM-resident data),
3. fetch the (k+1)-th value with one extra pass (count<=p, then min of keys
   strictly greater) for the interpolation midpoint,
4. invert the key transform back to f32.

Per-row ranks differ (each row has its own valid-sample count), so ranks ride
in as a ``[S, 2]`` operand. Rows are blocked over a 1-D grid; each block's
window slab lives in VMEM for all 64+2 passes — one HBM read of the data
total, vs. the sort's repeated round trips.

Exactness: identical results to ``sort + reference_percentile_sorted`` for
every float input (the bit search recovers the exact stored element bits, not
an approximation) — property-tested against the sort path in
tests/test_pallas_kernels.py. The kernel is f32-only; f64 parity mode and
non-TPU backends use the sort path (ops/stats.py chooses per dtype/backend).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .stats import percentile_rank  # single source of the reference index math

# numpy scalars: inlined as literals when traced inside the kernel (a closed-
# over jnp array would be a captured constant, which pallas_call rejects)
_SIGN = np.uint32(0x80000000)
_LOW31 = np.uint32(0x7FFFFFFF)
_SMAX = np.int32(0x7FFFFFFF)


def _f32_to_ukey(x: jnp.ndarray) -> jnp.ndarray:
    """Order-preserving f32 -> uint32 (NaN > +inf, -0.0 < +0.0)."""
    u = jax.lax.bitcast_convert_type(x, jnp.uint32)
    neg = (u & _SIGN) != 0
    return jnp.where(neg, ~u, u | _SIGN)


def _ukey_to_f32(u: jnp.ndarray) -> jnp.ndarray:
    """Inverse of :func:`_f32_to_ukey`."""
    neg = (u & _SIGN) == 0  # encoded negatives lost their sign bit
    raw = jnp.where(neg, ~u, u & _LOW31)
    return jax.lax.bitcast_convert_type(raw, jnp.float32)


def _ukey_to_skey(u: jnp.ndarray) -> jnp.ndarray:
    """uint32 key -> int32 with the same order (flip the sign bit): the TPU
    compiler implements no reductions over unsigned integers, so the
    kernel's min runs on these."""
    return jax.lax.bitcast_convert_type(u ^ _SIGN, jnp.int32)


def _skey_to_ukey(s: jnp.ndarray) -> jnp.ndarray:
    return jax.lax.bitcast_convert_type(s, jnp.uint32) ^ _SIGN


def _select_kernel(window_ref, ranks_ref, v1_ref, v2_ref, *, n_ranks: int):
    """One row-block: exact values at rank k and k+1 for each requested rank.

    window_ref [BR, N] f32 (NaN = empty slot), ranks_ref [BR, n_ranks] int32
    (1-indexed; any value is safe — rows gate on count outside), outputs
    [BR, n_ranks] f32.
    """
    skey = _ukey_to_skey(_f32_to_ukey(window_ref[...]))  # [BR, N]
    for j in range(n_ranks):
        k = ranks_ref[:, j : j + 1]  # [BR, 1]
        p = jnp.zeros_like(k, dtype=jnp.uint32)
        for b in range(31, -1, -1):
            cand = p | np.uint32(1 << b)
            cnt = jnp.sum((skey < _ukey_to_skey(cand)).astype(jnp.int32), axis=1, keepdims=True)
            p = jnp.where(cnt < k, cand, p)
        # p is now the exact ukey of the k-th smallest element
        ps = _ukey_to_skey(p)
        le = jnp.sum((skey <= ps).astype(jnp.int32), axis=1, keepdims=True)
        nxt = jnp.min(jnp.where(skey > ps, skey, _SMAX), axis=1, keepdims=True)
        p2 = jnp.where(le >= k + 1, ps, nxt)  # duplicates: rank k+1 == rank k
        v1_ref[:, j : j + 1] = _ukey_to_f32(p)
        v2_ref[:, j : j + 1] = _ukey_to_f32(_skey_to_ukey(p2))


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def select_ranks(
    window: jnp.ndarray,  # [S, N] f32, NaN = empty
    ranks: jnp.ndarray,  # [S, R] int32, 1-indexed
    *,
    block_rows: int = 256,
    interpret: bool = False,
):
    """Exact (value at rank k, value at rank k+1) per row for each rank column.

    Rows are processed in ``block_rows`` slabs; a non-divisible row count is
    padded internally (pad-row outputs are sliced off). N should be
    lane-aligned (pad with NaN) for TPU efficiency.
    """
    S, N = window.shape
    R = ranks.shape[1]
    block_rows = min(block_rows, ((S + 7) // 8) * 8)
    s_pad = (-S) % block_rows
    if s_pad:
        window = jnp.pad(window, ((0, s_pad), (0, 0)), constant_values=jnp.nan)
        ranks = jnp.pad(ranks, ((0, s_pad), (0, 0)), constant_values=1)
    grid = ((S + s_pad) // block_rows,)
    mem = {} if interpret else {"memory_space": pltpu.VMEM}
    out_shape = [
        jax.ShapeDtypeStruct((S + s_pad, R), jnp.float32),
        jax.ShapeDtypeStruct((S + s_pad, R), jnp.float32),
    ]
    v1, v2 = pl.pallas_call(
        functools.partial(_select_kernel, n_ranks=R),
        out_shape=out_shape,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_rows, N), lambda i: (i, 0), **mem),
            pl.BlockSpec((block_rows, R), lambda i: (i, 0), **mem),
        ],
        out_specs=[
            pl.BlockSpec((block_rows, R), lambda i: (i, 0), **mem),
            pl.BlockSpec((block_rows, R), lambda i: (i, 0), **mem),
        ],
        interpret=interpret,
    )(window, ranks)
    return v1[:S], v2[:S]




def window_percentiles(
    window: jnp.ndarray,  # [S, N] float (any), NaN = empty
    counts: jnp.ndarray,  # [S] int32 valid samples per row
    ps=(75, 95),
    *,
    block_rows: int = 256,
    interpret: bool = False,
) -> tuple:
    """Exact reference percentiles for each p in ``ps`` via the selection
    kernel. Returns a tuple of [S] arrays (NaN where count == 0). Pads rows
    and lanes internally; caller passes raw shapes."""
    S, N = window.shape
    orig_dtype = window.dtype
    w = window.astype(jnp.float32)
    n_pad = (-N) % 128
    if n_pad:
        w = jnp.pad(w, ((0, 0), (0, n_pad)), constant_values=jnp.nan)

    ranks = []
    pairs = []
    for p in ps:
        r, tp = percentile_rank(counts, p)
        ranks.append(r)
        pairs.append(tp)
    ranks_arr = jnp.stack(ranks, axis=1)  # [S, R]
    v1, v2 = select_ranks(w, ranks_arr, block_rows=block_rows, interpret=interpret)
    out = []
    for i, p in enumerate(ps):
        val = jnp.where(pairs[i], (v1[:, i] + v2[:, i]) / 2.0, v1[:, i])
        out.append(jnp.where(counts > 0, val, jnp.nan).astype(orig_dtype))
    return tuple(out)
