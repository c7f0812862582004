"""Public water-filling wrapper: ragged conn sets -> padded kernel tiles.

Pads every axis to the f32 tile grid (connection lanes to whole kernel
tiles, VMs and edges to 128), row-replicates the per-lane vectors, and
flips the kernel to interpret mode off-TPU. When link contention is
disabled (``ed_cap is None``) every connection is pinned to a single
dummy edge with a BIG budget — the edge term then can never bind (BIG /
n_conns still dwarfs any real VM share), which keeps the kernel free of
optional operands.
"""

from __future__ import annotations

import jax
import numpy as np

from .waterfill import BIG, pad_lanes, waterfill_8x


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _pad128(n: int) -> int:
    return max(128, -(-n // 128) * 128)


def lane8(vec, width, fill=0.0, dtype=np.float32):
    """[n] -> [8, width] row-replicated lane tile, padded with ``fill``."""
    row = np.full(width, fill, dtype=dtype)
    row[: len(vec)] = vec
    return np.broadcast_to(row, (8, width))


def waterfill_rates(caps, src, dst, eg_cap, in_cap, eid=None, ed_cap=None,
                    active=None, *, n_iters: int | None = None):
    """Max-min fair rates for connections (accelerator fast path).

    caps/src/dst [NC] with optional eid [NC] + ed_cap [NE] shared-edge
    budgets and an optional ``active`` lane mask; eg_cap/in_cap [NV].
    Returns f32 rates [NC], 0.0 on inactive lanes. f32-tolerance
    companion to ``ref.masked_maxmin_rates`` (the f64 parity oracle).
    """
    caps = np.asarray(caps, dtype=np.float32)
    nc = caps.shape[0]
    nv = int(eg_cap.shape[0])
    if active is None:
        active = np.ones(nc, dtype=bool)
    if ed_cap is None:
        eid = np.zeros(nc, dtype=np.int32)
        ed_cap = np.full(1, BIG, dtype=np.float32)
    ne = len(ed_cap)
    ncp, nvp, nep = pad_lanes(nc), _pad128(nv), _pad128(ne)
    if n_iters is None:
        n_iters = 2 * nv + ne + 4
    rates8 = waterfill_8x(
        lane8(caps, ncp), lane8(np.asarray(active, np.float32), ncp),
        lane8(src, ncp, 0, np.int32), lane8(dst, ncp, 0, np.int32),
        lane8(eid, ncp, 0, np.int32),
        lane8(eg_cap, nvp, BIG), lane8(in_cap, nvp, BIG), lane8(ed_cap, nep, BIG),
        n_iters=int(n_iters), interpret=_interpret(),
    )
    return np.asarray(rates8[0, :nc])
