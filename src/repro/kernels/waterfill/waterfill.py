"""Max-min water-filling Pallas kernel (one-hot matmul form).

One round of the iterative bottleneck-link saturation step per grid
iteration (grid = (n_iters,), cribbing the scratch-across-grid pattern
from ``kernels/ssd_scan``): the per-VM / per-edge segment sums and the
per-connection gathers both become one-hot matmuls on the MXU —
``counts = un @ S`` and ``share_per_conn = share_per_vm @ S^T`` for a
one-hot scatter matrix ``S [NCp, NVp]``. The one-hot matrices are never
materialized whole: each round walks the connection lanes in tiles of
``TILE`` and builds the ``[NVp|NEp, TILE]`` one-hot of a tile in VMEM from
the int32 endpoint indices, so VMEM holds O(lanes + VMs + edges) words and
not O(lanes x VMs). All per-lane vectors ride in ``[8, X]``
row-replicated tiles (f32 min tile is 8 x 128); the running rate / fixed /
residual-budget state lives in VMEM scratch, initialized on grid step 0
and emitted on the last step. Saturated rounds past convergence are
natural no-ops (no unfixed lanes -> zero counts -> no newly-fixed lanes),
so a grid step that finds no unfixed lane skips its passes and the static
iteration bound costs little past convergence.

``BIG`` stands in for +inf: infinities would turn the gather matmuls
into NaN (inf * 0), while BIG survives them (BIG * 0 == 0). The f32
saturation tolerance is correspondingly looser than the f64 oracle's
(1e-6 vs 1e-12) — this kernel is the accelerator fast path, checked
against ``ref.masked_maxmin_rates`` at f32 tolerance, not bitwise. The
kernel computes in f32 and i32 whatever the caller's x64 setting (the jax
sim engine calls it under ``jax.enable_x64``): ``waterfill_8x`` casts its
operands and traces the kernel with x64 off.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BIG = 1e30  # finite stand-in for +inf (survives `* 0.0` in matmuls)
_EPS32 = 1e-6  # f32 saturation tolerance (oracle uses 1e-12 in f64)
TILE = 512  # connection lanes per in-kernel one-hot tile
_F32 = jnp.float32
# Scoped VMEM the kernel may use. v5e has 128 MiB of VMEM per core; the
# compiler's default scope is 16 MiB. ``fits`` keeps the estimate of what
# the kernel holds under half of this, leaving the rest to temporaries.
VMEM_LIMIT = 64 * 1024 * 1024


def pad_lanes(n: int) -> int:
    """Connection lanes padded to whole tiles: multiples of 128 up to one
    tile, whole ``TILE``s beyond."""
    step = 128 if n <= TILE else TILE
    return max(128, -(-n // step) * step)


def vmem_bytes(ncp: int, nvp: int, nep: int) -> int:
    """What one kernel call keeps in VMEM: 5 lane inputs, the output and 3
    lane scratches of [8, ncp] f32/i32, the VM and edge budgets with their
    scratches, and one tile's three one-hot matrices."""
    tile = min(ncp, TILE)
    lanes = 9 * 8 * ncp
    budgets = 2 * 8 * (2 * nvp + nep)
    onehots = tile * (2 * nvp + nep)
    return 4 * (lanes + budgets + onehots)


def fits(ncp: int, nvp: int, nep: int) -> bool:
    """Whether the kernel's VMEM estimate leaves half of ``VMEM_LIMIT``."""
    return vmem_bytes(ncp, nvp, nep) <= VMEM_LIMIT // 2


def _onehot(idx_row, width):
    """[1, T] int32 indices -> [width, T] f32 one-hot (column c has its 1
    at row idx[c])."""
    rows = jax.lax.broadcasted_iota(jnp.int32, (width, idx_row.shape[1]), 0)
    return (rows == idx_row).astype(_F32)


def _gather(v, oh):
    """[8, W] per-VM values -> [8, T] per-lane values through oh [W, T]."""
    return jax.lax.dot_general(
        v, oh, (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST, preferred_element_type=_F32,
    )


def _scatter(u, oh):
    """[8, T] per-lane values -> [8, W] per-VM sums through oh [W, T]."""
    return jax.lax.dot_general(
        u, oh, (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST, preferred_element_type=_F32,
    )


def _waterfill_kernel(caps_ref, act_ref, src_ref, dst_ref, eid_ref, eg_ref,
                      in_ref, ed_ref, rate_out_ref, rate_s, fixed_s, share_s,
                      eg_s, in_s, ed_s, *, n_iters: int):
    i = pl.program_id(0)
    r, ncp = caps_ref.shape
    nvp = eg_ref.shape[1]
    nep = ed_ref.shape[1]
    tile = min(ncp, TILE)
    n_tiles = ncp // tile

    @pl.when(i == 0)
    def _init():
        rate_s[...] = jnp.zeros_like(rate_s)
        fixed_s[...] = 1.0 - act_ref[...]
        eg_s[...] = eg_ref[...]
        in_s[...] = in_ref[...]
        ed_s[...] = ed_ref[...]

    def lanes(t):
        return pl.ds(pl.multiple_of(t * tile, tile), tile)

    def unfixed(sl):  # [8, T], 0/1
        return act_ref[:, sl] * (1.0 - fixed_s[:, sl])

    def onehots(sl):
        return (_onehot(src_ref[0:1, sl], nvp), _onehot(dst_ref[0:1, sl], nvp),
                _onehot(eid_ref[0:1, sl], nep))

    def scatter3(sl, u, acc):
        o_src, o_dst, o_ed = onehots(sl)
        return (acc[0] + _scatter(u, o_src), acc[1] + _scatter(u, o_dst),
                acc[2] + _scatter(u, o_ed))

    def zeros3():
        return (jnp.zeros((r, nvp), _F32), jnp.zeros((r, nvp), _F32),
                jnp.zeros((r, nep), _F32))

    # A round past convergence (no unfixed lane left) would change nothing:
    # skip its three passes. The grid keeps the static worst-case bound.
    @pl.when(jnp.max(act_ref[...] * (1.0 - fixed_s[...])) > 0)
    def _round():
        # pass 1: unfixed lanes per VM (egress / ingress) and per edge
        def count(t, acc):
            return scatter3(lanes(t), unfixed(lanes(t)), acc)

        cnt_out, cnt_in, cnt_ed = jax.lax.fori_loop(0, n_tiles, count,
                                                    zeros3())

        def share_of(budget, cnt):
            return jnp.where(cnt > 0, budget / jnp.maximum(cnt, 1.0), BIG)

        share_out = share_of(eg_s[...], cnt_out)
        share_in = share_of(in_s[...], cnt_in)
        share_ed = share_of(ed_s[...], cnt_ed)

        # pass 2: each lane's share; whether any lane saturates its own cap,
        # and the smallest share (tile-wise running max / min, reduced after)
        def gather(t, acc):
            sl = lanes(t)
            un = unfixed(sl)
            o_src, o_dst, o_ed = onehots(sl)
            share = jnp.minimum(_gather(share_out, o_src),
                                _gather(share_in, o_dst))
            share = jnp.minimum(share, _gather(share_ed, o_ed))
            # gather-matmuls zero out padding lanes; restore their BIG
            # sentinel so the threshold min below never sees a spurious 0
            share = jnp.where(un > 0, share, BIG)
            share_s[:, sl] = share
            cap_hit = (un > 0) & (caps_ref[:, sl] <= share + _EPS32)
            cap_hit = cap_hit.astype(_F32)
            return jnp.maximum(acc[0], cap_hit), jnp.minimum(acc[1], share)

        hit, low = jax.lax.fori_loop(
            0, n_tiles, gather,
            (jnp.zeros((r, tile), _F32), jnp.full((r, tile), BIG, _F32)),
        )
        anyc = jnp.max(hit)  # 1.0 when any lane saturated its own cap
        thresh = jnp.min(low)

        # pass 3: fix this round's lanes; take their rates off the budgets
        def fix(t, acc):
            sl = lanes(t)
            un = unfixed(sl)
            caps = caps_ref[:, sl]
            share = share_s[:, sl]
            cap_hit = ((un > 0) & (caps <= share + _EPS32)).astype(_F32)
            th_hit = ((un > 0) & (share <= thresh + _EPS32)).astype(_F32)
            newly = anyc * cap_hit + (1.0 - anyc) * th_hit
            chosen = anyc * caps + (1.0 - anyc) * share
            rate = jnp.where(newly > 0, chosen, rate_s[:, sl])
            rate_s[:, sl] = rate
            fixed_s[:, sl] = jnp.minimum(fixed_s[:, sl] + newly, 1.0)
            return scatter3(sl, jnp.where(newly > 0, rate, 0.0), acc)

        used_out, used_in, used_ed = jax.lax.fori_loop(0, n_tiles, fix,
                                                       zeros3())
        eg_s[...] = jnp.maximum(eg_s[...] - used_out, 0.0)
        in_s[...] = jnp.maximum(in_s[...] - used_in, 0.0)
        ed_s[...] = jnp.maximum(ed_s[...] - used_ed, 0.0)

    @pl.when(i == n_iters - 1)
    def _emit():
        rate_out_ref[...] = rate_s[...]


@functools.partial(jax.jit, static_argnames=("n_iters", "interpret"))
def waterfill_8x(caps8, act8, src8, dst8, eid8, eg8, in8, ed8, *,
                 n_iters: int, interpret: bool = False):
    """Padded-tile water-filling: caps8/act8 [8, NCp] f32, src8/dst8 [8, NCp]
    int32 VM indices, eid8 [8, NCp] int32 edge indices, eg8/in8 [8, NVp]
    and ed8 [8, NEp] f32 budgets -> rates [8, NCp] f32 (rows identical).
    NCp must come from ``pad_lanes``; NVp and NEp are multiples of 128."""
    r, ncp = caps8.shape
    nvp = eg8.shape[1]
    nep = ed8.shape[1]
    args = (caps8.astype(_F32), act8.astype(_F32), src8.astype(jnp.int32),
            dst8.astype(jnp.int32), eid8.astype(jnp.int32), eg8.astype(_F32),
            in8.astype(_F32), ed8.astype(_F32))
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    # traced with 32-bit defaults whatever the caller's x64 setting: under
    # x64, Python scalars in the kernel body would become 64-bit values
    # that Mosaic cannot mix with the i32 loop indices and f32 scratch
    with jax.enable_x64(False):
        return pl.pallas_call(
            functools.partial(_waterfill_kernel, n_iters=n_iters),
            grid=(n_iters,),
            in_specs=[vmem] * 8,
            out_specs=vmem,
            out_shape=jax.ShapeDtypeStruct((r, ncp), _F32),
            scratch_shapes=[
                pltpu.VMEM((r, ncp), _F32), pltpu.VMEM((r, ncp), _F32),
                pltpu.VMEM((r, ncp), _F32), pltpu.VMEM((r, nvp), _F32),
                pltpu.VMEM((r, nvp), _F32), pltpu.VMEM((r, nep), _F32),
            ],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",),
                vmem_limit_bytes=VMEM_LIMIT,
            ),
            interpret=interpret,
        )(*args)
