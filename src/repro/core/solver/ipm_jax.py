"""Batched JAX interior-point LP solver for the planner's solve pipelines.

The paper's §5.2 throughput-max mode solves ~100 cost-min LPs at different
throughput goals, and the §5.1.3 round-down pipeline adds feasibility-repair
probes and fixed-N / fixed-N+M refits. All of those LPs share their matrices
and differ only in the RHS — either the two goal rows of b or the
pinned-variable shifts produced by ``milp.LPStructure.batch_b_ub`` — a
textbook vmap: the Mehrotra predictor-corrector of the numpy batch engine
(``ipm_batch.solve_standard_form_batched``: same Ruiz scaling, starting
point, step rule and stopping rules), jitted under scoped float64
(`jax.enable_x64` context — no global state) and vmapped over b, each
sample in a ``lax.while_loop`` that stops where the numpy engine stops. On
the 12-region pruned graph a whole frontier stage solves in one batched
call.

Following the numpy engine's iterates, and not just its optimum, is what
makes the plans match: the round-down floors N and M of the LP solution,
and where the optimal face is degenerate (connection counts M usually
are) a different interior-point path lands on a different optimal point
and floors to a different integer plan. Each iteration Cholesky-factorizes
the normal matrix A D A^T (plus a trace-scaled ridge, escalated on
failure as ``ipm._NormalFactor`` does) once and reuses the factor for the
predictor and corrector solves. Cholesky and not LU: the TPU compiler
implements f64 Cholesky and triangular solves, but not f64 LU.

Every device call is padded into a bucketed shape: rows and columns to
powers of four of at least 64 and 128, the batch to ``_MIN_BATCH`` or
``_MAX_BATCH`` samples. Each sample carries its own matrix,
so ``solve_lp_batches`` puts the samples of every LP batch it is handed
that pad to the same shape into the same calls. A TPU compile of this
solver takes tens of seconds and each call runs a whole interior-point
solve, while one planner sweep produces dozens of small batches of
distinct LP shapes (every pinned stage solves on its own reduced
structures): the buckets fold them into a few programs, and the shared
calls into a few runs per stage.

The numpy solver (ipm.py) remains the reference. ``solve_lp_batched``
judges each sample with a KKT check computed in float64 numpy on the host
from the device's final iterate, so the certificate does not rest on the
device's arithmetic; ``ipm_batch.solve_lp_batched_with_fallback``
re-solves the failing samples with the numpy IPM. The planner reaches
this engine through ``ipm_batch``'s dispatch: it is selected when jax has
an accelerator backend, while CPU-only hosts use the stacked-LAPACK numpy
engine instead (XLA's CPU triangular/LU solve lowering is 20-30x slower
than LAPACK on these problem sizes — measured, see ipm_batch.py).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs.metrics import REGISTRY
from repro.obs.trace import region

from .ipm import _ruiz_equilibrate

_EPS = 1e-11
_KKT_TOL = 1e-7
# the numpy engines' stopping rule: tolerance and iteration budget
_TOL = 1e-9
_MAX_ITER = 100
# padded shapes (``_Scaled``, ``solve_lp_batches``): row and column floors,
# and the two batch sizes of a device call (at 256x512, 128 f64 LPs take
# ~4.5 GiB of device temporaries)
_MIN_ROWS = 64
_MIN_COLS = 128
_MIN_BATCH = 16
_MAX_BATCH = 128

# the work of the device calls: calls, padded and real rows, trips of the
# vmapped loop (its slowest row's iterations), rows times trips, and the
# real rows' own iterations
_device_calls = REGISTRY.counter("ipm.device_calls")
_batch_rows = REGISTRY.counter("ipm.batch_rows")
_batch_rows_real = REGISTRY.counter("ipm.batch_rows_real")
_loop_trips = REGISTRY.counter("ipm.loop_trips")
_row_trips = REGISTRY.counter("ipm.row_trips")
_sample_iters = REGISTRY.counter("ipm.sample_iters")


def _build_standard(c, A_ub, A_eq):
    """Standard-form matrix [A_ub I; A_eq 0] and extended objective."""
    n = c.shape[0]
    m_ub = A_ub.shape[0] if A_ub is not None and A_ub.size else 0
    m_eq = A_eq.shape[0] if A_eq is not None and A_eq.size else 0
    A = np.zeros((m_ub + m_eq, n + m_ub))
    if m_ub:
        A[:m_ub, :n] = A_ub
        A[:m_ub, n:] = np.eye(m_ub)
    if m_eq:
        A[m_ub:, :n] = A_eq
    cs = np.concatenate([c, np.zeros(m_ub)])
    return A, cs, m_ub, m_eq


def _chol(M, tr, reg0):
    """Cholesky of M + reg*tr*I, escalating reg x100 (up to 6 tries) while
    the factor is not finite — ``ipm._NormalFactor``'s ladder."""
    eye = jnp.eye(M.shape[0], dtype=M.dtype)

    def fac(reg):
        return jnp.linalg.cholesky(M + reg * tr * eye)

    def cond(c):
        k, _, L = c
        return (k < 5) & ~jnp.all(jnp.isfinite(L))

    def body(c):
        k, reg, _ = c
        return k + 1, reg * 100.0, fac(reg * 100.0)

    L = fac(reg0)
    return jax.lax.while_loop(cond, body, (jnp.int32(0), reg0, L))[2]


def _solve(A, b, c, rmask, cmask):
    """Mehrotra on one equilibrated, padded standard-form LP.

    A port of ``ipm_batch.solve_standard_form_batched`` for one sample
    (same start, step rule and stopping rules); every reduction runs over
    the real rows/columns only (``rmask``/``cmask``), and the pad block is
    held at x = s = 1, y = 0, so the real block follows the unpadded
    iterates. Returns (x, y, s, converged, iterations): under ``vmap`` the
    loop runs until its slowest sample stops, and each sample's count
    says how many of those trips advanced it."""
    m_r = jnp.sum(rmask)
    n_r = jnp.sum(cmask)
    real = cmask > 0
    pad_rows = jnp.diag(1.0 - rmask)

    def dot(u, v):
        return jnp.sum(jnp.where(real, u * v, 0.0))

    def rmin(v):  # numpy's v.min(initial=0.0) over the real columns
        return jnp.minimum(jnp.min(jnp.where(real, v, jnp.inf)), 0.0)

    def normal(d):
        # A D A^T; pad rows get an identity block (decoupled, well posed)
        M = (A * d[None, :]) @ A.T + pad_rows
        tr = jnp.maximum(jnp.sum(jnp.diag(M) * rmask) / m_r, 1.0)
        return M, tr

    def solve(L, rhs):
        return jax.scipy.linalg.cho_solve((L, True), rhs)

    def max_step(v, dv):
        neg = real & (dv < 0)
        r = jnp.where(neg, -v / jnp.where(neg, dv, -1.0), jnp.inf)
        return jnp.minimum(1.0, jnp.min(r))

    def hold(x, y, s):
        return (jnp.where(real, x, 1.0), jnp.where(rmask > 0, y, 0.0),
                jnp.where(real, s, 1.0))

    cnorm = 1.0 + jnp.linalg.norm(c * cmask)
    bnorm = 1.0 + jnp.linalg.norm(b)
    L0 = _chol(*normal(jnp.ones_like(c)), 1e-10)
    y0 = solve(L0, A @ c)
    s = c - A.T @ y0
    x = A.T @ solve(L0, b)
    x = x + jnp.maximum(-1.5 * rmin(x), 0.0)
    s = s + jnp.maximum(-1.5 * rmin(s), 0.0)
    xs = dot(x, s)
    bad = xs <= 0
    x = jnp.where(bad, 1.0, x)
    s = jnp.where(bad, 1.0, s)
    xs = jnp.where(bad, n_r, xs)
    x = x + 0.5 * xs / jnp.maximum(dot(s, 1.0), _EPS)
    s = s + 0.5 * xs / jnp.maximum(dot(x, 1.0), _EPS)
    x, y, s = hold(jnp.maximum(x, 1e-4), y0, jnp.maximum(s, 1e-4))

    def cond(st):
        return ~st[-1]

    def body(st):
        it, x, y, s, best_pres, stall, best_gap, floor_stall, _, _ = st
        it = it + 1
        rb = A @ x - b
        rc = A.T @ y + s - c
        mu = dot(x, s) / n_r
        pres = jnp.linalg.norm(rb) / bnorm
        dres = jnp.linalg.norm(rc) / cnorm
        gap = n_r * mu / (1.0 + jnp.abs(dot(c, x)))

        converged = (pres < _TOL) & (dres < _TOL) & (gap < _TOL)
        gap_improving = gap < best_gap * 0.5
        best_gap = jnp.where(gap_improving, gap, best_gap)
        floor_stall = jnp.where(gap_improving, 0, floor_stall + 1)
        relaxed = (pres < 1e-7) & (dres < 1e-7) & (gap < 1e-7)
        converged |= relaxed & (floor_stall >= 5)
        improving = pres < best_pres * 0.9
        best_pres = jnp.where(improving, pres, best_pres)
        stall = jnp.where(improving, 0, stall + 1)
        stalled = (stall >= 12) & (pres > 1e-6) & ~converged
        last = it == _MAX_ITER
        converged |= last & relaxed
        finished = converged | stalled | last

        d = x / s
        with jax.named_scope("factor"):
            L = _chol(*normal(d), 1e-12)
        with jax.named_scope("predictor"):  # affine step
            r_xs = x * s
            rhs = -rb - A @ (d * rc - r_xs / s)
            dy_a = solve(L, rhs)
            dx_a = d * (A.T @ dy_a + rc) - r_xs / s
            ds_a = -(r_xs + s * dx_a) / x
            a_pri = max_step(x, dx_a)
            a_dua = max_step(s, ds_a)
            mu_aff = dot(x + a_pri * dx_a, s + a_dua * ds_a) / n_r
            sigma = jnp.clip((mu_aff / jnp.maximum(mu, _EPS)) ** 3, 0.0, 1.0)
        with jax.named_scope("corrector"):  # same factor
            r_xs = x * s + dx_a * ds_a - sigma * mu
            rhs = -rb - A @ (d * rc - r_xs / s)
            dy = solve(L, rhs)
            dx = d * (A.T @ dy + rc) - r_xs / s
            dsv = -(r_xs + s * dx) / x
            eta = jnp.minimum(0.999, 0.9 + 0.09 * it / _MAX_ITER)
            a_pri = eta * max_step(x, dx)
            a_dua = eta * max_step(s, dsv)
        x2, y2, s2 = hold(
            jnp.maximum(x + a_pri * dx, _EPS), y + a_dua * dy,
            jnp.maximum(s + a_dua * dsv, _EPS),
        )
        # a finished sample keeps the iterate it was judged on
        x, y, s = (jnp.where(finished, u, v)
                   for u, v in ((x, x2), (y, y2), (s, s2)))
        return (it, x, y, s, best_pres, stall, best_gap, floor_stall,
                converged, finished)

    inf = jnp.asarray(jnp.inf, c.dtype)
    zero, no = jnp.int32(0), jnp.bool_(False)
    st = (zero, x, y, s, inf, zero, inf, zero, no, no)
    st = jax.lax.while_loop(cond, body, st)
    return st[1], st[2], st[3], st[8], st[0]


# one device call: per-sample A [B, m, n], b [B, m], c [B, n], masks
# -> per-sample (x, y, s, converged, iterations)
_solve_batched = jax.jit(jax.vmap(_solve))


def _bucket(n: int, floor: int) -> int:
    """Smallest floor * 4**k >= n: keeps the jit cache to a few shapes."""
    b = floor
    while b < n:
        b *= 4
    return b


class _Scaled:
    """One LP batch in Ruiz-scaled standard form, and the bucketed shape
    its device calls pad it to (with zero rows and columns, which
    ``_solve`` holds fixed and masks out of every reduction)."""

    def __init__(self, c, A_ub, b_ub_batch, A_eq, b_eq):
        self.c = np.asarray(c, np.float64)
        A, cstd, m_ub, m_eq = _build_standard(
            self.c,
            np.asarray(A_ub, np.float64),
            np.asarray(A_eq, np.float64) if A_eq is not None else None,
        )
        b_ub_batch = np.asarray(b_ub_batch, np.float64)
        self.B = b_ub_batch.shape[0]
        self.m, self.n = A.shape
        bs = np.zeros((self.B, self.m))
        bs[:, :m_ub] = b_ub_batch
        if m_eq:  # broadcasts [m_eq] / [B, m_eq]
            bs[:, m_ub:] = np.asarray(b_eq, np.float64)
        # As = A / (rsc ⊗ csc), as in the numpy engines
        self.As, rsc, self.csc = _ruiz_equilibrate(A)
        self.bs = bs / rsc[None, :]
        self.cs = cstd / self.csc
        self.shape = (_bucket(self.m, _MIN_ROWS), _bucket(self.n, _MIN_COLS))

    def finish(self, x, y, s, conv):
        """Device iterates -> (x, fun, ok); ok is the device's verdict
        confirmed by a float64 KKT check on the real rows and columns."""
        x, y, s = x[:, : self.n], y[:, : self.m], s[:, : self.n]
        pres = np.linalg.norm(x @ self.As.T - self.bs, axis=1) / (
            1.0 + np.linalg.norm(self.bs, axis=1)
        )
        dres = np.linalg.norm(y @ self.As + s - self.cs, axis=1) / (
            1.0 + np.linalg.norm(self.cs)
        )
        gap = np.einsum("bi,bi->b", x, s) / (1.0 + np.abs(x @ self.cs))
        with np.errstate(invalid="ignore"):
            ok = conv & (pres < _KKT_TOL) & (dres < _KKT_TOL) & (
                gap < _KKT_TOL
            )
        x = (x / self.csc[None, :])[:, : self.c.shape[0]]
        return x, x @ self.c, ok


def solve_lp_batches(problems):
    """Solve several LP batches, each ``(c, A_ub, b_ub_batch, A_eq, b_eq)``
    as in ``solve_lp_batched``. The samples of all batches that pad to the
    same shape share device calls of up to ``_MAX_BATCH`` samples (the
    planner's pinned stages hand over many small batches at once, and each
    device call costs a full interior-point run). Returns one
    ``(x, fun, ok)`` per batch."""
    lps = [_Scaled(*p) for p in problems]
    out = [[None] * lp.B for lp in lps]
    slots: dict[tuple, list] = {}
    for k, lp in enumerate(lps):
        if lp.m:
            slots.setdefault(lp.shape, []).extend(
                (k, i) for i in range(lp.B)
            )
    for (mp, n_pad), todo in slots.items():
        for lo in range(0, len(todo), _MAX_BATCH):
            part = todo[lo : lo + _MAX_BATCH]
            Bp = _MIN_BATCH if len(part) <= _MIN_BATCH else _MAX_BATCH
            with region("ipm.pack", track="planner", rows=Bp):
                A = np.zeros((Bp, mp, n_pad))
                b = np.zeros((Bp, mp))
                c = np.ones((Bp, n_pad))
                rmask = np.zeros((Bp, mp))
                cmask = np.zeros((Bp, n_pad))
                for j in range(Bp):  # extra rows repeat the first sample
                    k, i = part[j] if j < len(part) else part[0]
                    lp = lps[k]
                    A[j, : lp.m, : lp.n] = lp.As
                    b[j, : lp.m] = lp.bs[i]
                    c[j, : lp.n] = lp.cs
                    rmask[j, : lp.m] = 1.0
                    cmask[j, : lp.n] = 1.0
                with jax.enable_x64(True):
                    args = [jnp.asarray(a) for a in (A, b, c, rmask, cmask)]
            with region("ipm.device_call", track="planner", rows=Bp):
                with jax.enable_x64(True):
                    res = _solve_batched(*args)
                *res, iters = (np.asarray(a) for a in res)
            trips = int(iters.max())
            _device_calls.inc()
            _batch_rows.inc(Bp)
            _batch_rows_real.inc(len(part))
            _loop_trips.inc(trips)
            _row_trips.inc(Bp * trips)
            _sample_iters.inc(int(iters[: len(part)].sum()))
            for j, (k, i) in enumerate(part):
                out[k][i] = tuple(a[j] for a in res)
    results = []
    with region("ipm.certify", track="planner"):
        for lp, rows in zip(lps, out):
            if not lp.m:
                results.append((np.zeros((lp.B, lp.c.shape[0])),
                                np.zeros(lp.B), np.ones(lp.B, dtype=bool)))
                continue
            results.append(lp.finish(*(np.stack(a) for a in zip(*rows))))
    return results


def solve_lp_batched(c, A_ub, b_ub_batch, A_eq, b_eq):
    """Solve a batch of LPs sharing (c, A_ub, A_eq) but differing in RHS.

    b_ub_batch: [B, m_ub]; b_eq may be [m_eq] (shared) or [B, m_eq] (e.g.
    per-sample pinned-variable shifts). Returns (x [B, n], fun [B], ok [B])
    where ok is the device's convergence verdict confirmed by a KKT check
    (primal/dual residuals + gap below ``_KKT_TOL``) recomputed in float64
    numpy from the returned iterate.
    """
    return solve_lp_batches([(c, A_ub, b_ub_batch, A_eq, b_eq)])[0]
