"""Plain reference for integer transfer plans (Skyplane §5.1, Eq. 4a-4j).

Given the region grid, a request (source, destination, throughput goal)
and a plan (flow F, VMs N, connections M), it restates the formulation
directly and answers three questions, each with LPs solved by HiGHS in
float64, independent of the program's solvers:

* ``viol``: the largest violation of constraints 4b-4j, of non-negativity
  and of integrality of N and M, relative to the goal (flows) or to the
  connection limit (connections);
* ``tput_gap``: how far the plan's achieved throughput (its stated goal
  and the flow leaving the source) lies from what its (N, M) can carry,
  min(goal, max-flow(N, M)), relative to the goal;
* ``cost_gap``: how far the plan's $/GB lies from the cheapest flow that
  its (N, M) admit at its achieved throughput, relative to that cost.

``shortfall`` answers a fourth: how far the plan's achieved throughput
lies below the requested goal, relative to the goal. ``optimum`` gives
the least $/GB of any integer plan carrying a goal, by HiGHS branch and
cut on the whole of Eq. 4a-4j over every region of the grid; it is read
in calibration, not in a run (see ``bench/traffic/kinds/plan.py``).

``refit`` gives the precision control: the min-cost flow for a given
(N, M) computed by a plain interior-point method in a chosen dtype.
"""

from __future__ import annotations

import dataclasses

import numpy as np
from scipy import sparse
from scipy.optimize import Bounds, LinearConstraint, linprog, milp

GBIT_PER_GB = 8.0
_HIGHS = {"primal_feasibility_tolerance": 1e-10,
          "dual_feasibility_tolerance": 1e-10}


@dataclasses.dataclass(frozen=True)
class Grid:
    tput: np.ndarray  # [V, V] Gbit/s per VM pair
    price_egress: np.ndarray  # [V, V] $/GB
    price_vm: np.ndarray  # [V] $/s per VM
    limit_egress: np.ndarray  # [V] Gbit/s per VM
    limit_ingress: np.ndarray  # [V]
    limit_conn: int
    limit_vm: int


@dataclasses.dataclass(frozen=True)
class Plan:
    F: np.ndarray  # [V, V]
    N: np.ndarray  # [V]
    M: np.ndarray  # [V, V]
    tput_goal: float  # the throughput the plan states it achieves
    cost_per_gb: float  # the plan's stated $/GB


def cost_per_gb(grid: Grid, F, N, src: int) -> float:
    """$/GB of a flow: egress billed per GB moved, VMs per second of the
    transfer (whose length is volume / throughput)."""
    thr = float(F[src, :].sum())
    return float((F * grid.price_egress).sum()
                 + GBIT_PER_GB * (N @ grid.price_vm)) / max(thr, 1e-9)


def _edges(grid: Grid, M, src: int, dst: int):
    """Edges that may carry flow under M: positive grid throughput and
    connections, none into the source or out of the destination."""
    mask = (grid.tput > 0) & (np.rint(M) > 0)
    np.fill_diagonal(mask, False)
    mask[:, src] = False
    mask[dst, :] = False
    return np.argwhere(mask)


def _flow_lp(grid: Grid, N, M, src: int, dst: int):
    """Rows of the flow LP with N and M fixed: variables F_e on the usable
    edges, bounds from 4b, conservation (4e) as equalities, per-region
    ingress/egress (4f/4g) as inequalities."""
    E = _edges(grid, M, src, dst)
    V = grid.tput.shape[0]
    n = len(E)
    ub = grid.tput[E[:, 0], E[:, 1]] / grid.limit_conn * np.rint(
        M[E[:, 0], E[:, 1]])
    out_of = np.zeros((V, n))
    into = np.zeros((V, n))
    out_of[E[:, 0], np.arange(n)] = 1.0
    into[E[:, 1], np.arange(n)] = 1.0
    mid = [v for v in range(V) if v not in (src, dst)
           and (out_of[v].any() or into[v].any())]
    A_eq = (into - out_of)[mid]
    A_ub = np.vstack([into, out_of])
    b_ub = np.concatenate([grid.limit_ingress * N, grid.limit_egress * N])
    return E, ub, out_of, into, A_eq, A_ub, b_ub


def max_flow(grid: Grid, N, M, src: int, dst: int) -> float:
    E, ub, out_of, into, A_eq, A_ub, b_ub = _flow_lp(grid, N, M, src, dst)
    if len(E) == 0:
        return 0.0
    res = linprog(-out_of[src], A_ub=A_ub, b_ub=b_ub, A_eq=A_eq,
                  b_eq=np.zeros(len(A_eq)), bounds=list(zip(0 * ub, ub)),
                  method="highs", options=_HIGHS)
    return float(-res.fun) if res.status == 0 else 0.0


def min_cost(grid: Grid, N, M, src: int, dst: int, goal: float):
    """Cheapest $/GB of a flow carrying ``goal`` under (N, M), or None."""
    E, ub, out_of, into, A_eq, A_ub, b_ub = _flow_lp(grid, N, M, src, dst)
    if len(E) == 0:
        return None
    A = np.vstack([A_ub, -out_of[src], -into[dst]])
    b = np.concatenate([b_ub, [-goal, -goal]])
    c = grid.price_egress[E[:, 0], E[:, 1]]
    res = linprog(c, A_ub=A, b_ub=b, A_eq=A_eq, b_eq=np.zeros(len(A_eq)),
                  bounds=list(zip(0 * ub, ub)), method="highs",
                  options=_HIGHS)
    if res.status != 0:
        return None
    return float(res.fun + GBIT_PER_GB * (N @ grid.price_vm)) / goal


def violation(grid: Grid, plan: Plan, src: int, dst: int, goal: float):
    """Largest violation of 4b-4j, signs and integrality."""
    F, N, M = plan.F, plan.N, plan.M
    g = max(goal, 1.0)
    L = grid.limit_conn
    v = [
        max(-F.min(), 0.0) / g,
        max(-N.min(), -M.min(), 0.0),
        np.abs(N - np.rint(N)).max(),
        np.abs(M - np.rint(M)).max(),
        max((F - grid.tput * M / L).max(), 0.0) / g,  # 4b
        max(plan.tput_goal - F[src, :].sum(), 0.0) / g,  # 4c
        max(plan.tput_goal - F[:, dst].sum(), 0.0) / g,  # 4d
        max((F.sum(0) - grid.limit_ingress * N).max(), 0.0) / g,  # 4f
        max((F.sum(1) - grid.limit_egress * N).max(), 0.0) / g,  # 4g
        max((M.sum(1) - L * N).max(), 0.0) / L,  # 4h
        max((M.sum(0) - L * N).max(), 0.0) / L,  # 4i
        max((N - grid.limit_vm).max(), 0.0),  # 4j
    ]
    mid = np.ones(F.shape[0], dtype=bool)
    mid[[src, dst]] = False
    v.append(np.abs(F.sum(0) - F.sum(1))[mid].max() / g)  # 4e
    v.append(F[:, src].sum() / g + F[dst, :].sum() / g)  # no flow back
    return float(max(v))


def check(grid: Grid, plan: Plan, src: int, dst: int, goal: float) -> dict:
    """The three numbers compared for one plan (see the module doc)."""
    viol = violation(grid, plan, src, dst, goal)
    mf = max_flow(grid, plan.N, plan.M, src, dst)
    want = min(goal, mf)
    thr = float(plan.F[src, :].sum())
    tput_gap = max(abs(plan.tput_goal - want), abs(thr - want)) / goal
    ref = min_cost(grid, plan.N, plan.M, src, dst, min(plan.tput_goal, mf))
    if ref is None:
        cost_gap = float("inf")
    else:
        cost_gap = abs(plan.cost_per_gb - ref) / ref
    return {"viol": viol, "tput_gap": tput_gap, "cost_gap": cost_gap}


def optimum(grid: Grid, src: int, dst: int, goal: float,
            rel_gap: float = 1e-5, time_limit: float = 30.0) -> float:
    """Least $/GB of an integer plan carrying ``goal`` (Eq. 4a-4j with N
    and M integer), found by HiGHS to within ``rel_gap`` of its bound, or
    the best it has found after ``time_limit`` seconds (never below the
    optimum, so a plan's gap from it is never overstated). Variables
    [F_e, M_e, N_v] over every edge with grid throughput, none into the
    source or out of the destination."""
    V = grid.tput.shape[0]
    mask = grid.tput > 0
    np.fill_diagonal(mask, False)
    mask[:, src] = False
    mask[dst, :] = False
    E = np.argwhere(mask)
    n, L = len(E), grid.limit_conn
    cols = np.arange(n)
    out_of = sparse.csr_matrix((np.ones(n), (E[:, 0], cols)), shape=(V, n))
    into = sparse.csr_matrix((np.ones(n), (E[:, 1], cols)), shape=(V, n))
    zE, zV = sparse.csr_matrix((V, n)), sparse.csr_matrix((V, V))
    mid = [v for v in range(V) if v not in (src, dst)]
    blocks = [
        # 4b: F_e <= tput_e / L * M_e
        ([sparse.eye(n), sparse.diags(-grid.tput[E[:, 0], E[:, 1]] / L),
          sparse.csr_matrix((n, V))], -np.inf, 0.0, n),
        # 4c, 4d: the goal leaves the source and reaches the destination
        ([out_of[src], sparse.csr_matrix((1, n)), zV[:1]], goal, np.inf, 1),
        ([into[dst], sparse.csr_matrix((1, n)), zV[:1]], goal, np.inf, 1),
        # 4e: conservation at every other region
        ([(into - out_of)[mid], zE[mid], zV[mid]], 0.0, 0.0, len(mid)),
        # 4f, 4g: ingress and egress per VM
        ([into, zE, -sparse.diags(grid.limit_ingress)], -np.inf, 0.0, V),
        ([out_of, zE, -sparse.diags(grid.limit_egress)], -np.inf, 0.0, V),
        # 4h, 4i: connections per VM
        ([zE, out_of, -L * sparse.eye(V)], -np.inf, 0.0, V),
        ([zE, into, -L * sparse.eye(V)], -np.inf, 0.0, V),
    ]
    A = sparse.vstack([sparse.hstack(b) for b, *_ in blocks]).tocsr()
    lo = np.concatenate([np.full(k, a) for _, a, _, k in blocks])
    hi = np.concatenate([np.full(k, b) for _, _, b, k in blocks])
    c = np.concatenate([grid.price_egress[E[:, 0], E[:, 1]], np.zeros(n),
                        GBIT_PER_GB * grid.price_vm])
    ub = np.concatenate([np.full(n, np.inf), np.full(n, L * grid.limit_vm),
                         np.full(V, grid.limit_vm)])  # 4j
    res = milp(c, constraints=LinearConstraint(A, lo, hi),
               integrality=np.r_[np.zeros(n), np.ones(n + V)],
               bounds=Bounds(np.zeros(2 * n + V), ub),
               options={"mip_rel_gap": rel_gap, "time_limit": time_limit})
    if res.x is None:
        raise RuntimeError(f"no integer plan carries {goal} Gbit/s: "
                           f"{res.message}")
    return float(res.fun) / goal


def shortfall(plan: Plan, src: int, goal: float) -> float:
    """How far the plan's achieved throughput (the flow leaving the
    source, or its stated throughput if lower) lies below ``goal``,
    relative to it."""
    thr = min(float(plan.F[src, :].sum()), plan.tput_goal)
    return max(goal - thr, 0.0) / goal


def _ipm(c, A, b, dtype, iters=100):
    """Plain Mehrotra predictor-corrector for min c@x, A x = b, x >= 0,
    computed in ``dtype`` throughout (normal equations by Cholesky)."""
    c, A, b = (np.asarray(a, dtype) for a in (c, A, b))
    m, n = A.shape
    eps = float(np.finfo(dtype).eps)
    eye = np.eye(m, dtype=dtype)

    def solve(d, rhs):
        K = (A * d) @ A.T
        scale = max(float(np.trace(K)) / m, 1.0)
        try:
            L = np.linalg.cholesky(K + dtype(eps * scale) * eye)
        except np.linalg.LinAlgError:
            L = np.linalg.cholesky(K + dtype(1e-6 * scale) * eye)
        return np.linalg.solve(L.T, np.linalg.solve(L, rhs)).astype(dtype)

    def step(v, dv):
        neg = dv < 0
        return min(1.0, float((-v[neg] / dv[neg]).min())) if neg.any() else 1.0

    one = np.ones(n, dtype)
    y = solve(one, A @ c)
    s = c - A.T @ y
    x = A.T @ solve(one, b)
    x = x + max(-1.5 * float(x.min()), 0.0)
    s = s + max(-1.5 * float(s.min()), 0.0)
    xs = float(x @ s)
    if xs <= 0:
        x, s, xs = one.copy(), one.copy(), float(n)
    x = x + dtype(0.5 * xs / float(s.sum()))
    s = s + dtype(0.5 * xs / float(x.sum()))
    x, s = np.maximum(x, dtype(1e-4)), np.maximum(s, dtype(1e-4))
    tol = eps ** 0.75
    for it in range(iters):
        rb = A @ x - b
        rc = A.T @ y + s - c
        mu = float(x @ s) / n
        if (np.linalg.norm(rb) <= tol * (1 + np.linalg.norm(b))
                and np.linalg.norm(rc) <= tol * (1 + np.linalg.norm(c))
                and mu * n <= tol * (1 + abs(float(c @ x)))):
            break
        d = x / s

        def direction(r_xs):
            dy = solve(d, -rb - A @ (d * rc - r_xs / s))
            dx = d * (A.T @ dy + rc) - r_xs / s
            return dx, dy, -(r_xs + s * dx) / x

        dx, dy, ds = direction(x * s)
        ap, ad = step(x, dx), step(s, ds)
        sigma = min(1.0, (float((x + ap * dx) @ (s + ad * ds)) / n / mu) ** 3)
        dx, dy, ds = direction(x * s + dx * ds - dtype(sigma * mu))
        eta = min(0.999, 0.9 + 0.09 * it / iters)
        ap, ad = dtype(eta * step(x, dx)), dtype(eta * step(s, ds))
        x = np.maximum(x + ap * dx, dtype(1e-30)).astype(dtype)
        y = (y + ad * dy).astype(dtype)
        s = np.maximum(s + ad * ds, dtype(1e-30)).astype(dtype)
    return x


def refit(grid: Grid, N, M, src: int, dst: int, goal: float, dtype):
    """The min-cost flow for fixed (N, M) carrying ``goal``, by ``_ipm`` in
    ``dtype`` on the standard form with slacks. Returns an [V, V] array."""
    E, ub, out_of, into, A_eq, A_ub, b_ub = _flow_lp(grid, N, M, src, dst)
    n = len(E)
    keep = np.abs(A_ub).sum(1) > 0
    A_in = np.vstack([A_ub[keep], -out_of[src], -into[dst], np.eye(n)])
    b_in = np.concatenate([b_ub[keep], [-goal, -goal], ub])
    k = len(A_in)
    A = np.block([[A_in, np.eye(k)], [A_eq, np.zeros((len(A_eq), k))]])
    b = np.concatenate([b_in, np.zeros(len(A_eq))])
    c = np.concatenate([grid.price_egress[E[:, 0], E[:, 1]], np.zeros(k)])
    x = _ipm(c, A, b, dtype)
    F = np.zeros_like(grid.tput, dtype=float)
    F[E[:, 0], E[:, 1]] = x[:n].astype(float)
    return F
