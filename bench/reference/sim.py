"""Plain reference of the multi-job transfer data plane (unicast jobs).

A self-contained restatement of the simulator's semantics: the same
materialization of VMs, connections, stragglers and chunk streams from a
plan, and the same event loop (arrivals and faults applied when due,
cascade refills in connection order under relay-buffer limits, max-min
water-filling of rates over per-connection caps, per-VM egress/ingress
caps and shared link caps, advance to the next completion or due event).
It follows the object-per-connection oracle of the simulator's test
suite, with the per-event arithmetic on arrays, and imports nothing of
the program: it takes region-grid arrays, plan arrays and fault tuples.

``dtype`` sets the precision of times and volumes, ``rate_dtype`` that of
the water-filling of rates (its inputs rounded to it, its saturation
tolerance that of the precision). The stated precisions are float64 for
times and float32 for rates (the program's TPU rate kernel); the
precision control keeps times in float32 as well.
"""

from __future__ import annotations

import collections
import dataclasses

import numpy as np

GBIT_PER_GB = 8.0
T_EPS = 1e-9
_EPS = 1e-12
# saturation tolerance of a water-filling round: the simulator's 1e-12 in
# float64, and the rate kernel's stated 1e-6 in float32
_SAT = {np.dtype(np.float64): 1e-12, np.dtype(np.float32): 1e-6}


@dataclasses.dataclass(frozen=True)
class Grid:
    """The region grid a scenario runs on."""

    tput: np.ndarray  # [V, V] Gbit/s per VM pair
    limit_egress: np.ndarray  # [V] Gbit/s per VM
    limit_ingress: np.ndarray  # [V]
    limit_conn: int


@dataclasses.dataclass(frozen=True)
class Job:
    F: np.ndarray  # [V, V] Gbit/s
    N: np.ndarray  # [V]
    M: np.ndarray  # [V, V]
    src: int
    dst: int
    volume_gb: float
    chunk_mb: float
    arrival_s: float


@dataclasses.dataclass(frozen=True)
class JobOut:
    status: str
    chunks_delivered: int
    time_s: float


def conn_efficiency(n: float, limit: int) -> float:
    if n <= 0:
        return 0.0
    return min(1.0, (n / limit) ** 0.9)


def _widest_path(F, src, dst):
    v = F.shape[0]
    width = np.full(v, 0.0)
    prev = np.full(v, -1, dtype=np.int64)
    width[src] = np.inf
    visited = np.zeros(v, dtype=bool)
    for _ in range(v):
        u, best = -1, 0.0
        for i in range(v):
            if not visited[i] and width[i] > best:
                best, u = width[i], i
        if u < 0:
            break
        visited[u] = True
        if u == dst:
            break
        for w in range(v):
            cand = min(width[u], F[u, w])
            if cand > width[w] + 1e-12:
                width[w] = cand
                prev[w] = u
    if width[dst] <= 1e-9:
        return None
    path = [dst]
    while path[-1] != src:
        path.append(int(prev[path[-1]]))
    path.reverse()
    return path, float(width[dst])


def paths(job: Job, rel_eps: float = 1e-6):
    """Greedy widest-path decomposition of F into (region path, Gbit/s)."""
    F = np.array(job.F, dtype=float)
    tol = rel_eps * max(float(job.F[job.src, :].sum()), 1e-9)
    out = []
    for _ in range(int((F > 1e-9).sum()) + 4):
        hit = _widest_path(F, job.src, job.dst)
        if hit is None:
            break
        path, flow = hit
        for a, b in zip(path[:-1], path[1:]):
            F[a, b] -= flow
        out.append((path, flow))
        if float(F[job.src, :].sum()) <= tol:
            break
    return out


class _Setup:
    """VMs, stages, connections and chunk streams of every job."""

    def __init__(self, grid: Grid, jobs: list[Job], seed, straggler_prob,
                 straggler_speed):
        self.n_chunks, self.chunk_gbit, self.chunk_path = [], [], []
        self.vm_eg, self.vm_in, self.vm_region, self.vm_job = [], [], [], []
        self.stage_hop, self.stage_children, self.stage_deliver = [], [], []
        self.first_stage = []
        conn = {k: [] for k in ("job", "sid", "src", "dst", "rate", "edge")}
        self.max_hops = 1
        for j, job in enumerate(jobs):
            rng = np.random.default_rng([seed, j])
            cg = job.chunk_mb * 8.0 / 1024.0
            self.chunk_gbit.append(cg)
            n = max(1, int(np.ceil(job.volume_gb * GBIT_PER_GB / cg)))
            self.n_chunks.append(n)
            vm_of = {}
            for r in range(len(job.N)):
                ids = []
                for _ in range(int(round(job.N[r]))):
                    ids.append(len(self.vm_eg))
                    self.vm_eg.append(grid.limit_egress[r])
                    self.vm_in.append(grid.limit_ingress[r])
                    self.vm_region.append(r)
                    self.vm_job.append(j)
                vm_of[r] = ids
            ps = paths(job)
            if not ps:
                raise ValueError(f"job {j} carries no flow")
            stage_of = {}
            plen = {pid: len(p) - 1 for pid, (p, _) in enumerate(ps)}
            self.max_hops = max(self.max_hops, max(plen.values()))
            for pid in range(len(ps)):
                for hop in range(plen[pid]):
                    stage_of[(pid, hop)] = len(self.stage_hop)
                    self.stage_hop.append(hop)
                    self.stage_children.append([])
                    self.stage_deliver.append(-1)
            for (pid, hop), sid in stage_of.items():
                if hop + 1 < plen[pid]:
                    self.stage_children[sid] = [stage_of[(pid, hop + 1)]]
                else:
                    self.stage_deliver[sid] = j  # one delivery slot per job
            self.first_stage.append(
                [stage_of[(pid, 0)] for pid in range(len(ps))]
            )
            total = {}
            for path, flow in ps:
                for e in zip(path[:-1], path[1:]):
                    total[e] = total.get(e, 0.0) + flow
            for pid, (path, flow) in enumerate(ps):
                for hop, (a, b) in enumerate(zip(path[:-1], path[1:])):
                    m_edge = int(round(job.M[a, b]))
                    n_conn = max(1, int(round(m_edge * flow / total[(a, b)])))
                    vms_a, vms_b = vm_of.get(a) or [], vm_of.get(b) or []
                    if not vms_a or not vms_b:
                        raise ValueError(f"job {j}: flow on {a}->{b}, no VMs")
                    per_pair = max(n_conn / (len(vms_a) * len(vms_b)), 1e-9)
                    eff = conn_efficiency(per_pair * len(vms_b),
                                          grid.limit_conn)
                    nominal = grid.tput[a, b] * eff / n_conn * len(vms_a)
                    for c in range(n_conn):
                        if rng.uniform() < straggler_prob:
                            mult = float(rng.uniform(*straggler_speed))
                        else:
                            mult = float(np.exp(rng.normal(0.0, 0.05)))
                        conn["job"].append(j)
                        conn["sid"].append(stage_of[(pid, hop)])
                        conn["src"].append(vms_a[c % len(vms_a)])
                        conn["dst"].append(vms_b[c % len(vms_b)])
                        conn["rate"].append(nominal * mult)
                        conn["edge"].append((a, b))
            flows = np.array([f for _, f in ps])
            self.chunk_path.append(
                rng.choice(len(ps), size=n, p=flows / flows.sum())
            )
        self.edges_used = sorted(set(conn["edge"]))
        index = {e: i for i, e in enumerate(self.edges_used)}
        self.conn_job = np.asarray(conn["job"], dtype=np.int64)
        self.conn_sid = np.asarray(conn["sid"], dtype=np.int64)
        self.conn_src = np.asarray(conn["src"], dtype=np.int64)
        self.conn_dst = np.asarray(conn["dst"], dtype=np.int64)
        self.conn_rate = np.asarray(conn["rate"], dtype=float)
        self.conn_edge = np.asarray([index[e] for e in conn["edge"]],
                                    dtype=np.int64)


def problem_sizes(grid: Grid, jobs: list[Job], seed: int = 0):
    """(connection lanes, VMs, shared edges) of a scenario: the sizes of
    each of its rate solves."""
    su = _Setup(grid, jobs, seed, 0.05, (0.15, 0.5))
    return len(su.conn_job), len(su.vm_eg), len(su.edges_used)


def _maxmin(caps, src, dst, eid, eg_cap, in_cap, ed_rem0, dtype):
    """Max-min water-filling over the active connections."""
    n = caps.shape[0]
    nv = max(int(src.max()), int(dst.max())) + 1
    eg_rem = eg_cap[:nv].astype(dtype)
    in_rem = in_cap[:nv].astype(dtype)
    ne = 0 if ed_rem0 is None else ed_rem0.shape[0]
    ed_rem = None if ed_rem0 is None else ed_rem0.astype(dtype)
    eps = dtype(_SAT[np.dtype(dtype)])
    caps = caps.astype(dtype)
    rate = np.zeros(n, dtype=dtype)
    fixed = np.zeros(n, dtype=bool)
    for _ in range(2 * nv + ne + 4):
        un = ~fixed
        if not un.any():
            break
        cnt_out = np.bincount(src[un], minlength=nv).astype(dtype)
        cnt_in = np.bincount(dst[un], minlength=nv).astype(dtype)
        with np.errstate(divide="ignore", invalid="ignore"):
            s_out = np.where(cnt_out > 0, eg_rem / np.maximum(cnt_out, 1),
                             np.inf).astype(dtype)
            s_in = np.where(cnt_in > 0, in_rem / np.maximum(cnt_in, 1),
                            np.inf).astype(dtype)
        share = np.minimum(s_out[src], s_in[dst])
        if ne:
            cnt_ed = np.bincount(eid[un], minlength=ne).astype(dtype)
            with np.errstate(divide="ignore", invalid="ignore"):
                s_ed = np.where(cnt_ed > 0, ed_rem / np.maximum(cnt_ed, 1),
                                np.inf).astype(dtype)
            share = np.minimum(share, s_ed[eid])
        newly = un & (caps <= share + eps)
        if newly.any():
            rate[newly] = caps[newly]
        else:
            thresh = share[un].min()
            newly = un & (share <= thresh + eps)
            rate[newly] = share[newly]
        w = rate[newly]
        eg_rem = np.maximum(
            eg_rem - np.bincount(src[newly], weights=w, minlength=nv), 0.0
        ).astype(dtype)
        in_rem = np.maximum(
            in_rem - np.bincount(dst[newly], weights=w, minlength=nv), 0.0
        ).astype(dtype)
        if ne:
            ed_rem = np.maximum(
                ed_rem - np.bincount(eid[newly], weights=w, minlength=ne), 0.0
            ).astype(dtype)
        fixed |= newly
    return rate


def simulate(grid: Grid, jobs: list[Job], faults, *, seed: int,
             link_capacity_scale: float = 2.0, straggler_prob: float = 0.05,
             straggler_speed=(0.15, 0.5), relay_buffer_chunks: int = 64,
             dtype=np.float64, rate_dtype=None):
    """Run the scenario to completion. ``faults``: tuples
    ``("rate", t_s, src, dst, factor)`` for link degrades, gray failures
    and restores, ``("vm", t_s, job, region, count)`` for VM failures.
    Returns (per-job ``JobOut``, events)."""
    su = _Setup(grid, jobs, seed, straggler_prob, straggler_speed)
    rate_dtype = rate_dtype or dtype
    J, nc = len(jobs), len(su.conn_job)
    rate = su.conn_rate.astype(dtype)
    alive = np.ones(nc, dtype=bool)
    chunk = np.full(nc, -1, dtype=np.int64)
    remaining = np.zeros(nc, dtype=dtype)
    edge_cap = np.array([grid.tput[a, b] * link_capacity_scale
                         for a, b in su.edges_used], dtype=dtype)
    eg_cap = np.asarray(su.vm_eg, dtype=dtype)
    in_cap = np.asarray(su.vm_in, dtype=dtype)
    vm_alive = [True] * len(su.vm_eg)
    arrived = np.zeros(J, dtype=bool)
    ns = len(su.stage_hop)
    ready = [collections.deque() for _ in range(ns)]
    relay_occ = np.zeros(ns, dtype=np.int64)
    done_hops, enqueued = set(), set()
    delivered = [0] * J
    finish: list = [None] * J
    chunk_gbit = [dtype(g) for g in su.chunk_gbit]

    sched = [(float(job.arrival_s), j, ("arrive", j))
             for j, job in enumerate(jobs)]
    sched += [(float(f[1]), J + i, f) for i, f in enumerate(faults)]
    sched.sort(key=lambda e: (e[0], e[1]))
    ptr = 0
    now = dtype(0.0)

    def apply_due():
        nonlocal ptr
        while ptr < len(sched) and sched[ptr][0] <= now + T_EPS:
            ev = sched[ptr][2]
            ptr += 1
            if ev[0] == "arrive":
                j = ev[1]
                arrived[j] = True
                firsts = su.first_stage[j]
                for ch in range(su.n_chunks[j]):
                    ready[firsts[int(su.chunk_path[j][ch])]].append(ch)
            elif ev[0] == "rate":
                _, _, a, b, factor = ev
                if (a, b) in su.edges_used:
                    want = su.edges_used.index((a, b))
                    hit = su.conn_edge == want
                    rate[hit] = (rate[hit] * factor).astype(dtype)
                    edge_cap[want] = dtype(edge_cap[want] * factor)
            elif ev[0] == "vm":
                _, _, job, region, count = ev
                kill = [v for v in range(len(vm_alive))
                        if vm_alive[v] and su.vm_job[v] == job
                        and su.vm_region[v] == region][:count]
                for v in kill:
                    vm_alive[v] = False
                killset = set(kill)
                for ci in range(nc):
                    if alive[ci] and (int(su.conn_src[ci]) in killset
                                      or int(su.conn_dst[ci]) in killset):
                        if chunk[ci] >= 0:
                            sid = int(su.conn_sid[ci])
                            ready[sid].append(int(chunk[ci]))
                            if su.stage_hop[sid] > 0:
                                relay_occ[sid] += 1
                            chunk[ci] = -1
                            remaining[ci] = 0.0
                        alive[ci] = False
            else:
                raise TypeError(f"unknown event {ev!r}")

    def refill(ci):
        sid = int(su.conn_sid[ci])
        for nsid in su.stage_children[sid]:
            if relay_occ[nsid] >= relay_buffer_chunks:
                return False
        q = ready[sid]
        if not q:
            return False
        chunk[ci] = q.popleft()
        remaining[ci] = chunk_gbit[int(su.conn_job[ci])]
        if su.stage_hop[sid] > 0:
            relay_occ[sid] -= 1
        return True

    max_events = (sum(n * 6 for n in su.n_chunks) * su.max_hops + 10000
                  + 8 * len(sched))
    events = 0
    for _ in range(max_events):
        apply_due()
        progressed = True
        while progressed:  # cascade refills, passes in connection order
            progressed = False
            qlen = np.fromiter((len(q) for q in ready), np.int64, ns)
            cand = np.flatnonzero(
                (chunk < 0) & alive & arrived[su.conn_job]
                & (qlen[su.conn_sid] > 0)
            )
            for ci in cand:
                if refill(ci):
                    progressed = True
        active = np.flatnonzero(chunk >= 0)
        t_next = sched[ptr][0] if ptr < len(sched) else None
        if active.size == 0:
            if t_next is not None:
                now = dtype(t_next)
                continue
            break
        events += 1
        ed = None if link_capacity_scale is None else edge_cap
        r = _maxmin(rate[active], su.conn_src[active], su.conn_dst[active],
                    su.conn_edge[active], eg_cap, in_cap, ed,
                    rate_dtype).astype(dtype)
        if r.max() <= 1e-9 and t_next is None:
            break  # every remaining link dead: stall
        dt = (remaining[active] / np.maximum(r, dtype(_EPS))).min()
        dt = max(dt, dtype(1e-9))
        if t_next is not None and now + dt > t_next:
            dt = dtype(t_next - now)
        now = dtype(now + dt)
        remaining[active] = remaining[active] - r * dt
        for ci in active[remaining[active] <= 1e-9]:
            ch = int(chunk[ci])
            chunk[ci] = -1
            remaining[ci] = 0.0
            sid = int(su.conn_sid[ci])
            if (sid, ch) in done_hops:
                continue
            done_hops.add((sid, ch))
            j = su.stage_deliver[sid]
            if j >= 0:
                delivered[j] += 1
                if delivered[j] >= su.n_chunks[j]:
                    finish[j] = float(now)
            for nsid in su.stage_children[sid]:
                if (nsid, ch) in enqueued:
                    continue
                enqueued.add((nsid, ch))
                ready[nsid].append(ch)
                relay_occ[nsid] += 1
        if all(f is not None for f in finish):
            break

    out = []
    for j, job in enumerate(jobs):
        end = finish[j] if finish[j] is not None else float(now)
        dur = max(end - float(job.arrival_s), 1e-9)
        if finish[j] is not None:
            status = "done"
        elif not arrived[j]:
            status, dur = "pending", 0.0
        else:
            status = "stalled"
        out.append(JobOut(status, int(delivered[j]), float(dur)))
    return out, events
