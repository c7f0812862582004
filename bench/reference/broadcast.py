"""Plain reference of the data plane for one multicast (broadcast) job.

A self-contained restatement of the simulator's semantics for a one-to-
many job, on arrays, importing nothing of the program. It takes the
region grid, the host plan's arrays (envelope ``G``, per-destination
flows ``F``, VMs ``N``, connections ``M``, ``src``, ``dsts``), the fault
tuples and the seed, and:

* peels the flows into distribution trees by the rule that
  ``MulticastPlan.trees`` documents: every destination's flow scaled to
  the slowest delivery rate; each round takes the widest remaining path
  to each destination and carves the least width out of all of them;
  stop once the residual is below 1e-3 of that rate;
* makes one stage per (tree, distinct edge) with the tree's fan-out as
  children, VMs per region, and each edge's ``M`` split over the trees on
  it by rate share, drawing stragglers and the chunk-to-tree assignment
  in the program's order (per job ``default_rng([seed, job])``: one draw
  or two per connection, then the assignment);
* runs the event loop of ``reference/sim.py`` with per-destination
  deliveries (a stage whose head is a destination delivers there; a
  completed hop feeds every child once) and done once every destination
  holds every chunk;
* applies a VM failure as the program states it: the lowest-id live VMs
  of the job in the region die, their connections die and carried chunks
  return to the stage queue; a stage left with no live connection whose
  two regions keep a live VM re-opens its lowest-index connection, at
  its rate, between the live VMs with the fewest live connections
  (lowest id on ties), stages in ascending order; otherwise it stalls.

The active destinations are those the plan delivers to (the program also
keeps a destination with a positive goal and no flow; a sound plan has
none). Rates are water-filled in ``rate_dtype`` (the program's rate
kernel: float32), times and volumes kept in ``dtype`` (float64).
"""

from __future__ import annotations

import collections
import dataclasses

import numpy as np

from reference.sim import (
    GBIT_PER_GB,
    T_EPS,
    _EPS,
    Grid,
    _maxmin,
    _widest_path,
    conn_efficiency,
)

FLOW_EPS = 1e-9
REL_EPS = 1e-3  # the peel's stopping share of the common rate

__all__ = ["Grid", "McJob", "McOut", "problem_sizes", "simulate", "trees"]


@dataclasses.dataclass(frozen=True)
class McJob:
    G: np.ndarray  # [V, V] envelope Gbit/s
    F: np.ndarray  # [D, V, V] per-destination Gbit/s
    N: np.ndarray  # [V]
    M: np.ndarray  # [V, V]
    src: int
    dsts: tuple
    volume_gb: float  # delivered to each destination
    chunk_mb: float
    arrival_s: float


@dataclasses.dataclass(frozen=True)
class McOut:
    status: str
    chunks_delivered: int  # chunks every destination holds
    per_dst: dict  # destination region -> chunks delivered there
    time_s: float


def trees(job: McJob):
    """Distribution trees: [(rate, {dest: [src, ..., dest]})]."""
    rate_of = {}
    for k, d in enumerate(job.dsts):
        r = float(job.F[k][:, d].sum())
        if r > FLOW_EPS:
            rate_of[int(d)] = r
    if not rate_of:
        return []
    act = list(rate_of)
    r_min = min(rate_of.values())
    res = {d: np.array(job.F[list(job.dsts).index(d)], dtype=float)
           * (r_min / rate_of[d]) for d in act}
    left = {d: r_min for d in act}
    tol = REL_EPS * max(r_min, 1e-9)
    out = []
    for _ in range(int((np.asarray(job.F) > FLOW_EPS).sum())
                   + 4 * len(act) + 4):
        live = [d for d in act if left[d] > tol]
        if not live:
            break
        paths, widths = {}, []
        for d in live:
            hit = _widest_path(res[d], job.src, d)
            if hit is None:
                break
            paths[d] = hit[0]
            widths.append(min(hit[1], left[d]))
        if len(paths) < len(live):
            break
        rate = float(min(widths))
        if rate <= FLOW_EPS:
            break
        for d in live:
            p = paths[d]
            for a, b in zip(p[:-1], p[1:]):
                res[d][a, b] -= rate
            left[d] -= rate
        out.append((rate, paths))
    return out


def _tree_edges(paths):
    """Distinct edges in first-appearance order (destinations ascending),
    each edge's hop (its least index on a path), children in edge order,
    and the edge delivering to each destination."""
    edges, hop = [], {}
    for d in sorted(paths):
        p = paths[d]
        for i, e in enumerate(zip(p[:-1], p[1:])):
            if e not in hop:
                edges.append(e)
            hop[e] = min(hop.get(e, i), i)
    order = {e: i for i, e in enumerate(edges)}
    kids = {e: set() for e in edges}
    for p in paths.values():
        for i in range(len(p) - 2):
            kids[(p[i], p[i + 1])].add((p[i + 1], p[i + 2]))
    kids = {e: sorted(c, key=order.__getitem__) for e, c in kids.items()}
    delivers = {(p[-2], p[-1]): d for d, p in paths.items()}
    firsts = {(p[0], p[1]) for p in paths.values()}
    roots = [e for e in edges if e in firsts]
    return edges, hop, kids, delivers, roots


class _Setup:
    """VMs, stages, connections and the chunk stream of the job."""

    def __init__(self, grid: Grid, job: McJob, seed, straggler_prob,
                 straggler_speed):
        if (np.asarray(job.F) > np.asarray(job.G)[None] + 1e-6).any():
            raise ValueError("a destination's flow exceeds the envelope")
        rng = np.random.default_rng([seed, 0])
        self.chunk_gbit = job.chunk_mb * 8.0 / 1024.0
        self.n_chunks = max(1, int(np.ceil(job.volume_gb * GBIT_PER_GB
                                           / self.chunk_gbit)))
        self.vm_eg, self.vm_in, self.vm_region = [], [], []
        vm_of = {}
        for r in range(len(job.N)):
            ids = []
            for _ in range(int(round(job.N[r]))):
                ids.append(len(self.vm_eg))
                self.vm_eg.append(grid.limit_egress[r])
                self.vm_in.append(grid.limit_ingress[r])
                self.vm_region.append(r)
            vm_of[r] = ids
        ts = trees(job)
        if not ts:
            raise ValueError("the job carries no flow")
        served = sorted({d for _, paths in ts for d in paths})
        self.slots = {d: i for i, d in enumerate(served)}
        self.stage_hop, self.stage_children, self.stage_deliver = [], [], []
        self.first_stage, stage_of = [], []
        self.max_hops = 1
        shapes = [_tree_edges(paths) for _, paths in ts]
        for edges, hop, kids, delivers, roots in shapes:
            self.max_hops = max(self.max_hops, len(edges))
            s_of = {}
            for e in edges:
                s_of[e] = len(self.stage_hop)
                self.stage_hop.append(hop[e])
                self.stage_children.append([])
                self.stage_deliver.append(-1)
            for e in edges:
                self.stage_children[s_of[e]] = [s_of[c] for c in kids[e]]
            for e, d in delivers.items():
                self.stage_deliver[s_of[e]] = self.slots[d]
            stage_of.append(s_of)
            self.first_stage.append([s_of[e] for e in roots])
        total = {}
        for (rate, _), shape in zip(ts, shapes):
            for e in shape[0]:
                total[e] = total.get(e, 0.0) + rate
        conn = {k: [] for k in ("sid", "src", "dst", "rate", "edge")}
        for tid, ((rate, _), shape) in enumerate(zip(ts, shapes)):
            for a, b in shape[0]:
                n_conn = max(1, int(round(int(round(job.M[a, b]))
                                          * rate / total[(a, b)])))
                vms_a, vms_b = vm_of.get(a) or [], vm_of.get(b) or []
                if not vms_a or not vms_b:
                    raise ValueError(f"flow on {a}->{b} with no VMs")
                per_pair = max(n_conn / (len(vms_a) * len(vms_b)), 1e-9)
                eff = conn_efficiency(per_pair * len(vms_b), grid.limit_conn)
                nominal = grid.tput[a, b] * eff / n_conn * len(vms_a)
                for c in range(n_conn):
                    if rng.uniform() < straggler_prob:
                        mult = float(rng.uniform(*straggler_speed))
                    else:
                        mult = float(np.exp(rng.normal(0.0, 0.05)))
                    conn["sid"].append(stage_of[tid][(a, b)])
                    conn["src"].append(vms_a[c % len(vms_a)])
                    conn["dst"].append(vms_b[c % len(vms_b)])
                    conn["rate"].append(nominal * mult)
                    conn["edge"].append((a, b))
        rates = np.array([r for r, _ in ts])
        self.chunk_tree = rng.choice(len(ts), size=self.n_chunks,
                                     p=rates / rates.sum())
        self.edges_used = sorted(set(conn["edge"]))
        index = {e: i for i, e in enumerate(self.edges_used)}
        self.conn_sid = np.asarray(conn["sid"], dtype=np.int64)
        self.conn_src = np.asarray(conn["src"], dtype=np.int64)
        self.conn_dst = np.asarray(conn["dst"], dtype=np.int64)
        self.conn_rate = np.asarray(conn["rate"], dtype=float)
        self.conn_edge = np.asarray([index[e] for e in conn["edge"]],
                                    dtype=np.int64)


def problem_sizes(grid: Grid, job: McJob, seed: int = 0):
    """(connection lanes, VMs, shared edges) of the job: the sizes of each
    of its rate solves."""
    su = _Setup(grid, job, seed, 0.05, (0.15, 0.5))
    return len(su.conn_sid), len(su.vm_eg), len(su.edges_used)


def simulate(grid: Grid, job: McJob, faults, *, seed: int,
             link_capacity_scale: float = 2.0, straggler_prob: float = 0.05,
             straggler_speed=(0.15, 0.5), relay_buffer_chunks: int = 64,
             dtype=np.float64, rate_dtype=None):
    """Run the job to completion or stall. ``faults``: tuples
    ``("vm", t_s, job, region, count)`` (job 0 is this job). Returns
    (``McOut``, events)."""
    su = _Setup(grid, job, seed, straggler_prob, straggler_speed)
    rate_dtype = rate_dtype or dtype
    nc, ns, n = len(su.conn_sid), len(su.stage_hop), su.n_chunks
    rate = su.conn_rate.astype(dtype)
    src, dst = su.conn_src.copy(), su.conn_dst.copy()
    alive = np.ones(nc, dtype=bool)
    chunk = np.full(nc, -1, dtype=np.int64)
    remaining = np.zeros(nc, dtype=dtype)
    edge_cap = np.array([grid.tput[a, b] * link_capacity_scale
                         for a, b in su.edges_used], dtype=dtype)
    eg_cap = np.asarray(su.vm_eg, dtype=dtype)
    in_cap = np.asarray(su.vm_in, dtype=dtype)
    vm_alive = np.ones(len(su.vm_eg), dtype=bool)
    vm_region = np.asarray(su.vm_region, dtype=np.int64)
    ready = [collections.deque() for _ in range(ns)]
    relay_occ = np.zeros(ns, dtype=np.int64)
    done_hops, enqueued = set(), set()
    delivered = np.zeros(len(su.slots), dtype=np.int64)
    finish = None
    arrived = False
    chunk_gbit = dtype(su.chunk_gbit)

    sched = [(float(job.arrival_s), 0, ("arrive",))]
    sched += [(float(f[1]), 1 + i, f) for i, f in enumerate(faults)]
    sched.sort(key=lambda e: (e[0], e[1]))
    ptr = 0
    now = dtype(0.0)

    def reopen(sid):
        lanes = np.flatnonzero(su.conn_sid == sid)
        if alive[lanes].any():
            return
        ci = lanes[0]
        load = (np.bincount(src[alive], minlength=len(vm_alive))
                + np.bincount(dst[alive], minlength=len(vm_alive)))
        pick = []
        for r in su.edges_used[su.conn_edge[ci]]:
            vms = np.flatnonzero(vm_alive & (vm_region == r))
            if not vms.size:
                return  # no live VM in the region: the stage stalls
            pick.append(vms[np.argmin(load[vms])])
        src[ci], dst[ci] = pick
        alive[ci] = True

    def apply_due():
        nonlocal ptr, arrived
        while ptr < len(sched) and sched[ptr][0] <= now + T_EPS:
            ev = sched[ptr][2]
            ptr += 1
            if ev[0] == "arrive":
                arrived = True
                for ch in range(n):
                    for s0 in su.first_stage[int(su.chunk_tree[ch])]:
                        ready[s0].append(ch)
            elif ev[0] == "vm":
                _, _, j, region, count = ev
                if j != 0:
                    continue
                kill = np.flatnonzero(vm_alive & (vm_region == region))
                kill = kill[:count]
                vm_alive[kill] = False
                hit = alive & (np.isin(src, kill) | np.isin(dst, kill))
                for ci in np.flatnonzero(hit):
                    if chunk[ci] >= 0:
                        sid = int(su.conn_sid[ci])
                        ready[sid].append(int(chunk[ci]))
                        if su.stage_hop[sid] > 0:
                            relay_occ[sid] += 1
                        chunk[ci] = -1
                        remaining[ci] = 0.0
                alive[hit] = False
                for sid in sorted(set(su.conn_sid[hit].tolist())):
                    reopen(sid)
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
        remaining[ci] = chunk_gbit
        if su.stage_hop[sid] > 0:
            relay_occ[sid] -= 1
        return True

    max_events = n * 6 * su.max_hops + 10000 + 8 * len(sched)
    events = 0
    for _ in range(max_events):
        apply_due()
        progressed = arrived
        while progressed:  # cascade refills, passes in connection order
            progressed = False
            qlen = np.fromiter((len(q) for q in ready), np.int64, ns)
            cand = np.flatnonzero((chunk < 0) & alive
                                  & (qlen[su.conn_sid] > 0))
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
        r = _maxmin(rate[active], src[active], dst[active],
                    su.conn_edge[active], eg_cap, in_cap, edge_cap,
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
            slot = su.stage_deliver[sid]
            if slot >= 0:
                delivered[slot] += 1
                if finish is None and delivered.min() >= n:
                    finish = float(now)
            for nsid in su.stage_children[sid]:
                if (nsid, ch) in enqueued:
                    continue
                enqueued.add((nsid, ch))
                ready[nsid].append(ch)
                relay_occ[nsid] += 1
        if finish is not None:
            break

    end = finish if finish is not None else float(now)
    dur = max(end - float(job.arrival_s), 1e-9)
    if finish is not None:
        status = "done"
    elif not arrived:
        status, dur = "pending", 0.0
    else:
        status = "stalled"
    per_dst = {d: int(delivered[s]) for d, s in su.slots.items()}
    return McOut(status, int(delivered.min()), per_dst, float(dur)), events
