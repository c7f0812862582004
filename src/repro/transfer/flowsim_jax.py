"""Accelerator-resident multi-job flow simulator (fixed-shape jax).

Third engine of the ``transfer.sim`` dispatcher ("jax"), bitwise-pinned
against the numpy SoA loop (``flowsim.simulate_multi``) and therefore
against the object-per-connection oracle. The event loop runs entirely
on-device under ``lax.while_loop`` over padded structure-of-arrays state
with validity masks; the host keeps only the scripted schedule — each
segment of the loop runs until the next due event, the host applies it
(numpy, the exact reference logic, emitting the same Skytrace stream)
and re-enters. A ``VMFailure`` is one of those events: the host kills
the connections, requeues their chunks and, where a stage has no live
connection left but both its regions keep a live VM of the job, re-opens
one (``events.reopen_dead_stages``). The lane endpoints are therefore
operands of ``_segment`` that the host replaces, not constants folded
into its compile; a stage with a region left without a live VM stalls
the job. The max-min water-filling step is the masked pure-jnp
transliteration (``kernels.waterfill.ref.masked_maxmin_rates``, bitwise
vs the numpy oracle under f64) on CPU, or the f32 Pallas one-hot-matmul
kernel (``kernels.waterfill``) on TPU backends up to the VMEM size rule
of ``_rate_solver_for``.

Exact-semantics notes (each is load-bearing for chunk-for-chunk parity):

  * ``None`` horizons / exhausted schedules are encoded as +inf — every
    comparison the SoA loop makes (``now >= horizon - T_EPS``,
    ``t_next < horizon``, ``now + dt > t_next``, the stall check's
    ``t_next is None``) evaluates identically under IEEE inf;
  * cascade refills are stage-batched passes: blocked-ness is per stage
    (a child buffer at capacity), ``relay_occ`` only decreases during a
    cascade and nothing is pushed, so every unblocked stage takes
    min(idle lanes, queue) chunks FIFO in ascending-lane order once, and
    stages unblock monotonically up the stage DAG. With no buffer at
    capacity that is a single pass; with one full, passes that read
    blocked-ness at their start repeat until one takes nothing — at most
    one per DAG level plus one — and reach the reference's fixed point;
  * ``moved = rates * dt`` feeds both the remaining-update and the
    telemetry segment-sums — the multiple use (plus living inside
    ``lax.while_loop``) keeps LLVM from contracting the multiply-subtract
    into an FMA, which would break last-ulp parity with numpy;
  * the loop's counts (stage takes and enqueues, deliveries, unmet
    slots) are dense one-hot reductions in int32, exact in any order, and
    the per-(job, edge) activity is a boolean any; the one float sum, the
    per-(job, edge) Gbit moved, keeps ``np.bincount`` order under
    ``"masked"`` (a segment-sum over masked lanes adds interspersed
    ``+0.0`` terms, which cannot change an IEEE sum) and is a dense
    reduction under ``"pallas"``; masked minima pad with ``+inf``, which
    never wins.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.ops import segment_sum

from repro.core.plan import MulticastPlan
from repro.core.topology import GBIT_PER_GB
from repro.obs.metrics import REGISTRY
from repro.obs.trace import get_tracer, region

from .simconfig import SimConfig
from .simconfig import resolve as resolve_sim_config

_EPS = 1e-12  # flowsim._EPS
_INF = float("inf")
_TRACK = "sim-host"  # the dispatcher's wall spans (the sim track is sim time)

# the dispatcher's work: segments run, scalars of the loop state read back
# on the host, loop iterations, those that ran the multi-pass cascade,
# that cascade's passes, and chunk copies enqueued to child stages
_segments = REGISTRY.counter("sim.segments")
_host_syncs = REGISTRY.counter("sim.host_syncs")
_loop_iters = REGISTRY.counter("sim.loop_iters")
_cascade_seq_iters = REGISTRY.counter("sim.cascade_seq_iters")
_cascade_seq_passes = REGISTRY.counter("sim.cascade_seq_passes")
_fanout_enqueues = REGISTRY.counter("sim.fanout_enqueues")


class _Sc(NamedTuple):
    """Static (hashable) shape/config key — jit retraces per value."""

    ncp: int  # conns padded to a multiple of 8
    ns: int  # stages (buffers carry one extra dump row)
    j: int  # jobs
    nslot: int  # completion slots
    ne: int  # shared edges
    qcap: int  # ready-queue ring capacity (>= max chunks per job)
    maxch: int  # max children per stage
    nv: int  # VMs
    ne_bound: int  # edge count in the water-filling round bound (0 when
    # link contention is off — the oracle's bound excludes edges then)
    solver: str  # "masked" (f64 parity) | "pallas" (f32 TPU kernel)
    n_iters: int  # pallas kernel grid length


class _Cn(NamedTuple):
    """Per-scenario operands (traced; the device loop never mutates them,
    the host re-points lane endpoints on a connection re-open)."""

    conn_job: jnp.ndarray
    conn_sid: jnp.ndarray
    conn_src: jnp.ndarray  # lane endpoints (global VM ids)
    conn_dst: jnp.ndarray
    conn_edge: jnp.ndarray
    conn_valid: jnp.ndarray
    chunk_size: jnp.ndarray
    conn_first: jnp.ndarray  # first conn index of this conn's stage
    stage_hop: jnp.ndarray  # [NS + 1]
    stage_deliver: jnp.ndarray  # [NS + 1]
    children: jnp.ndarray  # [NS + 1, MAXCH], -1 padded
    slot_job: jnp.ndarray
    slot_need: jnp.ndarray  # n_chunks of the slot's job
    vm_eg: jnp.ndarray
    vm_in: jnp.ndarray
    horizon: jnp.ndarray  # f64 scalar, +inf when None
    drain: jnp.ndarray  # bool scalar
    relay_cap: jnp.ndarray  # i64 scalar
    max_events: jnp.ndarray  # i64 scalar
    t_eps: jnp.ndarray  # f64 scalar (events.T_EPS)
    one: jnp.ndarray  # f64 1.0, runtime-traced — FMA defeat (see _step)
    # pallas solver operands, [8, lanes] tiles (1-element dummies under
    # "masked"); p_src8/p_dst8 follow conn_src/conn_dst
    p_src8: jnp.ndarray
    p_dst8: jnp.ndarray
    p_eid8: jnp.ndarray
    p_eg8: jnp.ndarray
    p_in8: jnp.ndarray


class _St(NamedTuple):
    """Mutable simulation state (the while-loop carry)."""

    now: jnp.ndarray
    it: jnp.ndarray  # loop iterations (the reference's for-range budget)
    events: jnp.ndarray  # iterations that reached the rate step
    seq: jnp.ndarray  # iterations with a relay buffer at capacity
    seq_passes: jnp.ndarray  # stage-batched passes those iterations ran
    fanout: jnp.ndarray  # chunk copies enqueued to child stages
    draining: jnp.ndarray
    stop: jnp.ndarray  # terminal break reached
    t_sched: jnp.ndarray  # next unapplied scripted event time (+inf)
    chunk_arr: jnp.ndarray  # [NCp] chunk id in flight, -1 idle
    remaining: jnp.ndarray  # [NCp] Gbit left of the in-flight chunk
    rate_eff: jnp.ndarray  # [NCp] per-conn cap (host scales on events)
    conn_alive: jnp.ndarray
    arrived: jnp.ndarray  # [J]
    ready_buf: jnp.ndarray  # [NS + 1, QCAP] ring buffers (+ dump row)
    q_head: jnp.ndarray  # [NS + 1] monotonic pop counter
    q_tail: jnp.ndarray  # [NS + 1] monotonic push counter
    relay_occ: jnp.ndarray  # [NS + 1]
    done_bm: jnp.ndarray  # [NS + 1, QCAP] hop-completion dedup
    enq_bm: jnp.ndarray  # [NS + 1, QCAP] fan-in enqueue dedup
    delivered: jnp.ndarray  # [NSLOT]
    finished: jnp.ndarray  # [J]
    finish: jnp.ndarray  # [J] f64, +inf until finished
    jeg: jnp.ndarray  # [J * NE] per-(job, edge) Gbit moved
    jeo: jnp.ndarray  # [J * NE] observation-window Gbit
    jeb: jnp.ndarray  # [J * NE] observation-window busy seconds
    edge_cap: jnp.ndarray  # [NE] shared caps (BIG-like when disabled)
    rates: jnp.ndarray  # [NCp] cached water-filling solution
    last_active: jnp.ndarray  # [NCp] membership the cache was solved for
    rates_valid: jnp.ndarray
    td_time: jnp.ndarray  # [J + 1] buffered sim.job_done instants
    td_job: jnp.ndarray
    td_n: jnp.ndarray


def _onehot(idx, n):
    """``[lanes, n]`` bool: lane ``i`` is in segment ``idx[i]`` (an ``idx``
    outside ``[0, n)`` is in none). Compared in int32: the ids are small
    and the TPU emulates i64."""
    return idx.astype(jnp.int32)[:, None] == jnp.arange(n, dtype=jnp.int32)


def _onehot_sum(v, idx, n):
    """``segment_sum(v, idx, n)`` as a dense compare-and-reduce over
    ``_onehot(idx, n)``. The TPU runs a scatter-add one update at a time;
    this is a few vector ops. A bool ``v`` counts its true lanes in int32,
    exact in any order (a count never exceeds the lane count). A float
    ``v`` adds in the reduction's order, not ``np.bincount``'s."""
    hit = _onehot(idx, n)
    if v.dtype == jnp.bool_:
        return jnp.sum(hit & v[:, None], axis=0, dtype=jnp.int32)
    return jnp.sum(jnp.where(hit, v[:, None], 0), axis=0)


def _compute_rates(st: _St, cn: _Cn, sc: _Sc, active):
    if sc.solver == "pallas":
        from repro.kernels.waterfill.ops import _interpret, _pad128
        from repro.kernels.waterfill.waterfill import pad_lanes, waterfill_8x

        ncl = pad_lanes(sc.ncp)

        def lane(v, width):
            row = jnp.zeros(width, dtype=jnp.float32)
            row = row.at[: v.shape[0]].set(v.astype(jnp.float32))
            return jnp.broadcast_to(row[None, :], (8, width))

        r8 = waterfill_8x(
            lane(st.rate_eff, ncl), lane(active, ncl),
            cn.p_src8, cn.p_dst8, cn.p_eid8,
            cn.p_eg8, cn.p_in8, lane(st.edge_cap, _pad128(sc.ne)),
            n_iters=sc.n_iters, interpret=_interpret(),
        )
        return r8[0, : sc.ncp].astype(st.rates.dtype)
    from repro.kernels.waterfill.ref import masked_maxmin_rates

    return masked_maxmin_rates(
        st.rate_eff, cn.conn_src, cn.conn_dst, cn.vm_eg, cn.vm_in,
        cn.conn_edge, st.edge_cap, active, n_vms=sc.nv, n_edges=sc.ne,
        n_edges_bound=sc.ne_bound,
    )


def _cascade_batch(small, st: _St, cn: _Cn, sc: _Sc, run, blocked=None):
    """One batched refill pass over ``small`` = (chunk_arr, remaining,
    q_head, relay_occ); returns the new four and the lanes that took.

    Every idle lane of a stage not in ``blocked`` ([NS + 1] bool; None
    blocks no stage, which is exact while no relay buffer is full) takes
    the next chunk of its stage's queue in ascending-lane (FIFO) order,
    while the queue lasts. The ring buffers are read through ``st``.
    ``run`` predicates the whole pass (False turns every take off): the
    hot loop calls this unconditionally instead of under ``lax.cond``,
    because a cond whose branches carry the state would make XLA copy the
    O(chunks) ring buffers/bitmaps every iteration (see ``_step``)."""
    chunk_arr, remaining, q_head, relay_occ = small
    i64 = q_head.dtype
    idle = (
        run & (chunk_arr < 0) & st.conn_alive
        & st.arrived[cn.conn_job] & cn.conn_valid
    )
    qlen = st.q_tail - q_head
    elig = idle & (qlen[cn.conn_sid] > 0)
    if blocked is not None:
        elig = elig & ~blocked[cn.conn_sid]
    ef = elig.astype(i64)
    excl = jnp.cumsum(ef) - ef
    rank = excl - excl[cn.conn_first]
    take = elig & (rank < qlen[cn.conn_sid])
    row = jnp.where(take, cn.conn_sid, sc.ns)
    pos = (q_head[row] + rank) % sc.qcap
    ch = st.ready_buf[row, jnp.where(take, pos, 0)]
    cnt = _onehot_sum(take, row, sc.ns + 1)
    return (
        jnp.where(take, ch, chunk_arr),
        jnp.where(take, cn.chunk_size, remaining),
        q_head + cnt,
        relay_occ - jnp.where(cn.stage_hop > 0, cnt, 0),
    ), take


def _cascade_seq(small, st: _St, cn: _Cn, sc: _Sc):
    """The reference cascade with relay buffers binding, as stage-batched
    passes; returns the new ``small`` and the number of passes run.

    Each pass blocks the stages with a child buffer at capacity (read at
    the pass start) and lets every other stage take (``_cascade_batch``),
    until a pass takes nothing. This reaches the reference's fixed point:
    a stage's blocked-ness depends only on its children's ``relay_occ``,
    which only falls during a cascade and nothing is pushed, so an
    unblocked stage takes min(idle lanes, queue) FIFO once and the
    stages unblock monotonically up the stage DAG — at most one taking
    pass per level. Carries only the four small arrays (``small`` =
    (chunk_arr, remaining, q_head, relay_occ)); the ready ring buffers
    are read through ``st`` as a closure capture, so the enclosing
    ``lax.cond`` never has the big buffers among its outputs."""
    kids = jnp.maximum(cn.children, 0)

    def pass_body(carry):
        small, _, passes = carry
        relay_occ = small[3]
        blocked = jnp.any(
            (cn.children >= 0) & (relay_occ[kids] >= cn.relay_cap), axis=1
        )
        small, take = _cascade_batch(small, st, cn, sc, True, blocked)
        return small, jnp.any(take), passes + 1

    small, _, passes = jax.lax.while_loop(
        lambda c: c[1], pass_body,
        (small, jnp.bool_(True), jnp.zeros((), st.seq.dtype)),
    )
    return small, passes


def _step(st: _St, cn: _Cn, sc: _Sc) -> _St:
    """Rate solve + stall check + fluid step + event-less jump, merged.

    The reference picks work vs jump vs stall with branches; here every
    effect is PREDICATED (``jnp.where`` on small arrays, no-op dump-row
    scatters on the big ones) instead of routed through ``lax.cond`` on
    the whole state. XLA resolves conditional aliasing by inserting
    copies, so a state-carrying cond duplicates the O(chunks) ring
    buffers and dedup bitmaps on EVERY loop iteration — measured ~14 MB
    per event at 1e5 chunks, which is what made the device loop lose to
    the numpy engine. Only ``_compute_rates`` (padded-lane output) and
    the rare full-relay multi-pass cascade stay behind conds, and neither
    carries a chunk-sized output."""
    i64 = st.q_head.dtype
    active = st.chunk_arr >= 0
    has_active = jnp.any(active)
    work = ~st.stop & has_active
    jump = ~st.stop & ~has_active
    events = st.events + work.astype(i64)

    changed = work & (~st.rates_valid | jnp.any(active != st.last_active))
    with jax.named_scope("rate_solve"):
        rates = jax.lax.cond(
            changed,
            lambda: _compute_rates(st, cn, sc, active),
            lambda: st.rates,
        )
    with jax.named_scope("state_update"):
        return _advance(st, cn, sc, active, work, jump, events, rates)


def _advance(st: _St, cn: _Cn, sc: _Sc, active, work, jump, events,
             rates) -> _St:
    """The rest of ``_step`` once the rates are known: stall check, fluid
    step, event-less jump, hop completions and enqueues."""
    i64 = st.q_head.dtype
    last_active = jnp.where(work, active, st.last_active)
    rates_valid = st.rates_valid | work
    t_next = jnp.where(st.draining, _INF, st.t_sched)
    stalled = work & (jnp.max(rates) <= 1e-9) & ~jnp.isfinite(t_next)
    adv = work & ~stalled
    jok = jnp.isfinite(t_next) & (t_next < cn.horizon - cn.t_eps)

    # ---- fluid step: the formulas are the reference's verbatim; every
    # consumer masks on ``adv`` (the garbage they produce when adv is
    # False never lands anywhere)
    safe = jnp.maximum(rates, _EPS)
    ratio = jnp.where(active, st.remaining / safe, _INF)
    dt = jnp.maximum(jnp.min(ratio), 1e-9)
    dt = jnp.where(
        jnp.isfinite(t_next) & (st.now + dt > t_next), t_next - st.now, dt
    )
    obs_live = ~st.draining  # telemetry window ends where the drain starts
    cross = adv & (st.now + dt >= cn.horizon - cn.t_eps)
    horizon_hit = cross & ~cn.drain
    draining = st.draining | (cross & cn.drain)
    dt = jnp.where(horizon_hit, cn.horizon - st.now, dt)
    now = jnp.where(adv, st.now + dt, jnp.where(jump & jok, t_next, st.now))

    # The trailing * cn.one (a runtime-traced 1.0) is an FMA defeat: LLVM
    # contracts `rem - rates * dt` (and the segment-sum adds of it) into
    # fused multiply-adds, a 1-ulp drift vs the numpy loop. XLA fusions
    # clone cheap ops, so multi-use alone does not protect the multiply,
    # and bitcast round-trips fold away below XLA. With the extra multiply
    # the contractible producer is `x * one`, and fma(x, 1.0, r) IS the
    # correctly-rounded r + x (the * 1.0 is exact) — contraction becomes
    # harmless instead of prevented.
    moved = rates * dt * cn.one
    act_adv = active & adv
    remaining = jnp.where(act_adv, st.remaining - moved, st.remaining)
    w = jnp.where(act_adv, moved, 0.0)
    je = cn.conn_job * sc.ne + cn.conn_edge
    nje = sc.j * sc.ne
    # the one float sum: np.bincount's order (bitwise parity) under
    # "masked"; dense under "pallas", whose f32 rates are not bitwise
    if sc.solver == "pallas":
        seg = _onehot_sum(w, je, nje)
    else:
        seg = segment_sum(w, je, num_segments=nje)
    jeg = jnp.where(adv, st.jeg + seg, st.jeg)
    je_on = jnp.any(act_adv[:, None] & _onehot(je, nje), axis=0)
    jeo = jnp.where(adv & obs_live, st.jeo + seg, st.jeo)
    jeb = jnp.where(adv & obs_live & je_on, st.jeb + dt, st.jeb)

    # ---- batched hop completions (ascending-conn order is preserved:
    # one parent per child stage, contiguous conns per stage)
    completed = act_adv & (remaining <= 1e-9)
    ch = jnp.maximum(st.chunk_arr, 0)
    sid = cn.conn_sid
    newdone = completed & ~st.done_bm[sid, ch]
    done_bm = st.done_bm.at[sid, ch].max(newdone)
    slot = cn.stage_deliver[sid]
    sval = newdone & (slot >= 0)
    delivered = st.delivered + _onehot_sum(sval, slot, sc.nslot)
    ok_slot = delivered >= cn.slot_need
    bad = _onehot_sum(~ok_slot, cn.slot_job, sc.j)
    job_ok = adv & (bad == 0)
    newly = job_ok & ~st.finished
    finished = st.finished | job_ok
    finish = jnp.where(newly, now, st.finish)
    nf = newly.astype(i64)
    idx = jnp.where(newly, st.td_n + jnp.cumsum(nf) - nf, sc.j)
    td_time = st.td_time.at[idx].set(now)
    td_job = st.td_job.at[idx].set(jnp.arange(sc.j, dtype=i64))
    td_n = st.td_n + jnp.sum(nf)

    ready_buf, q_tail, relay_occ, enq_bm = (
        st.ready_buf, st.q_tail, st.relay_occ, st.enq_bm
    )
    fanout = st.fanout
    for k in range(sc.maxch):
        nsid = cn.children[sid, k]
        has = newdone & (nsid >= 0)
        nsid_cl = jnp.where(has, nsid, sc.ns)
        val = has & ~enq_bm[nsid_cl, ch]
        vf = val.astype(i64)
        excl = jnp.cumsum(vf) - vf
        rank = excl - excl[cn.conn_first]
        row = jnp.where(val, nsid_cl, sc.ns)
        pos = jnp.where(val, (q_tail[row] + rank) % sc.qcap, 0)
        ready_buf = ready_buf.at[row, pos].set(
            jnp.where(val, ch, ready_buf[row, pos])
        )
        cnt = _onehot_sum(val, row, sc.ns + 1)
        q_tail = q_tail + cnt
        relay_occ = relay_occ + cnt
        enq_bm = enq_bm.at[row, ch].max(val)
        fanout = fanout + jnp.sum(vf)

    stop = jnp.where(
        adv, horizon_hit | jnp.all(finished),
        jnp.where(jump, ~jok,
                  jnp.where(stalled, jnp.bool_(True), st.stop)),
    )
    return st._replace(
        now=now, draining=draining, stop=stop, events=events,
        rates=rates, last_active=last_active, rates_valid=rates_valid,
        chunk_arr=jnp.where(completed, -1, st.chunk_arr),
        remaining=jnp.where(completed, 0.0, remaining),
        ready_buf=ready_buf, q_tail=q_tail, relay_occ=relay_occ,
        done_bm=done_bm, enq_bm=enq_bm, fanout=fanout, delivered=delivered,
        finished=finished, finish=finish, jeg=jeg, jeo=jeo, jeb=jeb,
        td_time=td_time, td_job=td_job, td_n=td_n,
    )


@functools.partial(jax.jit, static_argnames=("sc",))
def _segment(st: _St, cn: _Cn, sc: _Sc) -> _St:
    """Run event-loop iterations until a scripted event is due (the host
    applies it and re-enters), a terminal break is reached, or the
    iteration budget is spent."""

    def cond(st):
        would = ~st.draining & (st.t_sched <= st.now + cn.t_eps)
        return ~st.stop & (st.it < cn.max_events) & ~would

    def body(st):
        # Straight-line, predicated body. lax.cond branches that output the
        # O(chunks) buffers force XLA copy-insertion of those buffers every
        # iteration (14MB/iter at 1e5 chunks); every effect below is instead
        # masked with jnp.where / no-op dump-row scatters so the big arrays
        # are donated through the loop carry untouched.
        st = st._replace(it=st.it + 1)
        cross = st.now >= cn.horizon - cn.t_eps
        st = st._replace(
            stop=cross & ~cn.drain, draining=st.draining | (cross & cn.drain)
        )
        run = ~st.stop & ~st.draining
        use_seq = jnp.any(st.relay_occ[: sc.ns] >= cn.relay_cap)
        small = (st.chunk_arr, st.remaining, st.q_head, st.relay_occ)
        with jax.named_scope("cascade_batch"):
            small, _ = _cascade_batch(small, st, cn, sc, run & ~use_seq)
        # The multi-pass cascade (relay caps binding) is rare; it stays
        # behind a cond, but only the four small arrays it writes are
        # carried — the big buffers are closure-read.
        with jax.named_scope("cascade_seq"):
            small, passes = jax.lax.cond(
                run & use_seq,
                lambda t: _cascade_seq(t, st, cn, sc),
                lambda t: (t, jnp.zeros((), st.seq.dtype)),
                small,
            )
        st = st._replace(
            chunk_arr=small[0], remaining=small[1],
            q_head=small[2], relay_occ=small[3],
            seq=st.seq + (run & use_seq).astype(st.seq.dtype),
            seq_passes=st.seq_passes + passes,
        )
        return _step(st, cn, sc)

    return jax.lax.while_loop(cond, body, st)


# ------------------------------------------------------------------ host side
def _pull(cast, x):
    """One scalar of the loop state read back on the host: a sync."""
    _host_syncs.inc()
    return cast(x)


def _build(su, cfg, sched, solver: str):
    """Materialized scenario -> (static key, constants, initial state)."""
    from repro.kernels.waterfill.waterfill import BIG

    nc = int(su.conn_job.shape[0])
    ncp = max(8, -(-nc // 8) * 8)
    ns = int(su.n_stages)
    j = int(su.arrivals.shape[0])
    nslot = int(su.slot_job.shape[0])
    ne = len(su.edges_used)
    nv = int(su.vm_eg_cap.shape[0])
    qcap = max(1, int(su.n_chunks.max()))
    # maxch == 0 (no stage has children anywhere in the batch) statically
    # removes the hop fan-out block from _step — for direct-plan-only
    # workloads its dump-row scatters were pure overhead (~40% of the
    # per-event wall at 1e5 chunks)
    maxch = max((len(c) for c in su.stage_children), default=0)

    def padc(a, fill):
        out = np.full(ncp, fill, dtype=np.asarray(a).dtype)
        out[:nc] = a
        return out

    def pads(a, fill):
        out = np.full(ns + 1, fill, dtype=np.asarray(a).dtype)
        out[:ns] = a
        return out

    children = np.full((ns + 1, maxch), -1, dtype=np.int64)
    for s, kids in enumerate(su.stage_children):
        children[s, : len(kids)] = kids
    first_ci = np.searchsorted(su.conn_sid, np.arange(ns))
    conn_first = padc(first_ci[su.conn_sid], 0)

    use_edge = cfg.link_capacity_scale is not None
    if use_edge:
        edge_cap = np.array([
            su.top.tput[a, b] * cfg.link_capacity_scale
            for a, b in su.edges_used
        ])
    else:
        edge_cap = np.full(ne, BIG)

    n_iters = 2 * nv + ne + 4
    if solver == "pallas":
        from repro.kernels.waterfill.ops import _pad128, lane8
        from repro.kernels.waterfill.waterfill import pad_lanes

        ncl, nv128 = pad_lanes(ncp), _pad128(nv)
        pall = (
            lane8(su.conn_edge, ncl, 0, np.int32),
            lane8(su.vm_eg_cap, nv128, BIG), lane8(su.vm_in_cap, nv128, BIG),
        )
    else:
        pall = (np.zeros((1, 1), dtype=np.float32),) * 3

    from .events import T_EPS

    sc = _Sc(
        ncp=ncp, ns=ns, j=j, nslot=nslot, ne=ne, qcap=qcap, maxch=maxch,
        nv=nv, ne_bound=ne if use_edge else 0, solver=solver,
        n_iters=n_iters,
    )
    max_events = (
        int((su.n_chunks * 6).sum()) * su.max_hops + 10000 + 8 * len(sched)
    )
    cn = _Cn(
        conn_job=jnp.asarray(padc(su.conn_job, 0)),
        conn_sid=jnp.asarray(padc(su.conn_sid, ns)),
        **_lanes(su, sc),
        conn_edge=jnp.asarray(padc(su.conn_edge, 0)),
        conn_valid=jnp.asarray(np.arange(ncp) < nc),
        chunk_size=jnp.asarray(padc(su.chunk_gbit[su.conn_job], 0.0)),
        conn_first=jnp.asarray(conn_first),
        stage_hop=jnp.asarray(pads(su.stage_hop, 0)),
        stage_deliver=jnp.asarray(pads(su.stage_deliver, -1)),
        children=jnp.asarray(children),
        slot_job=jnp.asarray(su.slot_job),
        slot_need=jnp.asarray(su.n_chunks[su.slot_job]),
        vm_eg=jnp.asarray(su.vm_eg_cap),
        vm_in=jnp.asarray(su.vm_in_cap),
        horizon=jnp.float64(
            _INF if cfg.horizon_s is None else cfg.horizon_s
        ),
        drain=jnp.bool_(cfg.drain),
        relay_cap=jnp.int64(cfg.relay_buffer_chunks),
        max_events=jnp.int64(max_events),
        t_eps=jnp.float64(T_EPS),
        one=jnp.float64(1.0),
        p_eid8=jnp.asarray(pall[0]), p_eg8=jnp.asarray(pall[1]),
        p_in8=jnp.asarray(pall[2]),
    )
    st = _St(
        now=jnp.float64(0.0), it=jnp.int64(0), events=jnp.int64(0),
        seq=jnp.int64(0), seq_passes=jnp.int64(0), fanout=jnp.int64(0),
        draining=jnp.bool_(False), stop=jnp.bool_(False),
        t_sched=jnp.float64(sched[0][0] if sched else _INF),
        chunk_arr=jnp.full(ncp, -1, dtype=jnp.int64),
        remaining=jnp.zeros(ncp),
        rate_eff=jnp.asarray(padc(su.conn_rate, 0.0)),
        conn_alive=jnp.asarray(np.arange(ncp) < nc),
        arrived=jnp.zeros(j, dtype=bool),
        ready_buf=jnp.zeros((ns + 1, qcap), dtype=jnp.int64),
        q_head=jnp.zeros(ns + 1, dtype=jnp.int64),
        q_tail=jnp.zeros(ns + 1, dtype=jnp.int64),
        relay_occ=jnp.zeros(ns + 1, dtype=jnp.int64),
        done_bm=jnp.zeros((ns + 1, qcap), dtype=bool),
        enq_bm=jnp.zeros((ns + 1, qcap), dtype=bool),
        delivered=jnp.zeros(nslot, dtype=jnp.int64),
        finished=jnp.zeros(j, dtype=bool),
        finish=jnp.full(j, _INF),
        jeg=jnp.zeros(j * ne), jeo=jnp.zeros(j * ne),
        jeb=jnp.zeros(j * ne),
        edge_cap=jnp.asarray(edge_cap),
        rates=jnp.zeros(ncp),
        last_active=jnp.zeros(ncp, dtype=bool),
        rates_valid=jnp.bool_(False),
        td_time=jnp.zeros(j + 1), td_job=jnp.zeros(j + 1, dtype=jnp.int64),
        td_n=jnp.int64(0),
    )
    return sc, cn, st


def _lanes(su, sc: _Sc) -> dict:
    """The lane endpoint operands of ``_Cn`` from ``su``'s current
    ``conn_src`` / ``conn_dst`` (padded lanes point at VM 0)."""
    src = np.zeros(sc.ncp, dtype=np.int64)
    dst = np.zeros(sc.ncp, dtype=np.int64)
    nc = su.conn_src.shape[0]
    src[:nc], dst[:nc] = su.conn_src, su.conn_dst
    out = {"conn_src": jnp.asarray(src), "conn_dst": jnp.asarray(dst)}
    if sc.solver == "pallas":
        from repro.kernels.waterfill.ops import lane8
        from repro.kernels.waterfill.waterfill import pad_lanes

        ncl = pad_lanes(sc.ncp)
        out["p_src8"] = jnp.asarray(lane8(su.conn_src, ncl, 0, np.int32))
        out["p_dst8"] = jnp.asarray(lane8(su.conn_dst, ncl, 0, np.int32))
    else:
        out["p_src8"] = out["p_dst8"] = jnp.zeros((1, 1), jnp.float32)
    return out


def _host_apply_due(st: _St, su, sched, ptr, vm_alive, retried, use_edge,
                    cn: _Cn, sc: _Sc, tr):
    """Apply every due scripted event — numpy, the exact reference logic
    (including its Skytrace instants). Returns (new state, new operands,
    new ptr): a connection re-open re-points lane endpoints."""
    from .events import RATE_EVENTS, T_EPS, VMFailure, reopen_dead_stages

    now = _pull(float, st.now)
    # np.array (copy): np.asarray of a jax array can be a read-only view
    h = {
        "chunk_arr": np.array(st.chunk_arr), "remaining":
        np.array(st.remaining), "rate_eff": np.array(st.rate_eff),
        "conn_alive": np.array(st.conn_alive), "arrived":
        np.array(st.arrived), "ready_buf": np.array(st.ready_buf),
        "q_tail": np.array(st.q_tail), "relay_occ":
        np.array(st.relay_occ), "edge_cap": np.array(st.edge_cap),
    }
    nc = su.conn_job.shape[0]
    qcap = sc.qcap
    reopened_any = False

    def push(sid, ch):
        h["ready_buf"][sid, h["q_tail"][sid] % qcap] = ch
        h["q_tail"][sid] += 1

    applied_t = None
    rate_n = 0
    while ptr < len(sched) and sched[ptr][0] <= now + T_EPS:
        t_ev = sched[ptr][0]
        ev = sched[ptr][2]
        ptr += 1
        applied_t = t_ev
        if isinstance(ev, int):  # job arrival
            h["arrived"][ev] = True
            firsts = su.first_stage[ev]
            for ch in range(int(su.n_chunks[ev])):
                for s0 in firsts[int(su.chunk_path[ev][ch])]:
                    push(s0, ch)
            if tr.enabled:
                tr.instant("sim.arrival", t_ev, job=int(ev),
                           chunks=int(su.n_chunks[ev]))
        elif isinstance(ev, RATE_EVENTS):
            on_edge = np.array(
                [e == (ev.src, ev.dst) for e in su.edges_used], dtype=bool
            )
            hit = on_edge[su.conn_edge]
            h["rate_eff"][:nc][hit] *= ev.factor
            if use_edge:
                h["edge_cap"][on_edge] *= ev.factor
            rate_n += 1
        elif isinstance(ev, VMFailure):
            kill = [
                v for v in np.flatnonzero(
                    (su.vm_job == ev.job) & (su.vm_region == ev.region)
                )
                if vm_alive[v]
            ][: ev.count]
            requeued = reopened = 0
            if kill:
                vm_alive[kill] = False
                hit = h["conn_alive"][:nc] & (
                    np.isin(su.conn_src, kill)
                    | np.isin(su.conn_dst, kill)
                )
                for ci in np.flatnonzero(hit):
                    if h["chunk_arr"][ci] >= 0:
                        sid = int(su.conn_sid[ci])
                        push(sid, int(h["chunk_arr"][ci]))
                        if su.stage_hop[sid] > 0:
                            h["relay_occ"][sid] += 1
                        retried[su.conn_job[ci]] += 1
                        h["chunk_arr"][ci] = -1
                        h["remaining"][ci] = 0.0
                        requeued += 1
                ca = h["conn_alive"][:nc]
                ca[hit] = False
                reopened = reopen_dead_stages(
                    su, su.conn_sid[hit], ca, vm_alive
                )
                reopened_any |= reopened > 0
            if tr.enabled:
                tr.instant("sim.vm_failure", t_ev, job=int(ev.job),
                           region=int(ev.region), killed=len(kill),
                           requeued=requeued, reopened=reopened)
        else:
            raise TypeError(f"unknown event {ev!r}")
    if applied_t is not None and tr.enabled:
        if rate_n:
            tr.instant("sim.rate_events", applied_t, n=rate_n)
        counts = np.bincount(
            su.conn_edge[h["chunk_arr"][:nc] >= 0],
            minlength=len(su.edges_used),
        )
        for i, (a, b) in enumerate(su.edges_used):
            if counts[i]:
                tr.sample(f"link {a}->{b}", applied_t, int(counts[i]))
    if applied_t is not None:
        st = st._replace(
            chunk_arr=jnp.asarray(h["chunk_arr"]),
            remaining=jnp.asarray(h["remaining"]),
            rate_eff=jnp.asarray(h["rate_eff"]),
            conn_alive=jnp.asarray(h["conn_alive"]),
            arrived=jnp.asarray(h["arrived"]),
            ready_buf=jnp.asarray(h["ready_buf"]),
            q_tail=jnp.asarray(h["q_tail"]),
            relay_occ=jnp.asarray(h["relay_occ"]),
            edge_cap=jnp.asarray(h["edge_cap"]),
            rates_valid=jnp.bool_(False),  # events invalidate the cache
        )
    if reopened_any:
        cn = cn._replace(**_lanes(su, sc))
    st = st._replace(
        t_sched=jnp.float64(sched[ptr][0] if ptr < len(sched) else _INF)
    )
    return st, cn, ptr


def _finalize(st: _St, su, jobs, cfg, retried, tr):
    """Pull the final device state and build MultiSimResult — the exact
    accounting of the reference tail."""
    from .events import T_EPS, JobSimResult, MultiSimResult

    top = su.top
    ne = len(su.edges_used)
    nc = su.conn_job.shape[0]
    # one transfer of everything the accounting reads
    (now, events, it, seq, seq_passes, fanout, chunk_arr, arrived, finished,
     finish_t, delivered, job_edge_gbit, job_edge_obs_gbit,
     job_edge_busy) = jax.device_get((
         st.now, st.events, st.it, st.seq, st.seq_passes, st.fanout,
         st.chunk_arr, st.arrived, st.finished, st.finish, st.delivered,
         st.jeg, st.jeo, st.jeb))
    now = float(now)
    chunk_arr = chunk_arr[:nc]
    _loop_iters.inc(int(it))
    _cascade_seq_iters.inc(int(seq))
    _cascade_seq_passes.inc(int(seq_passes))
    _fanout_enqueues.inc(int(fanout))
    horizon_s = cfg.horizon_s

    horizon_cut = horizon_s is not None and now >= horizon_s - T_EPS
    out = []
    for j, job in enumerate(jobs):
        end = float(finish_t[j]) if finished[j] else now
        dur = max(end - float(su.arrivals[j]), 1e-9)
        eg = job_edge_gbit[j * ne : (j + 1) * ne]
        ego = job_edge_obs_gbit[j * ne : (j + 1) * ne]
        busy = job_edge_busy[j * ne : (j + 1) * ne]
        per_edge_gb = {
            f"{a}->{b}": eg[i] / GBIT_PER_GB
            for i, (a, b) in enumerate(su.edges_used) if eg[i] > 0
        }
        per_edge_obs_gb = {
            f"{a}->{b}": ego[i] / GBIT_PER_GB
            for i, (a, b) in enumerate(su.edges_used) if busy[i] > 0
        }
        per_edge_active_s = {
            f"{a}->{b}": float(busy[i])
            for i, (a, b) in enumerate(su.edges_used) if busy[i] > 0
        }
        eg_cost = sum(
            eg[i] / GBIT_PER_GB * top.price_egress[a, b]
            for i, (a, b) in enumerate(su.edges_used)
        )
        if finished[j]:
            status = "done"
        elif not arrived[j]:
            status, dur = "pending", 0.0
        elif horizon_cut:
            status = "running"
        else:
            status = "stalled"
        slots = su.job_slots[j]
        full_copies = int(min(delivered[s] for s in slots))
        per_dst = (
            {int(su.slot_dst[s]): int(delivered[s]) for s in slots}
            if isinstance(job.plan, MulticastPlan) else None
        )
        vm_cost = float(job.plan.N @ job.plan.top.price_vm) * dur
        out.append(JobSimResult(
            job=j,
            name=job.name,
            time_s=dur,
            tput_gbps=float(full_copies * su.chunk_gbit[j]) / max(dur, 1e-9),
            chunks_delivered=full_copies,
            n_chunks=int(su.n_chunks[j]),
            retried_chunks=int(retried[j]),
            egress_cost=float(eg_cost),
            vm_cost=vm_cost,
            total_cost=float(eg_cost + vm_cost),
            status=status,
            per_edge_gb=per_edge_gb,
            per_dst_delivered=per_dst,
            per_edge_active_s=per_edge_active_s,
            per_edge_obs_gb=per_edge_obs_gb,
            chunks_in_flight=int(np.count_nonzero(
                (su.conn_job == j) & (chunk_arr >= 0)
            )),
        ))
    if tr.enabled:
        tr.instant("sim.end", now,
                   delivered=sum(int(r.chunks_delivered) for r in out))
    return MultiSimResult(jobs=out, time_s=now, events=int(events))


def _rate_solver_for(platform: str, nc: int, nv: int, ne: int) -> str:
    """The rate solver for a scenario of nc connections over nv VMs and ne
    shared edges on a jax ``platform``. On TPU it is the Pallas kernel
    whenever its VMEM estimate at the padded sizes fits
    (``waterfill.fits``; up to ~1e5 connection lanes), and the masked jnp
    solver beyond that. Elsewhere it is always the masked solver: the f64
    path that is bitwise equal to the numpy engine (the Pallas kernel would
    run in interpret mode)."""
    if platform != "tpu":
        return "masked"
    from repro.kernels.waterfill.ops import _pad128
    from repro.kernels.waterfill.waterfill import fits, pad_lanes

    ncp = max(8, -(-nc // 8) * 8)
    if fits(pad_lanes(ncp), _pad128(nv), _pad128(ne)):
        return "pallas"
    return "masked"


def simulate_multi_jax(
    jobs,
    faults=(),
    *,
    config: SimConfig | None = None,
    link_capacity_scale: float | None = 2.0,
    straggler_prob: float = 0.05,
    straggler_speed: tuple[float, float] = (0.15, 0.5),
    relay_buffer_chunks: int = 64,
    seed: int = 0,
    horizon_s: float | None = None,
    exec_top=None,
    drain: bool = False,
    _rate_solver: str = "auto",  # "masked" (f64 parity) | "pallas" | auto:
    # the rule of _rate_solver_for
):
    """Accelerator-resident multi-job simulation (``SimConfig`` knobs and
    ``events`` scenarios identical to the other engines; results pinned
    chunk-for-chunk against them). Prefer ``transfer.sim.simulate`` with
    ``engine="jax"`` over calling this directly."""
    from .events import T_EPS, materialize_jobs, sorted_schedule

    cfg = resolve_sim_config(
        config, link_capacity_scale=link_capacity_scale,
        straggler_prob=straggler_prob, straggler_speed=straggler_speed,
        relay_buffer_chunks=relay_buffer_chunks, seed=seed,
        horizon_s=horizon_s, exec_top=exec_top, drain=drain,
    )
    if _rate_solver not in ("auto", "masked", "pallas"):
        raise ValueError(f"unknown rate solver {_rate_solver!r}")
    with region("sim.run", track=_TRACK, jobs=len(jobs)):
        with region("sim.materialize", track=_TRACK):
            su = materialize_jobs(
                jobs, seed=cfg.seed, straggler_prob=cfg.straggler_prob,
                straggler_speed=cfg.straggler_speed, exec_top=cfg.exec_top,
            )
            sched = sorted_schedule(jobs, faults)
        solver = _rate_solver
        if solver == "auto":
            solver = _rate_solver_for(
                jax.default_backend(), int(su.conn_job.shape[0]),
                int(su.vm_eg_cap.shape[0]), len(su.edges_used),
            )
        # runs per rate solver: which one the size rule picked
        REGISTRY.counter(f"sim.rate_solver.{solver}").inc()
        tr = get_tracer()
        if tr.enabled:
            tr.instant("sim.start", 0.0, jobs=len(jobs),
                       scheduled=len(sched))
        retried = np.zeros(len(jobs), dtype=np.int64)
        vm_alive = np.ones(su.vm_eg_cap.shape[0], dtype=bool)
        with jax.enable_x64(True):
            with region("sim.build", track=_TRACK):
                sc, cn, st = _build(su, cfg, sched, solver)
            ptr = 0
            max_events = int(cn.max_events)
            while True:
                if not _pull(bool, st.draining):
                    with region("sim.apply_due", track=_TRACK):
                        st, cn, ptr = _host_apply_due(
                            st, su, sched, ptr, vm_alive, retried,
                            cfg.link_capacity_scale is not None, cn, sc, tr,
                        )
                _segments.inc()
                with region("sim.segment", track=_TRACK):
                    st = _segment(st, cn, sc)
                    n_td = _pull(int, st.td_n)
                if n_td and tr.enabled:
                    td_time = np.asarray(st.td_time)
                    td_job = np.asarray(st.td_job)
                    for i in range(n_td):
                        tr.instant("sim.job_done", float(td_time[i]),
                                   job=int(td_job[i]))
                if n_td:
                    st = st._replace(td_n=jnp.int64(0))
                if _pull(bool, st.stop) or _pull(int, st.it) >= max_events:
                    break
                due = not _pull(bool, st.draining) and ptr < len(sched) and (
                    sched[ptr][0] <= _pull(float, st.now) + T_EPS
                )
                if not due:
                    break
            with region("sim.finalize", track=_TRACK):
                return _finalize(st, su, jobs, cfg, retried, tr)
