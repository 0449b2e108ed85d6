"""``repro.core.simjax`` — jitted, batched lockstep fifo engine (DESIGN.md §17).

The numpy :class:`~repro.core.simulator.Simulator` advances one scenario
instance at a time; a sweep is N independent Python processes.  This
module ports the **fifo** hot path — MADD bottleneck walk over the
flow→links table, dedup backfill, per-flow event horizons — to jitted
JAX so B seeds/scenario-instances advance **in lockstep** as stacked
arrays: one dispatch serves lane 0's event 312 and lane 19's event 87
simultaneously.  Lanes are padded to the batch maxima (jobs, DAG nodes,
flows, path length, links) and finished lanes are masked
no-ops, so a batch needs ``max(per-lane events)`` steps, not the union.

Two structural choices keep the step fast on CPU XLA, where scatter
serializes: every segment count of the settle cascade (flow→metaflow,
flow→job, edge→node, node→job) is a *static-permutation prefix-sum* —
the index arrays are sorted at pack time, so a reduction is cumsum +
two gathers — and both sequential sweeps (the MADD walk, the backfill)
run as priority *waves*: any group whose contended links are free of
higher-priority pending groups executes now, which reproduces the
sequential order link-by-link (flows sharing a link always execute in
key order across waves) while finishing in a handful of iterations.
The MADD's (job, link) demand and every flow↔link lookup inside a
backfill wave read no index: each is a masked reduction over a static
flow × link mask, because a TPU runs a gather one element at a time.

The numpy core stays the oracle (the ``simref.ReferenceSimulator``
pattern): results agree per-lane on JCT/CCT within float tolerance —
not bit-exact, because XLA may fuse and reorder float accumulations —
and ``tests/test_simjax.py`` gates that on every registered scenario.
Scope: fifo policy, fault-free, uniform ``machine_speed``; anything
else runs on the numpy engine (``repro.experiments.run_cells_batched``
routes accordingly).  The contract a policy must satisfy to join this
engine is written down in DESIGN.md §17.

Worked example — two seeds of a one-job scenario as one batch::

    >>> from repro.core import Fabric
    >>> from repro.core.metaflow import JobDAG
    >>> def lane(size):
    ...     job = JobDAG("j0")
    ...     job.add_metaflow("m0", [(0, 1, size)])
    ...     return pack_instance(Fabric(n_ports=2), [job])
    >>> res = run_fifo_batch([lane(10.0), lane(30.0)])
    >>> [r.jct["j0"] for r in res]      # unit caps: size / 1.0 seconds
    [10.0, 30.0]

The host-CPU wall-clock win over sequential numpy runs for the 20-seed
fifo lanes (≥5x on pipe_serve, the paper's headline scenario, on the
CPU backend) is recorded in ``BENCH_sim_core.json`` by
``benchmarks/perf_sim_core.py --batched``, per scenario and with cold
(compile-inclusive) numbers — batching also amortizes the jit trace:
20 lanes share one program.  It is not a TPU number; ``chip_smoke.py``
runs the engine on the chip.
"""

from __future__ import annotations

import os
from dataclasses import dataclass
from pathlib import Path
from typing import NamedTuple, Sequence

import numpy as np

import jax

# The engine is compared against a float64 oracle; JAX defaults to f32.
# The flag is global, but this engine is the only module under src/ that
# imports JAX (tests/test_simjax.py checks it), so nothing else in a
# process that loads it meets the flipped default.
jax.config.update("jax_enable_x64", True)

import jax.numpy as jnp  # noqa: E402  (after the x64 flag, deliberately)
from jax import lax  # noqa: E402

from repro.core.fabric import Fabric  # noqa: E402
from repro.core.metaflow import EPS, ComputeTask, JobDAG  # noqa: E402

__all__ = [
    "LaneResult",
    "PackedInstance",
    "pack_instance",
    "place_compile_cache",
    "run_fifo_batch",
    "trace_count",
]

#: Priority-key sentinel larger than any real backfill key.
_BIG = np.int64(2 ** 62)


# --------------------------------------------------------------------- pack
@dataclass(frozen=True)
class PackedInstance:
    """One scenario instance flattened to arrays (lane-local sizes).

    Node space: per job (sorted by ``(arrival, name)``, the simulator's
    admission and fifo priority order), compute tasks then metaflows in
    DAG insertion order — the order the numpy core snapshots
    dependency-free roots in, so same-event activation sequences agree.
    Flows are packed metaflow-contiguously, so ``flow_node`` and the
    derived ``flow_job`` are sorted — the invariant behind the
    prefix-sum reductions.
    """

    job_names: tuple[str, ...]          # sorted by (arrival, name)
    arrival: np.ndarray                 # [J] f8
    node_job: np.ndarray                # [N] i4  owning job index
    node_is_mf: np.ndarray              # [N] bool
    node_load: np.ndarray               # [N] f8  compute load (0 for mfs)
    node_pend: np.ndarray               # [N] i4  unmet dependency count
    edge_parent: np.ndarray             # [E] i4
    edge_child: np.ndarray              # [E] i4 (sorted)
    flow_node: np.ndarray               # [F] i4  owning metaflow node
    flow_size: np.ndarray               # [F] f8
    flow_links: np.ndarray              # [F, L] i4, short paths padded
    link_cap: np.ndarray                # [n_links] f8
    n_links: int
    machine_speed: float


def pack_instance(fabric: Fabric, jobs: Sequence[JobDAG],
                  machine_speed: float = 1.0) -> PackedInstance:
    """Flatten ``(fabric, jobs)`` into the array form the batched engine
    consumes.  Mirrors ``Simulator._build_tables``: job order, node
    order, flow order and deterministic routes all match the numpy
    core."""
    for j in jobs:
        j.validate()
    names = [j.name for j in jobs]
    if len(set(names)) != len(names):
        raise ValueError("job names must be unique")
    jobs = sorted(jobs, key=lambda j: (j.arrival, j.name))
    topo = fabric.topology

    node_id: dict[tuple[int, str], int] = {}
    node_job: list[int] = []
    node_is_mf: list[bool] = []
    node_load: list[float] = []
    node_pend: list[int] = []
    edge_parent: list[int] = []
    edge_child: list[int] = []
    flow_node: list[int] = []
    flow_size: list[float] = []
    flow_paths: list[tuple[int, ...]] = []

    for ji, job in enumerate(jobs):
        for name in list(job.tasks) + list(job.metaflows):
            node_id[(ji, name)] = len(node_job)
            node = job.node(name)
            node_job.append(ji)
            is_mf = not isinstance(node, ComputeTask)
            node_is_mf.append(is_mf)
            node_load.append(0.0 if is_mf else float(node.load))
            node_pend.append(len(node.deps))
        for name in list(job.tasks) + list(job.metaflows):
            nid = node_id[(ji, name)]
            for dep in job.node(name).deps:
                edge_parent.append(node_id[(ji, dep)])
                edge_child.append(nid)
        for mf in job.metaflows.values():
            nid = node_id[(ji, mf.name)]
            for f in mf.flows:
                flow_node.append(nid)
                flow_size.append(float(f.size))
                flow_paths.append(tuple(topo.path(f.src, f.dst)))

    n_links = fabric.n_links
    max_len = max((len(p) for p in flow_paths), default=1)
    links = np.full((len(flow_paths), max_len), n_links, dtype=np.int32)
    for i, p in enumerate(flow_paths):
        links[i, :len(p)] = p

    return PackedInstance(
        job_names=tuple(j.name for j in jobs),
        arrival=np.array([j.arrival for j in jobs], dtype=np.float64),
        node_job=np.asarray(node_job, dtype=np.int32),
        node_is_mf=np.asarray(node_is_mf, dtype=bool),
        node_load=np.asarray(node_load, dtype=np.float64),
        node_pend=np.asarray(node_pend, dtype=np.int32),
        edge_parent=np.asarray(edge_parent, dtype=np.int32),
        edge_child=np.asarray(edge_child, dtype=np.int32),
        flow_node=np.asarray(flow_node, dtype=np.int32),
        flow_size=np.asarray(flow_size, dtype=np.float64),
        flow_links=links,
        link_cap=np.asarray(fabric.cap, dtype=np.float64).copy(),
        n_links=n_links,
        machine_speed=float(machine_speed),
    )


class _Batch(NamedTuple):
    """Stacked lanes, padded to batch maxima, plus the static index
    machinery for scatter-free reductions.  Dummy slots: job ``J``
    (arrival=inf, invalid), node ``N`` (pend huge, never activates),
    link ``K`` (cap=inf, absorbs padded path positions)."""

    arrival: jnp.ndarray        # [B, J+1] f8 (pad inf)
    job_valid: jnp.ndarray      # [B, J+1] bool
    node_job: jnp.ndarray       # [B, N+1] i4 (pad J)
    node_is_mf: jnp.ndarray     # [B, N+1] bool
    node_load: jnp.ndarray      # [B, N+1] f8
    node_pend0: jnp.ndarray     # [B, N+1] i4
    node_valid: jnp.ndarray     # [B, N+1] bool
    edge_parent: jnp.ndarray    # [B, E] i4 (pad N)
    flow_node: jnp.ndarray      # [B, F] i4 (pad N, sorted)
    flow_job: jnp.ndarray       # [B, F] i4 (pad J, sorted)
    flow_size: jnp.ndarray      # [B, F] f8 (pad 0)
    flow_pos: jnp.ndarray       # [B, F] i8 position within its metaflow
    link_cap: jnp.ndarray       # [B, K+1] f8 (pad/dummy inf)
    speed: jnp.ndarray          # [B] f8
    # prefix-sum segment bounds (each [B, D+1] for D segments)
    nf_bounds: jnp.ndarray      # flow_node   -> nodes   [B, N+2]
    ne_bounds: jnp.ndarray      # edge_child  -> nodes   [B, N+2]
    jn_bounds: jnp.ndarray      # node_job    -> jobs    [B, J+2]
    jf_bounds: jnp.ndarray      # flow_job    -> jobs    [B, J+2]
    # flow x link incidence: flow f crosses real link k (dummy K never)
    link_mask: jnp.ndarray      # [B, F, K+1] bool


class _State(NamedTuple):
    t: jnp.ndarray              # [B] f8
    admitted: jnp.ndarray       # [B, J+1] bool
    node_state: jnp.ndarray     # [B, N+1] i4  0 idle / 1 active / 2 done
    pend: jnp.ndarray           # [B, N+1] i4
    task_rem: jnp.ndarray       # [B, N+1] f8
    act_seq: jnp.ndarray        # [B, N+1] i8  per-lane activation sequence
    act_ctr: jnp.ndarray        # [B] i8
    flow_rem: jnp.ndarray       # [B, F] f8
    flow_done: jnp.ndarray      # [B, F] bool
    job_done: jnp.ndarray       # [B, J+1] bool
    job_finish: jnp.ndarray     # [B, J+1] f8
    last_flow: jnp.ndarray      # [B, J+1] f8
    done: jnp.ndarray           # [B] bool
    deadlock: jnp.ndarray       # [B] bool
    events: jnp.ndarray         # [B] i8
    # Loop iterations run while the lane was unfinished (int32: 64-bit
    # integers are emulated on the TPU).
    waves: jnp.ndarray          # [B] i4  backfill wave iterations
    cascades: jnp.ndarray       # [B] i4  settle cascade iterations
    steps: jnp.ndarray          # []  i4  lockstep steps the device ran


def _bounds(ids: np.ndarray, n_segs: int) -> np.ndarray:
    """Segment bounds of a *sorted* id array: segment ``d`` occupies
    ``[out[d], out[d+1])``."""
    return np.searchsorted(ids, np.arange(n_segs + 1)).astype(np.int32)


def _seg_sum(vals: jnp.ndarray, bounds: jnp.ndarray) -> jnp.ndarray:
    """Count ``vals`` (integer, [B, M]) over the static segments
    described by ``bounds`` ([B, D+1]) — prefix sum + two gathers, no
    scatter.

    Integer only: ``jnp.cumsum`` lowers to a length-M ``reduce_window``,
    which the TPU compiler builds in well under a second for integers
    but takes minutes over in emulated float64."""
    cs = jnp.pad(jnp.cumsum(vals, axis=1), ((0, 0), (1, 0)))
    bi = jnp.arange(vals.shape[0])[:, None]
    return cs[bi, bounds[:, 1:]] - cs[bi, bounds[:, :-1]]


def _pack_batch(lanes: Sequence[PackedInstance]) -> _Batch:
    B = len(lanes)
    J = max(p.arrival.size for p in lanes)
    N = max(p.node_job.size for p in lanes)
    E = max(p.edge_parent.size for p in lanes)
    F = max(p.flow_node.size for p in lanes)
    L = max(p.flow_links.shape[1] for p in lanes)
    K = max(p.n_links for p in lanes)

    arrival = np.full((B, J + 1), np.inf)
    job_valid = np.zeros((B, J + 1), dtype=bool)
    node_job = np.full((B, N + 1), J, dtype=np.int32)
    node_is_mf = np.zeros((B, N + 1), dtype=bool)
    node_load = np.zeros((B, N + 1))
    node_pend0 = np.full((B, N + 1), 2 ** 30, dtype=np.int32)
    node_valid = np.zeros((B, N + 1), dtype=bool)
    edge_parent = np.full((B, E), N, dtype=np.int32)
    edge_child = np.full((B, E), N, dtype=np.int32)
    flow_node = np.full((B, F), N, dtype=np.int32)
    flow_job = np.full((B, F), J, dtype=np.int32)
    flow_size = np.zeros((B, F))
    flow_links = np.full((B, F, L), K, dtype=np.int32)
    flow_pos = np.zeros((B, F), dtype=np.int64)
    link_cap = np.full((B, K + 1), np.inf)
    speed = np.empty(B)

    for b, p in enumerate(lanes):
        j, n, e, f = (p.arrival.size, p.node_job.size, p.edge_parent.size,
                      p.flow_node.size)
        arrival[b, :j] = p.arrival
        job_valid[b, :j] = True
        node_job[b, :n] = p.node_job
        node_is_mf[b, :n] = p.node_is_mf
        node_load[b, :n] = p.node_load
        node_pend0[b, :n] = p.node_pend
        node_valid[b, :n] = True
        edge_parent[b, :e] = p.edge_parent
        edge_child[b, :e] = p.edge_child
        flow_node[b, :f] = p.flow_node
        flow_job[b, :f] = p.node_job[p.flow_node]
        flow_size[b, :f] = p.flow_size
        flow_links[b, :f, :p.flow_links.shape[1]] = np.where(
            p.flow_links == p.n_links, K, p.flow_links)
        # Position within the owning metaflow: flows are packed
        # metaflow-contiguously, so each group is a run of equal
        # flow_node values.
        if f:
            starts = np.flatnonzero(np.diff(p.flow_node, prepend=-1) != 0)
            pos = np.arange(f, dtype=np.int64)
            flow_pos[b, :f] = pos - np.repeat(
                pos[starts], np.diff(np.append(starts, f)))
        link_cap[b, :p.n_links] = p.link_cap
        speed[b] = p.machine_speed

    # --- static reduction machinery (all id arrays above are sorted)
    nf_bounds = np.stack([_bounds(flow_node[b], N + 1) for b in range(B)])
    ne_bounds = np.stack([_bounds(edge_child[b], N + 1) for b in range(B)])
    jn_bounds = np.stack([_bounds(node_job[b], J + 1) for b in range(B)])
    jf_bounds = np.stack([_bounds(flow_job[b], J + 1) for b in range(B)])

    # The mask counts a link once per flow, which stands for the route
    # only if no route crosses a link twice.
    legs = np.sort(flow_links, axis=2)
    if ((legs[..., 1:] == legs[..., :-1]) & (legs[..., 1:] < K)).any():
        raise ValueError("a route crosses one link twice")
    real = flow_links < K
    b_ix, f_ix, _ = np.nonzero(real)
    link_mask = np.zeros((B, F, K + 1), dtype=bool)
    link_mask[b_ix, f_ix, flow_links[real]] = True

    return _Batch(*map(jnp.asarray, (
        arrival, job_valid, node_job, node_is_mf, node_load, node_pend0,
        node_valid, edge_parent, flow_node, flow_job, flow_size,
        flow_pos, link_cap, speed,
        nf_bounds, ne_bounds, jn_bounds, jf_bounds, link_mask)))


def _init_state(pk: _Batch) -> _State:
    B, J1 = pk.arrival.shape
    N1 = pk.node_job.shape[1]
    return _State(
        t=jnp.zeros(B),
        admitted=jnp.zeros((B, J1), dtype=bool),
        node_state=jnp.zeros((B, N1), dtype=jnp.int32),
        pend=pk.node_pend0,
        task_rem=pk.node_load,
        act_seq=jnp.full((B, N1), _BIG),
        act_ctr=jnp.zeros(B, dtype=jnp.int64),
        flow_rem=pk.flow_size,
        # Zero-size flows are born finished (Simulator._build_tables
        # presets _flow_done), so they never stamp last_flow.
        flow_done=pk.flow_size <= EPS,
        job_done=jnp.zeros((B, J1), dtype=bool),
        job_finish=jnp.zeros((B, J1)),
        last_flow=jnp.where(jnp.isfinite(pk.arrival), pk.arrival, 0.0),
        done=jnp.zeros(B, dtype=bool),
        deadlock=jnp.zeros(B, dtype=bool),
        events=jnp.zeros(B, dtype=jnp.int64),
        waves=jnp.zeros(B, dtype=jnp.int32),
        cascades=jnp.zeros(B, dtype=jnp.int32),
        steps=jnp.zeros((), dtype=jnp.int32),
    )


# ------------------------------------------------------------------- settle
@jax.named_scope("simjax.settle")
def _settle(pk: _Batch, s: _State) -> _State:
    """Commit everything instantaneous at the current lane times:
    admissions, flow/metaflow/task completions, the DAG activation
    cascade (breadth-first waves to a fixpoint), job retirement, and
    lane-done flags.  Idempotent on the simulated state — running it
    twice changes nothing but the ``cascades`` counter."""
    B = s.t.shape[0]
    bi = jnp.arange(B)[:, None]

    admitted = s.admitted | (pk.job_valid & (pk.arrival <= s.t[:, None] + EPS))

    # Newly drained flows stamp the owning job's last-flow time (the
    # numpy core does this in its completion commit).
    new_fd = ~s.flow_done & (s.flow_rem <= EPS)
    flow_done = s.flow_done | new_fd
    hit = _seg_sum(new_fd.astype(jnp.int32), pk.jf_bounds) > 0
    last_flow = jnp.where(hit, s.t[:, None], s.last_flow)
    # Live-flow counts per metaflow are fixed for the whole cascade
    # (flow_rem only changes in _kick).
    flows_left = _seg_sum((~flow_done).astype(jnp.int32), pk.nf_bounds)
    adm_node = admitted[bi, pk.node_job]

    def cascade(carry):
        node_state, pend, act_seq, act_ctr, last_flow, n, _ = carry
        new_done = (node_state == 1) & jnp.where(pk.node_is_mf,
                                                 flows_left == 0,
                                                 s.task_rem <= EPS)
        node_state = jnp.where(new_done, 2, node_state)
        # finish_metaflow stamps last_flow even for zero-flow metaflows.
        mf_hit = _seg_sum((new_done & pk.node_is_mf).astype(jnp.int32),
                          pk.jn_bounds) > 0
        last_flow = jnp.where(mf_hit, s.t[:, None], last_flow)
        dec = _seg_sum(new_done[bi, pk.edge_parent].astype(jnp.int32),
                       pk.ne_bounds)
        pend = pend - dec
        act = (node_state == 0) & (pend <= 0) & pk.node_valid & adm_node
        node_state = jnp.where(act, 1, node_state)
        rank = jnp.cumsum(act.astype(jnp.int64), axis=1)
        act_seq = jnp.where(act, act_ctr[:, None] + rank - 1, act_seq)
        act_ctr = act_ctr + rank[:, -1]
        changed = (new_done | act).any()
        return node_state, pend, act_seq, act_ctr, last_flow, n + 1, changed

    carry = (s.node_state, s.pend, s.act_seq, s.act_ctr, last_flow,
             jnp.int32(0), jnp.array(True))
    carry = lax.while_loop(lambda c: c[-1], cascade, carry)
    node_state, pend, act_seq, act_ctr, last_flow, n_casc, _ = carry

    unfin = _seg_sum(((node_state != 2) & pk.node_valid).astype(jnp.int32),
                     pk.jn_bounds)
    new_jd = admitted & (unfin == 0) & ~s.job_done
    job_done = s.job_done | new_jd
    job_finish = jnp.where(new_jd, s.t[:, None], s.job_finish)
    done = (job_done | ~pk.job_valid).all(axis=1)

    return s._replace(admitted=admitted, node_state=node_state, pend=pend,
                      act_seq=act_seq, act_ctr=act_ctr, flow_done=flow_done,
                      job_done=job_done, job_finish=job_finish,
                      last_flow=last_flow, done=done,
                      cascades=s.cascades + jnp.where(s.done, 0, n_casc))


# --------------------------------------------------------------------- kick
def _demand(pk: _Batch, w: jnp.ndarray, job: jnp.ndarray) -> jnp.ndarray:
    """Job ``job``'s demand on each link, ``[B, K+1]``: the sum of ``w``
    ([B, F], never negative) over the job's flows that cross the link.

    A masked sum over the flow axis of ``link_mask``, with no gather:
    the TPU runs a gather one element at a time.  A link that no flow of
    the job with positive weight crosses sums exact ``+0.0``s, so
    ``dem > 0`` marks exactly the links the job uses; the dummy link's
    column is empty, so padded legs add nothing.  One job at a time, so
    that the chip's compiler keeps the links in the vector lanes and
    sums the flows across vector rows, as in the backfill waves: over
    all jobs at once (``[B, F, J+1, K+1]``) it lays the flows in the
    lanes where flows outnumber links, at 4 to 17 times the cost of an
    element (DESIGN.md §17)."""
    on = pk.link_mask & (pk.flow_job == job)[:, :, None]
    return jnp.where(on, w[:, :, None], 0.0).sum(axis=1)


def _kick(pk: _Batch, s: _State) -> _State:
    """One fifo decision + fluid advance per lane: MADD each job's
    coflow (all its active metaflows) in job-priority order on the
    residual link capacities, work-conserving backfill over the live
    flows in priority waves, then advance every lane to its own next
    event time.  Done lanes get dt=0 and stay bit-frozen; lanes with no
    possible progress raise the deadlock flag (checked on the host)."""
    B, F = s.flow_rem.shape
    J1 = pk.arrival.shape[1]
    N1 = pk.node_job.shape[1]
    bi = jnp.arange(B)[:, None]

    with jax.named_scope("simjax.madd"):
        # --- MADD walk: a scan over jobs in fifo order whose body is a
        # masked reduction and elementwise work on [B, links] and
        # [B, flows]; no index is read but each flow's node state.
        live = (s.node_state[bi, pk.flow_node] == 1) & (s.flow_rem > EPS)
        w = jnp.where(live, s.flow_rem, 0.0)

        def madd(carry, j):
            res, rates = carry
            dem = _demand(pk, w, j)
            used = dem > 0.0
            blocked = (used & (res <= EPS)).any(axis=1)
            gamma = jnp.where(used & (res > EPS), dem / res, 0.0).max(axis=1)
            ok = ~blocked & (gamma > EPS)
            safe = jnp.where(ok, gamma, 1.0)
            res = jnp.where(ok[:, None],
                            jnp.clip(res - dem / safe[:, None], 0.0, None), res)
            rates = jnp.where(live & (pk.flow_job == j) & ok[:, None],
                              s.flow_rem / safe[:, None], rates)
            return (res, rates), None

        (res, rates), _ = lax.scan(madd, (pk.link_cap, jnp.zeros((B, F))),
                                   jnp.arange(J1 - 1, dtype=jnp.int32))

    with jax.named_scope("simjax.backfill"):
        # --- backfill: priority key = (job, metaflow activation order, flow
        # position) — the numpy walk's concatenation order.  Flows execute
        # in priority *waves*: a flow runs once no pending higher-priority
        # flow shares any of its links, which applies the per-link
        # subtractions in exactly the sequential sweep's order.  The numpy
        # core's first-live-flow-per-route optimization needs no analogue
        # here: a grant zeroes the path's smallest residual, so same-route
        # followers are retired by the capacity filter below, exactly.
        seq = jnp.minimum(s.act_seq[bi, pk.flow_node], N1 + 1)
        key = ((pk.flow_job.astype(jnp.int64) * (N1 + 2) + seq) * (F + 1)
               + pk.flow_pos)
        keyed = jnp.where(live, key, _BIG)
        # Every flow <-> link lookup of a wave is a masked reduction over
        # the [B, F, K+1] incidence: no gathers in the loop body.  The
        # dummy link is in no flow's row and no flow is in its column, so
        # padded path legs and padded flows drop out of every reduction.
        mask = pk.link_mask

        def wave(carry):
            res, rates, pending, n, _ = carry
            # Residuals only shrink during the sweep, so a flow whose path
            # minimum is already ≤ EPS can never receive a grant at its
            # turn — retiring it now is exact and collapses the priority
            # chains to the few flows with actual capacity.
            h_row = jnp.where(mask, res[:, None, :], jnp.inf).min(axis=2)
            pending = pending & (h_row > EPS)
            key_p = jnp.where(pending, keyed, _BIG)
            best = jnp.where(mask, key_p[:, :, None], _BIG).min(axis=1)
            # A flow is at its turn iff it is the best (minimum-key) pending
            # flow on EVERY link it crosses.  best ≤ key on each of its
            # links (its own key participates in those minima), so the
            # test is min-over-links == key.
            at_turn = pending & (jnp.where(mask, best[:, None, :], _BIG)
                                 .min(axis=2) == keyed)
            h = jnp.where(at_turn, h_row, 0.0)
            rates = rates + h
            # At-turn flows are link-disjoint (keys are unique), so each
            # link's sum has at most one non-zero term: exact.
            res = res - jnp.where(mask, h[:, :, None], 0.0).sum(axis=1)
            pending = pending & ~at_turn
            return res, rates, pending, n + 1, pending.any()

        carry = (res, rates, live, jnp.int32(0), live.any())
        res, rates, _, n_waves, _ = lax.while_loop(lambda c: c[-1], wave,
                                                   carry)

    with jax.named_scope("simjax.horizon"):
        # --- event horizon
        flowing = (rates > EPS) & (s.flow_rem > EPS)
        dt = jnp.where(flowing, s.flow_rem / jnp.where(flowing, rates, 1.0),
                       jnp.inf).min(axis=1)
        task_running = (s.node_state == 1) & ~pk.node_is_mf & pk.node_valid
        dt = jnp.minimum(dt, jnp.where(task_running, s.task_rem, jnp.inf)
                         .min(axis=1) / pk.speed)
        waiting = pk.job_valid & ~s.admitted
        dt = jnp.minimum(dt, jnp.where(waiting, pk.arrival, jnp.inf)
                         .min(axis=1) - s.t)
        dead = ~s.done & jnp.isinf(dt)
        dt = jnp.where(s.done | dead, 0.0, jnp.maximum(dt, 0.0))

        # --- fluid advance
        flow_rem = jnp.where(
            flowing, jnp.clip(s.flow_rem - rates * dt[:, None], 0.0, None),
            s.flow_rem)
        task_rem = jnp.where(
            task_running,
            jnp.maximum(s.task_rem - pk.speed[:, None] * dt[:, None], 0.0),
            s.task_rem)
        return s._replace(t=s.t + dt, flow_rem=flow_rem, task_rem=task_rem,
                          deadlock=s.deadlock | dead,
                          events=s.events + (~s.done).astype(jnp.int64),
                          waves=s.waves + jnp.where(s.done, 0, n_waves))


_TRACES = 0


def _step(pk: _Batch, s: _State) -> _State:
    """One lockstep event for every unfinished lane: advance each lane
    to its own next event time, then settle the consequences."""
    global _TRACES
    _TRACES += 1                     # executes at trace time only
    return _settle(pk, _kick(pk, s))


def _multi_step(pk: _Batch, s: _State, n: int) -> _State:
    """Lockstep events in one device program while some lane is neither
    done nor deadlocked, at most ``n`` of them: the program stops on the
    device at the last lane's end, and the host reads the flags once,
    after it.  The state's ``steps`` counts the steps run."""
    def body(carry):
        i, st = carry
        st = _step(pk, st)
        return i + 1, st._replace(steps=st.steps + 1)

    def running(carry):
        i, st = carry
        return (i < n) & ~(st.done | st.deadlock).all()

    return lax.while_loop(running, body, (jnp.int32(0), s))[1]


_multi_step_jit = jax.jit(_multi_step, static_argnums=2)
_settle_jit = jax.jit(_settle)


def place_compile_cache() -> str:
    """Place JAX's persistent compilation cache; returns its directory.

    Entry points that run the engine call this before their first
    compile; importing the module sets nothing.
    ``JAX_COMPILATION_CACHE_DIR``, when set, is JAX's own setting and
    wins.  Otherwise the cache is ``.jax_cache/`` at the root of this
    checkout: a fixed path, because the directory is part of what a
    later run must find."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(Path(__file__).resolve().parents[3] / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def trace_count() -> int:
    """How many times the jitted step has been traced (== number of
    distinct batch shapes seen).  The recompilation-guard test pins one
    trace per scenario shape."""
    return _TRACES


# ---------------------------------------------------------------------- run
@dataclass(frozen=True)
class LaneResult:
    """Per-lane outcome, keyed like ``SimResult``: per-job JCT/CCT by
    job name, plus the lane makespan and lockstep event count.

    The counters say what the engine did for the lane: backfill wave
    and settle cascade iterations run while the lane was unfinished
    (the batch's count is the maximum over its lanes), and, the same for
    every lane of a batch, the lockstep steps the device executed (the
    device's own count, which stops at the last lane's end) and the
    host syncs (reads of the done/deadlock flags) it took."""

    jct: dict[str, float]
    cct: dict[str, float]
    makespan: float
    events: int
    wave_iters: int = 0
    cascade_iters: int = 0
    batch_steps: int = 0
    batch_syncs: int = 0


def run_fifo_batch(lanes: Sequence[PackedInstance], *,
                   steps_per_sync: int = 65_536,
                   max_events: int = 5_000_000) -> list[LaneResult]:
    """Advance every lane to completion under the fifo policy; returns
    per-lane results in input order.  Each dispatch runs one device
    program that steps until every lane is done or deadlocked, at most
    ``steps_per_sync`` steps; the host then reads the done/deadlock
    flags once.  ``steps_per_sync`` is thus the window of the
    ``max_events`` livelock guard, counted on the host.  Its default is
    far above the steps of every benchmark cell (the longest,
    DeepSeek-V3's EP64 stage, takes about 3,750), so a call ends in one
    program, one dispatch and one read; and a livelocked program still
    returns to the guard within about two minutes at the slowest step
    measured on a TPU v5e (1.6 ms).  Raises on deadlock (mirroring the
    numpy core) and on ``max_events``.

    Under ``jax.profiler`` the call shows as host spans, on the device
    trace's clock: ``simjax.pack_batch`` (padding and the copies to the
    device), ``simjax.init`` (first settle), then per program
    ``simjax.dispatch`` (its enqueue) and ``simjax.sync`` (the
    done/deadlock read, which waits for it), and ``simjax.readback``.
    Its device ops carry the named scopes ``simjax.settle``,
    ``simjax.madd``, ``simjax.backfill`` and ``simjax.horizon`` in their
    op names.  With the profiler off the spans cost a no-op each.
    Counters: see :class:`LaneResult`; ``wave_iters / batch_steps`` is
    backfill waves per step.  DESIGN.md §17 says how to read them."""
    if not lanes:
        return []
    with jax.profiler.TraceAnnotation("simjax.pack_batch"):
        pk = _pack_batch(lanes)
    with jax.profiler.TraceAnnotation("simjax.init"):
        s = _settle_jit(pk, _init_state(pk))
    steps = syncs = 0
    while True:
        with jax.profiler.TraceAnnotation("simjax.dispatch"):
            s = _multi_step_jit(pk, s, steps_per_sync)
        steps += steps_per_sync
        with jax.profiler.TraceAnnotation("simjax.sync"):
            halted = np.asarray(s.done | s.deadlock)
        syncs += 1
        if halted.all():
            break
        if steps > max_events:
            raise RuntimeError(
                "batched simulator exceeded max_events — livelock?")
    with jax.profiler.TraceAnnotation("simjax.readback"):
        if bool(np.asarray(s.deadlock).any()):
            bad = [i for i, d in enumerate(np.asarray(s.deadlock).tolist())
                   if d]
            raise RuntimeError(
                f"deadlock: no progress possible in lanes {bad}")

        t = np.asarray(s.t)
        jf = np.asarray(s.job_finish)
        lf = np.asarray(s.last_flow)
        ev = np.asarray(s.events)
        wv = np.asarray(s.waves)
        cs = np.asarray(s.cascades)
        n_steps = int(s.steps)
        return [
            LaneResult(
                jct={n: float(jf[b, i] - p.arrival[i])
                     for i, n in enumerate(p.job_names)},
                cct={n: float(lf[b, i] - p.arrival[i])
                     for i, n in enumerate(p.job_names)},
                makespan=float(t[b]),
                events=int(ev[b]),
                wave_iters=int(wv[b]),
                cascade_iters=int(cs[b]),
                batch_steps=n_steps,
                batch_syncs=syncs,
            )
            for b, p in enumerate(lanes)
        ]
