"""Plain fifo reference: one lane, one flow and one event at a time.

The yardstick that decides ``correct``.  It states the semantics the
engine under test is held to and imports nothing of the program: a lane
arrives as plain data (see ``simulate``), routes are worked out here from
the fabric the configuration names, and every sum is taken in order, in
the number type it is given.

Fifo over a fluid network:

* jobs are served in ``(arrival, name)`` order; a job is admitted at its
  arrival, and a node (compute task or metaflow) becomes active once its
  job is admitted and all its dependencies are done.  Activation order
  (per lane) is a counter taken in node order within one cascade round;
* each event, every job in turn takes MADD on the residual link
  capacities: all its live flows together finish at one time ``gamma``,
  the largest demand/residual ratio over the links they use; a job that
  needs an exhausted link gets nothing from MADD;
* then a work-conserving backfill: live flows in priority order (job,
  activation order of the metaflow, position in the metaflow) each take
  the smallest residual along their path;
* time advances to the next flow drain, task finish or arrival.
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np

EPS = 1e-9


@dataclass
class Result:
    """Per-job completion (JCT) and last-transfer (CCT) times."""

    jct: dict[str, float]
    cct: dict[str, float]
    events: int


def big_switch_path(n_ports: int, src: int, dst: int) -> tuple[int, ...]:
    """The source's egress link then the destination's ingress link."""
    return (src, n_ports + dst)


def simulate(lane: dict, dtype=np.float64, max_events: int = 1_000_000
             ) -> Result:
    """Run one lane to completion.

    ``lane``: ``{"n_ports": int, "port_capacity": float, "machine_speed":
    float, "jobs": [{"name", "arrival", "nodes": [{"name", "deps",
    "load"} | {"name", "deps", "flows": [(src, dst, size), ...]}]}]}``,
    nodes in the job's own order (compute tasks, then metaflows).
    ``dtype`` is the number type every time, size and rate is held in.
    """
    num = float if dtype == np.float64 else np.dtype(dtype).type
    n_ports = lane["n_ports"]
    res0 = [num(lane["port_capacity"])] * (2 * n_ports)
    speed = num(lane.get("machine_speed", 1.0))
    jobs = sorted(lane["jobs"], key=lambda j: (j["arrival"], j["name"]))
    arrival = [num(j["arrival"]) for j in jobs]

    # Nodes in lane order: jobs in service order, each job's own order.
    node_job, node_is_mf, node_pend, children = [], [], [], []
    task_rem, node_flows = [], []
    flow_job, flow_node, flow_path, flow_rem, flow_done = [], [], [], [], []
    job_nodes: list[list[int]] = []
    for ji, job in enumerate(jobs):
        ids = {}
        first = len(node_job)
        for nd in job["nodes"]:
            ids[nd["name"]] = len(node_job)
            node_job.append(ji)
            node_pend.append(len(nd["deps"]))
            children.append([])
            is_mf = "flows" in nd
            node_is_mf.append(is_mf)
            task_rem.append(num(0.0) if is_mf else num(nd["load"]))
            fl = []
            for src, dst, size in nd.get("flows", ()):
                fl.append(len(flow_rem))
                flow_job.append(ji)
                flow_node.append(ids[nd["name"]])
                flow_path.append(big_switch_path(n_ports, src, dst))
                flow_rem.append(num(size))
                # Zero-size flows are finished from the start.
                flow_done.append(num(size) <= EPS)
            node_flows.append(fl)
        for nd in job["nodes"]:
            for dep in nd["deps"]:
                children[ids[dep]].append(ids[nd["name"]])
        job_nodes.append(list(range(first, len(node_job))))

    n_jobs, n_nodes = len(jobs), len(node_job)
    state = [0] * n_nodes               # 0 idle, 1 active, 2 done
    act_seq = [0] * n_nodes
    act_ctr = 0
    admitted = [False] * n_jobs
    job_done = [False] * n_jobs
    finish = [num(0.0)] * n_jobs
    last_flow = list(arrival)
    t = num(0.0)

    def settle() -> None:
        nonlocal act_ctr
        for j in range(n_jobs):
            if not admitted[j] and arrival[j] <= t + EPS:
                admitted[j] = True
        for f, rem in enumerate(flow_rem):
            if not flow_done[f] and rem <= EPS:
                flow_done[f] = True
                last_flow[flow_job[f]] = t
        flows_left = [sum(not flow_done[f] for f in node_flows[n])
                      for n in range(n_nodes)]
        changed = True
        while changed:
            newly = [n for n in range(n_nodes) if state[n] == 1 and (
                flows_left[n] == 0 if node_is_mf[n]
                else task_rem[n] <= EPS)]
            for n in newly:
                state[n] = 2
                if node_is_mf[n]:
                    last_flow[node_job[n]] = t
                for c in children[n]:
                    node_pend[c] -= 1
            act = [n for n in range(n_nodes) if state[n] == 0
                   and node_pend[n] <= 0 and admitted[node_job[n]]]
            for n in act:
                state[n] = 1
                act_seq[n] = act_ctr
                act_ctr += 1
            changed = bool(newly or act)
        for j in range(n_jobs):
            if admitted[j] and not job_done[j] and all(
                    state[n] == 2 for n in job_nodes[j]):
                job_done[j] = True
                finish[j] = t

    def kick() -> None:
        nonlocal t
        live = [f for f in range(len(flow_rem))
                if state[flow_node[f]] == 1 and flow_rem[f] > EPS]
        res = list(res0)
        rate = {f: num(0.0) for f in live}
        by_job: dict[int, list[int]] = {}
        for f in live:
            by_job.setdefault(flow_job[f], []).append(f)
        for j in range(n_jobs):                 # MADD, in fifo order
            fl = by_job.get(j)
            if not fl:
                continue
            dem: dict[int, object] = {}
            for f in fl:
                for lk in flow_path[f]:
                    dem[lk] = dem.get(lk, num(0.0)) + flow_rem[f]
            used = [lk for lk, d in dem.items() if d > 0.0]
            if any(res[lk] <= EPS for lk in used):
                continue
            gamma = max((dem[lk] / res[lk] for lk in used),
                        default=num(0.0))
            if not gamma > EPS:
                continue
            for lk in used:
                res[lk] = max(res[lk] - dem[lk] / gamma, num(0.0))
            for f in fl:
                rate[f] = flow_rem[f] / gamma
        # Work-conserving backfill in priority order.
        for f in sorted(live, key=lambda f: (
                flow_job[f], act_seq[flow_node[f]], f)):
            h = min(res[lk] for lk in flow_path[f])
            if h > EPS:
                rate[f] = rate[f] + h
                for lk in flow_path[f]:
                    res[lk] = res[lk] - h
        flowing = [f for f in live if rate[f] > EPS]
        dts = [flow_rem[f] / rate[f] for f in flowing]
        running = [n for n in range(n_nodes)
                   if state[n] == 1 and not node_is_mf[n]]
        dts += [task_rem[n] / speed for n in running]
        dts += [arrival[j] - t for j in range(n_jobs) if not admitted[j]]
        if not dts:
            raise RuntimeError("deadlock: no flow, task or arrival left")
        dt = max(min(dts), num(0.0))
        for f in flowing:
            flow_rem[f] = max(flow_rem[f] - rate[f] * dt, num(0.0))
        for n in running:
            task_rem[n] = max(task_rem[n] - speed * dt, num(0.0))
        t = t + dt

    settle()
    events = 0
    while not all(job_done):
        if events >= max_events:
            raise RuntimeError(f"no end after {max_events} events")
        kick()
        settle()
        events += 1
    return Result(
        jct={j["name"]: float(finish[i] - arrival[i])
             for i, j in enumerate(jobs)},
        cct={j["name"]: float(last_flow[i] - arrival[i])
             for i, j in enumerate(jobs)},
        events=events)
