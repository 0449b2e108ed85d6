"""FB2010-1Hr-150-0: coflows of a 150-rack cluster at the trace's rate.

Each lane replays ``traffic["coflows"]`` coflows drawn from the trace's
published shape, arriving Poisson at the trace's mean rate (526 in an
hour), each on racks drawn at random.  A coflow is one job whose DAG is
synthesised as the Metaflow paper does (one metaflow per reducer, the
reducer tasks in a partial order).  Sizes are MB and a port moves one
MB per time unit, so the trace's seconds are scaled by the port speed.

The draws are a copy of ``repro.core.workload.synth_fb_coflow`` and of
the partial-order branch of ``build_job``, so the yardstick does not
move with the program; ``bench/tests/fingerprints.json`` pins the lanes.
"""

from __future__ import annotations

import random

from repro.core.metaflow import JobDAG


def _width(rng: random.Random) -> int:
    u = rng.random()
    if u < 0.52:
        return 1
    if u < 0.85:
        return rng.randint(2, 8)
    if u < 0.97:
        return rng.randint(9, 30)
    return rng.randint(31, 100)


def _flow_mb(rng: random.Random) -> float:
    if rng.random() < 0.9:
        return max(0.1, rng.lognormvariate(1.0, 1.2))
    return max(1.0, rng.lognormvariate(4.0, 1.0))


def coflow(rng: random.Random) -> tuple[int, int, list[list[float]]]:
    """``(mappers, reducers, MB[m][r])``: heavy-tailed widths drawn apart
    for the two sides, log-normal flow sizes with a tail, and a
    log-normal skew per reducer partition."""
    m, r = _width(rng), _width(rng)
    skew = [rng.lognormvariate(0.0, 1.3) for _ in range(r)]
    return m, r, [[_flow_mb(rng) * skew[j] for j in range(r)]
                  for _ in range(m)]


def job(name: str, arrival: float, m: int, r: int, sizes, racks,
        compute_ratio: float, rng: random.Random) -> JobDAG:
    """One coflow as a job: metaflow ``MF<i>`` carries every mapper's
    flow into reducer ``i``; task ``c<i>`` on reducer ``i``'s rack needs
    ``MF<i>`` and, past the first ``w`` (drawn from 2..4), ``c<i-w>``.
    Compute totals ``compute_ratio`` times the coflow's bottleneck
    transfer, spread by reducer input."""
    width = rng.randint(2, 4)
    out = JobDAG(name=name, arrival=arrival)
    for i in range(r):
        out.add_metaflow(f"MF{i}", [(racks[a], racks[m + i], sizes[a][i])
                                    for a in range(m)])
    total = sum(map(sum, sizes))
    gamma = max(max(sum(row) for row in sizes),
                max(sum(sizes[a][i] for a in range(m)) for i in range(r)))
    scale = compute_ratio * gamma / total
    for i in range(r):
        deps = [f"MF{i}"] + ([f"c{i - width}"] if i >= width else [])
        out.add_task(f"c{i}", scale * sum(sizes[a][i] for a in range(m)),
                     machine=racks[m + i], deps=deps)
    out.validate()
    return out


def build_lanes(seeds, traffic: dict, config: dict) -> list:
    """``(n_ports, jobs)`` of each seed: the lane's coflows, in arrival
    order."""
    n_ports = config["n_ports"]
    gap = (config["trace_seconds"] / config["trace_coflows"]
           * config["port_mb_per_s"] / config["port_capacity"])
    out = []
    for seed in seeds:
        rng = random.Random(seed)
        jobs, t = [], 0.0
        for i in range(traffic["coflows"]):
            while True:
                m, r, sizes = coflow(rng)
                if m + r <= n_ports:
                    break
            jobs.append(job(f"coflow{i}", t, m, r, sizes,
                            rng.sample(range(n_ports), m + r),
                            config["compute_ratio"], rng))
            t += rng.expovariate(1.0 / gap)
        out.append((n_ports, jobs))
    return out
