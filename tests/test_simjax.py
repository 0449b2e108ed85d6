"""The lockstep JAX engine vs the numpy oracle (DESIGN.md §17).

Three contracts:
  1. per-lane JCT/CCT equivalence with the numpy ``Simulator`` on every
     registered scenario, >= 5 seeds per scenario, within float
     tolerance (XLA reorders float accumulations, so bit-exactness is
     not promised — observed divergence is ~1e-12);
  2. padding/masking invariants: heterogeneous lanes batched together
     (different job counts, flow counts, path lengths) behave exactly
     as if each ran alone — padding slots never leak into results
     (hypothesis-randomized when available, pinned cases always);
  3. one jit trace per batch shape: re-running a shape recompiles
     nothing (``trace_count`` guard).

The engine turns on JAX's process-wide ``jax_enable_x64`` flag at
import; ``test_simjax_is_the_only_jax_user`` keeps that safe by keeping
every other module under ``src/`` free of JAX.
"""

from __future__ import annotations

import random
import re
from pathlib import Path

import numpy as np
import pytest

jax = pytest.importorskip(
    "jax", reason="the lockstep engine is optional: everything else "
                  "runs on the numpy core without JAX installed")
jnp = jax.numpy

from repro.appdag.mixer import SCENARIOS, build_scenario  # noqa: E402
from repro.core import Fabric, JobDAG, make_scheduler, simulate  # noqa: E402
from repro.core.fabric import Topology, make_topology  # noqa: E402
from repro.core.simjax import (LaneResult, _demand,  # noqa: E402
                               _pack_batch, pack_instance, run_fifo_batch,
                               trace_count)

TOL = 1e-6
N_SEEDS = 5


def _numpy_oracle(scenario: str, seed: int):
    fabric, jobs = build_scenario(scenario, seed=seed, quick=True,
                                  lint=False)
    return simulate(jobs, make_scheduler("fifo"), fabric=fabric)


def _max_diff(lane: LaneResult, ref) -> float:
    assert set(lane.jct) == set(ref.jct)
    diff = max(abs(lane.jct[n] - ref.jct[n]) for n in ref.jct)
    return max(diff, max(abs(lane.cct[n] - ref.cct[n]) for n in ref.cct))


class TestEquivalence:
    @pytest.mark.parametrize("scenario", sorted(SCENARIOS))
    def test_matches_numpy_per_lane(self, scenario):
        lanes = []
        for seed in range(N_SEEDS):
            fabric, jobs = build_scenario(scenario, seed=seed, quick=True,
                                          lint=False)
            lanes.append(pack_instance(fabric, jobs))
        results = run_fifo_batch(lanes)
        for seed, lane in enumerate(results):
            ref = _numpy_oracle(scenario, seed)
            assert _max_diff(lane, ref) < TOL, (
                f"{scenario}/seed{seed} diverged from the numpy core")
            assert lane.makespan == pytest.approx(ref.makespan, abs=TOL)


class TestPaddingMask:
    """Lanes padded into a shared batch shape must be unaffected by
    their neighbours: result(batch)[i] == result([lane_i])[0]."""

    def test_heterogeneous_lanes_independent(self):
        built = [build_scenario(s, seed=i, quick=True, lint=False)
                 for i, s in enumerate(("pipe_serve", "dense_dp", "moe_ep"))]
        lanes = [pack_instance(f, j) for f, j in built]
        # Shapes genuinely differ, so padding is exercised.
        assert len({p.flow_node.size for p in lanes}) > 1
        together = run_fifo_batch(lanes)
        for lane, result in zip(lanes, together):
            alone = run_fifo_batch([lane])[0]
            assert result.jct == pytest.approx(alone.jct, abs=TOL)
            assert result.cct == pytest.approx(alone.cct, abs=TOL)

    def test_single_flow_lanes(self):
        def lane(size, arrival=0.0):
            job = JobDAG("j0", arrival=arrival)
            job.add_metaflow("m0", [(0, 1, size)])
            return pack_instance(Fabric(n_ports=2), [job])

        res = run_fifo_batch([lane(10.0), lane(30.0), lane(5.0, 2.0)])
        assert [r.jct["j0"] for r in res] == [10.0, 30.0, 5.0]
        assert res[2].makespan == 7.0

    def test_hypothesis_padding_invariants(self):
        hyp = pytest.importorskip(
            "hypothesis", reason="randomized padding invariants need "
                                 "hypothesis; pinned cases above still run")
        from hypothesis import given, settings
        from hypothesis import strategies as st

        def draw_specs(rng):
            """Lane specs: (n_ports, [(arrival, [metaflow flow lists])])
            — plain data, so oracle and lane build independent JobDAGs."""
            specs = []
            for _ in range(rng.randint(1, 3)):
                n_ports = rng.choice((2, 4, 8))
                job_specs = []
                for _ in range(rng.randint(1, 3)):
                    mfs = []
                    for _ in range(rng.randint(1, 3)):
                        flows = [(rng.randrange(n_ports),
                                  rng.randrange(n_ports),
                                  round(rng.uniform(0.5, 8.0), 3))
                                 for _ in range(rng.randint(1, 4))]
                        flows = [(s, d, z) for s, d, z in flows if s != d]
                        if flows:
                            mfs.append(flows)
                    if mfs:
                        job_specs.append((round(rng.uniform(0, 3), 3), mfs))
                if job_specs:
                    specs.append((n_ports, job_specs))
            return specs

        def build_jobs(job_specs):
            jobs = []
            for ji, (arrival, mfs) in enumerate(job_specs):
                job = JobDAG(f"j{ji}", arrival=arrival)
                for mi, flows in enumerate(mfs):
                    job.add_metaflow(f"m{mi}", flows)
                job.validate()
                jobs.append(job)
            return jobs

        @settings(max_examples=10, deadline=None)
        @given(st.integers(0, 2 ** 16))
        def run(seed):
            specs = draw_specs(random.Random(seed))
            if not specs:
                return
            lanes = [pack_instance(Fabric(n_ports=n), build_jobs(js))
                     for n, js in specs]
            refs = [simulate(build_jobs(js), make_scheduler("fifo"),
                             n_ports=n) for n, js in specs]
            for lane, ref in zip(run_fifo_batch(lanes), refs):
                assert _max_diff(lane, ref) < TOL

        run()



class TestLinkMask:
    """The backfill's flow x link mask stands for the packed routes.  It
    counts a link once per flow, so it matches a route only if the route
    crosses no link twice."""

    def test_mask_equals_link_lists(self):
        # Big switch, 3:1 leaf-spine and fat tree lanes in one batch:
        # link counts, path lengths and flow counts all differ.
        built = [build_scenario(s, seed=i, quick=True, lint=False,
                                topology=t)
                 for i, (s, t) in enumerate((
                     ("pipe_serve", None), ("mixed_oversub_3to1", None),
                     ("fb_shuffle", "fat_tree"), ("dense_dp", None)))]
        lanes = [pack_instance(f, j) for f, j in built]
        assert len({p.n_links for p in lanes}) == len(lanes)
        assert len({p.flow_links.shape[1] for p in lanes}) > 1
        mask = np.asarray(_pack_batch(lanes).link_mask)
        want = np.zeros_like(mask)
        for b, p in enumerate(lanes):
            for f, route in enumerate(p.flow_links):
                for k in route:
                    if k < p.n_links:
                        want[b, f, k] = True
        assert mask.shape[2] == max(p.n_links for p in lanes) + 1
        assert (mask == want).all()
        assert not mask[:, :, -1].any()          # the dummy link

    @pytest.mark.parametrize("spec", ["big_switch", "leaf_spine_3to1",
                                      "fat_tree"])
    def test_routes_never_repeat_a_link(self, spec):
        topo = make_topology(spec, 48)
        for src in range(topo.n_ports):
            for dst in range(topo.n_ports):
                for route in topo.route_candidates(src, dst):
                    assert len(set(route)) == len(route), (src, dst, route)

    def test_engine_refuses_a_route_that_repeats_a_link(self):
        class Hairpin(Topology):
            kind = "hairpin"

            def __init__(self):
                super().__init__(2, np.ones(5), [f"l{i}" for i in range(5)])

            def _route(self, src, dst):
                return (src, 4, 4, 2 + dst)

        job = JobDAG("j0")
        job.add_metaflow("m0", [(0, 1, 1.0)])
        lane = pack_instance(Fabric(topology=Hairpin()), [job])
        with pytest.raises(ValueError, match="twice"):
            run_fifo_batch([lane])


class TestDemand:
    """The MADD's (job, link) demand, a masked sum over the flow axis,
    against a plain loop over each lane's flows and their routes."""

    def test_demand_equals_a_loop_over_routes(self):
        built = [build_scenario(s, seed=i, quick=True, lint=False,
                                topology=t)
                 for i, (s, t) in enumerate((
                     ("pipe_serve", None), ("mixed_oversub_3to1", None),
                     ("fb_shuffle", "fat_tree"), ("mixed", None)))]
        assert {f.topology.kind for f, _ in built} == {
            "big_switch", "leaf_spine", "fat_tree"}
        lanes = [pack_instance(f, j) for f, j in built]
        pk = _pack_batch(lanes)
        B, F = pk.flow_size.shape
        J1, K1 = pk.arrival.shape[1], pk.link_cap.shape[1]
        rng = np.random.default_rng(7)
        # Weights over six decades; every odd job has drained (weight 0)
        # while the even jobs still load the same links.
        w = np.zeros((B, F))
        want = np.zeros((B, J1, K1))
        drained = []
        for b, p in enumerate(lanes):
            flow_job = p.node_job[p.flow_node]
            for f, (j, route) in enumerate(zip(flow_job, p.flow_links)):
                if j % 2:
                    continue
                w[b, f] = 10.0 ** rng.uniform(-3, 3)
                for k in route:
                    if k < p.n_links:
                        want[b, j, k] += w[b, f]
            drained += [(b, j) for j in set(flow_job.tolist()) if j % 2]
        assert drained

        got = np.stack([np.asarray(_demand(pk, jnp.asarray(w), j))
                        for j in range(J1)], axis=1)
        assert got.shape == (B, J1, K1)
        assert ((got == 0.0) == (want == 0.0)).all()
        used = want > 0.0
        assert np.abs(got[used] / want[used] - 1.0).max() < 1e-12
        # A (job, link) whose flows have all drained reads exactly zero,
        # on links the other jobs still load.
        for b, j in drained:
            assert (got[b, j] == 0.0).all()
        assert (got[:, :, -1] == 0.0).all()      # the dummy link
        assert (got[:, -1] == 0.0).all()         # the dummy job


class TestRecordedBatch:
    """One fixed batch of registered scenarios on all three topologies
    gives, lane by lane, exactly the recorded loop counters, events,
    JCTs and CCTs.  The backfill's masked reductions take the same
    minima and make the same single grant per link as the per-link
    gathers they replaced, bit for bit.  The MADD's masked demand sum
    adds a (job, link)'s flows in another order than the prefix sums it
    replaced, so JCTs and CCTs moved in their last bits (at most 2e-13)
    and the counters not at all: ``RECORD_GATHERED`` keeps the numbers
    of the prefix-sum demand, within 1e-9 of today's."""

    LANES = (("pipe_serve", 0, None), ("dense_dp", 1, None),
             ("moe_ep", 2, None), ("mixed_oversub_3to1", 3, None),
             ("fb_shuffle", 4, "fat_tree"), ("mixed", 5, None))
    # (wave_iters, cascade_iters, events, jct, cct) per lane
    RECORD = [
        (379, 152, 75,
         {"serve#0": 281.0019547048092, "serve#1": 360.33759879233907,
          "serve#2": 420.3218178096332, "serve#3": 362.16550466510466},
         {"serve#0": 255.6864632899616, "serve#1": 335.0221073774915,
          "serve#2": 395.0063263947857, "serve#3": 336.850013250257}),
        (493, 204, 101,
         {"train#0": 4.67602088994994, "train#1": 7.866070205297936,
          "train#2": 10.411464833379688},
         {"train#0": 4.671207700354511, "train#1": 7.861257015702508,
          "train#2": 10.40665164378426}),
        (481, 198, 98,
         {"moe#0": 16.40380928565132, "moe#1": 23.148629829518363},
         {"moe#0": 16.389515451075347, "moe#1": 23.13433599494239}),
        (419, 168, 83,
         {"serve#0": 191.55388503901415, "fb1#1": 79.6881067374001,
          "train#2": 9.327975831922732, "serve#3": 247.8111611681394,
          "fb1#4": 137.18511772468116},
         {"serve#0": 174.676890762449, "fb1#1": 68.03812682579382,
          "train#2": 9.323162642327304, "serve#3": 230.93416689157425,
          "fb1#4": 125.5351378130749}),
        (183, 66, 32,
         {"fb0#0": 173.7314199780476, "fb1#1": 233.86458548499718,
          "fb1#2": 197.73064734061217, "fb2#3": 156.02470330984912},
         {"fb0#0": 100.0, "fb1#1": 136.13393814438507,
          "fb1#2": 100.00000000000004, "fb2#3": 100.00000000000003}),
        (566, 265, 139,
         {"fb0#0": 37.79084860135737, "fb0#1": 38.042267319530126,
          "fb1#2": 25.861941028862514, "fb0#3": 37.79084860135737,
          "fb1#4": 36.803826918314115},
         {"fb0#0": 21.82829694354606, "fb0#1": 22.079715661718822,
          "fb1#2": 13.893873161258167, "fb0#3": 21.828296943546068,
          "fb1#4": 31.3028544748484}),
    ]
    RECORD_GATHERED = [
        (379, 152, 75,
         {"serve#0": 281.001954704809, "serve#1": 360.33759879233895,
          "serve#2": 420.3218178096331, "serve#3": 362.16550466510455},
         {"serve#0": 255.68646328996147, "serve#1": 335.0221073774914,
          "serve#2": 395.00632639478556, "serve#3": 336.8500132502569}),
        (493, 204, 101,
         {"train#0": 4.676020889949943, "train#1": 7.866070205297948,
          "train#2": 10.4114648333797},
         {"train#0": 4.671207700354515, "train#1": 7.86125701570252,
          "train#2": 10.406651643784272}),
        (481, 198, 98,
         {"moe#0": 16.40380928565135, "moe#1": 23.1486298295184},
         {"moe#0": 16.389515451075372, "moe#1": 23.134335994942425}),
        (419, 168, 83,
         {"serve#0": 191.55388503901415, "fb1#1": 79.68810673740006,
          "train#2": 9.327975831922933, "serve#3": 247.8111611681394,
          "fb1#4": 137.18511772468116},
         {"serve#0": 174.676890762449, "fb1#1": 68.03812682579378,
          "train#2": 9.323162642327505, "serve#3": 230.93416689157425,
          "fb1#4": 125.5351378130749}),
        (183, 66, 32,
         {"fb0#0": 173.7314199780476, "fb1#1": 233.86458548499712,
          "fb1#2": 197.7306473406121, "fb2#3": 156.02470330984906},
         {"fb0#0": 100.0, "fb1#1": 136.13393814438507,
          "fb1#2": 100.00000000000001, "fb2#3": 100.00000000000003}),
        (566, 265, 139,
         {"fb0#0": 37.79084860135734, "fb0#1": 38.042267319530104,
          "fb1#2": 25.86194102886248, "fb0#3": 37.79084860135734,
          "fb1#4": 36.80382691831409},
         {"fb0#0": 21.82829694354603, "fb0#1": 22.079715661718787,
          "fb1#2": 13.893873161258153, "fb0#3": 21.828296943546032,
          "fb1#4": 31.30285447484837}),
    ]

    def test_counters_and_results_unchanged(self):
        lanes = [pack_instance(*build_scenario(s, seed=i, quick=True,
                                               lint=False, topology=t))
                 for s, i, t in self.LANES]
        got = [(r.wave_iters, r.cascade_iters, r.events, r.jct, r.cct)
               for r in run_fifo_batch(lanes)]
        assert got == self.RECORD
        for now, then in zip(got, self.RECORD_GATHERED):
            assert now[:3] == then[:3]
            for times, times_then in zip(now[3:], then[3:]):
                assert times.keys() == times_then.keys()
                for name, t in times.items():
                    assert t == pytest.approx(times_then[name], rel=0,
                                              abs=1e-9), name

class TestRecompilation:
    def test_one_trace_per_batch_shape(self):
        def lanes():
            out = []
            for seed in (0, 1):
                fabric, jobs = build_scenario("pipe_serve", seed=seed,
                                              quick=True, lint=False)
                out.append(pack_instance(fabric, jobs))
            return out

        first = lanes()
        run_fifo_batch(first)
        traced = trace_count()
        # Same batch shape (fresh packs, same scenario/seeds): no retrace.
        run_fifo_batch(lanes())
        assert trace_count() == traced
        # A shape no other test produces traces exactly once — and only
        # on its first run.
        job = JobDAG("j0")
        job.add_metaflow("m0", [(0, 1, float(f + 1)) for f in range(5)])
        odd = pack_instance(Fabric(n_ports=2), [job])
        run_fifo_batch([odd])
        assert trace_count() == traced + 1
        run_fifo_batch([odd])
        assert trace_count() == traced + 1


class TestCounters:
    """The engine's loop counters on batches small enough to count by
    hand: a wave per backfill round, a cascade iteration per settle
    round (the last one finding nothing to do), each counted for a lane
    while it is unfinished."""

    @staticmethod
    def _shared_link():
        # j0 saturates ports 0 -> 1, so j1's MADD is refused and its two
        # flows into port 3 backfill one after the other: two waves in
        # step 1, one in step 2 (j1's last flows, MADD leaves nothing).
        j0 = JobDAG("j0")
        j0.add_metaflow("m0", [(0, 1, 1.0)])
        j1 = JobDAG("j1")
        j1.add_metaflow("m0", [(0, 1, 1.0), (2, 3, 1.0), (4, 3, 1.0)])
        return [j0, j1]

    @staticmethod
    def _chain(depth: int):
        # ``depth`` flowless metaflows in a chain, then one flow: the
        # first settle activates and retires one link of the chain per
        # iteration.
        job = JobDAG("j0")
        deps: list[str] = []
        for i in range(depth):
            job.add_metaflow(f"m{i}", [], deps=deps)
            deps = [f"m{i}"]
        job.add_metaflow("last", [(0, 1, 1.0)], deps=deps)
        return [job]

    def _check_oracle(self, lane: LaneResult, jobs, n_ports: int):
        ref = simulate(jobs, make_scheduler("fifo"), n_ports=n_ports)
        assert _max_diff(lane, ref) < TOL

    def test_two_flows_sharing_one_link(self):
        (res,) = run_fifo_batch([pack_instance(Fabric(n_ports=5),
                                               self._shared_link())])
        self._check_oracle(res, self._shared_link(), 5)
        assert res.jct == {"j0": 1.0, "j1": 2.0}
        assert res.events == 2
        assert res.wave_iters == 2 + 1
        # First settle (activate both roots, then nothing) and one
        # settle per step (retire a metaflow or none, then nothing).
        assert res.cascade_iters == 2 + 2 + 2
        # One window of 16 steps, read before and after it.
        assert (res.batch_steps, res.batch_syncs) == (16, 2)

    @pytest.mark.parametrize("depth", [1, 3])
    def test_chain_cascade_depth(self, depth):
        (res,) = run_fifo_batch([pack_instance(Fabric(n_ports=2),
                                               self._chain(depth))])
        self._check_oracle(res, self._chain(depth), 2)
        assert res.events == 1 and res.wave_iters == 1
        # First settle: the root, each chain link's retirement with its
        # child's activation, then nothing; the step's settle: 2.
        assert res.cascade_iters == (depth + 2) + 2

    def test_batch_counts_while_unfinished(self):
        """Loops run batch-wide: a lane counts every iteration while it
        is unfinished, including rounds only another lane needed."""
        steps = 4
        res = run_fifo_batch(
            [pack_instance(Fabric(n_ports=5), self._shared_link()),
             pack_instance(Fabric(n_ports=2), self._chain(3))],
            steps_per_sync=steps)
        self._check_oracle(res[0], self._shared_link(), 5)
        self._check_oracle(res[1], self._chain(3), 2)
        # Lane 1 finishes in step 1, so it sees step 1's two waves only.
        assert [r.wave_iters for r in res] == [3, 2]
        # The first settle runs the chain's 5 iterations for both lanes.
        assert [r.cascade_iters for r in res] == [5 + 2 + 2, 5 + 2]
        assert {(r.batch_steps, r.batch_syncs) for r in res} == {(steps, 2)}

    def test_windows_overshoot(self):
        """Steps run in whole windows: 16 steps for 2 needed, and a sync
        before each window and after the last."""
        lanes = [pack_instance(Fabric(n_ports=5), self._shared_link())]
        for per_sync, steps, syncs in ((1, 2, 3), (16, 16, 2)):
            (res,) = run_fifo_batch(lanes, steps_per_sync=per_sync)
            assert (res.batch_steps, res.batch_syncs) == (steps, syncs)
            assert res.wave_iters == 3 and res.cascade_iters == 6


class TestRunnerIntegration:
    def test_run_cells_batched_order_and_fallback(self):
        from repro.experiments import Cell, run_cell, run_cells_batched

        cells = [Cell("pipe_serve", "fifo", "big_switch", s)
                 for s in range(2)]
        cells.append(Cell("pipe_serve", "msa", "big_switch", 0))
        recs = run_cells_batched(cells, quick=True, workers=1)
        assert [r["seed"] for r in recs] == [0, 1, 0]
        assert [r.get("engine") for r in recs] == ["simjax", "simjax", None]
        ref = run_cell(cells[0], quick=True)
        for key in ("jct", "cct"):
            for name, val in ref["result"][key].items():
                assert recs[0]["result"][key][name] == \
                    pytest.approx(val, abs=TOL)


def test_simjax_is_the_only_jax_user():
    src = Path(__file__).resolve().parent.parent / "src"
    jax_import = re.compile(r"^\s*(import|from)\s+jax\b", re.MULTILINE)
    users = sorted(p.relative_to(src).as_posix()
                   for p in src.rglob("*.py")
                   if jax_import.search(p.read_text()))
    assert users == ["repro/core/simjax.py"]
