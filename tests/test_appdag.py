"""repro.appdag: collective lowering, plan extractors, arrival mixer.

The byte-conservation pins here are the fast tier-1 anchors; the
hypothesis sweep over arbitrary group sizes lives in test_property.py
(slow-marked).
"""

import pytest

from repro.appdag import (PlanAxes, build_scenario, dense_train_dag,
                          lower_collective, lower_grouped, moe_train_dag,
                          n_units, pipeline_serve_dag, poisson_mix,
                          JobTemplate)
from repro.appdag.lowering import add_lowered
from repro.appdag.mixer import comm_balanced
from repro.configs import ARCH_NAMES, get_config
from repro.configs.base import LM_SHAPES
from repro.core import JobDAG, make_scheduler, simulate


# ------------------------------------------------------------- lowering
class TestLowering:
    def test_ring_all_reduce_conserves_bytes(self):
        """Ring all-reduce of a ``size`` buffer over P ranks puts exactly
        2*size*(P-1) on the wire: (P-1) reduce-scatter rounds + (P-1)
        all-gather rounds of P chunk flows each."""
        for p in (2, 3, 5, 8):
            lc = lower_collective("all_reduce", range(p), 12.0, "ring")
            assert lc.total_bytes == pytest.approx(2 * 12.0 * (p - 1))
            assert len(lc.rounds) == 2 * (p - 1)
            assert all(len(r) == p for r in lc.rounds)

    def test_halving_doubling_all_reduce_conserves_bytes(self):
        """Recursive halving-doubling moves the same 2*size*(P-1) total in
        2*log2(P) rounds."""
        for p in (2, 4, 8, 16):
            lc = lower_collective("all_reduce", range(p), 12.0,
                                  "halving_doubling")
            assert lc.total_bytes == pytest.approx(2 * 12.0 * (p - 1))
            assert len(lc.rounds) == 2 * (p.bit_length() - 1)

    def test_algorithms_agree_on_totals(self):
        for kind, expect in (("all_reduce", 2 * 7 * 9.0),
                             ("reduce_scatter", 7 * 9.0),
                             ("all_gather", 7 * 9.0)):
            totals = {alg: lower_collective(kind, range(8), 9.0,
                                            alg).total_bytes
                      for alg in ("ring", "halving_doubling", "direct")}
            for alg, tot in totals.items():
                assert tot == pytest.approx(expect), (kind, alg)

    def test_no_self_flows_and_conservation_on_sparse_ranks(self):
        """Non-contiguous port numberings (a job placed mid-fabric) must
        conserve bytes and stay self-flow-free exactly like range(P)."""
        for alg in ("ring", "halving_doubling", "direct"):
            for kind, expect in (("all_reduce", 2 * 3 * 5.0),
                                 ("reduce_scatter", 3 * 5.0),
                                 ("all_gather", 3 * 5.0),
                                 ("all_to_all", 3 * 5.0)):
                lc = lower_collective(kind, [3, 7, 11, 19], 5.0, alg)
                assert lc.total_bytes == pytest.approx(expect), (kind, alg)
                for r in lc.rounds:
                    for (s, d, _) in r:
                        assert s != d and s in lc.ranks and d in lc.ranks

    def test_all_to_all_total(self):
        lc = lower_collective("all_to_all", range(4), 8.0)
        assert lc.total_bytes == pytest.approx(8.0 * 3)
        assert len(lc.rounds) == 1

    def test_p2p(self):
        lc = lower_collective("p2p", (2, 5), 3.0)
        assert lc.rounds == (((2, 5, 3.0),),)
        with pytest.raises(ValueError):
            lower_collective("p2p", (1, 2, 3), 3.0)

    def test_degenerate_single_rank(self):
        lc = lower_collective("all_reduce", [4], 9.0)
        assert lc.rounds == () and lc.total_bytes == 0.0

    def test_halving_doubling_rejects_non_power_of_two(self):
        with pytest.raises(ValueError, match="power-of-two"):
            lower_collective("all_reduce", range(6), 1.0, "halving_doubling")

    def test_rejects_bad_inputs(self):
        with pytest.raises(ValueError):
            lower_collective("gossip", range(4), 1.0)
        with pytest.raises(ValueError):
            lower_collective("all_reduce", range(4), 1.0, "butterfly")
        with pytest.raises(ValueError):
            lower_collective("all_reduce", [1, 1, 2], 1.0)
        with pytest.raises(ValueError):
            lower_collective("all_reduce", range(4), -1.0)

    def test_grouped_merges_rounds_and_requires_disjoint(self):
        lc = lower_grouped("all_reduce", [(0, 1, 2, 3), (4, 5, 6, 7)], 4.0)
        solo = lower_collective("all_reduce", range(4), 4.0)
        assert len(lc.rounds) == len(solo.rounds)
        assert lc.total_bytes == pytest.approx(2 * solo.total_bytes)
        assert all(len(r) == 8 for r in lc.rounds)
        with pytest.raises(ValueError, match="disjoint"):
            lower_grouped("all_reduce", [(0, 1), (1, 2)], 4.0)

    def test_add_lowered_chains_rounds(self):
        job = JobDAG(name="j")
        job.add_task("producer", load=1.0)
        lc = lower_collective("all_reduce", range(3), 6.0)
        last = add_lowered(job, "g", lc, deps=["producer"])
        job.add_task("consumer", load=1.0, deps=[last])
        job.validate()
        assert last == f"g/r{len(lc.rounds) - 1}"
        assert job.metaflows["g/r0"].deps == ["producer"]
        assert job.metaflows["g/r1"].deps == ["g/r0"]
        # Degenerate lowering: nothing to add, callers keep their deps.
        assert add_lowered(job, "empty",
                           lower_collective("all_reduce", [0], 6.0)) is None

    def test_lowered_all_reduce_simulates_to_bandwidth_bound(self):
        """On unit ports, a lone ring all-reduce finishes in exactly
        2*size*(P-1)/P — the classic ring time."""
        job = JobDAG(name="j")
        p, size = 4, 8.0
        last = add_lowered(job, "ar",
                           lower_collective("all_reduce", range(p), size))
        job.add_task("c", load=0.0, deps=[last])
        res = simulate([job], make_scheduler("msa"), n_ports=p)
        assert res.avg_cct == pytest.approx(2 * size * (p - 1) / p)


# ------------------------------------------------------------ extractors
class TestPlans:
    def test_dense_train_structure(self):
        cfg = get_config("qwen2-7b")
        job = dense_train_dag(cfg, LM_SHAPES["train_4k"], PlanAxes(dp=4),
                              max_units=3)
        assert {f"bwd{u}" for u in range(3)} <= set(job.tasks)
        assert {f"opt{u}" for u in range(3)} <= set(job.tasks)
        # opt waits on the last all-gather round of its unit's grad sync.
        assert job.tasks["opt0"].deps == [f"g0/r{2 * (4 - 1) - 1}"]
        assert job.tasks["bwd1"].deps == ["bwd2"]   # backward runs top-down
        assert max(job.ports_used()) == 3

    def test_dense_train_pp_emits_activation_hops(self):
        cfg = get_config("qwen2-7b")
        job = dense_train_dag(cfg, LM_SHAPES["train_4k"],
                              PlanAxes(dp=2, pp=2), max_units=4)
        assert "act2" in job.metaflows          # units 2|3 -> stage boundary
        (flow,) = [f for f in job.metaflows["act2"].flows if f.src == 2]
        assert flow.dst == 0                    # stage 1 rank -> stage 0 rank

    def test_dense_train_dp1_has_no_grad_metaflows(self):
        cfg = get_config("qwen2-7b")
        job = dense_train_dag(cfg, LM_SHAPES["train_4k"], PlanAxes(dp=1),
                              max_units=2)
        assert not job.metaflows
        assert job.tasks["opt1"].deps == ["bwd1"]

    def test_moe_train_has_a2a_and_expert_sync(self):
        cfg = get_config("mixtral-8x22b")       # MoE every layer
        job = moe_train_dag(cfg, LM_SHAPES["train_4k"],
                            PlanAxes(dp=4, ep=2), max_units=2)
        assert "a2a_c1/r0" in job.metaflows
        assert "a2a_d1/r0" in job.metaflows
        assert any(n.startswith("ge1/") for n in job.metaflows)   # replicas
        assert any(n.startswith("g1/") for n in job.metaflows)    # dense grads
        job2 = moe_train_dag(cfg, LM_SHAPES["train_4k"],
                             PlanAxes(dp=4, ep=4), max_units=1)
        assert not any(n.startswith("ge0/") for n in job2.metaflows)
        with pytest.raises(ValueError, match="not an MoE"):
            moe_train_dag(get_config("qwen2-7b"), LM_SHAPES["train_4k"],
                          PlanAxes(dp=4, ep=2))

    def test_pipeline_serve_grid(self):
        cfg = get_config("qwen2-7b")
        job = pipeline_serve_dag(cfg, PlanAxes(pp=3), n_microbatches=2)
        assert len(job.tasks) == 6
        assert len(job.metaflows) == 4          # 2 boundaries x 2 microbatches
        assert sorted(job.tasks["c1m1"].deps) == ["c1m0", "x1m1"]
        res = simulate([job], make_scheduler("msa"), n_ports=3)
        assert res.jct[job.name] > 0

    def test_plan_axes_validation(self):
        with pytest.raises(ValueError, match="divide"):
            PlanAxes(dp=4, ep=3)
        with pytest.raises(ValueError):
            PlanAxes(dp=0)
        plan = PlanAxes(dp=4, tp=2, pp=2)
        assert plan.world == 16
        ranks = [plan.rank(p, d, t) for p in range(2) for d in range(4)
                 for t in range(2)]
        assert sorted(ranks) == list(range(16))


# Scan units per assigned architecture, as the per-layer model code gave
# them when it still counted them: the step DAGs of ``appdag.plans`` and
# ``core/comm_schedule.py`` hold one gradient bucket per unit.
UNITS = {
    "mixtral-8x22b": 56, "llama4-maverick-400b-a17b": 48, "whisper-base": 6,
    "llama3-405b": 126, "qwen2-7b": 28, "qwen1.5-4b": 40,
    "deepseek-coder-33b": 62, "mamba2-370m": 48, "llava-next-34b": 60,
    "jamba-1.5-large-398b": 9,
}


@pytest.mark.parametrize("arch", ARCH_NAMES)
def test_n_units_matches_recorded(arch):
    assert n_units(get_config(arch)) == UNITS[arch]


# ----------------------------------------------------------------- mixer
class TestMixer:
    def test_instantiate_template(self):
        job = JobDAG(name="t", arrival=1.0)
        job.add_metaflow("m", flows=[(0, 1, 4.0)])
        job.add_task("c", load=2.0, machine=1, deps=["m"])
        inst = job.instantiate(name="t#0", arrival=3.0, port_offset=10,
                               comm_scale=2.0, compute_scale=0.5)
        assert inst.name == "t#0" and inst.arrival == 3.0
        f = inst.metaflows["m"].flows[0]
        assert (f.src, f.dst, f.size, f.remaining) == (10, 11, 8.0, 8.0)
        assert inst.tasks["c"].load == 1.0 and inst.tasks["c"].machine == 11
        assert f.id != job.metaflows["m"].flows[0].id
        # the template is untouched
        assert job.metaflows["m"].flows[0].size == 4.0

    def test_instantiate_rejects_out_of_fabric_ports(self):
        """Eager port validation: a template relocated past the fabric
        edge fails at instantiation with the offending port named, not
        deep inside the simulator (consistent with ``Fabric.degrade``)."""
        job = JobDAG(name="t")
        job.add_metaflow("m", flows=[(0, 3, 4.0)])
        job.add_task("c", load=1.0, machine=3, deps=["m"])
        with pytest.raises(ValueError, match="outside the fabric"):
            job.instantiate(name="t#0", arrival=0.0, port_offset=2,
                            n_ports=4)
        with pytest.raises(ValueError, match="outside the fabric"):
            job.instantiate(name="t#1", arrival=0.0,
                            port_map={0: 1, 3: 7}, n_ports=4)
        # In-range relocation with the same guard enabled still works.
        inst = job.instantiate(name="t#2", arrival=0.0, port_offset=1,
                               n_ports=5)
        assert inst.tasks["c"].machine == 4

    def test_poisson_mix_places_and_names(self):
        tpl = JobDAG(name="t")
        tpl.add_metaflow("m", flows=[(0, 1, 1.0)])
        tpl.add_task("c", load=1.0, deps=["m"])
        jobs = poisson_mix([JobTemplate("t", tpl)], 20, n_ports=6,
                           mean_interarrival=1.0, seed=7)
        assert len({j.name for j in jobs}) == 20
        arrivals = [j.arrival for j in jobs]
        assert arrivals == sorted(arrivals) and arrivals[0] == 0.0
        for j in jobs:
            assert max(j.ports_used()) <= 5
        assert len({min(j.ports_used()) for j in jobs}) > 1   # placement varies

    def test_comm_balanced_sets_bottleneck_ratio(self):
        job = JobDAG(name="t")
        job.add_metaflow("m", flows=[(0, 1, 100.0)])
        job.add_task("c", load=5.0, deps=["m"])
        bal = comm_balanced(job, ratio=2.0)
        assert bal.metaflows["m"].flows[0].size == pytest.approx(10.0)

    @pytest.mark.parametrize("scen", ["dense_dp", "moe_ep", "pipe_serve",
                                      "mixed", "mixed_oversub_3to1",
                                      "fb_shuffle"])
    def test_scenarios_simulate_end_to_end(self, scen):
        fabric, jobs = build_scenario(scen, seed=0, quick=True)
        if scen == "mixed_oversub_3to1":     # the new default topology axis
            assert fabric.topology.kind == "leaf_spine"
        res = simulate(jobs, make_scheduler("msa"), fabric=fabric,
                       debug_checks=True)
        assert len(res.jct) == len(jobs)
        assert all(v > 0 for v in res.jct.values())
        assert all(res.cct[j] <= res.jct[j] + 1e-9 for j in res.jct)
