"""repro.analysis contention + structure layers (DESIGN.md §16).

Proof obligations for the cross-job pass:

* the release-date-aware link-load bound matches hand arithmetic, and
  the batch load+chain composition dominates the per-job bounds by
  construction — pinned *exactly*, per registered scenario and policy,
  and never exceeds any policy's achieved makespan / last-flow drain;
* the tight per-job bound dominates the PR-6 chain-only bound exactly
  on randomized workloads (the dominance acceptance gate);
* the static characterizer separates the shipped scenarios across the
  flow/metaflow/coflow spectrum and its predicted-MSA-advantage
  ranking puts the pipelined serving chain first;
* the analysis CLI's ``--json`` document parses, its exit code reflects
  only error-severity findings, and the aggregate's ``structure`` block
  appears only in analyze mode (plain fingerprints stay byte-identical).
"""

import json

import pytest

from repro.analysis import (BatchBounds, assert_batch_bounds_hold,
                            assert_bounds_hold, batch_bounds,
                            contention_graph, job_lower_bounds, job_structure,
                            link_load_bound, predicted_ranking,
                            rank_agreement, scenario_lower_bounds,
                            scenario_structure)
from repro.appdag import SCENARIOS, build_scenario
from repro.core import (JobDAG, Simulator, available_policies, big_switch,
                        make_scheduler)
from test_sim_core_equiv import _random_batch


def _shared_link_jobs():
    """Two jobs pushing 4 bytes each through port 0's unit egress,
    arriving at t=0 and t=10."""
    jobs = []
    for k, arrival in enumerate((0.0, 10.0)):
        j = JobDAG(name=f"j{k}", arrival=arrival)
        j.add_metaflow("m", flows=[(0, 1, 4.0)])
        j.add_task("c", load=0.0, deps=["m"])
        jobs.append(j)
    return jobs


# --------------------------------------------------------------- contention
class TestContention:
    def test_contention_graph_aggregates_across_jobs(self):
        top = big_switch(2)
        graph = contention_graph(_shared_link_jobs(), top)
        assert graph                                   # busiest first
        busiest = graph[0]
        assert busiest.bytes == pytest.approx(8.0)
        assert busiest.n_jobs == 2
        assert busiest.seconds == pytest.approx(8.0 / busiest.cap)
        assert busiest.name                            # named, not an index
        assert contention_graph([], top) == []

    def test_link_load_bound_release_date_math(self):
        """cap 1, 4 bytes at t=0 and 4 at t=10: suffixes give
        max(10 + 4, 0 + 8) = 14."""
        assert link_load_bound(_shared_link_jobs(), big_switch(2)) \
            == pytest.approx(14.0)

    def test_link_load_bound_simultaneous_is_plain_sum(self):
        jobs = _shared_link_jobs()
        for j in jobs:
            j.arrival = 0.0
        assert link_load_bound(jobs, big_switch(2)) == pytest.approx(8.0)

    def test_batch_bounds_compose_load_and_chain(self):
        jobs = _shared_link_jobs()
        bb = batch_bounds(jobs, big_switch(2))
        assert isinstance(bb, BatchBounds)
        assert bb.load_lb == pytest.approx(14.0)
        # chain: j1 arrives at 10 with a 4-second job -> 14 too.
        assert bb.chain_lb == pytest.approx(14.0)
        assert bb.makespan_lb == pytest.approx(14.0)
        assert bb.batch_cct_lb == pytest.approx(14.0)
        assert bb.bottleneck is not None
        doc = bb.to_json()
        assert doc["makespan_lb"] == bb.makespan_lb
        assert doc["bottleneck"] == bb.bottleneck

    def test_batch_bounds_empty_batch(self):
        bb = batch_bounds([], big_switch(2))
        assert bb.makespan_lb == 0.0 and bb.batch_cct_lb == 0.0
        assert bb.bottleneck is None

    def test_assert_batch_bounds_hold_fires(self):
        bb = batch_bounds(_shared_link_jobs(), big_switch(2))
        with pytest.raises(AssertionError, match="makespan bound violated"):
            assert_batch_bounds_hold(bb, 5.0, {}, {}, "test")
        with pytest.raises(AssertionError, match="batch CCT bound violated"):
            assert_batch_bounds_hold(bb, 20.0, {"j0": 4.0}, {"j0": 0.0},
                                     "test")
        # Achieved at (or above) the bound passes.
        assert_batch_bounds_hold(bb, 14.0, {"j0": 4.0, "j1": 4.0},
                                 {"j0": 0.0, "j1": 10.0}, "test")


# ------------------------------------------------------- bounds edge cases
class TestBoundsEdgeCases:
    def test_empty_job_list(self):
        jct_b, cct_b = scenario_lower_bounds([], big_switch(2))
        assert jct_b == {} and cct_b == {}

    def test_zero_byte_metaflows(self):
        j = JobDAG(name="j")
        j.add_metaflow("m", flows=[(0, 1, 0.0)])
        j.add_task("c", load=2.0, deps=["m"])
        jct_lb, cct_lb = job_lower_bounds(j, big_switch(2))
        assert cct_lb == 0.0
        assert jct_lb == pytest.approx(2.0)    # compute chain survives

    def test_compute_only_job(self):
        j = JobDAG(name="j")
        j.add_task("a", load=3.0)
        j.add_task("b", load=2.0, deps=["a"])
        jct_lb, cct_lb = job_lower_bounds(j, big_switch(2))
        assert cct_lb == 0.0
        assert jct_lb == pytest.approx(5.0)
        bb = batch_bounds([j], big_switch(2))
        assert bb.load_lb == 0.0
        assert bb.makespan_lb == pytest.approx(5.0)    # chain term only
        assert bb.bottleneck is None

    @pytest.mark.parametrize("seed", range(6))
    def test_tight_dominates_chain_only_exactly(self, seed):
        """The dominance acceptance gate on randomized workloads: every
        PR-6 term is retained in the tight DP, so >= holds exactly —
        no tolerance."""
        n_ports, jobs = _random_batch(seed=seed)
        top = big_switch(n_ports)
        loose_j, loose_c = scenario_lower_bounds(jobs, top, tight=False)
        tight_j, tight_c = scenario_lower_bounds(jobs, top, tight=True)
        for name in loose_j:
            assert tight_j[name] >= loose_j[name]
            assert tight_c[name] >= loose_c[name]
        assert any(tight_j[n] > loose_j[n] for n in loose_j) or \
            all(tight_j[n] == loose_j[n] for n in loose_j)


# ------------------------------------------- scenario x policy acceptance
@pytest.mark.parametrize("scen", sorted(SCENARIOS))
def test_bounds_acceptance_per_scenario(scen):
    """For every registered scenario x every policy: the tight bound
    dominates the chain-only bound exactly, and no achieved JCT/CCT/
    makespan beats its certified bound."""
    fabric, jobs = build_scenario(scen, seed=0, quick=True, lint=False)
    top = fabric.topology
    loose_j, loose_c = scenario_lower_bounds(jobs, top, tight=False)
    tight_j, tight_c = scenario_lower_bounds(jobs, top, tight=True)
    for name in loose_j:
        assert tight_j[name] >= loose_j[name]       # exact, no tolerance
        assert tight_c[name] >= loose_c[name]
    bb = batch_bounds(jobs, top)
    assert bb.chain_lb >= max(
        j.arrival + tight_j[j.name] for j in jobs)
    for pname in available_policies():
        fabric, jobs = build_scenario(scen, seed=0, quick=True, lint=False)
        res = Simulator(fabric, jobs, make_scheduler(pname)).run()
        assert_bounds_hold(res.jct, tight_j, f"{scen}/{pname} jct")
        assert_bounds_hold(res.cct, tight_c, f"{scen}/{pname} cct")
        arrivals = {j.name: j.arrival for j in jobs}
        assert_batch_bounds_hold(bb, res.makespan, res.cct, arrivals,
                                 f"{scen}/{pname}")


# ---------------------------------------------------------------- structure
class TestJobStructure:
    def test_pipelined_chain_is_flow(self):
        j = JobDAG(name="chain")
        j.add_metaflow("m0", flows=[(0, 1, 4.0)])
        j.add_task("t0", load=0.5, deps=["m0"])
        j.add_metaflow("m1", flows=[(1, 2, 4.0)], deps=["t0"])
        j.add_task("t1", load=0.5, deps=["m1"])
        s = job_structure(j, big_switch(3))
        assert s.classification == "flow"
        assert s.barrier_density == 0.0
        assert s.fan_out == pytest.approx(1.0)
        assert s.mf_depth == 2
        assert 0.0 < s.msa_advantage_score <= 1.0

    def test_wide_shallow_gather_is_coflow(self):
        j = JobDAG(name="shuffle")
        j.add_metaflow("m", flows=[(i, 4, 2.0) for i in range(4)])
        j.add_task("reduce", load=0.1, deps=["m"])
        s = job_structure(j, big_switch(5))
        assert s.classification == "coflow"
        assert s.barrier_density == 1.0
        assert s.mean_barrier_width == pytest.approx(4.0)

    def test_deep_barrier_dag_is_metaflow(self):
        j = JobDAG(name="dp")
        prev = None
        for k in range(3):
            deps = [prev] if prev else []
            j.add_metaflow(f"ar{k}",
                           flows=[(i, (i + 1) % 4, 1.0) for i in range(4)],
                           deps=deps)
            prev = f"t{k}"
            j.add_task(prev, load=1.0, deps=[f"ar{k}"])
        s = job_structure(j, big_switch(4))
        assert s.classification == "metaflow"
        assert s.mf_depth == 3

    def test_join_density_counts_multi_mf_consumers(self):
        j = JobDAG(name="join")
        j.add_metaflow("a", flows=[(0, 2, 1.0)])
        j.add_metaflow("b", flows=[(1, 2, 1.0)])
        j.add_task("merge", load=0.0, deps=["a", "b"])
        s = job_structure(j, big_switch(3))
        assert s.join_density == pytest.approx(1.0)
        assert s.msa_advantage_score == 0.0        # joins zero the score

    def test_compute_only_job_scores_zero(self):
        j = JobDAG(name="cpu")
        j.add_task("t", load=5.0)
        s = job_structure(j, big_switch(2))
        assert s.comm_fraction == 0.0
        assert s.msa_advantage_score == 0.0
        assert s.n_flows == 0


class TestScenarioStructure:
    @pytest.fixture(scope="class")
    def structs(self):
        out = {}
        for scen in sorted(SCENARIOS):
            fabric, jobs = build_scenario(scen, seed=0, quick=True,
                                          lint=False)
            out[scen] = scenario_structure(scen, jobs, fabric.topology)
        return out

    def test_shipped_scenarios_span_the_spectrum(self, structs):
        assert structs["pipe_serve"].classification == "flow"
        assert structs["fb_shuffle"].classification == "coflow"
        assert structs["dense_dp"].classification == "metaflow"
        assert structs["moe_ep"].classification == "metaflow"
        assert structs["mixed"].classification == "mixed"

    def test_class_counts_cover_all_jobs(self, structs):
        for s in structs.values():
            assert sum(dict(s.class_counts).values()) == s.n_jobs
            assert s.n_jobs == len(s.jobs)

    def test_predicted_ranking_puts_pipelined_serving_first(self, structs):
        ranking = predicted_ranking(structs.values())
        assert set(ranking) == set(SCENARIOS)
        assert ranking[0] == "pipe_serve"
        # The training scenarios trail the field: barriers in dense_dp and
        # moe_ep, joins of one-flow legs at every task of dsv3_ep64.
        assert set(ranking[-3:]) == {"dense_dp", "moe_ep", "dsv3_ep64"}

    def test_to_json_shape(self, structs):
        doc = structs["mixed"].to_json()
        assert set(doc["class_counts"]) == {"flow", "metaflow", "coflow"}
        assert len(doc["jobs"]) == doc["n_jobs"]
        json.dumps(doc)                            # serializable as-is


class TestRankAgreement:
    def test_perfect_agreement_and_inversion(self):
        pred = {"a": 3.0, "b": 2.0, "c": 1.0}
        assert rank_agreement(pred, {"a": 9.0, "b": 5.0, "c": 1.0}) == 1.0
        assert rank_agreement(pred, {"a": 1.0, "b": 5.0, "c": 9.0}) == -1.0

    def test_ties_drop_pairs(self):
        pred = {"a": 1.0, "b": 1.0, "c": 0.0}
        got = rank_agreement(pred, {"a": 2.0, "b": 1.0, "c": 0.0})
        # (a,b) tied in pred -> dropped; the other 2 pairs agree.
        assert got == pytest.approx(2.0 / 3.0)

    def test_too_few_common_keys_is_none(self):
        assert rank_agreement({"a": 1.0}, {"a": 2.0}) is None
        assert rank_agreement({"a": 1.0, "b": 2.0}, {"c": 3.0}) is None

    def test_ignores_uncommon_keys(self):
        assert rank_agreement({"a": 2.0, "b": 1.0, "x": 9.0},
                              {"a": 4.0, "b": 3.0, "y": 0.0}) == 1.0


# ---------------------------------------------------------------- CLI gate
class TestAnalysisCli:
    def test_json_document_parses_and_exits_zero(self, capsys):
        from repro.analysis.cli import main
        rc = main(["--quick", "--scenario", "dense_dp", "--structure",
                   "--json"])
        assert rc == 0
        doc = json.loads(capsys.readouterr().out)
        assert doc["n_errors"] == 0
        entry = doc["scenarios"]["dense_dp"]
        assert entry["n_errors"] == 0
        assert entry["structure"]["classification"] == "metaflow"
        assert entry["batch_bounds"]["makespan_lb"] > 0
        assert doc["predicted_ranking"] == ["dense_dp"]

    def test_structure_table_prints_ranking(self, capsys):
        from repro.analysis.cli import main
        rc = main(["--quick", "--structure"])
        assert rc == 0
        out = capsys.readouterr().out
        assert "predicted MSA advantage" in out
        assert out.count(" ok ") == len(SCENARIOS)

    def test_warnings_do_not_fail_the_gate(self, capsys):
        from repro.analysis.cli import main
        rc = main(["--quick", "--json"])
        doc = json.loads(capsys.readouterr().out)
        n_warn = sum(e["n_warnings"] for e in doc["scenarios"].values())
        assert rc == 0 and doc["n_errors"] == 0
        assert n_warn >= 0                       # warnings never gate

    def test_error_findings_drive_exit_code(self, capsys, monkeypatch):
        import repro.analysis.cli as cli
        from repro.analysis.lint import Finding
        monkeypatch.setattr(
            cli, "lint_scenario",
            lambda name, seed=0, quick=False: [
                Finding(check="dag_structure", severity="error",
                        message="injected breakage")])
        rc = cli.main(["--quick", "--scenario", "dense_dp", "--json"])
        assert rc == 1
        doc = json.loads(capsys.readouterr().out)
        assert doc["n_errors"] == 1
        f = doc["scenarios"]["dense_dp"]["findings"][0]
        assert f["severity"] == "error"

    def test_lint_main_shim_delegates(self, capsys):
        from repro.analysis import lint
        assert lint.main(["--quick", "--scenario", "pipe_serve"]) == 0
        assert " ok " in capsys.readouterr().out


# ------------------------------------------------------------ wire-through
class TestAnalyzeWiring:
    def test_run_cell_analyze_carries_makespan_bound(self):
        from repro.core.results import RunResult
        from repro.experiments import Cell, run_cell
        cell = Cell("pipe_serve", "msa", "big_switch", 0)
        plain = run_cell(cell, quick=True)["result"]
        assert "makespan_bound" not in plain
        assert RunResult.from_json(plain).makespan_bound is None
        rec = run_cell(cell, quick=True, analyze=True)["result"]
        assert rec["makespan"] >= rec["makespan_bound"] * (1 - 1e-9)
        rr = RunResult.from_json(rec)
        assert rr.makespan_bound == rec["makespan_bound"]
        assert rr.to_json()["makespan_bound"] == rec["makespan_bound"]

    def test_aggregate_structure_block_only_in_analyze_mode(self, tmp_path):
        from repro.experiments import SweepSpec, aggregate, run_sweep
        spec = SweepSpec(scenarios=("pipe_serve",),
                         policies=("msa", "varys"), n_seeds=2, quick=True,
                         cells_per_shard=4)
        plain_docs = [
            run_sweep(spec, str(tmp_path / f"plain{k}"), workers=1,
                      resume=False)
            for k in range(2)]
        plain = [aggregate(spec, d) for d in plain_docs]
        # Plain sweeps: no structure block, byte-identical fingerprints.
        assert "structure" not in plain[0]
        assert plain[0]["fingerprint"] == plain[1]["fingerprint"]
        stripped = [{k: v for k, v in d.items() if k != "timing"}
                    for d in plain]
        assert json.dumps(stripped[0], sort_keys=True) \
            == json.dumps(stripped[1], sort_keys=True)

        docs = run_sweep(spec, str(tmp_path / "an"), workers=1,
                         resume=False, analyze=True)
        doc = aggregate(spec, docs)
        struct = doc["structure"]
        assert struct["predicted_ranking"] == ["pipe_serve"]
        assert "pipe_serve" in struct["measured_msa_over_varys"]
        assert struct["rank_agreement"] is None    # 1 common key
        entry = doc["results"]["pipe_serve|msa|big_switch"]
        assert entry["makespan_gap"]["mean"] >= 1.0
        # The analyze fingerprint differs (bounds ride on the payload),
        # but the spec hash is the same sweep.
        assert doc["spec_hash"] == plain[0]["spec_hash"]
        assert doc["fingerprint"] != plain[0]["fingerprint"]
