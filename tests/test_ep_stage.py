"""The expert-parallel stage emitter (``appdag.plans.ep_stage_dag``), its
router (``appdag.routing``) and rail all-to-all, and the ``dsv3_ep64``
scenario and benchmark cell built on them."""

from __future__ import annotations

import dataclasses
import hashlib
import json
import math

import numpy as np
import pytest

from repro.analysis.bounds import assert_bounds_hold, scenario_lower_bounds
from repro.analysis.lint import lint_scenario, strict
from repro.appdag.lowering import rail_all_to_all
from repro.appdag.mixer import build_scenario
from repro.appdag.plans import ep_stage_dag
from repro.appdag.routing import RouteStats, dispatch_bytes, route, \
    select_experts
from repro.configs.deepseek_v3 import CONFIG as DSV3
from repro.core import Fabric, make_scheduler, simulate

#: 2 nodes x 4 GPUs, 16 experts (2 a GPU) in 4 groups, top-4 experts
#: from 2 groups: DeepSeek-V3's router at a size a loop checks.
SMALL = dataclasses.replace(DSV3, n_experts=16, experts_per_token=4,
                            n_expert_groups=4, groups_per_token=2)
TOL = 1e-9


def _small_stage(seed: int, layers: int = 1):
    return ep_stage_dag(SMALL, np.random.default_rng(seed), moe_layers=layers,
                        tokens_per_rank=512, ep=8, gpus_per_node=4,
                        bias_sigma=0.1, sample=16)


def _loop_select(z, n_groups, groups_per_token, k):
    """Per token, plainly: group score = sum of its two best sigmoid
    affinities; keep the best groups; the k best experts in them."""
    per = z.shape[1] // n_groups
    out = []
    for row in z.tolist():
        aff = [1.0 / (1.0 + math.exp(-x)) for x in row]
        score = [sum(sorted(aff[g * per:(g + 1) * per])[-2:])
                 for g in range(n_groups)]
        groups = sorted(range(n_groups), key=score.__getitem__)[
            -groups_per_token:]
        cand = [e for g in groups for e in range(g * per, (g + 1) * per)]
        out.append(set(sorted(cand, key=aff.__getitem__)[-k:]))
    return out


# ----------------------------------------------------------------- router
@pytest.mark.parametrize("seed", range(3))
def test_router_matches_a_per_token_loop(seed):
    rng = np.random.default_rng(seed)
    z = rng.standard_normal((200, 16), dtype=np.float32) \
        + rng.normal(0, 0.1, 16).astype(np.float32)
    got = select_experts(z, 4, 2, 4)
    assert [set(row) for row in got.tolist()] == _loop_select(z, 4, 2, 4)


@pytest.mark.parametrize("cfg,ranks,gpn", [(SMALL, 8, 4), (DSV3, 64, 8)])
def test_a_token_reaches_k_experts_on_few_nodes(cfg, ranks, gpn):
    rng = np.random.default_rng(7)
    z = rng.standard_normal((300, cfg.n_experts), dtype=np.float32)
    experts = select_experts(z, cfg.n_expert_groups, cfg.groups_per_token,
                             cfg.experts_per_token)
    nodes = ranks // gpn
    for row in experts.tolist():
        assert len(set(row)) == cfg.experts_per_token
        assert len({e * nodes // cfg.n_experts for e in row}) \
            <= cfg.groups_per_token
    stats = route(cfg, ranks, gpn, 4096, rng, bias_sigma=0.1,
                  sample=32).stats
    assert sum(stats.nodes_hist) == ranks * 32
    assert len(stats.nodes_hist) == cfg.groups_per_token + 1
    assert stats.nodes_hist[0] == 0


def test_route_counts_and_stats():
    rng = np.random.default_rng(3)
    rt = route(DSV3, 64, 8, 4096, rng, bias_sigma=0.1, sample=32)
    assert rt.node_tokens.shape == (64, 8) and rt.pairs.shape == (64,)
    # Every token is counted once per node it reaches, k pairs in all.
    reached = sum(k * c for k, c in enumerate(rt.stats.nodes_hist))
    assert rt.node_tokens.sum() == pytest.approx(reached * 4096 / 32)
    assert rt.pairs.sum() == pytest.approx(64 * 4096 * 8)
    assert rt.stats.pairs_mean == pytest.approx(4096 * 8)
    assert rt.stats.pairs_max >= rt.stats.pairs_mean
    assert rt.stats.ib_bytes_max >= rt.stats.ib_bytes_mean > 0
    remote = rt.node_tokens.sum() - sum(rt.node_tokens[r, r // 8]
                                        for r in range(64))
    # Each remote token leaves one port and enters another.
    assert rt.stats.ib_bytes_mean == pytest.approx(
        2 * remote * dispatch_bytes(DSV3) / 64)
    assert dispatch_bytes(DSV3) == 7392
    with pytest.raises(ValueError, match="split"):
        route(DSV3, 48, 8, 4096, rng, bias_sigma=0.1, sample=8)


def test_rail_all_to_all_legs():
    sizes = [[5.0, 1.0, 0.0], [2.0, 3.0, 4.0],
             [1.0, 0.0, 6.0], [7.0, 8.0, 9.0],
             [0.0, 2.0, 1.0], [3.0, 0.0, 1.0]]
    legs = rail_all_to_all(sizes, gpus_per_node=2)
    # Own-node and zero legs dropped; (m, i) -> (n, i) on the rail.
    assert legs == [(0, 1, (0, 2, 1.0)),
                    (1, 1, (1, 3, 3.0)), (1, 2, (1, 5, 4.0)),
                    (2, 0, (2, 0, 1.0)), (2, 2, (2, 4, 6.0)),
                    (3, 0, (3, 1, 7.0)), (3, 2, (3, 5, 9.0)),
                    (4, 1, (4, 2, 2.0)), (5, 0, (5, 1, 3.0))]


# ---------------------------------------------------------------- emitter
def test_stage_structure_and_meta():
    job = _small_stage(0, layers=2)
    legs = [m for m in job.metaflows.values()]
    assert all(len(m.flows) == 1 for m in legs)
    # 4 exchanges of 2 all-to-alls, 8 ranks x 1 other node each.
    assert len(legs) == 2 * 2 * 2 * 8
    for m in legs:
        f = m.flows[0]
        assert f.src // 4 != f.dst // 4 and f.src % 4 == f.dst % 4
    # An expert task waits on its node's own legs and ranks only.
    e = job.tasks["f1/E0"]
    assert sorted(e.deps) == sorted(
        [f"f1/D{r}>0" for r in range(4, 8)] + [f"f1/A{r}" for r in range(4)])
    assert job.tasks["b1/E1"].load == pytest.approx(
        2 * job.tasks["f1/E1"].load)
    assert job.tasks["turn3"].load == 0.0
    stats = job.meta["route_stats"]
    assert len(stats) == 2 and all(isinstance(s, RouteStats) for s in stats)


def test_dsv3_scenario_keeps_route_stats():
    for quick, ports in ((True, 16), (False, 64)):
        _, jobs = build_scenario("dsv3_ep64", seed=0, quick=quick)
        stats = jobs[0].meta["route_stats"]
        assert len(stats) == 2
        assert stats[0].pairs_mean == pytest.approx(4096 * 8)
        # The forward dispatch legs carry what the stats count, in MB.
        sent = sum(m.flows[0].size for n, m in jobs[0].metaflows.items()
                   if n.startswith("f0/D"))
        assert stats[0].ib_bytes_mean == pytest.approx(2 * sent * 1e6
                                                       / ports)
        assert sum(stats[0].nodes_hist) == ports * 128


@pytest.mark.parametrize("seed", range(2))
def test_small_stage_agrees_across_engines(seed):
    """The lockstep engine, the numpy core and the benchmark's plain
    reference give the same JCT and CCT; the reference in float32 does
    not."""
    from bench import harness, reference
    from repro.core import simjax

    job = _small_stage(seed)
    fabric = Fabric(n_ports=8)
    lane = simjax.run_fifo_batch([simjax.pack_instance(fabric, [job])])[0]
    numpy_core = simulate([_small_stage(seed)], make_scheduler("fifo"),
                          fabric=Fabric(n_ports=8))
    plain = harness.plain_lane([job], {"n_ports": 8, "port_capacity": 1.0})
    ref = reference.simulate(plain)
    for other in (numpy_core, ref):
        for n in ref.jct:
            assert lane.jct[n] == pytest.approx(other.jct[n], abs=TOL)
            assert lane.cct[n] == pytest.approx(other.cct[n], abs=TOL)
    low = reference.simulate(plain, dtype=np.float32)
    worst = max(max(abs(low.jct[n] - ref.jct[n]) for n in ref.jct),
                max(abs(low.cct[n] - ref.cct[n]) for n in ref.cct))
    assert worst > 1e-6


def test_quick_scenario_lints_and_keeps_its_bounds():
    strict(lint_scenario("dsv3_ep64", seed=0, quick=True))
    fabric, jobs = build_scenario("dsv3_ep64", seed=0, quick=True)
    jct_lb, cct_lb = scenario_lower_bounds(jobs, fabric.topology)
    res = simulate(jobs, make_scheduler("fifo"), fabric=fabric)
    assert_bounds_hold(res.jct, jct_lb, "dsv3_ep64/fifo jct")
    assert_bounds_hold(res.cct, cct_lb, "dsv3_ep64/fifo cct")


def test_deepseek_v3_config():
    """The catalog's widths, and 58 MoE layers after the 3 dense ones."""
    assert (DSV3.d_model, DSV3.expert_ff, DSV3.d_ff) == (7168, 2048, 18432)
    moe = [i for i in range(DSV3.n_layers) if DSV3.is_moe_layer(i)]
    assert moe == list(range(3, 61))


def test_moe_ep_builds_what_it_built():
    """``moe_train_dag`` prices experts with the expert width now; for
    Mixtral that is ``d_ff``, so ``moe_ep``'s DAGs are unchanged."""
    want = {True: "773d6d40bfe6b85f2a86040262da6f1c"
                  "1e67644c3d09bd231dc7a859d70db97e",
            False: "89192853d48ad29e923fefe615fb146d"
                   "10e402b95a792eb2012237302535a38d"}
    for quick, digest in want.items():
        _, jobs = build_scenario("moe_ep", seed=3, quick=quick, lint=False)
        plain = [{"name": j.name, "arrival": j.arrival,
                  "tasks": [(t.name, t.load, t.machine, t.deps)
                            for t in j.tasks.values()],
                  "mfs": [(m.name, [(f.src, f.dst, f.size) for f in m.flows],
                           m.deps) for m in j.metaflows.values()]}
                 for j in jobs]
        assert hashlib.sha256(
            json.dumps(plain).encode()).hexdigest() == digest


# -------------------------------------------------------------- the cell
def test_bench_copy_builds_the_program_lanes():
    """``bench/configs/dsv3_ep64.py`` and the program's scenario give
    byte-identical plain lanes for the cell's seeds."""
    from bench import harness

    spec = harness.load_spec("dsv3_ep64.layers2")
    traffic, config = spec["traffic"], spec["config"]
    seeds = range(traffic["lanes"])
    bench = harness.build_lanes([(s, list(range(config["n_ports"])))
                                 for s in seeds], traffic, config)
    for seed, (_, jobs) in zip(seeds, bench):
        _, prog = build_scenario("dsv3_ep64", seed=seed)
        assert json.dumps(harness.plain_lane(prog, config)) == json.dumps(
            harness.plain_lane(jobs, config))
