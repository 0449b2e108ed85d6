"""``repro.appdag`` — compile real ML parallelism plans into metaflow DAGs.

The bridge from published model configs (``repro.configs``) and
parallelism axes (DP/TP/PP/EP) to the scheduling core's ``JobDAG``
workloads.  Its layers (DESIGN.md §9):

  ``lowering``  logical collectives -> per-port flow rounds with exact
                byte accounting (ring / halving-doubling / direct), and
                the rail all-to-all whose legs follow a router,
  ``routing``   a seeded node-limited MoE router: tokens a rank sends to
                each node, pairs each rank's experts receive,
  ``plans``     model config x ``PlanAxes`` -> per-step communication DAG
                with compute nodes between collectives (dense training,
                MoE training, pipelined serving, a routed EP stage),
  ``mixer``     job templates x arrival process -> mixed-cluster
                scenarios (training + serving + MapReduce on one fabric).
"""

from repro.appdag.lowering import (ALGORITHMS, COLLECTIVES,
                                   LoweredCollective, add_lowered,
                                   lower_collective, lower_grouped,
                                   rail_all_to_all)
from repro.appdag.mixer import (SCENARIOS, JobTemplate, build_scenario,
                                mixed_templates, poisson_mix)
from repro.appdag.plans import (PlanAxes, dense_train_dag, ep_stage_dag,
                                moe_train_dag, n_units, pipeline_serve_dag,
                                unit_grad_bytes)
from repro.appdag.routing import RouteStats, route

__all__ = [
    "ALGORITHMS", "COLLECTIVES", "JobTemplate", "LoweredCollective",
    "PlanAxes", "RouteStats", "SCENARIOS", "add_lowered", "build_scenario",
    "dense_train_dag", "ep_stage_dag", "lower_collective", "lower_grouped",
    "mixed_templates", "moe_train_dag", "n_units", "pipeline_serve_dag",
    "poisson_mix", "rail_all_to_all", "route", "unit_grad_bytes",
]
