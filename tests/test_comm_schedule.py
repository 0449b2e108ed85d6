"""Metaflow/MSA integration with the training step: on every assigned
architecture the step-DAG plan's MSA order is no worse than the flat
barrier or Varys, and it orders every scan unit's bucket once."""

import pytest

from repro.configs import ARCH_NAMES, get_config
from repro.configs.base import LM_SHAPES, n_units
from repro.core.comm_schedule import plan_step_comm


class TestStepPlan:
    @pytest.mark.parametrize("arch", ARCH_NAMES)
    def test_msa_no_worse_than_barrier(self, arch):
        plan = plan_step_comm(get_config(arch), LM_SHAPES["train_4k"])
        assert plan.dag_steps["msa"] <= plan.dag_steps["flat"] + 1e-9
        assert plan.dag_steps["msa"] <= plan.dag_steps["varys"] + 1e-9

    @pytest.mark.parametrize("arch", ARCH_NAMES)
    def test_order_is_permutation(self, arch):
        cfg = get_config(arch)
        plan = plan_step_comm(cfg, LM_SHAPES["train_4k"])
        assert sorted(plan.order) == list(range(n_units(cfg)))

    def test_msa_order_prioritizes_late_backward_units(self):
        """Backward runs top unit first -> its grads arrive first; with a
        busy link MSA still transfers in availability order here (all
        buckets uniform), i.e. descending unit index prefix."""
        cfg = get_config("qwen2-7b")
        plan = plan_step_comm(cfg, LM_SHAPES["train_4k"])
        U = max(plan.order) + 1
        assert plan.order[0] == U - 1

    def test_overlap_reported(self):
        plan = plan_step_comm(get_config("llama3-405b"),
                              LM_SHAPES["train_4k"])
        assert 0.0 <= plan.overlap_fraction <= 1.0
