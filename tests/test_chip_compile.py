"""The lockstep engine compiles for a TPU v5e chip (DESIGN.md §17).

Nothing here runs on a chip: each test compiles ahead of time for a
*described* ``v5e:2x2`` topology with the TPU compiler that ships with
JAX, at the padded 20-lane shapes of ``pipe_serve`` (small-flow lanes)
and ``dense_dp`` (large-flow lanes).  A change the chip's compiler
refuses, or one that brings back a float64 ``reduce-window`` (what
``jnp.cumsum`` lowers to, and which took the step program minutes to
compile in emulated float64), fails here in seconds instead of on the
chip.  The compiled programs must also keep the engine's named scopes in
their op names and its loop counters in 32 bits, and the backfill wave
loop and the MADD's demand must stay free of gathers: the TPU runs a
gather one element at a time, about 10 ns each.

The topology is described inside a module-scoped fixture, never at
import: only one process at a time may load the TPU library.
"""

from __future__ import annotations

import inspect
import re

import pytest

jax = pytest.importorskip("jax")

from jax.sharding import SingleDeviceSharding  # noqa: E402

from repro.appdag.mixer import build_scenario  # noqa: E402
from repro.core import simjax  # noqa: E402

LANES = 20
#: The step program's window, as ``run_fifo_batch`` runs it.
STEPS = inspect.signature(simjax.run_fifo_batch).parameters[
    "steps_per_sync"].default
PHASES = ("simjax.settle", "simjax.madd", "simjax.backfill", "simjax.horizon")


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A chip compile is written to the persistent cache but cannot be
    # read back without a chip; keep the cache out of it.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _shapes(scenario: str, sharding):
    lanes = [simjax.pack_instance(*build_scenario(scenario, seed=s,
                                                  lint=False))
             for s in range(LANES)]
    pk = simjax._pack_batch(lanes)
    st = jax.eval_shape(simjax._init_state, pk)
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        (pk, st))


def _f64_reduce_windows(lowered) -> list[str]:
    return [line for line in lowered.as_text(dialect="hlo").splitlines()
            if "reduce-window(" in line
            and "f64[" in line.split("reduce-window(")[0]]


def _scopes(compiled_text: str) -> set[str]:
    return {p for p in PHASES if f"/{p}/" in compiled_text}


def _gathers_under(compiled_text: str, scope: str) -> list[str]:
    """The compiled program's gather instructions whose op name lies
    under ``scope``."""
    return [line for line in compiled_text.splitlines()
            if re.search(r"\sgather\(", line)
            and re.search(rf'op_name="[^"]*{re.escape(scope)}/', line)]


@pytest.fixture(scope="module")
def programs(one_chip):
    """Per scenario, compiled once for the module: the state's shapes,
    the lowered settle and step-window programs, and the text of each
    compiled program."""
    built = {}

    def get(scenario: str) -> dict:
        if scenario not in built:
            pk, st = _shapes(scenario, one_chip)
            settle = jax.jit(simjax._settle).lower(pk, st)
            step = jax.jit(simjax._multi_step,
                           static_argnums=2).lower(pk, st, STEPS)
            built[scenario] = {
                "state": st, "settle": settle, "step": step,
                "settle_text": settle.compile().as_text(),
                "step_text": step.compile().as_text()}
        return built[scenario]

    return get


@pytest.mark.parametrize("scenario", ["pipe_serve", "dense_dp"])
def test_step_window_compiles_for_v5e(programs, scenario):
    got = programs(scenario)
    st = got["state"]
    # The loop counters stay 32-bit: 64-bit integers are emulated there.
    assert (st.waves.dtype == st.cascades.dtype == st.steps.dtype
            == jax.numpy.int32)
    assert _f64_reduce_windows(got["settle"]) == []
    assert _f64_reduce_windows(got["step"]) == []
    # The named scopes survive the chip's compiler, in the op names a
    # profiler trace of the program carries.
    assert _scopes(got["settle_text"]) == {"simjax.settle"}
    assert _scopes(got["step_text"]) == set(PHASES)


@pytest.mark.parametrize("scenario", ["pipe_serve", "dense_dp"])
def test_backfill_wave_body_has_no_gather(programs, scenario):
    text = programs(scenario)["step_text"]
    # The scope is in the program, so an empty list is not vacuous.
    assert "simjax.backfill/while/body/" in text
    assert _gathers_under(text, "simjax.backfill/while/body") == []


@pytest.mark.parametrize("scenario", ["pipe_serve", "dense_dp"])
def test_madd_demand_has_no_gather(programs, scenario):
    text = programs(scenario)["step_text"]
    # The scope is in the program, so the count is not vacuous.
    assert "simjax.madd/" in text
    # The (job, link) demand is a masked reduction and each flow's rate
    # is set where its job index matches the scan's; what the MADD still
    # gathers is each flow's node state, for the live flows.
    assert len(_gathers_under(text, "simjax.madd")) == 1
