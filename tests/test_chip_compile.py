"""TPU v5e compile rehearsals of the device programs on the main path.

Each test compiles one program for a described v5e chip (none attached)
at the shapes ``chip_smoke.py`` runs, as the TPU compiler would on the
chip: a refusal here (an f64 op the TPU does not implement, VMEM
exhaustion, an unaligned slice) is one the chip run would hit. Nothing
runs, so these say nothing about results or times.

The topology is described inside a module-scoped fixture and never while
a module is imported: only one process at a time may load the TPU
library, and every pytest worker imports every test file. Keep all such
compiles in this one file, so that only the worker given it loads the
library. The persistent compilation cache is off around them (a
described-chip compile can be written to it but not read back).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _sim_scenarios import SRC, DST, sim_scenarios
from repro.core import Planner, default_topology, milp


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")  # no compiler logs in /tmp
        try:
            topo = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2"
            )
        except Exception as e:  # noqa: BLE001 - any failure means no chip
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        prev = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", prev)
            compilation_cache.reset_cache()


def _on(sharding, tree):
    """Arrays -> shape/dtype structs placed on the described chip."""
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree,
    )


@pytest.fixture(scope="module")
def top():
    return default_topology()


@pytest.mark.parametrize("lanes,vms,edges", [
    (3072, 128, 128),  # chip_smoke's overlay scenario: 3055 conns, 75 VMs
    (8192, 128, 256),  # the shape whole one-hot operands ran out of VMEM at
])
@pytest.mark.parametrize("x64", [False, True])
def test_waterfill_kernel_compiles(one_chip, lanes, vms, edges, x64):
    from repro.kernels.waterfill.waterfill import fits, waterfill_8x

    assert fits(lanes, vms, edges)
    f32 = jax.ShapeDtypeStruct((8, lanes), jnp.float32, sharding=one_chip)
    i32 = jax.ShapeDtypeStruct((8, lanes), jnp.int32, sharding=one_chip)
    vm = jax.ShapeDtypeStruct((8, vms), jnp.float32, sharding=one_chip)
    ed = jax.ShapeDtypeStruct((8, edges), jnp.float32, sharding=one_chip)
    with jax.enable_x64(x64):
        hlo = waterfill_8x.lower(
            f32, f32, i32, i32, i32, vm, vm, ed, n_iters=2 * 75 + 11 + 4,
        ).compile().as_text()
    assert "tpu_custom_call" in hlo


def test_batched_ipm_compiles_at_the_fig6_sweep_shape(one_chip, top):
    """The planner's root LPs of the Fig. 6 route as one device call of a
    100-sample Pareto sweep holds them."""
    from repro.core.solver import ipm_jax

    sub, s, t, _ = Planner(top)._prune(SRC, DST)
    st = milp.structure(sub, s, t)
    b_ub = np.zeros((100, st.A_ub.shape[0]))
    lp = ipm_jax._Scaled(st.c, st.A_ub, b_ub, st.A_eq, st.b_eq)
    mp, n_pad = lp.shape
    assert (mp, n_pad) == (256, 512)
    bp = ipm_jax._MAX_BATCH  # 100 samples fill one call

    def f64(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float64, sharding=one_chip)

    with jax.enable_x64(True):
        compiled = ipm_jax._solve_batched.lower(
            f64(bp, mp, n_pad), f64(bp, mp), f64(bp, n_pad), f64(bp, mp),
            f64(bp, n_pad),
        ).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 8 << 30


@pytest.mark.parametrize("name", ["bulk", "overlay", "multicast"])
def test_sim_segment_compiles_with_the_chip_rate_solver(one_chip, top, name,
                                                        monkeypatch):
    from repro.kernels.waterfill import ops
    from repro.transfer import flowsim_jax
    from repro.transfer.events import materialize_jobs, sorted_schedule
    from repro.transfer.simconfig import SimConfig

    jobs, faults = sim_scenarios(top)[name]
    cfg = SimConfig()
    su = materialize_jobs(
        jobs, seed=cfg.seed, straggler_prob=cfg.straggler_prob,
        straggler_speed=cfg.straggler_speed, exec_top=cfg.exec_top,
    )
    solver = flowsim_jax._rate_solver_for(
        "tpu", su.conn_job.shape[0], su.vm_eg_cap.shape[0],
        len(su.edges_used),
    )
    assert solver == "pallas"
    # this host's backend is the CPU: compile the kernel, not its interpreter
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    with jax.enable_x64(True):
        sc, cn, st = flowsim_jax._build(
            su, cfg, sorted_schedule(jobs, faults), solver
        )
        hlo = flowsim_jax._segment.lower(
            _on(one_chip, st), _on(one_chip, cn), sc
        ).compile().as_text()
    assert "tpu_custom_call" in hlo
