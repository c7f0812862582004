"""Multi-device behaviour, exercised in subprocesses so the main test
process keeps the real single-CPU device view (per the brief, XLA_FLAGS is
set only in dedicated entrypoints)."""

import json
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent


def _run(script: str, timeout: int = 600) -> str:
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(script)],
        capture_output=True, text=True, timeout=timeout,
        cwd=REPO,
    )
    assert out.returncode == 0, f"stdout:\n{out.stdout}\nstderr:\n{out.stderr[-3000:]}"
    return out.stdout


def test_ring_allreduce_matches_mean():
    _run("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import sys; sys.path.insert(0, "src")
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.transfer.collective import ring_allreduce_tree
        mesh = jax.make_mesh((4, 2), ("pod", "data"))
        def body(x):
            return ring_allreduce_tree({"g": x[0]}, "pod", [0, 2, 1, 3])["g"][None]
        h = jax.shard_map(body, mesh=mesh, in_specs=P("pod"),
                          out_specs=P("pod"), check_vma=False)
        x = jax.random.normal(jax.random.PRNGKey(0), (4, 6, 33))
        got = np.asarray(jax.jit(h)(x))
        want = np.broadcast_to(np.mean(np.asarray(x), 0, keepdims=True), x.shape)
        assert np.allclose(got, want, atol=1e-5), np.abs(got-want).max()
        print("OK")
    """)


def test_sharded_train_step_matches_single_device():
    """The same train step on a (2,2) mesh and on 1 device produces the same
    loss and parameters — sharding is semantics-preserving."""
    _run("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import sys; sys.path.insert(0, "src")
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_arch, reduced
        from repro.models import init_params
        from repro.models.model import abstract_params
        from repro.train import OptConfig, init_opt_state, make_train_step
        from repro.sharding.specs import (ShardingRules, set_mesh,
                                          make_param_shardings)
        import dataclasses

        cfg = reduced(get_arch("qwen2-7b"), vocab_size=256)
        key = jax.random.PRNGKey(0)
        params = init_params(cfg, key)
        opt = init_opt_state(params)
        batch = {"tokens": jax.random.randint(key, (4, 32), 0, 256),
                 "labels": jax.random.randint(key, (4, 32), 0, 256)}

        # single device reference
        rules0 = ShardingRules(batch=None, fsdp=None, tp=None)
        step0 = jax.jit(make_train_step(cfg, rules0, OptConfig()))
        p0, o0, m0 = step0(params, opt, batch)

        mesh = jax.make_mesh((2, 2), ("data", "model"))
        rules = ShardingRules(batch=("data",), fsdp="data", tp="model")
        set_mesh(mesh)
        pshard = make_param_shardings(mesh, rules, abstract_params(cfg))
        params_s = jax.device_put(params, pshard)
        opt_s = init_opt_state(params_s)
        with mesh:
            step1 = jax.jit(make_train_step(cfg, rules, OptConfig()))
            p1, o1, m1 = step1(params_s, opt_s, batch)
        # bf16 reduction order differs across shardings; semantics identical
        assert abs(float(m0["loss"]) - float(m1["loss"])) < 5e-3, (
            float(m0["loss"]), float(m1["loss"]))
        for a, b in zip(jax.tree.leaves(p0), jax.tree.leaves(p1)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=5e-3, rtol=1e-2)
        print("OK")
    """)


@pytest.mark.slow
def test_dryrun_single_cell_smoke():
    """One real dry-run cell end to end (multi-pod mesh, 512 devices)."""
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch", "smollm-135m",
         "--shape", "decode_32k", "--mesh", "multi", "--force",
         "--out", "/tmp/dryrun_test"],
        capture_output=True, text=True, timeout=900,
        cwd=REPO, env={**__import__("os").environ, "PYTHONPATH": "src"},
    )
    assert out.returncode == 0, out.stderr[-3000:]
    art = json.loads(
        Path("/tmp/dryrun_test/smollm-135m__decode_32k__multi.json").read_text()
    )
    assert art["status"] == "ok"
    assert art["full"]["flops_per_device"] > 0
    assert art["mesh_shape"] == {"pod": 2, "data": 16, "model": 16}


def test_existing_dryrun_artifacts_complete():
    """The committed sweep must cover all 40 cells x 2 meshes with no
    errors (skips must carry a reason)."""
    art_dir = REPO / "artifacts" / "dryrun"
    if not art_dir.exists():
        pytest.skip("dry-run sweep not generated yet")
    files = list(art_dir.glob("*__*.json"))
    cells = [json.loads(f.read_text()) for f in files
             if f.name.count("__") == 2]
    assert len(cells) >= 80
    for a in cells:
        assert a["status"] in ("ok", "skipped"), (a["arch"], a["shape"], a["mesh"])
        if a["status"] == "skipped":
            assert a["skip_reason"]
        else:
            assert a["full"]["flops_per_device"] > 0
