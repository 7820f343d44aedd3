"""Multi-device behaviour tests, run in subprocesses with forced host
devices (the flag must never leak into this process — see dryrun.py note)."""

import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(script: str, n_devices: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_devices}"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    r = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(script)],
        capture_output=True, text=True, env=env, timeout=600, cwd=ROOT,
    )
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    return r.stdout


def test_sharded_train_step_matches_single_device():
    _run("""
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P, NamedSharding
        from repro.configs.registry import get_config
        from repro.configs.base import ShapeSpec
        from repro.models import transformer as T
        from repro.distributed import sharding as sh
        from repro.launch.mesh import make_mesh
        from repro.models import layers as L

        cfg = get_config("internlm2-1.8b", reduced=True)
        shape = ShapeSpec("t", 32, 8, "train")
        params = T.init_params(cfg, 0)
        rng = np.random.default_rng(0)
        batch = {"tokens": rng.integers(0, cfg.vocab, (8, 32)).astype(np.int32)}

        # single-device reference
        l_ref, _ = jax.jit(lambda p, b: T.loss_fn(p, b, cfg))(params, batch)

        mesh = make_mesh((2, 4), ("data", "model"))
        L.set_hint_mesh(mesh)
        pspec = sh.param_pspecs(cfg, mesh)
        bspec = sh.batch_pspecs(cfg, shape, mesh)
        fn = jax.jit(lambda p, b: T.loss_fn(p, b, cfg)[0],
                     in_shardings=(sh.to_named(mesh, pspec), sh.to_named(mesh, bspec)))
        with mesh:
            l_sh = fn(params, batch)
        err = abs(float(l_ref) - float(l_sh)) / abs(float(l_ref))
        assert err < 2e-2, (float(l_ref), float(l_sh))
        print("OK", float(l_ref), float(l_sh))
    """)


def test_trainer_data_mesh_matches_unsharded():
    """Trainer(mesh=4x1) jits its step with the sharding rules: the batch
    is split over 4 devices, and its losses match the unsharded Trainer's
    from the same seed."""
    _run("""
        import jax
        from repro.configs.registry import get_config
        from repro.configs.base import ShapeSpec
        from repro.data.pipeline import make_batch
        from repro.launch.mesh import make_mesh
        from repro.models import layers as L
        from repro.optim.optimizer import OptimizerConfig
        from repro.train.trainer import Trainer

        cfg = get_config("internlm2-1.8b", reduced=True)
        shape = ShapeSpec("t", 32, 8, "train")
        opt = OptimizerConfig(kind="adamw", lr=1e-3, warmup_steps=1,
                              total_steps=10)

        def losses(mesh):
            tr = Trainer(cfg, shape, opt, mesh=mesh)
            out = tr.train(3)
            return tr, out, [r["loss"] for r in out["history"]]

        _, _, ref = losses(None)
        tr, out, sharded = losses(make_mesh((4, 1), ("data", "model")))
        assert L._HINT_MESH is None    # hint mesh restored after the step

        b = jax.device_put(make_batch(cfg, shape, 0), tr.batch_shardings)
        assert len(b["tokens"].sharding.device_set) == 4
        assert {s.data.shape for s in b["tokens"].addressable_shards} == {(2, 32)}
        m = out["state"]["opt"]["m"]["embed"]
        assert len(m.sharding.device_set) == 4

        for a, c in zip(ref, sharded):
            assert abs(a - c) / abs(a) < 2e-2, (ref, sharded)
        print("OK", ref, sharded)
    """, n_devices=4)


def test_moe_arch_sharded_matches():
    _run("""
        import numpy as np, jax, dataclasses
        from repro.configs.registry import get_config
        from repro.configs.base import ShapeSpec
        from repro.models import transformer as T
        from repro.distributed import sharding as sh
        from repro.launch.mesh import make_mesh
        from repro.models import layers as L

        cfg = dataclasses.replace(get_config("qwen3-moe-30b-a3b", reduced=True),
                                  capacity_factor=8.0)
        shape = ShapeSpec("t", 16, 4, "train")
        params = T.init_params(cfg, 0)
        rng = np.random.default_rng(0)
        batch = {"tokens": rng.integers(0, cfg.vocab, (4, 16)).astype(np.int32)}
        l_ref, _ = jax.jit(lambda p, b: T.loss_fn(p, b, cfg))(params, batch)

        mesh = make_mesh((2, 4), ("data", "model"))
        L.set_hint_mesh(mesh)
        fn = jax.jit(lambda p, b: T.loss_fn(p, b, cfg)[0],
                     in_shardings=(sh.to_named(mesh, sh.param_pspecs(cfg, mesh)),
                                   sh.to_named(mesh, sh.batch_pspecs(cfg, shape, mesh))))
        with mesh:
            l_sh = fn(params, batch)
        err = abs(float(l_ref) - float(l_sh)) / abs(float(l_ref))
        assert err < 2e-2, (float(l_ref), float(l_sh))
        print("OK")
    """)


def test_pipeline_parallel_matches_sequential():
    _run("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.distributed.pipeline_parallel import pipeline_apply, bubble_fraction
        from repro.launch.mesh import make_mesh

        mesh = make_mesh((4,), ("pipe",))
        n_stages, n_micro, mb, d = 4, 8, 2, 16
        rng = np.random.default_rng(0)
        ws = jnp.asarray(rng.standard_normal((n_stages, d, d)) * 0.3, jnp.float32)
        x = jnp.asarray(rng.standard_normal((n_micro, mb, d)), jnp.float32)

        def stage_fn(w, h):
            return jnp.tanh(h @ w)

        out = pipeline_apply(stage_fn, ws, x, mesh)

        ref = x
        for s in range(n_stages):
            ref = jax.vmap(lambda h: stage_fn(ws[s], h))(ref)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)
        assert abs(bubble_fraction(4, 8) - 3/11) < 1e-9
        print("OK")
    """)


def test_campaign_cell_collectives_on_2dev_mesh(tmp_path):
    """Mesh-dim feature validation (ROADMAP "Next" item): a data-parallel
    2-device grid lowered through launch/lowering must parse nonzero
    collective bytes — with collective-class ledger records to match —
    while the same cell on 1x1 parses exactly zero.  Otherwise every mesh
    feature the campaign fits on is vacuously zero."""
    _run("""
        from repro.campaign.plan import plan_grid
        from repro.campaign.runner import measure_cell

        results = {}
        for mesh in ("1x1", "2x1"):
            plan = plan_grid(archs=("qwen3-4b",),
                             shapes=("smoke_train_16x2",), meshes=(mesh,))
            assert len(plan.cells) == 1, (mesh, plan.skipped)
            # compile-only: collective bytes come from the HLO parse
            results[mesh] = measure_cell(plan.cells[0], run=False)

        one, two = results["1x1"], results["2x1"]
        assert one["collective_bytes"] == 0.0, one["collective_bytes"]
        assert "collective" not in one["cost_classes"]
        assert two["collective_bytes"] > 0.0
        assert two["n_devices"] == 2

        # ledger attribution agrees with the scalar: the collective class
        # carries ALL of it, and the breakdown re-sums exactly
        classes = two["cost_classes"]
        coll = sum(s.get("collective_bytes", 0.0) for s in classes.values())
        assert coll == two["collective_bytes"]
        assert classes["collective"]["collective_bytes"] == coll
        assert classes["collective"]["count"] > 0
        for key in ("flops", "hbm_bytes"):
            assert sum(s.get(key, 0.0) for s in classes.values()) == two[key]

        # records stamp the device fingerprint the fit-time guard checks
        from repro.engine.devices import get_device
        assert two["device_fingerprint"] == get_device("host_cpu").fingerprint()
        print("OK", two["collective_bytes"])
    """, n_devices=2)


def test_elastic_checkpoint_restore_different_mesh(tmp_path):
    _run(f"""
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P, NamedSharding
        from repro.launch.mesh import make_mesh
        from repro.train import checkpoint as ckpt

        d = {str(tmp_path)!r}
        state = {{"w": np.arange(64, dtype=np.float32).reshape(8, 8)}}
        mesh1 = make_mesh((4, 2), ("data", "model"))
        sharded = jax.device_put(state["w"], NamedSharding(mesh1, P("data", "model")))
        ckpt.save_checkpoint(d, 3, {{"w": sharded}})

        mesh2 = make_mesh((2, 4), ("data", "model"))
        step, restored = ckpt.restore_checkpoint(
            d, template=state,
            shardings={{"w": NamedSharding(mesh2, P("data", "model"))}})
        assert step == 3
        np.testing.assert_array_equal(np.asarray(restored["w"]), state["w"])
        assert restored["w"].sharding.mesh.devices.shape == (2, 4)
        print("OK")
    """)
