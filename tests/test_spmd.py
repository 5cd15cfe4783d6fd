"""SPMD correctness on 8 fake devices (subprocess: device count is fixed at
jax init, so each test execs a fresh interpreter with XLA_FLAGS set)."""

import os
import subprocess
import sys
import textwrap

import pytest

# heavy tier: each test boots a fresh 8-fake-device interpreter
pytestmark = pytest.mark.slow

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_spmd(body: str, devices: int = 8, timeout: int = 600):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    proc = subprocess.run([sys.executable, "-c", textwrap.dedent(body)],
                          env=env, capture_output=True, text=True,
                          timeout=timeout)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return proc.stdout


def test_sequence_parallel_scan_matches_sequential():
    run_spmd("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.core import scan as scan_lib
        from repro.distributed import context as mesh_ctx

        mesh = jax.make_mesh((8,), ("data",))
        key = jax.random.PRNGKey(0)
        k1, k2 = jax.random.split(key)
        a = jax.nn.sigmoid(jax.random.normal(k1, (2, 64, 4)))
        b = jax.random.normal(k2, (2, 64, 4))
        ref = scan_lib.scan_sequential(a, b)

        fn = mesh_ctx.shard_map(
            lambda a, b: scan_lib.scan_sequence_parallel(a, b, "data"),
            mesh=mesh, in_specs=(P(None, "data", None),) * 2,
            out_specs=P(None, "data", None))
        out = jax.jit(fn)(a, b)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)
        print("seq-parallel scan OK")
    """)


def test_moe_expert_parallel_matches_local():
    run_spmd("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs.base import ModelConfig, MoEConfig
        from repro.distributed import context as mesh_ctx
        from repro.models import moe

        import sys
        mode = sys.argv[1] if len(sys.argv) > 1 else "auto"
        cfg = ModelConfig(d_model=16, moe=MoEConfig(
            n_experts=8, top_k=2, d_expert=32, capacity_factor=16.0,
            ep_2d=mode))
        params = moe.moe_init(jax.random.PRNGKey(0), cfg)
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 12, 16))

        y_local, aux_local = moe.moe_apply(params, cfg, x)

        mesh = jax.make_mesh((2, 4), ("data", "model"))
        with mesh_ctx.use_mesh(mesh):
            y_ep, aux_ep = jax.jit(
                lambda p, x: moe.moe_apply(p, cfg, x))(params, x)
        np.testing.assert_allclose(np.asarray(y_ep), np.asarray(y_local),
                                   rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(float(aux_ep), float(aux_local),
                                   rtol=1e-4)
        print("EP MoE OK")
    """)


def test_dp_compressed_step_matches_single_device_trend():
    run_spmd("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import archs
        from repro.data import lm_corpus
        from repro.models import lm
        from repro.training import optimizer as opt_lib
        from repro.training import train_step as ts_lib

        cfg = archs.smoke("mingru-lm")
        ocfg = opt_lib.AdamWConfig(lr=1e-3, warmup_steps=0,
                                   schedule="constant")
        params = lm.init_params(jax.random.PRNGKey(0), cfg)
        opt_state = opt_lib.init(ocfg, params)
        data, _ = lm_corpus.build_corpus()
        batch = lm_corpus.lm_batch(data, 0, 0, 8, 32)

        ref_step = jax.jit(ts_lib.make_train_step(cfg, ocfg))
        p_ref, _, m_ref = ref_step(params, opt_state, batch)

        mesh = jax.make_mesh((4, 2), ("data", "model"))
        dp_step = ts_lib.make_dp_compressed_step(cfg, ocfg, mesh)
        p_dp, _, m_dp = dp_step(params, opt_state, batch)
        # bf16-compressed grads: parameters close, not bitwise
        for a, b in zip(jax.tree.leaves(p_ref), jax.tree.leaves(p_dp)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=0.1, atol=2e-3)
        assert abs(float(m_ref["loss"]) - float(m_dp["loss"])) < 1e-2
        print("dp compressed OK")
    """)


def test_tiny_dryrun_lower_compile():
    """The dry-run machinery end-to-end on a small mesh, smoke configs."""
    run_spmd("""
        import jax
        from repro.configs import archs
        from repro.configs.base import SHAPES, ShapeConfig
        from repro.distributed import context as mesh_ctx
        from repro.launch.dryrun import build_lowerable
        from repro.launch.mesh import make_debug_mesh

        mesh = make_debug_mesh(data=2, model=2, pod=2)
        shape = ShapeConfig("tiny_train", 64, 8, "train")
        dshape = ShapeConfig("tiny_decode", 64, 8, "decode")
        for arch in ("gemma-2b", "mamba2-370m", "deepseek-moe-16b",
                     "mingru-lm", "zamba2-2.7b"):
            cfg = archs.smoke(arch).replace(scan_layers=False)
            for sh in (shape, dshape):
                fn, args, in_sh, out_sh, donate = build_lowerable(
                    cfg, sh, mesh)
                kw = dict(in_shardings=in_sh)
                if out_sh is not None:
                    kw["out_shardings"] = out_sh
                with mesh_ctx.use_mesh(mesh):
                    c = jax.jit(fn, **kw).lower(*args).compile()
                ca = c.cost_analysis()
                assert ca["flops"] > 0
                print(arch, sh.name, "OK")
    """, timeout=900)


def test_checkpoint_reshard_restore():
    """Save unsharded, restore onto an 8-device mesh with shardings."""
    import tempfile
    tmp = tempfile.mkdtemp()
    run_spmd(f"""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.training import checkpoint as ckpt_lib

        tree = {{"layer": {{"kernel": jnp.arange(64, dtype=jnp.float32
                                                ).reshape(8, 8)}}}}
        ckpt_lib.save("{tmp}", 3, tree)

        mesh = jax.make_mesh((4, 2), ("data", "model"))
        sh = {{"layer": {{"kernel": NamedSharding(mesh,
                                                  P("data", "model"))}}}}
        step, restored, _ = ckpt_lib.restore(
            "{tmp}/step_00000003", shardings=sh)
        assert step == 3
        k = restored["layer"]["kernel"]
        assert len(k.sharding.device_set) == 8
        np.testing.assert_array_equal(np.asarray(k),
                                      np.asarray(tree["layer"]["kernel"]))
        print("reshard restore OK")
    """)
