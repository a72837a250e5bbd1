"""Multi-device behaviours via subprocesses (the parent process keeps its
single real CPU device; each subprocess sets XLA_FLAGS before importing jax).
"""
import os
import subprocess
import sys
import textwrap

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_py(code: str, n_devices: int = 8, timeout=420):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_devices}"
    env["PYTHONPATH"] = SRC
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, env=env,
                       timeout=timeout)
    assert r.returncode == 0, f"stderr:\n{r.stderr}\nstdout:\n{r.stdout}"
    return r.stdout


def test_hloanalysis_scan_trip_count_flops():
    """Loop-corrected FLOPs of a scanned matmul == unrolled (the bug that
    motivated the analyzer: cost_analysis counts while bodies once)."""
    out = run_py("""
        import jax, jax.numpy as jnp
        from repro.launch.hloanalysis import analyze
        W = jax.ShapeDtypeStruct((13, 128, 128), jnp.float32)
        x = jax.ShapeDtypeStruct((128, 128), jnp.float32)
        def f_scan(x, ws):
            return jax.lax.scan(lambda c, w: (c @ w, None), x, ws)[0]
        def f_unroll(x, ws):
            for i in range(13):
                x = x @ ws[i]
            return x
        a = analyze(jax.jit(f_scan).lower(x, W).compile().as_text())
        b = analyze(jax.jit(f_unroll).lower(x, W).compile().as_text())
        expected = 13 * 2 * 128**3
        assert abs(a["flops"] - expected) / expected < 0.01, a["flops"]
        assert abs(b["flops"] - expected) / expected < 0.01, b["flops"]
        print("OK", a["flops"], b["flops"])
    """, n_devices=1)
    assert "OK" in out


def test_hloanalysis_collective_bytes():
    """A known psum has known all-reduce operand bytes."""
    out = run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        from repro.launch.hloanalysis import analyze
        mesh = Mesh(np.asarray(jax.devices()).reshape(8), ("d",))
        x = jax.ShapeDtypeStruct((8, 1024), jnp.float32)
        def f(x):
            return jax.lax.with_sharding_constraint(
                jnp.broadcast_to(x.sum(axis=0, keepdims=True), x.shape),
                NamedSharding(mesh, P("d", None)))
        with mesh:
            c = jax.jit(f, in_shardings=NamedSharding(mesh, P("d", None))).lower(x).compile()
        a = analyze(c.as_text())
        total = a["collective_bytes_total"]
        assert total > 0, a
        print("OK", a["collective_count"], total)
    """, n_devices=8)
    assert "OK" in out


def test_dryrun_single_cell_small_mesh():
    """End-to-end Cell lower/compile + counters on an 8-device (4,2) mesh."""
    out = run_py("""
        import jax, numpy as np
        from repro.configs.base import RunPolicy, ShapeSpec
        from repro.configs.all_archs import smoke_config
        from repro.launch.steps import build_cell
        from repro.core.counters import measure_cell
        from repro.hw import V5E
        mesh = jax.sharding.Mesh(np.asarray(jax.devices()).reshape(4, 2),
                                 ("data", "model"))
        cfg = smoke_config("qwen2-1.5b")
        for kind, shape in [("train", ShapeSpec("t", "train", 64, 8)),
                            ("decode", ShapeSpec("d", "decode", 128, 8))]:
            pol = RunPolicy(remat="dots", n_microbatch=2)
            cell = build_cell(cfg, shape, pol, mesh)
            m = measure_cell(cell, V5E)
            assert m.roofline["bound_s"] > 0
            assert m.roofline["hlo_flops_per_dev"] > 0
            print("OK", kind, m.roofline["dominant"])
    """, n_devices=8)
    assert out.count("OK") == 2


def test_compressed_grad_reduction_multipod():
    """int8 EF compression on the pod axis: train step runs, loss finite,
    and the compiled HLO contains an s32 all-reduce (the compressed wire)."""
    out = run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs.base import RunPolicy, ShapeSpec
        from repro.configs.all_archs import smoke_config
        from repro.models import api
        from repro.train.optimizer import OptConfig
        from repro.train.train_step import make_train_step, make_init_opt
        mesh = jax.sharding.Mesh(np.asarray(jax.devices()).reshape(2, 2, 2),
                                 ("pod", "data", "model"))
        cfg = smoke_config("tinyllama-1.1b")
        pol = RunPolicy(remat="none", n_microbatch=1, grad_compress="int8",
                        dtype="f32")
        opt = OptConfig(warmup=1, decay_steps=10)
        params = api.init(cfg, jax.random.PRNGKey(0))
        st = make_init_opt(cfg, pol, opt, mesh)(params)
        step = jax.jit(make_train_step(cfg, pol, opt, mesh))
        batch = api.synthetic_batch(cfg, ShapeSpec("t", "train", 32, 8),
                                    jax.random.PRNGKey(1))
        with mesh:
            txt = step.lower(params, st, batch).compile().as_text()
            p2, st2, m = step(params, st, batch)
        assert "s32" in txt and "all-reduce" in txt
        l = float(m["loss"]); assert l == l and l > 0
        print("OK loss", l)
    """, n_devices=8)
    assert "OK" in out


def test_compression_error_feedback_unbiased():
    """EF compensates quantization: accumulated compressed updates converge
    to the true gradient direction (property over random tensors)."""
    out = run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh, PartitionSpec as P
        from repro.train.compression import reduce_grads
        mesh = Mesh(np.asarray(jax.devices()).reshape(4), ("pod",))
        g_global = jax.random.normal(jax.random.PRNGKey(0), (4, 64)) * 3.0

        def body(g, ef):
            red, ef2 = reduce_grads({"g": g[0]}, {"g": ef[0]}, "int8", "pod")
            return red["g"], ef2["g"][None]

        from repro.launch.mesh import shard_map
        f = shard_map(body, mesh=mesh, in_specs=(P("pod"), P("pod")),
                      out_specs=(P(), P("pod")), check_vma=False)
        true_mean = g_global.mean(axis=0)
        ef = jnp.zeros((4, 64))
        acc = jnp.zeros((64,))
        for step in range(20):
            red, ef = f(g_global, ef)
            acc = acc + red
        err = float(jnp.max(jnp.abs(acc / 20 - true_mean)))
        scale = float(jnp.max(jnp.abs(true_mean)))
        assert err / scale < 0.01, (err, scale)
        print("OK ef err", err / scale)
    """, n_devices=4)
    assert "OK" in out
