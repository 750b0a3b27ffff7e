"""Distribution-layer tests. Multi-device cases run in a subprocess with
forced host-platform devices (the main test process keeps 1 device so smoke
tests see the normal environment)."""

import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.distributed.sharding import DEFAULT_RULES


def _run_subprocess(body: str) -> str:
    code = textwrap.dedent("""
        import os
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import sys
        sys.path.insert(0, "src")
        import jax, jax.numpy as jnp
        import numpy as np
    """) + textwrap.dedent(body)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, cwd=Path(__file__).resolve().parents[1],
                         timeout=480)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_rules_override_and_divisibility():
    r = DEFAULT_RULES.override(seq_kv=("model",), batch=None)
    d = r.as_dict()
    assert d["seq_kv"] == ("model",) and d["batch"] is None
    # unknown axes preserved
    assert d["heads"] == ("model",)


def test_logical_to_mesh_drops_indivisible():
    body = """
        from repro.distributed.sharding import DEFAULT_RULES, logical_to_mesh
        mesh = jax.make_mesh((2, 4), ("data", "model"))
        # kv dim 6 not divisible by model=4 -> dropped to None
        spec = logical_to_mesh(mesh, DEFAULT_RULES, ("embed", "heads"),
                               (8, 6))
        print("spec", spec)
        assert spec[1] is None, spec
        spec2 = logical_to_mesh(mesh, DEFAULT_RULES, ("embed", "heads"),
                                (8, 8))
        assert spec2[1] == "model", spec2
        print("OK")
    """
    assert "OK" in _run_subprocess(body)


def test_seq_sharded_decode_matches_reference():
    """Distributed split-K decode (shard_map + LSE psum) == local oracle."""
    body = """
        from repro.distributed.collectives import seq_sharded_decode
        from repro.kernels.decode_attention.ref import ref_decode_attention
        mesh = jax.make_mesh((2, 4), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)
        rng = jax.random.PRNGKey(0)
        B, S, H, KV, D = 2, 64, 8, 4, 16
        q = jax.random.normal(rng, (B, H, D))
        k = jax.random.normal(jax.random.fold_in(rng, 1), (B, S, KV, D))
        v = jax.random.normal(jax.random.fold_in(rng, 2), (B, S, KV, D))
        fn = seq_sharded_decode(mesh, ("data", "model"))
        out = jax.jit(fn)(q, k, v)
        ref = ref_decode_attention(q, k, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)
        print("OK")
    """
    assert "OK" in _run_subprocess(body)


def test_sharded_train_step_matches_single_device():
    """One reduced LM train step on an 8-device mesh == 1-device result."""
    body = """
        from repro import configs as C
        from repro.launch import steps as S
        arch = C.get("stablelm-3b")
        shape = arch.shapes[0]
        mesh = jax.make_mesh((4, 2), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)
        cell1 = S.build_cell(arch, shape, mesh=None, reduced=True)
        args = S.init_concrete(cell1, jax.random.PRNGKey(0))
        _, m1 = jax.jit(cell1.step_fn)(*args)

        cell2 = S.build_cell(arch, shape, mesh=mesh, reduced=True)
        args2 = S.init_concrete(cell2, jax.random.PRNGKey(0))
        with jax.set_mesh(mesh):
            _, m2 = jax.jit(cell2.step_fn,
                            in_shardings=cell2.in_shardings(mesh))(*args2)
        a, b = float(m1["loss"]), float(m2["loss"])
        assert abs(a - b) / a < 5e-3, (a, b)
        print("OK", a, b)
    """
    assert "OK" in _run_subprocess(body)
