"""Multi-device behaviour via subprocesses (XLA_FLAGS must precede jax init,
so the main pytest process stays single-device)."""
from __future__ import annotations

import os
import subprocess
import sys
import textwrap

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_py(code: str, devices: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    r = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, env=env, timeout=600,
    )
    assert r.returncode == 0, r.stderr[-4000:]
    return r.stdout


def test_sharded_plan_equals_single_device(hospital, tmp_path):
    """MLtoSQL-fused plan under shard_map over 8 devices == 1-device result."""
    from repro.core.optimizer import OptimizerOptions, RavenOptimizer
    from repro.relational.engine import execute_plan
    from repro.sql.parser import parse_prediction_query
    from tests.conftest import train_pipeline
    from repro.ml.pipeline import save_pipeline

    pipe = train_pipeline(hospital, "dt")
    mpath = str(tmp_path / "m.npz")
    save_pipeline(pipe, mpath)
    np.savez(str(tmp_path / "data.npz"), **hospital.tables["patients"])

    sql = "SELECT COUNT(*) FROM PREDICT(model='m', data=patients) AS p WHERE score >= 0.5"
    q = parse_prediction_query(sql, {"m": pipe}, hospital.tables)
    plan, _ = RavenOptimizer(
        options=OptimizerOptions(transform="sql")
    ).optimize(q)
    ref = float(
        np.asarray(
            execute_plan(plan, hospital.tables).columns["count_rows"]
        )[0]
    )

    out = _run_py(f"""
        import numpy as np, jax
        from repro.ml.pipeline import load_pipeline
        from repro.sql.parser import parse_prediction_query
        from repro.core.optimizer import OptimizerOptions, RavenOptimizer
        from repro.relational.engine import compile_plan_sharded

        data = dict(np.load({str(tmp_path / 'data.npz')!r}))
        pipe = load_pipeline({mpath!r})
        db = {{'patients': data}}
        sql = {sql!r}
        q = parse_prediction_query(sql, {{'m': pipe}}, db)
        plan, _ = RavenOptimizer(options=OptimizerOptions(transform='sql')).optimize(q)
        mesh = jax.make_mesh((8,), ('data',))
        run = compile_plan_sharded(plan, mesh, fact_table='patients')
        out = run(db)
        print('COUNT=', float(np.asarray(out.columns['count_rows'])[0]))
    """)
    got = float(out.split("COUNT=")[1].strip())
    assert got == ref


def test_hierarchical_psum_matches_flat():
    out = _run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.distributed import hierarchical_psum

        mesh = jax.make_mesh((2, 4), ('pod', 'data'))
        x = jnp.arange(32, dtype=jnp.float32).reshape(8, 4)

        def flat(v):
            return jax.lax.psum(v, ('pod', 'data'))

        def hier(v):
            return hierarchical_psum(v, intra_axis='data', inter_axis='pod')

        fa = jax.shard_map(flat, mesh=mesh, in_specs=P(('pod','data'), None),
                       out_specs=P(('pod','data'), None))(x)
        fb = jax.shard_map(hier, mesh=mesh, in_specs=P(('pod','data'), None),
                       out_specs=P(('pod','data'), None))(x)
        print('MATCH=', bool(jnp.allclose(fa, fb)))
    """)
    assert "MATCH= True" in out


def test_embed_lookup_vocab_sharded_matches_take():
    out = _run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.models.transformer import embed_lookup
        mesh = jax.make_mesh((2, 4), ('data', 'model'))
        V, D, B, S = 64, 16, 4, 8
        embed = jax.random.normal(jax.random.PRNGKey(0), (V, D), jnp.float32)
        toks = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, V)
        with jax.set_mesh(mesh):
            got = embed_lookup(embed, toks, mesh)
        want = jnp.take(embed, toks, axis=0)
        print('MATCH=', bool(jnp.allclose(got, want, atol=1e-6)))
        # B=1 path (long_500k): batch not divisible by data axis
        toks1 = toks[:1]
        with mesh:
            got1 = embed_lookup(embed, toks1, mesh)
        print('MATCH1=', bool(jnp.allclose(got1, jnp.take(embed, toks1, axis=0), atol=1e-6)))
    """)
    assert "MATCH= True" in out and "MATCH1= True" in out


def test_compressed_allreduce_inside_shard_map():
    out = _run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.distributed import ef_init, compressed_gradient_update

        mesh = jax.make_mesh((4,), ('pod',))
        g = jax.random.normal(jax.random.PRNGKey(0), (4, 32), jnp.float32)

        def body(gl):
            state = ef_init({'g': gl})
            out, _ = compressed_gradient_update({'g': gl}, state, axis_name='pod')
            return out['g']

        got = jax.shard_map(body, mesh=mesh, in_specs=P('pod', None),
                        out_specs=P('pod', None))(g)
        want = jnp.mean(g, axis=0, keepdims=True)  # psum/4 of per-pod grads
        err = float(jnp.abs(got - want).max())
        scale = float(jnp.abs(g).max()) / 127.0
        print('OK=', err <= 2.1 * scale)
    """)
    assert "OK= True" in out
