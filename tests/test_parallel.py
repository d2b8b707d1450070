"""Distribution tests on a small forced-device mesh (run in subprocesses so
the device-count XLA flag doesn't leak into other tests' single-device
view)."""

import json
import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(py_src: str, n_dev: int = 4, timeout=900):
    env = dict(os.environ)
    env["XLA_FLAGS"] = (f"--xla_force_host_platform_device_count={n_dev} "
                        + env.get("XLA_FLAGS", ""))
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    env["JAX_PLATFORMS"] = "cpu"      # a forced host mesh, never a chip
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(py_src)],
                         capture_output=True, text=True, env=env,
                         timeout=timeout)
    assert out.returncode == 0, out.stdout + "\n" + out.stderr
    return out.stdout


def test_sharded_train_step_matches_single_device():
    """Loss and params after one SPMD (2x2 mesh) train step must equal the
    single-device result — the sharding rules are numerically inert."""
    _run("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs import get
        from repro.configs.base import reduced
        from repro.data import pipeline
        from repro.launch.mesh import make_test_mesh
        from repro.models import model as M
        from repro.optim import adamw
        from repro.parallel import api as par
        from repro.train import steps as S

        cfg = reduced(get('deepseek-7b'))
        opt_cfg = adamw.AdamWConfig(lr=1e-3)
        b = pipeline.synthetic_batch(cfg, batch=4, seq=64, step=0)
        batch = {k: jnp.asarray(v) for k, v in b.items()}
        step = S.make_train_step(cfg, opt_cfg)

        # single device
        state0 = S.init_train_state(cfg, jax.random.key(0), opt_cfg)
        s1, m1 = jax.jit(step)(state0, batch)

        # 2x2 mesh
        mesh = make_test_mesh((2, 2), ('data', 'model'))
        rules = par.default_rules(mesh)
        state0b = S.init_train_state(cfg, jax.random.key(0), opt_cfg)
        ax = S.train_state_axes(cfg)
        shardings = jax.tree.map(
            lambda a, x: NamedSharding(
                mesh, par.param_spec(a.shape, x, rules) if x else P()),
            state0b, ax,
            is_leaf=lambda t: isinstance(t, tuple) and all(
                isinstance(e, (str, type(None))) for e in t))
        state0b = jax.device_put(state0b, shardings)
        with par.use_rules(rules), mesh:
            s2, m2 = jax.jit(step, in_shardings=(shardings, None))(
                state0b, batch)

        assert abs(float(m1['loss']) - float(m2['loss'])) < 1e-3, (
            float(m1['loss']), float(m2['loss']))
        f1 = jax.tree.leaves(s1['params'])
        f2 = jax.tree.leaves(s2['params'])
        for a, b2 in zip(f1, f2):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b2),
                                       rtol=2e-3, atol=2e-3)
        print('SPMD == single device OK')
    """)


def test_gpipe_pipeline_matches_sequential():
    _run("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh
        from repro.runtime import pipeline as PP
        mesh = Mesh(np.asarray(jax.devices()).reshape(4), ('stage',))
        params, stage_fn, ref = PP.make_pipelined_mlp(
            jax.random.key(0), 4, 32, 64)
        x = jax.random.normal(jax.random.key(1), (16, 32))
        for mb in (4, 8, 16):
            out = PP.pipeline_apply(stage_fn, params, x, mesh=mesh,
                                    microbatches=mb)
            np.testing.assert_allclose(np.asarray(out),
                                       np.asarray(ref(params, x)),
                                       rtol=2e-5, atol=2e-5)
        print('pipeline OK')
    """)


def test_param_spec_tp_plus_fsdp():
    _run("""
        import jax
        from jax.sharding import PartitionSpec as P
        from repro.launch.mesh import make_test_mesh
        from repro.parallel import api as par
        mesh = make_test_mesh((2, 2), ('data', 'model'))
        rules = par.default_rules(mesh)
        # TP on 'mlp' axis + FSDP on the other
        spec = par.param_spec((128, 256), ('embed', 'mlp'), rules)
        assert spec == P('data', 'model'), spec
        # unshardable small axis degrades gracefully
        spec = par.param_spec((3, 256), ('embed', 'mlp'), rules)
        assert spec == P(None, 'model'), spec
        # activation spec dedups + checks divisibility
        spec = par.activation_spec((8, 24, 10), ('batch', 'seq_kv', None),
                                   rules)
        assert spec == P('data', 'model', None), spec
        spec = par.activation_spec((7, 24, 10), ('batch', 'seq_kv', None),
                                   rules)
        assert spec == P(None, 'model', None), spec
        print('specs OK')
    """)


def test_dryrun_entrypoint_small():
    """The dry-run driver itself (reduced device count): one real cell."""
    out = _run("""
        import os
        os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=512'
        import sys
        sys.argv = ['dryrun', '--arch', 'mamba2-130m', '--shape',
                    'decode_32k', '--rolled', '--out',
                    '/tmp/dryrun_test_out']
        from repro.launch import dryrun
        try:
            dryrun.main()
        except SystemExit as e:
            assert e.code == 0, 'dry-run cell failed'
        import json
        rec = json.load(open('/tmp/dryrun_test_out/'
                             'mamba2-130m__decode_32k__16x16__rolled.json'))
        assert rec['status'] == 'ok'
        assert rec['roofline']['chips'] == 256
        print('dryrun cell OK')
    """, n_dev=512, timeout=1200)
    assert "dryrun cell OK" in out
