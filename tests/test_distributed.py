"""Distributed behaviour on virtual device meshes.  Needs
XLA_FLAGS=--xla_force_host_platform_device_count BEFORE jax import, which
must not leak into the other (single-device) tests -> subprocesses."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_py(code: str, n_dev: int = 8, timeout=480):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_dev}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, timeout=timeout,
                       env=env, cwd=REPO)
    assert r.returncode == 0, r.stdout + r.stderr
    return r.stdout


@pytest.mark.slow
def test_sharded_train_step_runs_and_matches_single_device():
    out = run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.models.registry import get_config, get_model, tiny_config
        from repro.optim.adamw import AdamWConfig
        from repro.train.step import (abstract_state, init_state,
                                      make_train_step, state_partition_specs)
        from repro.launch.dryrun import tree_shardings, batch_pspec
        from repro.launch.mesh import make_test_mesh
        from repro.data.tokens import TokenStream

        cfg = tiny_config(get_config('llama3.2-1b'))
        model = get_model(cfg)
        step = make_train_step(model, AdamWConfig(lr=1e-3, total_steps=10,
                                                  warmup_steps=1))
        state = init_state(model, jax.random.PRNGKey(0))
        batch = TokenStream(cfg.vocab, 8, 32, seed=1).batch_at(0)

        # single-device result
        _, m1 = jax.jit(step)(state, batch)

        mesh = make_test_mesh((4, 2), ('data', 'model'))
        st_sh = tree_shardings(abstract_state(model),
                               state_partition_specs(model), mesh)
        b_sh = jax.tree_util.tree_map(
            lambda s: NamedSharding(mesh, batch_pspec(
                jax.ShapeDtypeStruct(s.shape, s.dtype), mesh)), batch)
        with jax.set_mesh(mesh):
            stp = jax.jit(step, in_shardings=(st_sh, b_sh),
                          out_shardings=(st_sh, None))
            state_d = jax.device_put(state, st_sh)
            batch_d = jax.device_put(batch, b_sh)
            _, m2 = stp(state_d, batch_d)
        l1, l2 = float(m1['loss']), float(m2['loss'])
        assert abs(l1 - l2) / l1 < 2e-2, (l1, l2)
        print('OK', l1, l2)
    """)
    assert "OK" in out


def test_compressed_allreduce_accuracy():
    out = run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.distributed.collectives import compressed_allreduce
        from repro.launch.mesh import make_test_mesh, shard_map
        mesh = make_test_mesh((8,), ('data',))
        x = np.random.default_rng(0).standard_normal((8, 4097)).astype('f4')
        f = jax.jit(shard_map(
            lambda xs: compressed_allreduce(xs[0], 'data')[None],
            mesh=mesh, in_specs=P('data', None), out_specs=P('data', None),
            check=False))
        out = np.asarray(f(x))
        want = x.sum(0)
        err = np.abs(out - want[None]).max() / np.abs(want).max()
        assert err < 0.05, err
        print('OK', err)
    """)
    assert "OK" in out


@pytest.mark.slow
def test_elastic_reshard_across_mesh_sizes():
    out = run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.models.registry import get_config, get_model, tiny_config
        from repro.train.step import init_state, state_partition_specs, abstract_state
        from repro.launch.dryrun import tree_shardings
        from repro.launch.mesh import make_test_mesh
        from repro.checkpoint.ckpt import save_checkpoint, restore_checkpoint
        import tempfile, pathlib

        cfg = tiny_config(get_config('llama3.2-1b'))
        model = get_model(cfg)
        state = init_state(model, jax.random.PRNGKey(0))
        d = pathlib.Path(tempfile.mkdtemp())
        mesh_a = make_test_mesh((2, 4), ('data', 'model'))
        sh_a = tree_shardings(abstract_state(model),
                              state_partition_specs(model), mesh_a)
        state_a = jax.device_put(state, sh_a)
        save_checkpoint(d, state_a, 5)

        # 'scale down': restore the same checkpoint under a 2x2 mesh
        mesh_b = make_test_mesh((2, 2), ('data', 'model'))
        sh_b = tree_shardings(abstract_state(model),
                              state_partition_specs(model), mesh_b)
        state_b, step = restore_checkpoint(d, abstract_state(model),
                                           shardings=sh_b)
        assert step == 5
        for a, b in zip(jax.tree_util.tree_leaves(state),
                        jax.tree_util.tree_leaves(state_b)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        print('OK elastic')
    """)
    assert "OK elastic" in out


@pytest.mark.slow
def test_aligner_shards_over_mesh():
    """(@slow: superseded in tier-1 by tests/test_multidevice.py, which
    asserts bit-identical sharded-vs-single results rather than just a
    successful sharded run.)"""
    out = run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core.config import AlignerConfig
        from repro.serve.align_step import make_align_step
        from repro.launch.mesh import make_test_mesh
        from repro.data.genome import ReadSimConfig, simulate_reads, synth_genome
        from repro.core.windowing import self_tail_width

        g = synth_genome(30000, seed=2)
        rs = simulate_reads(g, 8, ReadSimConfig(read_len=200, error_rate=0.06,
                                                seed=3))
        cfg = AlignerConfig(W=64, O=24, k=12)
        mesh = make_test_mesh((8,), ('data',))
        stepf = make_align_step(cfg, 200, mesh)
        wt = self_tail_width(cfg)
        B = 8
        reads = np.full((B, 200 + cfg.W + 1), 255, np.uint8)
        refs = np.full((B, 300 + cfg.W + wt + 1), 9, np.uint8)
        rl = np.zeros(B, np.int32); fl = np.zeros(B, np.int32)
        for i in range(B):
            reads[i, :len(rs.reads[i])] = rs.reads[i]; rl[i] = len(rs.reads[i])
            refs[i, :len(rs.ref_segments[i])] = rs.ref_segments[i]
            fl[i] = len(rs.ref_segments[i])
        from jax.sharding import NamedSharding, PartitionSpec as P
        bsh = NamedSharding(mesh, P(('data',), None))
        vsh = NamedSharding(mesh, P(('data',)))
        args = (jax.device_put(jnp.array(reads), bsh),
                jax.device_put(jnp.array(rl), vsh),
                jax.device_put(jnp.array(refs), bsh),
                jax.device_put(jnp.array(fl), vsh))
        with jax.set_mesh(mesh):
            out, summary = stepf(*args)
        assert int(summary['n_failed']) == 0
        print('OK aligned', int(summary['total_edits']))
    """)
    assert "OK aligned" in out


@pytest.mark.slow
def test_rescued_aligner_shards_over_mesh():
    """make_align_step_rescued: the on-device k-doubling ladder inside one
    sharded jitted step — high-error pairs rescue without any host
    round-trip on any shard."""
    out = run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core.config import AlignerConfig
        from repro.serve.align_step import make_align_step_rescued
        from repro.launch.mesh import make_test_mesh
        from repro.data.genome import ReadSimConfig, simulate_reads, synth_genome
        from repro.core.windowing import rescue_schedule, self_tail_width

        g = synth_genome(30000, seed=2)
        rs = simulate_reads(g, 8, ReadSimConfig(read_len=80, error_rate=0.18,
                                                seed=3))
        cfg = AlignerConfig(W=32, O=12, k=4)
        rounds = 1
        mesh = make_test_mesh((8,), ('data',))
        stepf = make_align_step_rescued(cfg, 80, mesh, rescue_rounds=rounds)
        wt = self_tail_width(rescue_schedule(cfg, rounds)[-1])
        B = 8
        reads = np.full((B, 80 + cfg.W + 1), 255, np.uint8)
        refs = np.full((B, 120 + cfg.W + wt + 1), 9, np.uint8)
        rl = np.zeros(B, np.int32); fl = np.zeros(B, np.int32)
        for i in range(B):
            reads[i, :len(rs.reads[i])] = rs.reads[i]; rl[i] = len(rs.reads[i])
            refs[i, :len(rs.ref_segments[i])] = rs.ref_segments[i]
            fl[i] = len(rs.ref_segments[i])
        from jax.sharding import NamedSharding, PartitionSpec as P
        bsh = NamedSharding(mesh, P(('data',), None))
        vsh = NamedSharding(mesh, P(('data',)))
        args = (jax.device_put(jnp.array(reads), bsh),
                jax.device_put(jnp.array(rl), vsh),
                jax.device_put(jnp.array(refs), bsh),
                jax.device_put(jnp.array(fl), vsh))
        with jax.set_mesh(mesh):
            out, summary = stepf(*args)
        ku = np.asarray(out['k_used'])
        failed = np.asarray(out['failed'])
        assert int(summary['n_rescued']) == int(((ku > cfg.k) & ~failed).sum())
        assert int(summary['rounds_run']) >= 1
        print('OK rescued', int(summary['n_rescued']), int(summary['n_failed']))
    """)
    assert "OK rescued" in out
