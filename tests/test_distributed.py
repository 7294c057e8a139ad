"""Distributed P2HNNS on 8 simulated host devices (subprocess-isolated).

The device-count env var must be set before jax initializes, so the real
test body runs in a fresh subprocess.
"""
import os
import subprocess
import sys
import textwrap

import pytest

_BODY = textwrap.dedent(
    """
    import numpy as np, jax
    from repro.core import exact_search, append_ones
    from repro.core.balltree import normalize_query
    from repro.core.distributed import ShardedP2HIndex
    from repro.launch.mesh import make_mesh

    rng = np.random.default_rng(11)
    cents = rng.normal(size=(12, 24)) * 6
    data = (cents[rng.integers(0, 12, 9003)]
            + rng.normal(size=(9003, 24))).astype(np.float32)
    mesh = make_mesh((8,), ("data",))
    idx = ShardedP2HIndex.build(data, mesh, n0=128)
    q = rng.normal(size=(6, 25)).astype(np.float32)
    ed, ei = exact_search(append_ones(data), normalize_query(q), k=10)
    ed, ei = np.asarray(ed), np.asarray(ei)

    def check(bd, bi):
        # distances must agree; ids may swap only across f32-level ties
        assert np.allclose(bd, ed, rtol=1e-2, atol=1e-5), (bd, ed)
        for r in range(len(ei)):
            assert len(set(ei[r]) & set(bi[r])) >= 9, (ei[r], bi[r])

    bd, bi, st = idx.query(q, k=10)
    check(bd, bi)
    assert st["verified"] > 0
    # 2-axis sharding (pod x data), like the production mesh
    mesh2 = make_mesh((2, 4), ("pod", "data"))
    idx2 = ShardedP2HIndex.build(data, mesh2, axes=("pod", "data"), n0=128)
    bd2, bi2, _ = idx2.query(q, k=10)
    check(bd2, bi2)
    print("DISTRIBUTED_OK")
    """
)


@pytest.mark.slow
def test_sharded_index_matches_oracle_8dev():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src")
    )
    res = subprocess.run(
        [sys.executable, "-c", _BODY],
        env=env,
        capture_output=True,
        text=True,
        timeout=600,
    )
    assert res.returncode == 0, res.stderr[-4000:]
    assert "DISTRIBUTED_OK" in res.stdout

_TRAIN_BODY = textwrap.dedent(
    """
    import numpy as np, jax, jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.launch.mesh import make_mesh
    from repro.configs import get_model
    from repro.launch.steps import make_train_step, abstract_opt_state
    from repro.optim import adamw_init
    from repro.runtime.elastic import specs_for_mesh
    from repro.data import SyntheticLMDataset

    model, cfg = get_model("llama3.2-1b", smoke=True)
    ds = SyntheticLMDataset(vocab=cfg.vocab, seq=32, global_batch=8, seed=5)
    params, logical = model.init(jax.random.PRNGKey(0))
    opt = adamw_init(params)
    step = make_train_step(
        model, cfg, lr_fn=lambda s: 1e-3)

    b = ds.global_batch_arrays(0)
    batch = {"tokens": jnp.asarray(b["tokens"]),
             "labels": jnp.asarray(b["labels"])}

    # reference: single-device
    p1, o1, m1 = jax.jit(step)(params, opt, batch)

    # 8-device (data=4, model=2) mesh with full sharding path
    mesh = make_mesh((4, 2), ("data", "model"))
    param_sh = specs_for_mesh(
        logical, jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape,
                                                             x.dtype),
                              params), mesh, cfg.rules)
    from repro.optim.adamw import OptState
    rep = NamedSharding(mesh, P())
    opt_sh = OptState(mu=param_sh, nu=param_sh, count=rep)
    batch_sh = {k: NamedSharding(mesh, P(("data",), None)) for k in batch}
    # the ambient mesh makes the activation sharding constraints active
    with jax.set_mesh(mesh):
        jstep = jax.jit(step, in_shardings=(param_sh, opt_sh, batch_sh))
        p8, o8, m8 = jstep(
            jax.device_put(params, param_sh),
            jax.device_put(opt, opt_sh),
            {k: jax.device_put(v, batch_sh[k]) for k, v in batch.items()})

    # loss and updated params agree with the single-device step
    assert np.isclose(float(m1["loss"]), float(m8["loss"]),
                      rtol=5e-3), (m1["loss"], m8["loss"])
    diffs = jax.tree.map(
        lambda a, b: float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                           - b.astype(jnp.float32)))),
        p1, jax.device_get(p8))
    worst = max(jax.tree.leaves(diffs))
    assert worst < 5e-2, worst
    print("DP_TP_TRAIN_OK", float(m1["loss"]), float(m8["loss"]), worst)
    """
)


@pytest.mark.slow
def test_train_step_dp_tp_matches_single_device():
    """One optimizer step on a (data=4, model=2) mesh reproduces the
    single-device step: the GSPMD sharding configuration is semantics-
    preserving end to end (fwd, bwd, clip, AdamW)."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    res = subprocess.run([sys.executable, "-c", _TRAIN_BODY], env=env,
                         capture_output=True, text=True, timeout=600)
    assert res.returncode == 0, res.stderr[-4000:]
    assert "DP_TP_TRAIN_OK" in res.stdout


_ELASTIC_BODY = textwrap.dedent(
    """
    import numpy as np, jax, jax.numpy as jnp, tempfile
    from repro.checkpoint import CheckpointManager
    from repro.configs import get_model
    from repro.launch.mesh import make_mesh
    from repro.runtime.elastic import specs_for_mesh

    model, cfg = get_model("llama3.2-1b", smoke=True)
    params, logical = model.init(jax.random.PRNGKey(0))
    with tempfile.TemporaryDirectory() as td:
        mgr = CheckpointManager(td)
        mgr.save(1, params, blocking=True)
        # restore onto an 8-device mesh (elastic rescale path)
        mesh = make_mesh((2, 4), ("data", "model"))
        shapes = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), params)
        sh = specs_for_mesh(logical, shapes, mesh, cfg.rules)
        restored = mgr.restore(1, params, shardings=sh)
        same = jax.tree.map(
            lambda a, b: bool(jnp.allclose(a, jax.device_get(b))),
            params, restored)
        assert all(jax.tree.leaves(same))
    print("ELASTIC_RESTORE_OK")
    """
)


@pytest.mark.slow
def test_checkpoint_elastic_restore_8dev():
    """A checkpoint written without any mesh restores sharded onto an
    8-device (data=2, model=4) mesh bit-identically."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    res = subprocess.run([sys.executable, "-c", _ELASTIC_BODY], env=env,
                         capture_output=True, text=True, timeout=600)
    assert res.returncode == 0, res.stderr[-4000:]
    assert "ELASTIC_RESTORE_OK" in res.stdout
