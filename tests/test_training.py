"""Fault-tolerance substrate: checkpointing, restart, stragglers, compression."""

import itertools
import os
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import assert_marker, run_script
from repro.configs.base import GNNConfig
from repro.data.synthetic import full_graph_batch
from repro.models.gnn import gcn
from repro.training import checkpoint as ckpt
from repro.training import optimizer as opt_mod
from repro.training import train_steps
from repro.training.trainer import (SimulatedFailure, TrainerConfig,
                                    TrainState, run)


@pytest.fixture
def small_setup():
    cfg = GNNConfig(name="t", family="gcn", n_layers=2, d_hidden=8,
                    norm="sym", d_in=16, n_classes=4)
    batch = full_graph_batch(cfg, 128, pattern="block", seed=0)
    opt_cfg = opt_mod.OptimizerConfig(name="adamw", lr=1e-2)
    params = gcn.init_params(cfg, jax.random.PRNGKey(0))
    opt_state = opt_mod.init(opt_cfg, params)
    step = jax.jit(train_steps.gnn_train_step(cfg, opt_cfg))
    return params, opt_state, step, batch


class TestCheckpoint:
    def test_roundtrip(self, tmp_path, small_setup):
        params, opt_state, _, _ = small_setup
        tree = {"params": params, "opt": opt_state}
        ckpt.save(str(tmp_path), 7, tree, extra={"data": {"seed": 3}})
        assert ckpt.latest_step(str(tmp_path)) == 7
        restored, extra = ckpt.restore(str(tmp_path), 7, tree)
        for a, b in zip(jax.tree_util.tree_leaves(tree),
                        jax.tree_util.tree_leaves(restored)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert extra == {"data": {"seed": 3}}

    def test_torn_write_invisible(self, tmp_path, small_setup):
        params, opt_state, _, _ = small_setup
        tree = {"p": params}
        ckpt.save(str(tmp_path), 1, tree)
        # simulate a crash mid-write: tmp dir without manifest
        os.makedirs(tmp_path / "step_00000002.tmp")
        (tmp_path / "step_00000002.tmp" / "shard_0.ckpt").write_bytes(b"junk")
        # and a renamed dir missing its manifest
        os.makedirs(tmp_path / "step_00000003")
        assert ckpt.latest_step(str(tmp_path)) == 1

    def test_gc_keeps_newest(self, tmp_path, small_setup):
        params, _, _, _ = small_setup
        for s in range(6):
            ckpt.save(str(tmp_path), s, {"p": params}, keep=2)
        assert ckpt.all_steps(str(tmp_path)) == [4, 5]


class TestTrainerRecovery:
    def test_restart_matches_uninterrupted(self, tmp_path, small_setup):
        params, opt_state, step, batch = small_setup
        data = lambda: itertools.repeat((batch,))

        # uninterrupted reference
        ref = run(TrainerConfig(total_steps=20, ckpt_every=100, log_every=0),
                  step, TrainState(params, opt_state), data())

        # failure at step 10, then restart-from-latest
        d = str(tmp_path)
        with pytest.raises(SimulatedFailure):
            run(TrainerConfig(total_steps=20, ckpt_every=5, ckpt_dir=d,
                              log_every=0, fail_at_step=10),
                step, TrainState(params, opt_state), data())
        out = run(TrainerConfig(total_steps=20, ckpt_every=5, ckpt_dir=d,
                                log_every=0),
                  step, TrainState(params, opt_state), data())
        assert out["final_step"] == 20
        for a, b in zip(jax.tree_util.tree_leaves(ref["state"].params),
                        jax.tree_util.tree_leaves(out["state"].params)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-6, atol=1e-6)

    def test_straggler_counter(self, small_setup):
        params, opt_state, step, batch = small_setup
        out = run(TrainerConfig(total_steps=3, log_every=0,
                                step_deadline_s=1e-9),
                  step, TrainState(params, opt_state),
                  itertools.repeat((batch,)))
        assert out["stragglers"] == 3


_COMPRESSION_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P
    from repro.training.compression import compressed_psum

    mesh = jax.make_mesh((8,), ("dp",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    g = jax.random.normal(jax.random.PRNGKey(0), (8, 256))

    def exact(x):
        return jax.lax.psum(x, "dp")

    def approx(x, e):
        return compressed_psum(x, "dp", e)

    with mesh:
        ref = jax.shard_map(exact, mesh=mesh, in_specs=P("dp", None),
                            out_specs=P("dp", None))(g)[0]
        e = jnp.zeros((8, 256))
        total_err = []
        # error feedback: residual carried across steps shrinks the bias
        for _ in range(4):
            s, e = jax.shard_map(approx, mesh=mesh,
                                 in_specs=(P("dp", None), P("dp", None)),
                                 out_specs=(P("dp", None), P("dp", None)))(g, e)
            total_err.append(float(jnp.max(jnp.abs(s[0] - ref))))
    rel = total_err[0] / float(jnp.max(jnp.abs(ref)))
    assert rel < 0.1, f"one-shot int8 psum error too large: {rel}"
    print("COMPRESS_OK", rel)
""")


def test_compressed_psum_close_to_exact():
    assert_marker(run_script(_COMPRESSION_SCRIPT), "COMPRESS_OK")
