"""Sharding rules, gradient compression, fault tolerance, checkpoint store."""

import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro.checkpoint.store import CheckpointStore
from repro.configs.base import get_config
from repro.distributed.compression import (compress_residual, dequantize_int8,
                                           quantize_int8)
from repro.distributed.fault import (HeartbeatRegistry, RestartableLoop,
                                     SimulatedFailure, StepWatchdog,
                                     elastic_plan)
from repro.distributed.sharding import spec_for, zero1_spec
from repro.launch.mesh import make_host_mesh
from repro.models.common import abstract, logical_axes
from repro.models.transformer import model_spec


class FakeMesh:
    def __init__(self, shape):
        self.shape = shape

    @property
    def axis_names(self):
        return tuple(self.shape.keys())


class TestShardingRules:
    MESH = FakeMesh({"data": 16, "model": 16})
    PODMESH = FakeMesh({"pod": 2, "data": 16, "model": 16})

    def test_tp_axes(self):
        assert spec_for((4096, 24576), ("embed", "ff"), self.MESH) == \
            P(None, "model")
        assert spec_for((256000, 6144), ("vocab", "embed"), self.MESH) == \
            P("model")
        assert spec_for((64, 2048, 1408), ("expert", "embed", "ff"),
                        self.MESH) == P("model")   # expert wins model first

    def test_divisibility_fallback(self):
        # qwen2-vl: 28 q_heads * 128 = 3584 -> divisible; kv 4*128=512 OK;
        # but e.g. a 28-dim head axis alone must replicate
        assert spec_for((28, 100), ("q_heads", None), self.MESH) == P()
        assert spec_for((51865, 1024), ("vocab", "embed"), self.MESH) == P()

    def test_no_axis_reuse(self):
        s = spec_for((64, 4096, 1408), ("expert", "ff", "ff"), self.MESH)
        assert s == P("model")     # second ff cannot reuse model

    def test_zero1_adds_data_axis(self):
        s = zero1_spec((4096, 24576), ("embed", "ff"), self.MESH)
        assert s == P("data", "model")
        s2 = zero1_spec((233, 24576), ("embed", "ff"), self.MESH)
        assert s2 == P(None, "model") or s2 == P(None, "model")

    def test_full_model_spec_has_tp(self):
        cfg = get_config("nemotron_4_15b")
        spec = model_spec(cfg)
        ab, ax = abstract(spec), logical_axes(spec)
        s = spec_for(tuple(ab["blocks"]["s0"]["m0"]["mlp"]["w_in"].shape),
                     ax["blocks"]["s0"]["m0"]["mlp"]["w_in"], self.MESH)
        assert "model" in str(s)


class TestCompression:
    def test_quantize_roundtrip_error_bounded(self):
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.normal(size=(16, 256)).astype(np.float32))
        q, scale = quantize_int8(x)
        deq = dequantize_int8(q, scale)
        err = np.abs(np.asarray(deq - x))
        amax = np.abs(np.asarray(x)).max(-1, keepdims=True)
        assert (err <= amax / 127.0 * 0.51 + 1e-7).all()

    def test_error_feedback_carries_residual(self):
        rng = np.random.default_rng(1)
        x = jnp.asarray(rng.normal(size=(8, 64)).astype(np.float32))
        err = jnp.zeros_like(x)
        q, scale, new_err = compress_residual(x, err)
        deq = dequantize_int8(q, scale).reshape(x.shape)
        np.testing.assert_allclose(np.asarray(deq + new_err), np.asarray(x),
                                   rtol=1e-5, atol=1e-5)

    def test_compressed_allreduce_multidevice_subprocess(self):
        """Run the int8 all-reduce on 8 fake devices in a subprocess."""
        code = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from repro.distributed.compression import compressed_psum_mean
from repro.launch.mesh import make_host_mesh
mesh = make_host_mesh()
rng = np.random.default_rng(0)
g = jnp.asarray(rng.normal(size=(8, 4, 32)).astype(np.float32))  # per-shard grads
mean, err = compressed_psum_mean(g, mesh, "data", mode="int8")
exact = np.asarray(g).mean(0)
got = np.asarray(mean)[0] if np.asarray(mean).ndim == 3 else np.asarray(mean)
rel = np.abs(got - exact).max() / (np.abs(exact).max() + 1e-9)
assert rel < 0.02, rel
print("REL_ERR", rel)
"""
        root = Path(__file__).resolve().parents[1]
        env = dict(os.environ, PYTHONPATH=str(root / "src"))
        r = subprocess.run([sys.executable, "-c", code], cwd=root,
                           env=env, capture_output=True, text=True,
                           timeout=300)
        assert r.returncode == 0, r.stderr[-2000:]
        assert "REL_ERR" in r.stdout


class TestFaultTolerance:
    def test_watchdog_flags_stragglers(self):
        w = StepWatchdog(slow_factor=3.0, escalate_after=2)
        for _ in range(8):
            w.record(1.0)
        assert not w.record(1.1)["slow"]
        assert w.record(10.0)["slow"]
        out = w.record(12.0)
        assert out["slow"] and out["restart_recommended"]

    def test_heartbeats(self):
        h = HeartbeatRegistry(timeout_s=10)
        h.beat("w0", now=0.0)
        h.beat("w1", now=0.0)
        assert h.healthy(now=5.0)
        h.beat("w0", now=20.0)
        assert h.dead_workers(now=21.0) == ["w1"]

    def test_elastic_plan(self):
        assert elastic_plan(512, model_axis=16) == (32, 16)
        assert elastic_plan(256, model_axis=16) == (16, 16)
        assert elastic_plan(240, model_axis=16) == (15, 16)
        assert elastic_plan(8, model_axis=16) == (1, 8)

    def test_restartable_loop_replays(self):
        saves = {}

        def save(state, step):
            saves["ckpt"] = (dict(state), step)

        def restore():
            return dict(saves["ckpt"][0]), saves["ckpt"][1]

        crashed = {"done": False}

        def step_fn(state, step):
            if step == 7 and not crashed["done"]:
                crashed["done"] = True
                raise SimulatedFailure()
            state["x"] += 1
            return state

        loop = RestartableLoop(save, restore)
        state, step = loop.run({"x": 0}, 0, 10, step_fn, checkpoint_every=5)
        assert step == 10 and loop.restarts == 1
        # restore rewinds to the step-5 snapshot (x=5); steps 5..9 replay on
        # the restored state, so the final count is exactly 10 — replay must
        # NOT double-apply the crashed steps
        assert state["x"] == 10


class TestCheckpointStore:
    def test_roundtrip_and_gc(self, tmp_path):
        store = CheckpointStore(tmp_path, num_shards=3)
        tree = {"a": jnp.arange(10), "b": {"c": jnp.ones((4, 4))}}
        for step in (5, 10, 15, 20):
            store.save(tree, step=step, keep=2)
        assert store.all_steps() == [15, 20]
        got, meta = store.restore(20, like=tree)
        assert meta["step"] == 20
        np.testing.assert_array_equal(np.asarray(got["a"]), np.arange(10))

    def test_uncommitted_checkpoint_invisible(self, tmp_path):
        store = CheckpointStore(tmp_path)
        tree = {"a": jnp.arange(4)}
        store.save(tree, step=1)
        # simulate a crash mid-write: a dir without the commit marker
        bad = tmp_path / "step_000000099"
        bad.mkdir()
        (bad / "manifest.json").write_text("{}")
        assert store.latest_step() == 1

    def test_async_save(self, tmp_path):
        store = CheckpointStore(tmp_path)
        tree = {"a": jnp.arange(100)}
        store.save(tree, step=7, blocking=False)
        store.wait()
        assert store.all_steps() == [7]

    def test_elastic_restore_new_sharding(self, tmp_path):
        store = CheckpointStore(tmp_path)
        tree = {"w": jnp.arange(64, dtype=jnp.float32).reshape(8, 8)}
        store.save(tree, step=3)
        mesh = make_host_mesh(model=1)
        from jax.sharding import NamedSharding
        sh = {"w": NamedSharding(mesh, P("data"))}
        got, _ = store.restore(3, like=tree, shardings=sh)
        assert got["w"].sharding.is_equivalent_to(sh["w"], 2)
