"""Distributed runtime: sharding rules, checkpoint/reshard, fault, elastic,
compressed ring collective (multi-device via subprocess)."""
import json
import subprocess
import sys
import tempfile
import textwrap
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.distributed.checkpoint import CheckpointManager
from repro.distributed.elastic import best_mesh_shape, plan_reshape, repartition_tickets
from repro.distributed.fault import (
    FailureDetector,
    RestartPolicy,
    StragglerDetector,
    TrainSupervisor,
    WorkerState,
)
from repro.distributed.sharding import (
    DEFAULT_RULES,
    ShardingCtx,
    resolve_spec,
    single_device_ctx,
)


class TestShardingRules:
    def test_resolve_basic(self):
        ctx = single_device_ctx()
        mesh = ctx.mesh
        assert resolve_spec(("batch", "seq", "embed_nosplit"), mesh)[0] == "data"
        assert resolve_spec(("embed", "ff"), mesh) == jax.sharding.PartitionSpec("data", "model")

    def test_missing_axis_degrades_to_replication(self):
        ctx = single_device_ctx()  # no "pod" axis
        spec = resolve_spec(("batch",), ctx.mesh)
        assert spec[0] == "data"  # pod dropped, data kept

    def test_no_double_use_of_axis(self):
        ctx = single_device_ctx()
        spec = resolve_spec(("embed", "embed"), ctx.mesh)
        # second occurrence can't reuse "data"
        assert spec == jax.sharding.PartitionSpec("data")


class TestCheckpoint:
    def _state(self):
        return {"params": {"w": jnp.arange(64, dtype=jnp.float32).reshape(8, 8),
                           "b": jnp.ones((8,), jnp.bfloat16)},
                "opt": {"mu": jnp.zeros((8, 8))}, "step": jnp.int32(3)}

    def test_roundtrip(self, tmp_path):
        mgr = CheckpointManager(tmp_path)
        state = self._state()
        mgr.save(5, state)
        out = mgr.restore(5, state)
        for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(out)):
            np.testing.assert_allclose(np.asarray(a, np.float32), np.asarray(b, np.float32))

    def test_atomic_commit_ignores_partial(self, tmp_path):
        mgr = CheckpointManager(tmp_path)
        mgr.save(1, self._state())
        # simulate a crash mid-save: .tmp dir without manifest
        (tmp_path / "step_000000002.tmp").mkdir()
        (tmp_path / "step_000000003").mkdir()  # committed-looking but no manifest
        assert mgr.latest_step() == 1

    def test_async_save(self, tmp_path):
        mgr = CheckpointManager(tmp_path)
        mgr.save_async(7, self._state(), extra={"loader": {"epoch": 1, "cursor": 9}})
        mgr.wait()
        assert mgr.latest_step() == 7
        mani = json.loads((tmp_path / "step_000000007" / "manifest.json").read_text())
        assert mani["extra"]["loader"]["cursor"] == 9

    def test_gc_keeps_last_k(self, tmp_path):
        mgr = CheckpointManager(tmp_path, keep=2)
        for s in (1, 2, 3, 4):
            mgr.save(s, self._state())
        assert mgr.all_steps() == [3, 4]

    def test_restore_missing_leaf_raises(self, tmp_path):
        mgr = CheckpointManager(tmp_path)
        mgr.save(1, {"a": jnp.ones(3)})
        with pytest.raises(KeyError):
            mgr.restore(1, {"a": jnp.ones(3), "b": jnp.ones(3)})


class TestFault:
    def test_failure_detection(self):
        det = FailureDetector(timeout_s=0.2, suspect_s=0.05)
        det.register("w0")
        det.register("w1")
        det.heartbeat("w0")
        t0 = time.time()
        dead = det.sweep(now=t0 + 0.1)
        assert dead == [] and det.workers["w1"].state == WorkerState.SUSPECT
        dead = det.sweep(now=t0 + 0.3)
        assert set(dead) == {"w0", "w1"}
        det.heartbeat("w0")
        assert det.alive() == ["w0"]

    def test_straggler_flagging(self):
        s = StragglerDetector(factor=1.5, patience=2)
        flagged = []
        for step in range(3):  # flagged() evaluates once per step report round
            for w in ("a", "b", "c", "d"):
                s.report(w, 2.5 if w == "d" else 1.0)
            flagged = s.flagged()
        assert flagged == ["d"]
        # a recovered worker unflags
        for w in ("a", "b", "c", "d"):
            s.report(w, 1.0)
        assert s.flagged() == []

    def test_supervisor_restarts_from_checkpoint(self, tmp_path):
        mgr = CheckpointManager(tmp_path)
        mgr.save(10, {"x": jnp.ones(2)})
        calls = []

        def run(start):
            calls.append(start)
            if len(calls) == 1:
                raise RuntimeError("node died")
            return start + 5

        sup = TrainSupervisor(RestartPolicy(max_restarts=2, backoff_s=0.01), mgr,
                              logger=lambda m: None)
        assert sup.run(run) == 15
        assert calls == [10, 10]


class TestElastic:
    def test_best_mesh(self):
        assert best_mesh_shape(512) == (2, 16, 16)
        assert best_mesh_shape(300) == (1, 16, 16)
        assert best_mesh_shape(255) == (1, 8, 16)
        assert best_mesh_shape(1) == (1, 1, 1)

    def test_plan_keeps_global_batch(self):
        ch = plan_reshape(512, 256, keep_global_batch=True)
        assert ch.mesh_shape == (1, 16, 16) and ch.microbatch_scale == 2

    def test_ticket_repartition(self):
        a = repartition_tickets(10, ["h0", "h1", "h2"])
        assert sorted(sum(a.values(), [])) == list(range(10))
        assert max(map(len, a.values())) - min(map(len, a.values())) <= 1


REPO = Path(__file__).resolve().parents[1]


@pytest.mark.slow
def test_compressed_ring_allreduce_multidevice():
    """int8 ring psum ≈ exact psum on an 8-device host mesh (subprocess —
    device count is locked at first jax init, so this can't run in-process)."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh, PartitionSpec as P
        from repro.distributed.collectives import compressed_psum_ring
        mesh = Mesh(np.array(jax.devices()).reshape(8), ("data",))
        x = jnp.asarray(np.random.default_rng(0).standard_normal((8, 4096)), jnp.float32)
        def ring(xl):
            return compressed_psum_ring(xl.reshape(-1), "data")
        def exact(xl):
            return jax.lax.psum(xl.reshape(-1), "data")
        with mesh:
            r = jax.shard_map(ring, mesh=mesh, in_specs=P("data"), out_specs=P("data"), check_vma=False)(x)
            e = jax.shard_map(exact, mesh=mesh, in_specs=P("data"), out_specs=P("data"), check_vma=False)(x)
        r, e = np.asarray(r), np.asarray(e)
        rel = np.abs(r - e).max() / (np.abs(e).max() + 1e-9)
        assert rel < 0.02, rel
        print("REL_ERR", rel)
    """)
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True,
                          env={"PYTHONPATH": str(REPO / "src"), "PATH": "/usr/bin:/bin"},
                          timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "REL_ERR" in proc.stdout
