"""Checkpoint-subsystem tier (tony_tpu.ckpt): format crash consistency,
async overlap, elastic cross-topology restore — on the virtual 8-device CPU
mesh. The compat-shim surface pins live in test_checkpoint.py; the e2e
gang-restart resume in test_e2e.py."""

import os
import signal
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from tony_tpu import ckpt
from tony_tpu import parallel as par
from tony_tpu import profiler, train
from tony_tpu.ckpt import format as fmt
from tony_tpu.models import get_model
from tony_tpu.train import fsdp_shard_state

pytestmark = pytest.mark.ckpt


def _state(mesh=None, hidden=32, key=0):
    model = get_model("mnist-mlp", hidden=hidden)
    kx, ky, kr = jax.random.split(jax.random.PRNGKey(key), 3)
    x = jax.random.normal(kx, (16, 784), jnp.float32)
    y = jax.random.randint(ky, (16,), 0, 10)
    state = train.create_train_state(
        model, optax.sgd(0.1, momentum=0.9), x, kr)
    return state, {"x": x, "y": y}


def _leaves_equal(a, b):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        if hasattr(y, "shape"):
            np.testing.assert_array_equal(
                np.asarray(jax.device_get(x)), np.asarray(jax.device_get(y)))


class TestFormat:
    def test_commit_is_atomic_rename(self, tmp_path):
        tree = {"w": jnp.arange(12.0).reshape(3, 4), "n": jnp.int32(7)}
        c = ckpt.AsyncCheckpointer(tmp_path, keep=3)
        c.save(tree, step=5, block=True)
        c.close()
        assert fmt.committed_steps(tmp_path) == [5]
        manifest = fmt.read_manifest(tmp_path, 5)
        assert manifest["format"] == fmt.FORMAT_VERSION
        assert {m["path"] for m in manifest["leaves"]} \
            == {"['n']", "['w']"}
        # Every chunk checksummed; every file listed.
        assert all("crc32" in ch for ch in manifest["chunks"])
        assert manifest["files"][0]["file"] == fmt.shard_file_name(0)

    def test_latest_step_ignores_staging_and_garbage(self, tmp_path):
        tree = {"w": jnp.ones((2, 2))}
        c = ckpt.AsyncCheckpointer(tmp_path, keep=3)
        c.save(tree, step=1, block=True)
        c.close()
        # A torn tmp dir from a crashed writer and a committed-looking dir
        # without a manifest must both be invisible.
        (tmp_path / "step_00000002.tmp").mkdir()
        (tmp_path / "step_00000002.tmp" / "shards_00000.bin").write_bytes(
            b"torn")
        (tmp_path / "step_00000003").mkdir()
        assert ckpt.latest_step(tmp_path) == 1
        restored = ckpt.restore_pytree(tmp_path, {"w": np.zeros((2, 2))})
        np.testing.assert_array_equal(restored["w"], np.ones((2, 2)))

    def test_same_step_recommit_replaces_without_loss_window(self, tmp_path):
        """Re-saving an already-committed step swaps via rename-aside (no
        rmtree-then-replace window where the only copy is gone): the new
        payload wins and no .old residue is left behind."""
        c = ckpt.AsyncCheckpointer(tmp_path, keep=3)
        c.save({"w": jnp.ones((2, 2))}, step=1, block=True)
        c.save({"w": jnp.full((2, 2), 5.0)}, step=1, block=True)
        c.close()
        assert fmt.committed_steps(tmp_path) == [1]
        assert not list(Path(tmp_path).glob("*.old"))
        restored = ckpt.restore_pytree(tmp_path, {"w": np.zeros((2, 2))})
        np.testing.assert_array_equal(restored["w"],
                                      np.full((2, 2), 5.0))

    def test_host_numpy_leaf_snapshot_is_a_copy(self, tmp_path):
        """The snapshot contract for HOST leaves: mutating the live array
        after save() returns must not leak into the committed bytes."""
        live = np.ones((64, 64), np.float32)
        c = ckpt.AsyncCheckpointer(tmp_path, keep=3)
        c.save({"w": live}, step=1)          # async: write still in flight
        live[:] = -1.0                        # train loop mutates in place
        c.wait()
        c.close()
        restored = ckpt.restore_pytree(tmp_path,
                                       {"w": np.zeros((64, 64),
                                                      np.float32)})
        np.testing.assert_array_equal(restored["w"],
                                      np.ones((64, 64), np.float32))

    def test_keep_prunes_old_steps(self, tmp_path):
        tree = {"w": jnp.ones((2,))}
        c = ckpt.AsyncCheckpointer(tmp_path, keep=2)
        for s in (1, 2, 3, 4):
            c.save(jax.tree.map(lambda x: x * s, tree), step=s, block=True)
        c.close()
        assert fmt.committed_steps(tmp_path) == [3, 4]
        restored = ckpt.restore_pytree(tmp_path, {"w": np.zeros((2,))})
        np.testing.assert_array_equal(restored["w"], 4 * np.ones((2,)))

    def test_keep_gc_ignores_inflight_tmp(self, tmp_path):
        """tony.ckpt.keep GC contract: only the newest K COMMITTED step
        dirs survive a save, and an in-flight .tmp staging dir neither
        counts toward K nor gets deleted by the prune."""
        c = ckpt.AsyncCheckpointer(tmp_path, keep=2)
        for s in (1, 2, 3):
            c.save({"w": jnp.ones((2,)) * s}, step=s, block=True)
        assert fmt.committed_steps(tmp_path) == [2, 3]
        # Simulate a sibling's in-flight save: staged shards, no commit.
        inflight = tmp_path / "step_00000005.tmp"
        inflight.mkdir()
        (inflight / fmt.shard_file_name(0)).write_bytes(b"staging")
        c.save({"w": jnp.ones((2,)) * 4}, step=4, block=True)
        c.close()
        # K counts committed steps only; the .tmp neither displaced a
        # committed survivor nor was reclaimed by prune.
        assert fmt.committed_steps(tmp_path) == [3, 4]
        assert inflight.is_dir()
        assert (inflight / fmt.shard_file_name(0)).read_bytes() \
            == b"staging"
        # Direct prune: same contract without a save in the way.
        assert fmt.prune(tmp_path, 1) == [3]
        assert fmt.committed_steps(tmp_path) == [4]
        assert inflight.is_dir()

    def test_corrupt_payload_raises_crc(self, tmp_path):
        c = ckpt.AsyncCheckpointer(tmp_path, keep=3)
        c.save({"w": jnp.ones((8, 8))}, step=1, block=True)
        c.close()
        shard = fmt.step_dir(tmp_path, 1) / fmt.shard_file_name(0)
        raw = bytearray(shard.read_bytes())
        raw[3] ^= 0xFF
        shard.write_bytes(bytes(raw))
        with pytest.raises(IOError, match="CRC mismatch"):
            ckpt.restore_pytree(tmp_path, {"w": np.zeros((8, 8))})
        # verify=False trusts the payload (operator override).
        ckpt.restore_pytree(tmp_path, {"w": np.zeros((8, 8))},
                            verify=False)

    def test_shape_mismatch_raises(self, tmp_path):
        c = ckpt.AsyncCheckpointer(tmp_path, keep=3)
        c.save({"w": jnp.ones((4, 4))}, step=1, block=True)
        c.close()
        with pytest.raises(ValueError, match="different model"):
            ckpt.restore_pytree(tmp_path, {"w": np.zeros((8, 8))})

    def test_bf16_roundtrip(self, tmp_path):
        tree = {"w": jnp.arange(16, dtype=jnp.bfloat16).reshape(4, 4)}
        c = ckpt.AsyncCheckpointer(tmp_path, keep=3)
        c.save(tree, step=1, block=True)
        c.close()
        restored = ckpt.restore_pytree(
            tmp_path, {"w": jnp.zeros((4, 4), jnp.bfloat16)})
        assert restored["w"].dtype == jnp.bfloat16
        np.testing.assert_array_equal(np.asarray(restored["w"]),
                                      np.asarray(tree["w"]))


class TestAsync:
    def test_async_save_snapshots_before_return(self, tmp_path):
        """save() must copy device→host BEFORE returning: later updates to
        the state (or donation) cannot leak into the committed bytes."""
        state, batch = _state()
        step_fn = train.make_train_step()
        state, _ = step_fn(state, batch)
        saved_params = jax.device_get(state.params)
        c = ckpt.AsyncCheckpointer(tmp_path, keep=3)
        c.save(state.params, step=1)        # async — returns pre-commit
        for _ in range(3):                  # keep training over the write
            state, _ = step_fn(state, batch)
        c.wait()
        assert c.latest_step() == 1
        restored = ckpt.restore_pytree(
            tmp_path, jax.tree.map(
                lambda a: np.zeros(a.shape, a.dtype)
                if hasattr(a, "shape") else a, saved_params))
        _leaves_equal(restored, saved_params)

    def test_writer_error_surfaces_on_wait(self, tmp_path):
        c = ckpt.AsyncCheckpointer(tmp_path, keep=3)
        # Point the writer at an impossible path (a path THROUGH a file —
        # fails for root too, unlike permission bits).
        blocker = tmp_path / "blocker"
        blocker.write_text("not a dir")
        c.directory = blocker / "nope"
        c.save({"w": jnp.ones((2,))}, step=1)
        with pytest.raises(RuntimeError, match="writer failed"):
            c.wait()
        c.close()

    def test_profiler_records_stall_and_write(self, tmp_path):
        profiler.reset_records("ckpt")
        state, _ = _state()
        c = ckpt.AsyncCheckpointer(tmp_path, keep=3)
        c.save(state, step=1, block=True)
        c.close()
        rec = profiler.report("ckpt")["async_save"]
        assert rec["step"] == 1
        assert rec["nbytes"] > 0 and rec["n_chunks"] >= 1
        assert rec["stall_s"] >= 0 and rec["write_s"] > 0

    @pytest.mark.slow
    def test_large_state_async_stall_beats_blocking(self, tmp_path):
        """The overlap claim on a state big enough to measure (~50 MB):
        the async save's caller stall must undercut the blocking save."""
        state, batch = _state(hidden=4096)
        step_fn = train.make_train_step()
        state, _ = step_fn(state, batch)
        c = ckpt.AsyncCheckpointer(tmp_path / "b", keep=2)
        import time
        t0 = time.perf_counter()
        c.save(state, step=1, block=True)
        blocking_s = time.perf_counter() - t0
        c.close()
        a = ckpt.AsyncCheckpointer(tmp_path / "a", keep=2)
        a.save(state, step=1)
        stall_s = a.stats["stall_s"][0]
        state, _ = step_fn(state, batch)     # ride the write
        a.wait()
        restored = ckpt.restore_pytree(
            tmp_path / "a", jax.tree.map(
                lambda x: np.zeros(x.shape, x.dtype)
                if hasattr(x, "shape") else x, jax.device_get(state)))
        a.close()
        assert stall_s < blocking_s
        assert jax.tree.leaves(restored)     # committed and readable


class TestMultiProcessBarrier:
    def test_nonzero_process_blocks_until_global_commit(self, tmp_path):
        """Host-simulated 2-process commit: process 1's blocking save must
        not return at 'my shards landed' — it returns only once process
        0's manifest rename makes the step globally durable, so
        latest_step never diverges across the gang."""
        import threading
        import time as time_mod

        from tony_tpu.ckpt.snapshot import extract_snapshot, write_snapshot

        tree = {"w": jnp.arange(8.0)}
        snap1 = extract_snapshot(tree, 1)
        done1 = threading.Event()

        def proc1():
            write_snapshot(tmp_path, snap1, process_index=1,
                           num_processes=2, barrier_timeout_s=30.0)
            done1.set()

        t = threading.Thread(target=proc1, daemon=True)
        t.start()
        time_mod.sleep(0.3)
        assert not done1.is_set()            # shards landed, commit hasn't
        assert ckpt.latest_step(tmp_path) is None
        snap0 = extract_snapshot(tree, 1)
        write_snapshot(tmp_path, snap0, process_index=0, num_processes=2,
                       barrier_timeout_s=30.0)
        assert done1.wait(timeout=30.0)      # released by the commit
        assert ckpt.latest_step(tmp_path) == 1
        manifest = fmt.read_manifest(tmp_path, 1)
        assert len(manifest["files"]) == 2   # both processes' shard files

    def test_commit_times_out_on_missing_process(self, tmp_path):
        from tony_tpu.ckpt.snapshot import extract_snapshot, write_snapshot

        snap = extract_snapshot({"w": jnp.ones((2,))}, 1)
        with pytest.raises(TimeoutError, match="did not finish"):
            write_snapshot(tmp_path, snap, process_index=0,
                           num_processes=2, barrier_timeout_s=0.3)


class TestCrashConsistency:
    def test_sigkill_mid_save_preserves_previous_step(self, tmp_path):
        """THE acceptance pin: kill -9 between shard write and manifest
        commit never loses the previously committed step — it restores
        bit-exact, and the torn staging dir is reclaimed."""
        script = textwrap.dedent("""
            import jax, jax.numpy as jnp, numpy as np, sys
            from tony_tpu import ckpt
            root, expect = sys.argv[1], sys.argv[2]
            tree = {"w": jnp.arange(64, dtype=jnp.float32).reshape(8, 8),
                    "s": jnp.float32(3.5)}
            c = ckpt.AsyncCheckpointer(root, keep=3)
            c.save(tree, step=1, block=True)
            np.save(expect, np.asarray(tree["w"]))
            # Arm the fault injection for the SECOND save only: the env
            # hook SIGKILLs this process after the shard payload is
            # written but before the manifest commit rename.
            import os
            os.environ["TONY_CKPT_CRASH"] = "after_shards"
            c.save({"w": jnp.full((8, 8), 99.0),
                    "s": jnp.float32(9.9)}, step=2, block=True)
            print("UNREACHABLE")
        """)
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   PYTHONPATH=str(Path(__file__).resolve().parent.parent))
        env.pop("TONY_CKPT_CRASH", None)
        root = tmp_path / "d"
        expect = tmp_path / "expect.npy"
        proc = subprocess.run(
            [sys.executable, "-c", script, str(root), str(expect)],
            env=env, capture_output=True, text=True, timeout=180)
        assert proc.returncode == -signal.SIGKILL, (proc.returncode,
                                                    proc.stdout,
                                                    proc.stderr)
        assert "UNREACHABLE" not in proc.stdout
        # Previous step intact and bit-exact; step 2 never committed.
        assert ckpt.latest_step(root) == 1
        assert (root / "step_00000002.tmp").is_dir()   # the torn write
        restored = ckpt.restore_pytree(
            root, {"w": np.zeros((8, 8), np.float32),
                   "s": np.float32(0)})
        np.testing.assert_array_equal(restored["w"], np.load(expect))
        assert float(restored["s"]) == 3.5
        # A new checkpointer incarnation sweeps the torn staging dir.
        c = ckpt.AsyncCheckpointer(root, keep=3)
        c.close()
        assert not (root / "step_00000002.tmp").exists()
        assert ckpt.latest_step(root) == 1

    def test_crash_before_commit_rename(self, tmp_path):
        """Same invariant at the later phase boundary: manifest staged in
        the tmp dir, rename not issued — still nothing committed."""
        calls = []

        def hook(phase):
            calls.append(phase)
            if phase == "before_commit":
                raise KeyboardInterrupt("simulated kill")

        c = ckpt.AsyncCheckpointer(tmp_path, keep=3)
        c.save({"w": jnp.ones((4,))}, step=1, block=True)
        fmt.CRASH_HOOK = hook
        try:
            with pytest.raises(RuntimeError, match="writer failed"):
                c.save({"w": jnp.full((4,), 2.0)}, step=2, block=True)
        finally:
            fmt.CRASH_HOOK = None
            c.close()
        assert "before_commit" in calls
        assert ckpt.latest_step(tmp_path) == 1


@pytest.mark.multislice
class TestElasticRestore:
    def test_cross_topology_2slice_to_1slice(self, tmp_path):
        """THE elastic acceptance pin: a ZeRO-3 state saved on a (host-
        simulated) 2-slice fsdp=2 mesh restores onto a 1-slice fsdp=4 mesh
        AND onto fsdp=2, bit-exact, with train-step numerics pinned within
        1e-6 against the original topology."""
        mesh_a = par.make_mesh(slices=2, fsdp=2)   # slice=2 x data=2 x fsdp=2
        state, batch = _state(hidden=64)
        zstate = fsdp_shard_state(state, mesh_a)
        step_a = train.make_train_step(mesh=mesh_a, donate=False)
        zstate, _ = step_a(zstate, batch)
        c = ckpt.AsyncCheckpointer(tmp_path, keep=3)
        c.save(zstate, step=1, block=True)
        c.close()
        manifest = fmt.read_manifest(tmp_path, 1)
        assert manifest["mesh"]["shape"]["slice"] == 2
        assert any(m["spec"] and "fsdp" in str(m["spec"])
                   for m in manifest["leaves"])
        host = jax.device_get(zstate)
        for spec_kw in ({"fsdp": 4}, {"fsdp": 2}):
            mesh_b = par.make_mesh(**spec_kw)      # 1-slice relayouts
            abstract = jax.tree.map(
                lambda a: np.zeros(a.shape, a.dtype)
                if hasattr(a, "shape") else a, host)
            restored = ckpt.restore_pytree(tmp_path, abstract, mesh=mesh_b)
            _leaves_equal(restored, host)
            # Manifest specs mapped onto the NEW mesh: still fsdp-sharded.
            kernel = restored.params["Dense_0"]["kernel"]
            assert "fsdp" in str(kernel.sharding.spec)
            assert kernel.sharding.mesh.shape["fsdp"] == spec_kw["fsdp"]
            step_b = train.make_train_step(mesh=mesh_b, donate=False)
            _, m_b = step_b(restored, batch)
            zs2, m_a = step_a(zstate, batch)
            assert abs(float(m_b["loss"]) - float(m_a["loss"])) < 1e-6
            assert abs(float(m_b["grad_norm"])
                       - float(m_a["grad_norm"])) < 1e-6

    def test_adapt_spec_degrades_missing_axes(self):
        from jax.sharding import PartitionSpec as P
        mesh = par.make_mesh(fsdp=4)
        # Unknown axis name → replicated dim; known-but-indivisible → same.
        assert ckpt.adapt_spec(P("oldaxis"), (8,), mesh) == P(None)
        assert ckpt.adapt_spec(P("fsdp"), (6,), mesh) == P(None)
        assert ckpt.adapt_spec(P("fsdp"), (8,), mesh) == P("fsdp")
        assert ckpt.adapt_spec(None, (8,), mesh) == P()

    def test_restore_targets_committed_sharding_wins(self, tmp_path):
        """A target whose leaves carry committed shardings restores INTO
        those shardings (the shim contract) — manifest specs only fill in
        for shardingless targets."""
        mesh = par.make_mesh(fsdp=2)
        state, _ = _state(hidden=32)
        zstate = fsdp_shard_state(state, mesh)
        c = ckpt.AsyncCheckpointer(tmp_path, keep=3)
        c.save(zstate, step=1, block=True)
        c.close()
        mesh_b = par.make_mesh(fsdp=4)
        target = fsdp_shard_state(state, mesh_b)
        restored = ckpt.restore_pytree(tmp_path, target)
        kernel = restored.params["Dense_0"]["kernel"]
        assert kernel.sharding == \
            target.params["Dense_0"]["kernel"].sharding
        _leaves_equal(restored.params, jax.device_get(zstate.params))


class TestTrainLoop:
    def test_plain_fold_without_ckpt_dir(self):
        state, batch = _state()
        step_fn = train.make_train_step()
        final, metrics = train.train_loop(state, step_fn, [batch] * 3,
                                          ckpt_dir=None)
        assert int(final.step) == 3 and jnp.isfinite(metrics["loss"])

    def test_save_every_and_resume(self, tmp_path, monkeypatch):
        """The control-plane contract end to end: attempt 1 trains 4 steps
        saving every 2 (async), 'dies'; attempt 2 re-enters the SAME loop
        code and resumes from the newest committed step via the TONY_CKPT_*
        env the JAXRuntime injects."""
        from tony_tpu import constants
        monkeypatch.setenv(constants.ENV_CKPT_DIR, str(tmp_path / "c"))
        monkeypatch.setenv(constants.ENV_CKPT_EVERY, "2")
        monkeypatch.setenv(constants.ENV_CKPT_KEEP, "2")
        state, batch = _state()
        step_fn = train.make_train_step()
        seen = []
        final, _ = train.train_loop(state, step_fn, [batch] * 4,
                                    on_step=lambda i, m: seen.append(i))
        assert int(final.step) == 4 and seen == [1, 2, 3, 4]
        assert ckpt.latest_step(tmp_path / "c") == 4
        # Attempt 2: fresh init, same loop — resumes at 4, trains 2 more.
        state2, _ = _state(key=1)
        final2, _ = train.train_loop(state2, step_fn, [batch] * 2)
        assert int(final2.step) == 6
        assert ckpt.latest_step(tmp_path / "c") == 6

    def test_restore_on_start_false_ignores_checkpoint(self, tmp_path):
        state, batch = _state()
        step_fn = train.make_train_step()
        train.train_loop(state, step_fn, [batch] * 2,
                         ckpt_dir=str(tmp_path), save_every=1)
        fresh, _ = _state(key=2)
        final, _ = train.train_loop(fresh, step_fn, [batch],
                                    ckpt_dir=str(tmp_path),
                                    restore_on_start=False,
                                    save_final=False)
        assert int(final.step) == 1
