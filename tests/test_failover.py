"""Elastic mesh failover: device loss, preemption, and re-planning.

Four layers of proof, mirroring the runtime (launch/train.py +
launch/mesh.py + checkpoint/manager.py):

* **units** (any device count): the ``_deadline`` drain watchdog turns a
  hung sync into ``MeshLostError``, the sentinel's ``MESH_LOST`` verdict
  escalates straight to failover without touching the strike ladder, the
  ``--inject`` grammar accepts the infrastructure kinds, and the
  ``SimulatedDeviceLoss`` raise/hang semantics hold;
* **re-planning** (fake 8-device mesh): ``degraded_context`` +
  ``hotpath_param_specs`` + ``state_leaf_descriptors`` on the shrunken
  mesh legitimately flip regimes (replicated -> column when n/g crosses
  the 2r gate) and group sizes (g=8 -> g=4);
* **restore** (fake 8-device mesh): ``CheckpointManager.rollback`` takes
  TARGET-mesh shardings different from the ones it saved under — an
  8-device row-rs checkpoint restores onto a 4-device degraded mesh with
  bit-exact logical state, shard shapes straight off the re-planned
  programs;
* **e2e acceptance** (fake 8-device mesh, ``infra_fault`` marker): an
  ``--inject dev-loss@N`` run (both raise and hang flavours) completes
  without operator intervention — detection, mesh rebuild, re-plan,
  known-good elastic restore — and its post-failover losses match an
  uninjected 4-device run resumed from the same checkpoint;
  ``dev-loss@k,preempt@j`` chains failover into a clean preemption exit;
  and a subprocess run SIGTERMed mid-stream exits 0 with a known-good
  checkpoint + RESUME marker, then auto-resumes to losses matching an
  uninterrupted run.

Run the mesh classes with ``XLA_FLAGS=--xla_force_host_platform_device_count=8``.
"""

import os
import shutil
import signal
import subprocess
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from repro.checkpoint import CheckpointManager
from repro.checkpoint import transpose as xp
from repro.core.program import state_leaf_descriptors
from repro.core.subtrack import AdamHP, LowRankConfig, lowrank_optimizer
from repro.distributed import sharding as sh
from repro.launch.mesh import (MeshLostError, SimulatedDeviceLoss,
                               degraded_context, host_context)
from repro.launch.steps import (TrainState, checkpoint_descriptors,
                                train_state_shardings)
from repro.launch.train import (HealthSentinel, _deadline, parse_injections,
                                train)

ARGS = ["--arch", "llama-60m", "--smoke", "--batch", "4", "--seq", "32",
        "--update-interval", "4", "--rank", "8", "--warmup", "2",
        "--log-every", "100"]

needs_mesh = pytest.mark.skipif(
    jax.device_count() < 8,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=8")


# ---------------------------------------------------------------------------
# Units
# ---------------------------------------------------------------------------


class TestDeadline:
    def test_timeout_becomes_mesh_lost(self):
        with pytest.raises(MeshLostError, match="deadline exceeded"):
            _deadline(lambda: time.sleep(5.0), 0.1, "unit drain")

    def test_value_passes_through(self):
        assert _deadline(lambda: 41 + 1, 5.0, "unit") == 42

    def test_exception_reraised_on_caller_thread(self):
        with pytest.raises(ValueError, match="boom"):
            _deadline(lambda: (_ for _ in ()).throw(ValueError("boom")),
                      5.0, "unit")

    def test_zero_timeout_runs_inline(self):
        assert _deadline(lambda: "inline", 0.0, "unit") == "inline"


class TestSentinelMeshLost:
    def test_escalates_straight_to_failover(self):
        s = HealthSentinel()
        assert s.mesh_lost(7, "collective hung") == HealthSentinel.FAILOVER
        ev = s.events[-1]
        assert ev["verdict"] == HealthSentinel.MESH_LOST
        assert ev["action"] == HealthSentinel.FAILOVER
        # infrastructure faults never touch the numerical ladder
        assert s.strikes == 0 and s.rollbacks == 0

    def test_numerical_ladder_unaffected_after_mesh_lost(self):
        s = HealthSentinel()
        s.mesh_lost(3, "lost device")
        assert s.strike(4, "nan") == HealthSentinel.SKIP  # first strike


class TestInjectGrammar:
    def test_infrastructure_kinds_parse(self):
        got = parse_injections("dev-loss@15,preempt@30,slow-host@9")
        assert got == {15: "dev-loss", 30: "preempt", 9: "slow-host"}

    def test_unknown_kind_rejected(self):
        with pytest.raises(SystemExit, match="unknown kind"):
            parse_injections("rack-fire@3")


class TestSimulatedDeviceLoss:
    def test_raise_mode_fires_at_dispatch_from_fault_step(self):
        sim = SimulatedDeviceLoss()
        sim.arm(5, survivors=["d0", "d1"], mode="raise")
        sim.check(4, "dispatch")                 # pre-fault: no-op
        with pytest.raises(MeshLostError) as ei:
            sim.check(5, "dispatch")
        assert ei.value.survivors == ["d0", "d1"]
        assert ei.value.step == 5
        with pytest.raises(MeshLostError):
            sim.check(6, "drain")                # a lost device stays lost

    def test_hang_mode_blocks_only_the_drain(self):
        sim = SimulatedDeviceLoss()
        sim.arm(5, survivors=[], mode="hang", hang_s=0.05)
        sim.check(5, "dispatch")                 # hangs surface at the sync
        t0 = time.time()
        with pytest.raises(MeshLostError, match="hung"):
            sim.check(5, "drain")
        assert time.time() - t0 >= 0.05

    def test_disarm(self):
        sim = SimulatedDeviceLoss()
        sim.arm(5, survivors=[], mode="raise")
        sim.disarm()
        assert not sim.armed
        sim.check(9, "dispatch")                 # no-op after failover


class TestDegradedContext:
    def test_mirrors_host_layout(self):
        devs = jax.devices()[:max(1, jax.device_count() // 2)]
        ctx = degraded_context(devs)
        assert ctx.mesh.axis_names == ("data", "model")
        assert ctx.mesh.shape["data"] == 1
        assert ctx.mesh.shape["model"] == len(devs)
        assert ctx.batch_axes == ("data",)

    def test_empty_survivors_rejected(self):
        with pytest.raises(ValueError, match="no surviving devices"):
            degraded_context([])


# ---------------------------------------------------------------------------
# Re-planning on the degraded mesh
# ---------------------------------------------------------------------------


@needs_mesh
@pytest.mark.infra_fault
class TestReplanDegraded:
    """The same admissibility gates, re-run against the shrunken model
    axis: regimes and group sizes must flip where the rules say so."""

    RANK = 8

    def _descs(self, ctx):
        shapes = {
            "flip": jax.ShapeDtypeStruct((64, 100), jnp.float32),
            "wide": jax.ShapeDtypeStruct((64, 256), jnp.float32),
        }
        specs = sh.hotpath_param_specs(shapes, ctx, self.RANK)
        cfg = LowRankConfig(rank=self.RANK, update_interval=4,
                            use_kernels=True, adam=AdamHP())
        return state_leaf_descriptors(shapes, cfg, mesh=ctx.mesh,
                                      param_specs=specs)

    def test_regime_and_group_flips_8_to_4(self):
        full = self._descs(host_context())
        degraded = self._descs(degraded_context(jax.devices()[:4]))
        # n=100: indivisible by 8 (and m/8 < 2r) -> replicated on the
        # full mesh; on 4 devices n/g = 25 >= 2r = 16 -> column
        assert full["flip"].regime == "replicated"
        assert degraded["flip"].regime == "column"
        assert degraded["flip"].shards == 4
        # n=256 passes the column gate on both meshes -> the group size
        # is what changes, 8 -> 4
        assert full["wide"].regime == degraded["wide"].regime == "column"
        assert (full["wide"].shards, degraded["wide"].shards) == (8, 4)


# ---------------------------------------------------------------------------
# Rollback restore onto the degraded mesh (direct, no train loop)
# ---------------------------------------------------------------------------


M, N, RANK = 64, 256, 16
N_ODD = 250


def _mk_params(key):
    return {"w": 0.1 * jax.random.normal(key, (M, N)),
            "wodd": 0.1 * jax.random.normal(jax.random.fold_in(key, 7),
                                            (M, N_ODD))}


def _grad_at(key, params, s):
    return {k: (1.0 + 0.3 * s) * jax.random.normal(
        jax.random.fold_in(jax.random.fold_in(key, 100 + s), i), v.shape)
        for i, (k, v) in enumerate(sorted(params.items()))}


class _Prog:
    """A row-family program over the first ``g`` devices (reduce-scatter
    Adam state where n divides g), mirroring what the trainer plans."""

    def __init__(self, g):
        self.g = g
        self.cfg = LowRankConfig(rank=RANK, update_interval=4, eta=2e-5,
                                 use_kernels=True, adam=AdamHP(),
                                 row_state="reduce-scatter")
        self.mesh = Mesh(np.array(jax.devices()[:g]).reshape(g), ("x",))
        self.specs = {"w": P("x", None), "wodd": P("x", None)}
        self.opt = lowrank_optimizer(self.cfg, mesh=self.mesh,
                                     param_specs=self.specs)
        self.shardings = {k: jax.sharding.NamedSharding(self.mesh, s)
                          for k, s in self.specs.items()}

    def descriptors(self, params):
        return checkpoint_descriptors(params, self.opt, mesh=self.mesh,
                                      param_specs=self.specs)

    def evolve(self, state, key, steps):
        upd = jax.jit(self.opt.update,
                      static_argnames=("do_subspace_update",))
        params_d = jax.device_put(state.params, self.shardings)
        opt_state = state.opt
        with self.mesh:
            for s in steps:
                g = jax.device_put(_grad_at(key, state.params, s),
                                   self.shardings)
                _, opt_state = upd(g, opt_state, params_d, 0.03,
                                   do_subspace_update=(s > 0 and s % 4 == 0))
        return TrainState(params=state.params, opt=opt_state)


@needs_mesh
@pytest.mark.infra_fault
class TestRollbackOntoDegradedMesh:
    """The failover restore primitive: ``rollback`` with target-mesh
    shardings DIFFERENT from the saved ones."""

    @pytest.fixture(scope="class")
    def saved(self, tmp_path_factory):
        key = jax.random.PRNGKey(0)
        params = _mk_params(key)
        src = _Prog(8)
        state = TrainState(params=params, opt=src.opt.init(params))
        with src.mesh:
            state = TrainState(params=state.params,
                               opt=src.opt.warm_start(
                                   state.opt, _grad_at(key, params, 0)))
        state = src.evolve(state, key, range(5))
        root = tmp_path_factory.mktemp("failover_ckpt")
        mgr = CheckpointManager(root)
        mgr.save(5, state, blocking=True, known_good=True,
                 extra_meta=xp.state_program_records(
                     state, src.descriptors(params)))
        host = jax.tree.map(np.asarray, state)
        return {"key": key, "params": params, "src": src, "root": root,
                "host": host}

    def _restore_degraded(self, saved):
        tgt = _Prog(4)
        params = saved["params"]
        like = TrainState(params=params, opt=tgt.opt.init(params))
        descs = tgt.descriptors(params)
        shardings = train_state_shardings(
            like, descs, tgt.mesh,
            jax.tree.map(lambda s: jax.sharding.NamedSharding(tgt.mesh, s),
                         tgt.specs))
        got = CheckpointManager(saved["root"]).rollback(
            like, shardings=shardings, loader=xp.elastic_loader(descs))
        assert got is not None, "known-good step must be restorable"
        back, step = got
        assert step == 5
        return back, tgt

    def test_programs_flip_down_to_shard_shapes(self, saved):
        """Regime/group changes asserted from the descriptors AND from
        the restored arrays' physical shards."""
        params = saved["params"]
        src_d = saved["src"].descriptors(params)
        back, tgt = self._restore_degraded(saved)
        tgt_d = tgt.descriptors(params)
        # w (n=256): row-rs on both, group size 8 -> 4
        assert src_d["w"].regime == tgt_d["w"].regime == "row-rs"
        assert (src_d["w"].shards, tgt_d["w"].shards) == (8, 4)
        # wodd (n=250): n % g breaks on both -> replicated-M/V row flavour
        assert src_d["wodd"].regime == tgt_d["wodd"].regime == "row"
        # physical placement follows the 4-device programs: M reduce-
        # scattered into (r, n/4) slices, S row-sharded into (m/4, r)
        st = back.opt.inner["w"]
        assert st.M.sharding.spec == P(None, "x")
        assert st.M.addressable_shards[0].data.shape == (RANK, N // 4)
        assert st.S.sharding.spec == P("x", None)
        assert st.S.addressable_shards[0].data.shape == (M // 4, RANK)
        assert back.opt.inner["wodd"].M.sharding.spec == P(None, None)

    def test_logical_state_bit_exact(self, saved):
        back, _ = self._restore_degraded(saved)
        flat_src = jax.tree_util.tree_flatten_with_path(saved["host"])[0]
        flat_back = jax.tree_util.tree_leaves(back)
        assert len(flat_src) == len(flat_back)
        for (path, a), b in zip(flat_src, flat_back):
            np.testing.assert_array_equal(
                a, np.asarray(b), err_msg=jax.tree_util.keystr(path))

    def test_post_failover_trajectory_matches_degraded_run(self, saved):
        """10 steps on the 4-device mesh from the rollback-restored state
        equal 10 steps from a pristine 4-device restore of the same
        checkpoint — the failover continuation IS the uninjected
        degraded run."""
        key = saved["key"]
        a, tgt = self._restore_degraded(saved)
        b, _ = self._restore_degraded(saved)
        a = tgt.evolve(a, key, range(5, 15))
        b = tgt.evolve(b, key, range(5, 15))
        for x, y in zip(jax.tree_util.tree_leaves(a),
                        jax.tree_util.tree_leaves(b)):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ---------------------------------------------------------------------------
# slow-host: trips the watchdog, never corrupts state
# ---------------------------------------------------------------------------


class TestSlowHost:
    def test_stall_flags_straggler_without_corrupting_state(self):
        # the stall lands late (step 90) so the step-0 compile outlier
        # has decayed out of the watchdog's EMA (6-sigma thresh ~2s by
        # then), and is large (6s) so it clears the gate on any
        # plausibly-slow host.  The pipelined loop attributes a host
        # stall to the drain window of the injected step AND the one
        # before it, so the flag may land on either.
        base = train(ARGS + ["--steps", "100"])
        slow = train(ARGS + ["--steps", "100", "--stall-s", "6.0",
                             "--inject", "slow-host@90"])
        assert {89, 90} & {s for s, _ in slow["stragglers"]}
        assert slow["rollbacks"] == 0 and slow["failovers"] == 0
        assert not slow["quarantined_steps"]
        ref = {h["step"]: h["loss"] for h in base["history"]}
        for h in slow["history"]:
            np.testing.assert_allclose(h["loss"], ref[h["step"]],
                                       rtol=1e-6)


# ---------------------------------------------------------------------------
# End-to-end acceptance through real train() runs
# ---------------------------------------------------------------------------


E2E = ARGS + ["--mesh", "host", "--use-kernels", "--steps", "20",
              "--checkpoint-every", "6", "--step-timeout", "60"]


def _losses_by_step(summary):
    """step -> loss, keeping the LAST occurrence (post-rollback/failover
    replays append duplicates by design)."""
    return {h["step"]: h["loss"] for h in summary["history"]
            if h.get("loss") is not None}


@needs_mesh
@pytest.mark.infra_fault
class TestDeviceLossFailoverE2E:
    @pytest.mark.parametrize("mode,extra", [
        ("raise", []),
        ("hang", ["--hang-s", "30", "--step-timeout", "3"]),
    ])
    def test_dev_loss_run_completes_and_matches_degraded_reference(
            self, tmp_path, mode, extra):
        ck = tmp_path / f"ck_{mode}"
        out = train(E2E + ["--checkpoint-dir", str(ck),
                           "--inject", "dev-loss@15", "--survivors", "4",
                           "--dev-loss-mode", mode] + extra)
        # detection + failover happened, exactly once, and the run
        # finished without operator intervention
        assert out["failovers"] == 1
        assert out["mesh_devices"] == 4
        verdicts = [e for e in out["sentinel_events"]
                    if e.get("verdict") == HealthSentinel.MESH_LOST]
        assert len(verdicts) == 1 and verdicts[0]["step"] == 15
        ev = out["failover_events"][0]
        assert (ev["from_devices"], ev["to_devices"]) == (8, 4)
        # re-planning provably changed at least one leaf's program
        assert ev["program_changes"] >= 1
        assert ev["restored_step"] == 12       # newest known-good (6, 12)
        assert out["final_loss"] is not None
        assert np.isfinite(out["final_loss"])

        # reference: an uninjected 4-device run resumed from the SAME
        # known-good checkpoint — post-failover losses must match it
        ref_ck = tmp_path / f"ref_{mode}"
        ref_ck.mkdir()
        shutil.copytree(ck / "step_0000000012", ref_ck / "step_0000000012")
        ref = train(E2E + ["--mesh-devices", "4",
                           "--checkpoint-dir", str(ref_ck)])
        got, want = _losses_by_step(out), _losses_by_step(ref)
        compared = 0
        for s in range(ev["resume_step"], 20):
            np.testing.assert_allclose(got[s], want[s], rtol=1e-5,
                                       err_msg=f"step {s}")
            compared += 1
        assert compared >= 5

    def test_dev_loss_then_preempt_chain(self, tmp_path):
        """--inject dev-loss@k,preempt@j: failover, then a clean
        preemption exit, then auto-resume to completion — no operator in
        the loop at any point."""
        ck = tmp_path / "ck_chain"
        out = train(E2E + ["--checkpoint-dir", str(ck), "--survivors", "4",
                           "--inject", "dev-loss@9,preempt@16"])
        assert out["failovers"] == 1
        assert out["preempted"] is True
        assert (ck / "RESUME").exists()
        assert CheckpointManager(ck).known_good_steps()
        resumed = train(E2E + ["--mesh-devices", "4",
                               "--checkpoint-dir", str(ck)])
        assert not (ck / "RESUME").exists()    # marker consumed
        assert resumed["preempted"] is False
        assert np.isfinite(resumed["final_loss"])


# ---------------------------------------------------------------------------
# Preemption: subprocess SIGTERM e2e
# ---------------------------------------------------------------------------


class TestPreemptionSubprocess:
    STEPS = 120

    def test_sigterm_saves_known_good_and_resumes_to_reference(
            self, tmp_path):
        ck = tmp_path / "ck"
        env = dict(os.environ,
                   PYTHONPATH=str(Path(__file__).parent.parent / "src"))
        cmd = [sys.executable, "-m", "repro.launch.train"] + ARGS + [
            "--steps", str(self.STEPS), "--checkpoint-every", "10",
            "--checkpoint-dir", str(ck), "--log-every", "1"]
        proc = subprocess.Popen(cmd, env=env, stdout=subprocess.PIPE,
                                stderr=subprocess.STDOUT, text=True)
        # SIGTERM once the loop demonstrably runs past the first
        # checkpoint boundary — mid-run, far from completion.  stdout is
        # drained to EOF regardless, so the child never blocks on a full
        # pipe.
        fired = False
        for line in proc.stdout:
            parts = line.split()
            if (not fired and len(parts) >= 3 and parts[0] == "[train]"
                    and parts[1] == "step" and parts[2].isdigit()
                    and int(parts[2]) >= 12):
                proc.send_signal(signal.SIGTERM)
                fired = True
        rc = proc.wait(timeout=120)
        assert fired, "never saw training steps before the deadline"
        assert rc == 0, "preempted run must exit cleanly"
        mgr = CheckpointManager(ck)
        kg = mgr.known_good_steps()
        assert kg, "preemption drain must leave a known-good checkpoint"
        assert (ck / "RESUME").exists()

        resumed = train(ARGS + ["--steps", str(self.STEPS),
                                "--checkpoint-every", "10",
                                "--checkpoint-dir", str(ck)])
        assert not (ck / "RESUME").exists()
        ref = train(ARGS + ["--steps", str(self.STEPS)])
        got, want = _losses_by_step(resumed), _losses_by_step(ref)
        resumed_steps = sorted(got)
        assert resumed_steps and resumed_steps[0] > 0   # actually resumed
        compared = 0
        for s in resumed_steps:
            np.testing.assert_allclose(got[s], want[s], rtol=1e-4,
                                       atol=1e-6, err_msg=f"step {s}")
            compared += 1
        assert compared >= 10


@pytest.mark.parametrize("message,lost", [
    ("UNAVAILABLE: TPU device halted", True),
    ("DEADLINE_EXCEEDED: collective timed out", True),
    ("ABORTED: peer went away", True),
    ("RESOURCE_EXHAUSTED: Out of memory while trying to allocate", False),
    ("INTERNAL: Mosaic failed to compile TPU kernel", False),
    ("INVALID_ARGUMENT: shape mismatch", False),
])
def test_runtime_error_classification(message, lost):
    """Only a lost or hung participant enters failover; an out-of-memory
    or a faulting program propagates with its own message."""
    from repro.launch.mesh import is_device_loss
    assert is_device_loss(RuntimeError(message)) is lost
