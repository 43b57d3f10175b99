"""End-to-end driver integration: train + serve with CRAFT CR and faults."""
import numpy as np
import pytest

from repro.core.env import CraftEnv
from repro.launch import serve as serve_mod
from repro.launch import train as train_mod

pytestmark = pytest.mark.slow


def _env(tmp_path):
    return CraftEnv.capture({
        "CRAFT_CP_PATH": str(tmp_path / "pfs"), "CRAFT_USE_SCR": "0"})


ARCH = "h2o-danube-1.8b"


class TestTrainDriver:
    def test_loss_goes_down(self, tmp_path):
        tc = train_mod.TrainConfig(arch=ARCH, steps=16, cp_freq=8,
                                   global_batch=4, seq_len=32)
        out = train_mod.run(tc, env=_env(tmp_path))
        assert out["final_step"] == 16
        first, last = np.mean(out["losses"][:4]), np.mean(out["losses"][-4:])
        assert np.isfinite(out["losses"]).all()
        assert last < first
        assert out["stats"]["writes"] == 2

    def test_restart_resumes_and_matches(self, tmp_path):
        """Interrupted run + restart == uninterrupted run (exact resume:
        same data cursor, same state)."""
        env = _env(tmp_path)
        kw = dict(arch=ARCH, steps=20, cp_freq=5, global_batch=4, seq_len=32)

        # uninterrupted reference in a separate directory
        ref = train_mod.run(
            train_mod.TrainConfig(**kw),
            env=CraftEnv.capture({
                "CRAFT_CP_PATH": str(tmp_path / "ref"),
                "CRAFT_USE_SCR": "0"}))

        # interrupted at step 12 (after the v at step 10)
        with pytest.raises(KeyboardInterrupt):
            def boom(step, metrics):
                if step == 12:
                    raise KeyboardInterrupt

            train_mod.run(train_mod.TrainConfig(**kw), env=env,
                          on_step=boom)

        resumed = train_mod.run(train_mod.TrainConfig(**kw), env=env)
        # resumed run re-executes steps 11..20 (restart from v-2 @ step 10)
        assert resumed["final_step"] == 20
        np.testing.assert_allclose(
            resumed["losses"][-5:], ref["losses"][-5:], rtol=1e-4)

    def test_restore_frees_initial_state(self, tmp_path):
        """After a restore only the restored state is live on the device:
        the freshly initialized one it replaced is not kept alongside."""
        import gc

        import jax

        env = _env(tmp_path)
        kw = dict(arch=ARCH, steps=2, cp_freq=2, global_batch=4, seq_len=32)
        first = train_mod.run(train_mod.TrainConfig(**kw), env=env)
        state_bytes = sum(x.nbytes for x in
                          jax.tree_util.tree_leaves(first.pop("state")))
        del first
        seen = {}

        def on_start(step, state):
            gc.collect()
            seen["step"] = step
            seen["live"] = sum(a.nbytes for a in jax.live_arrays())

        train_mod.run(train_mod.TrainConfig(**kw), env=env, on_start=on_start)
        assert seen["step"] == 2
        assert seen["live"] < 1.5 * state_bytes

    def test_aft_zone_with_sim_comm(self, tmp_path):
        """Injected rank failure mid-training; AFT zone recovers and the
        final state matches the no-failure run."""
        from repro.core.comm_sim import SimWorld

        env_args = {"CRAFT_CP_PATH": str(tmp_path / "pfs"),
                    "CRAFT_USE_SCR": "0",
                    "CRAFT_COMM_RECOVERY_POLICY": "NON-SHRINKING"}
        env = CraftEnv.capture(env_args)
        world = SimWorld(2, spare_nodes=1, env=env)
        tc = train_mod.TrainConfig(arch=ARCH, steps=10, cp_freq=2,
                                   global_batch=4, seq_len=32,
                                   fail_at_step=5)

        def worker(comm):
            return train_mod.run(tc, comm=comm, env=env)

        results = world.run(worker, timeout=500)
        finals = [r["final_step"] for r in results.values()]
        assert all(f == 10 for f in finals)


class TestServeDriver:
    def test_greedy_decode_runs(self, tmp_path):
        sc = serve_mod.ServeConfig(arch=ARCH, batch=2, prompt_len=16,
                                   gen_tokens=8)
        out = serve_mod.run(sc, env=_env(tmp_path))
        assert out["tokens"].shape == (2, 8)
        assert out["resumed_at"] == 0

    def test_decode_restart_resumes_identically(self, tmp_path):
        env = _env(tmp_path)
        sc = serve_mod.ServeConfig(arch=ARCH, batch=2, prompt_len=16,
                                   gen_tokens=12, cp_freq=4)
        ref = serve_mod.run(sc, env=CraftEnv.capture({
            "CRAFT_CP_PATH": str(tmp_path / "ref"), "CRAFT_USE_SCR": "0"}))

        with pytest.raises(RuntimeError, match="injected"):
            serve_mod.run(sc, env=env, fail_at_token=9)
        out = serve_mod.run(sc, env=env)
        assert out["resumed_at"] == 8          # last v at token 8
        np.testing.assert_array_equal(out["tokens"], ref["tokens"])
