"""Real multiprocessing runtime: coordinator + workers, kill -9 fault model.

These spawn actual OS processes (the paper's fail-stop model is
``pkill -9``); they are the integration proof that AFT works outside the
in-process simulator.
"""
import time

import pytest

from repro.runtime.cluster import Cluster

pytestmark = pytest.mark.slow


# worker functions must be module-level (spawn start method pickles them)
def _sum_ranks(comm):
    return comm.allreduce(comm.rank, op="sum")


def _resilient_barriers(comm):
    from repro.core.comm import ProcFailedError, RevokedError

    recovered = False
    while True:
        try:
            for _ in range(40):
                comm.barrier()
                time.sleep(0.01)
            return ("recovered" if recovered else "fresh", comm.size)
        except (ProcFailedError, RevokedError):
            try:
                comm.revoke()
            except Exception:
                pass
            comm = comm.recover()
            recovered = True


def _aft_counting(comm):
    from repro.core.aft import aft_zone

    def body(c):
        for _ in range(30):
            c.barrier()
            time.sleep(0.01)
        return c.size

    return aft_zone(comm, body)


def test_collectives_across_processes():
    cluster = Cluster(n_procs=3)
    cluster.start(_sum_ranks)
    results = cluster.join(timeout=60)
    assert set(results.values()) == {3}


def test_kill9_nonshrinking_recovery():
    cluster = Cluster(n_procs=3, procs_per_node=1, spare_nodes=1,
                      recovery_policy="NON-SHRINKING")
    cluster.start(_resilient_barriers)
    time.sleep(0.6)
    cluster.kill(1)                      # SIGKILL — the paper's fault model
    results = cluster.join(timeout=120)
    assert len(results) == 3
    assert {v[1] for v in results.values()} == {3}
    assert any(v[0] == "recovered" for v in results.values())
    stats = cluster.coord.last_recovery
    assert stats.get("failed") == [1]


def test_kill9_shrinking_recovery():
    cluster = Cluster(n_procs=4, recovery_policy="SHRINKING")
    cluster.start(_resilient_barriers)
    time.sleep(0.6)
    cluster.kill(2)
    results = cluster.join(timeout=120)
    assert {v[1] for v in results.values()} == {3}


def test_aft_zone_survives_kill9():
    cluster = Cluster(n_procs=3, spare_nodes=1,
                      recovery_policy="NON-SHRINKING")
    cluster.start(_aft_counting)
    time.sleep(0.5)
    cluster.kill(0)                      # even rank 0 may die
    results = cluster.join(timeout=120)
    assert set(results.values()) == {3}


@pytest.mark.parametrize("given,expect", [(None, "cpu"), ("tpu", "tpu")])
def test_workers_stay_off_the_accelerator(given, expect):
    """Workers run JAX on the CPU unless the caller names a platform: a TPU
    chip belongs to one process, never to every worker at once."""
    overrides = {"JAX_PLATFORMS": given} if given else None
    with Cluster(n_procs=1, env_overrides=overrides) as cluster:
        assert cluster.env_overrides["JAX_PLATFORMS"] == expect
