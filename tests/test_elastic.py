"""Malleability: RM-triggered resizes drive the paper's adapt window and
agent-side redistribution; training continues with identical state."""
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.base import ShapeConfig
from repro.core import ICheckCluster
from repro.optim import AdamWConfig
from repro.train import ElasticTrainer

CFG = get_config("yi-6b", tiny=True)
SHAPE = ShapeConfig("t", "train", 32, 4)
OPT = AdamWConfig(lr=1e-3)


@pytest.mark.slow
def test_resize_preserves_trajectory():
    """Expand 1 -> 2 ranks mid-run: since global batch is constant, the
    loss trajectory must match an uninterrupted run exactly."""
    with ICheckCluster(n_icheck_nodes=2) as cluster:
        ref = ElasticTrainer(CFG, SHAPE, cluster, app_id="ref", seed=5,
                             opt_cfg=OPT, commit_every=100, probe_every=0,
                             total_steps=16)
        ref.run(16)
        ref_losses = [m["loss"] for m in ref.metrics_log]
        ref.finalize()

    with ICheckCluster(n_icheck_nodes=2) as cluster:
        t = ElasticTrainer(CFG, SHAPE, cluster, app_id="app", seed=5,
                           opt_cfg=OPT, commit_every=100, probe_every=0,
                           total_steps=16)
        t.run(8)
        cluster.rm.schedule_resize("app", 2)
        t.run(8)
        assert t.resizes == 1
        assert t.app.ranks == 2
        losses = [m["loss"] for m in t.metrics_log]
        t.finalize()

    np.testing.assert_allclose(losses, ref_losses, rtol=1e-5)


@pytest.mark.slow
def test_shrink_then_grow():
    with ICheckCluster(n_icheck_nodes=2) as cluster:
        t = ElasticTrainer(CFG, SHAPE, cluster, app_id="app", seed=1,
                           opt_cfg=OPT, commit_every=100, probe_every=0,
                           ranks=2, total_steps=12)
        t.run(4)
        cluster.rm.schedule_resize("app", 1)
        t.run(4)
        assert t.app.ranks == 1
        cluster.rm.schedule_resize("app", 2)
        t.run(4)
        assert t.app.ranks == 2
        assert t.resizes == 2
        assert np.isfinite(t.metrics_log[-1]["loss"])
        t.finalize()


def test_malleable_state_machine():
    """MPI_*_adapt analogue: probe -> begin -> commit transitions."""
    from repro.core import MalleableApp, ProcType, ResourceManager

    rm = ResourceManager()
    app = MalleableApp("a", rm, ranks=4)
    assert app.init_adapt() == ProcType.INITIAL
    assert app.probe_adapt() is None
    rm.schedule_resize("a", 8)
    ev = app.probe_adapt()
    assert ev is not None and ev.new_ranks == 8
    w = app.adapt_begin()
    assert w.old_ranks == 4 and w.new_ranks == 8
    app.adapt_commit()
    assert app.ranks == 8
    assert app.adaptations == 1
    assert app.probe_adapt() is None


def test_finalize_raises_a_failed_async_commit(monkeypatch):
    """An async save that failed, and is long done, still fails finalize."""
    from concurrent.futures import Future

    from repro.core import Agent, ICheckError

    def refused_put(self, key, payload, crc=None, *, epoch=None):
        fut = Future()
        fut.set_exception(ICheckError(f"store refused {key}"))
        return fut

    with ICheckCluster(n_icheck_nodes=2) as cluster:
        t = ElasticTrainer(CFG, SHAPE, cluster, app_id="app", seed=0,
                           opt_cfg=OPT, commit_every=0, probe_every=0)
        t.run(1)
        monkeypatch.setattr(Agent, "put", refused_put)
        h = t.commit()
        with pytest.raises(ICheckError, match="store refused"):
            h.wait(timeout=60)
        with pytest.raises(ICheckError, match="store refused"):
            t.finalize()
