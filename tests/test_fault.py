"""Fault tolerance: supervisor restore/retry, straggler detection,
elastic re-mesh, gradient compression."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.distributed.fault import FaultSupervisor, StragglerMonitor
from repro.distributed.elastic import remesh
from repro.launch.mesh import make_mesh


class TestStragglerMonitor:
    def test_flags_outlier(self):
        mon = StragglerMonitor(min_samples=8)
        for _ in range(20):
            assert not mon.observe(0.10 + np.random.default_rng(0).normal()
                                   * 1e-4)
        assert mon.observe(1.0)  # 10x median
        assert mon.stragglers == 1

    def test_tolerates_noise(self):
        rng = np.random.default_rng(1)
        mon = StragglerMonitor(min_samples=8)
        flags = [mon.observe(0.1 + abs(rng.normal()) * 0.005)
                 for _ in range(100)]
        assert sum(flags) <= 2


class TestFaultSupervisor:
    def test_restores_on_failure(self):
        saved = {"step": 3, "state": 30.0}
        sup = FaultSupervisor(
            restore_fn=lambda: (saved["step"], saved["state"]))
        calls = {"n": 0}

        def flaky(state):
            calls["n"] += 1
            if calls["n"] == 1:
                raise RuntimeError("chip fell over")
            return state + 1

        state, step, failed = sup.run(flaky, 50.0, 5)
        assert failed and step == 3 and state == 30.0
        state, step, failed = sup.run(flaky, state, step)
        assert not failed and step == 4 and state == 31.0
        assert sup.restarts == 1

    def test_gives_up_after_max(self):
        sup = FaultSupervisor(restore_fn=lambda: (0, 0.0), max_restarts=2)

        def always_fails(_):
            raise RuntimeError("dead host")

        for _ in range(2):
            _, _, failed = sup.run(always_fails, 0.0, 0)
            assert failed
        with pytest.raises(RuntimeError):
            sup.run(always_fails, 0.0, 0)


def test_remesh_roundtrip():
    """Params sharded on a 1-dev mesh re-shard onto a renamed mesh and
    degrade gracefully for non-divisible dims."""
    mesh_a = make_mesh((1,), ("data",))
    tree = {"w": jnp.arange(32.0).reshape(8, 4), "b": jnp.ones((3,))}
    specs = {"w": P("data", None), "b": P(None)}
    placed = jax.tree.map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh_a, s)),
        tree, specs)
    mesh_b = make_mesh((1,), ("data",))
    out = remesh(placed, mesh_b, specs)
    np.testing.assert_array_equal(np.asarray(out["w"]),
                                  np.asarray(tree["w"]))
    # non-divisible: b (3,) against data axis — replicated, value intact
    out_b = remesh({"b": placed["b"]}, mesh_b, {"b": P("data")})
    np.testing.assert_array_equal(np.asarray(out_b["b"]), np.ones((3,)))


@pytest.mark.parametrize("exc", [
    RuntimeError("INTERNAL: Mosaic failed to compile TPU kernel: ..."),
    RuntimeError("RESOURCE_EXHAUSTED: XLA:TPU compile permanent error"),
    ValueError("lowering: bad block shape"),
    NotImplementedError("Unimplemented primitive in Pallas TPU lowering"),
], ids=["mosaic", "hbm", "value", "not-implemented"])
def test_serving_supervisor_never_retries_compile_errors(exc):
    """A compile or lowering failure is deterministic: the slab attempt
    raises at once; only transient runtime errors are retried."""
    from repro.distributed.fault import ServingFaultSupervisor, retryable

    assert not retryable(exc)
    assert retryable(RuntimeError("chip fell over"))
    sup = ServingFaultSupervisor()
    calls = {"n": 0}

    def attempt():
        calls["n"] += 1
        raise exc

    with pytest.raises(type(exc)):
        sup.execute(attempt)
    assert calls["n"] == 1 and sup.transient_retries == 0
