"""Multi-host helpers: hybrid ICI/DCN mesh construction and sharded
compute over it (single-process: DCN axes of size 1, 8 virtual CPU
devices from the conftest XLA flags)."""

import numpy as np
import pytest

from nnstreamer_tpu.parallel.multihost import hybrid_mesh, process_info


def cpu_devices(n):
    import jax

    devs = jax.devices("cpu")
    if len(devs) < n:
        pytest.skip(f"need {n} cpu devices, have {len(devs)}")
    return devs


class TestInitialize:
    """`multihost.initialize` wraps jax.distributed.initialize with
    pass-only-what-was-given semantics (TPU pods autodetect everything;
    explicit args serve CPU/GPU clusters) — previously untested."""

    def test_explicit_args_pass_through(self, monkeypatch):
        import jax

        from nnstreamer_tpu.parallel import multihost

        calls = {}
        monkeypatch.setattr(jax.distributed, "initialize",
                            lambda **kw: calls.update(kw))
        multihost.initialize(coordinator_address="10.0.0.1:1234",
                             num_processes=4, process_id=2)
        assert calls == {"coordinator_address": "10.0.0.1:1234",
                         "num_processes": 4, "process_id": 2}

    def test_autodetect_passes_nothing(self, monkeypatch):
        import jax

        from nnstreamer_tpu.parallel import multihost

        calls = {"n": 0, "kw": None}

        def fake(**kw):
            calls["n"] += 1
            calls["kw"] = kw

        monkeypatch.setattr(jax.distributed, "initialize", fake)
        multihost.initialize()
        assert calls == {"n": 1, "kw": {}}


class _FakeDev:
    def __init__(self, pi, did):
        self.process_index = pi
        self.id = did

    def __repr__(self):
        return f"fake(p{self.process_index},d{self.id})"


class TestMeshByProcess:
    """`multihost._mesh_by_process` — the non-TPU fallback that groups
    devices by process_index (DCN axes span processes, ICI axes span
    each process's local devices) — previously untested."""

    def _devs(self, procs=2, per=2):
        # deliberately interleaved + shuffled ids: the grouper must
        # sort by process then device id, not rely on input order
        out = []
        for p in range(procs):
            for d in reversed(range(per)):
                out.append(_FakeDev(p, p * 10 + d))
        return out

    def test_groups_by_process_then_device_id(self):
        import jax

        from nnstreamer_tpu.parallel.multihost import _mesh_by_process

        arr = _mesh_by_process(jax, self._devs(2, 2), (2,), (2,))
        assert arr.shape == (2, 2)
        assert [[d.id for d in row] for row in arr] == [[0, 1],
                                                        [10, 11]]

    def test_local_prefix_when_more_devices_than_ici(self, caplog):
        import jax

        from nnstreamer_tpu.parallel.multihost import _mesh_by_process

        with caplog.at_level("WARNING", logger="nnstreamer_tpu"):
            arr = _mesh_by_process(jax, self._devs(2, 3), (2,), (2,))
        # 3 local devices, ici wants 2: the lowest-id prefix serves
        assert [[d.id for d in row] for row in arr] == [[0, 1],
                                                        [10, 11]]
        # ... and the devices left idle are named, once per process
        idle = [r.getMessage() for r in caplog.records
                if "stay idle" in r.getMessage()]
        assert len(idle) == 2 and "[2]" in idle[0] and "[12]" in idle[1]

    def test_wrong_process_count_raises(self):
        import jax

        from nnstreamer_tpu.parallel.multihost import _mesh_by_process

        with pytest.raises(ValueError):
            _mesh_by_process(jax, self._devs(3, 2), (2,), (2,))

    def test_too_few_local_devices_raises(self):
        import jax

        from nnstreamer_tpu.parallel.multihost import _mesh_by_process

        with pytest.raises(ValueError):
            _mesh_by_process(jax, self._devs(2, 1), (2,), (4,))


class TestHybridMesh:
    def test_single_slice_mesh_keeps_axis_names(self):
        devs = cpu_devices(4)
        m = hybrid_mesh([("model", 2), ("data", 2)], devices=devs[:4])
        assert m.axis_names == ("replica", "model", "data")
        assert m.shape == {"replica": 1, "model": 2, "data": 2}

    def test_sharded_compute_over_mesh(self):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        devs = cpu_devices(8)
        m = hybrid_mesh([("model", 2), ("data", 4)], devices=devs[:8])
        x = np.arange(64, dtype=np.float32).reshape(8, 8)
        s = NamedSharding(m, P("data", "model"))
        xd = jax.device_put(x, s)
        y = jax.jit(lambda a: a * 2 + 1, out_shardings=s)(xd)
        np.testing.assert_array_equal(np.asarray(y), x * 2 + 1)

    def test_insufficient_devices_raises(self):
        devs = cpu_devices(1)
        with pytest.raises(ValueError):
            hybrid_mesh([("model", 64)], devices=devs)

    def test_process_info_single_host(self):
        idx, count = process_info()
        assert idx == 0 and count >= 1
