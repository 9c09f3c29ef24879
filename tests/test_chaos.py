"""Chaos subsystem: FaultPlan determinism + seams, the shared retry
policy/circuit breaker, SLO-aware admission control, and the recovery
paths a FaultPlan now drives deterministically (query failover
resend-at-most-once, pool error fan-out, per-owner error routing,
mqtt/edge reconnect)."""

import queue as pyq
import threading
import time

import numpy as np
import pytest

from nnstreamer_tpu import chaos
from nnstreamer_tpu.chaos import (
    BreakerOpen,
    ChaosInvokeError,
    FaultPlan,
    FaultSpec,
    RetryPolicy,
)
from nnstreamer_tpu.chaos import hooks as chaos_hooks
from nnstreamer_tpu.chaos import retrypolicy
from nnstreamer_tpu.core import Buffer, TensorsSpec
from nnstreamer_tpu.obs.metrics import LinkMetrics
from nnstreamer_tpu.runtime import Pipeline
from nnstreamer_tpu.runtime.admission import (
    AdmissionController,
    parse_priority,
    priority_name,
)
from nnstreamer_tpu.runtime.events import MessageKind
from nnstreamer_tpu.runtime.registry import make
from nnstreamer_tpu.runtime.serving import MODEL_POOL, SharedBatcher

SPEC = TensorsSpec.parse("4:1", "float32")


@pytest.fixture(autouse=True)
def _clean_chaos():
    chaos.uninstall_plan()
    yield
    chaos.uninstall_plan()
    MODEL_POOL.clear()


# -- FaultPlan ----------------------------------------------------------------


class TestFaultPlan:
    def test_parse_grammar(self):
        p = FaultPlan.parse(
            "seed=42;drop:p=0.5;delay:ms=20,every=3,match=qcli;"
            "slow-invoke:ms=5,after=2,count=1;queue-pressure:ms=1")
        assert p.seed == 42
        assert [s.fault for s in p.specs] == [
            "drop", "delay", "slow-invoke", "queue-pressure"]
        assert p.specs[1].ms == 20 and p.specs[1].every == 3
        assert p.specs[2].after == 2 and p.specs[2].count == 1

    @pytest.mark.parametrize("bad", [
        "", "seed=1", "nosuchfault:p=0.5", "drop:p=2.0",
        "drop:wat=1", "drop:dir=sideways",
    ])
    def test_parse_rejects(self, bad):
        with pytest.raises(ValueError):
            FaultPlan.parse(bad)

    def test_seeded_determinism(self):
        def run():
            p = FaultPlan.parse("seed=7;drop:p=0.4")
            return [p.wire("l", "tx", b"x") is not None
                    for _ in range(50)]

        assert run() == run()
        other = FaultPlan.parse("seed=8;drop:p=0.4")
        assert run() != [other.wire("l", "tx", b"x") is not None
                         for _ in range(50)]

    def test_every_after_count(self):
        p = FaultPlan([FaultSpec("drop", every=3, after=2, count=2)])
        fired = [p.wire("l", "tx", b"x") is not None for _ in range(14)]
        # events 1-2 skipped (after); then every 3rd of the rest fires,
        # capped at 2 injections
        assert fired.count(True) == 2
        assert p.counts() == {"drop": 2}

    def test_match_filters_by_label(self):
        p = FaultPlan([FaultSpec("drop", match="qcli")])
        assert p.wire("other:peer", "tx", b"x") is None
        assert p.wire("qcli:127.0.0.1:5", "tx", b"x").frames == []

    def test_direction_filter(self):
        p = FaultPlan([FaultSpec("drop", direction="rx")])
        assert p.wire("l", "tx", b"x") is None
        assert p.wire("l", "rx", b"x").frames == []

    def test_duplicate_and_delay_compose(self):
        p = FaultPlan([FaultSpec("duplicate"), FaultSpec("delay", ms=30)])
        op = p.wire("l", "tx", b"abc")
        assert op.frames == [b"abc", b"abc"]
        assert op.delay_s == pytest.approx(0.03)

    def test_corrupt_flips_bytes_only(self):
        p = FaultPlan([FaultSpec("corrupt")], seed=5)
        op = p.wire("l", "tx", b"hello world")
        assert len(op.frames) == 1 and op.frames[0] != b"hello world"
        # object frames (inproc) cannot be corrupted: untouched
        assert p.wire("l", "tx", object()) is None

    def test_reorder_swaps_adjacent(self):
        p = FaultPlan([FaultSpec("reorder", every=1)])
        first = p.wire("l", "tx", b"A")
        assert first.frames == []  # held
        second = p.wire("l", "tx", b"B")
        assert second.frames == [b"B", b"A"]  # released after the next
        assert p.flush_held("l", "tx") is None

    def test_partition_window_drops_everything(self):
        p = FaultPlan([FaultSpec("partition", ms=150, count=1)])
        assert p.wire("l", "tx", b"x").frames == []  # opens the window
        assert p.wire("l", "rx", b"y").frames == []  # both directions
        time.sleep(0.2)
        assert p.wire("l", "tx", b"z") is None  # window closed

    def test_invoke_faults(self):
        p = FaultPlan([FaultSpec("slow-invoke", ms=10, count=1),
                       FaultSpec("fail-invoke", after=1, count=1)])
        assert p.invoke_fault("m") == ("slow", pytest.approx(0.01))
        assert p.invoke_fault("m") == ("fail", 0.0)
        assert p.invoke_fault("m") is None
        from nnstreamer_tpu.chaos.plan import apply_invoke_fault

        q = FaultPlan([FaultSpec("fail-invoke")])
        with pytest.raises(ChaosInvokeError):
            apply_invoke_fault(q, "m")

    def test_queue_stall(self):
        p = FaultPlan([FaultSpec("queue-pressure", ms=7, count=1)])
        assert p.queue_stall("b") == pytest.approx(0.007)
        assert p.queue_stall("b") == 0.0

    def test_registry_counter_exported(self):
        from nnstreamer_tpu.obs.metrics import REGISTRY

        p = FaultPlan([FaultSpec("drop", count=1)])
        p.wire("l", "tx", b"x")
        fams = REGISTRY.collect()
        samples = fams["nns_chaos_injected_total"]["samples"]
        row = [s for s in samples
               if s["labels"].get("fault") == "drop"]
        assert row and row[0]["value"] >= 1

    def test_env_install(self, monkeypatch):
        monkeypatch.setattr(chaos_hooks, "_env_checked", False)
        monkeypatch.setenv("NNS_TPU_CHAOS", "seed=3;drop:p=0.1")
        chaos_hooks.maybe_install_from_env()
        assert chaos.active_plan() is not None
        assert chaos.active_plan().seed == 3

    def test_env_malformed_is_ignored(self, monkeypatch):
        monkeypatch.setattr(chaos_hooks, "_env_checked", False)
        monkeypatch.setenv("NNS_TPU_CHAOS", "not-a-fault")
        chaos_hooks.maybe_install_from_env()
        assert chaos.active_plan() is None


# -- retry policy -------------------------------------------------------------


class TestRetryPolicy:
    def test_backoff_grows_exponentially_with_jitter_bounds(self):
        pol = RetryPolicy(base_s=0.1, max_s=10.0, multiplier=2.0,
                          jitter=0.5, seed=1)
        assert pol.backoff() == 0.0
        seen = []
        for n in range(1, 6):
            pol.failure(OSError("x"))
            d = pol.backoff()
            lo, hi = 0.1 * 2 ** (n - 1) * 0.5, 0.1 * 2 ** (n - 1) * 1.5
            assert lo <= d <= hi
            seen.append(d)
        pol.success()
        assert pol.backoff() == 0.0

    def test_backoff_caps_at_max(self):
        pol = RetryPolicy(base_s=1.0, max_s=2.0, jitter=0.0,
                          fail_threshold=100)
        for _ in range(8):
            pol.failure(OSError("x"))
        assert pol.backoff() == pytest.approx(2.0)

    def test_breaker_open_half_open_closed(self):
        pol = RetryPolicy(fail_threshold=3, open_s=0.15, jitter=0.0,
                          base_s=0.01)
        for _ in range(3):
            assert pol.allow()
            pol.failure(OSError("x"))
        assert pol.state == retrypolicy.OPEN
        assert not pol.allow()  # open: rejected
        with pytest.raises(BreakerOpen):
            pol.check()
        time.sleep(0.2)
        assert pol.allow()  # half-open probe admitted
        assert pol.state == retrypolicy.HALF_OPEN
        pol.failure(OSError("y"))  # probe failed: re-opens
        assert pol.state == retrypolicy.OPEN
        time.sleep(0.2)
        assert pol.allow()
        pol.success()
        assert pol.state == retrypolicy.CLOSED
        assert pol.breaker_opens == 2

    def test_state_mirrors_into_link_metrics(self):
        m = LinkMetrics("t-link", "peer:1", kind="test")
        pol = RetryPolicy(fail_threshold=2, metrics=m)
        pol.failure(OSError("x"))
        pol.failure(OSError("x"))
        snap = m.snapshot()
        assert snap["breaker_state"] == retrypolicy.OPEN
        assert snap["backoff_level"] == 2
        assert snap["breaker_opens"] == 1
        pol.success()
        assert m.snapshot()["breaker_state"] == retrypolicy.CLOSED

    def test_wait_interruptible(self):
        pol = RetryPolicy(base_s=5.0, jitter=0.0)
        pol.failure(OSError("x"))
        stop = threading.Event()
        stop.set()
        t0 = time.monotonic()
        assert pol.wait(stop=stop, max_s=5.0) is False
        assert time.monotonic() - t0 < 1.0


# -- admission control --------------------------------------------------------


class TestAdmission:
    def test_parse_priority(self):
        assert parse_priority("high") == 0
        assert parse_priority("normal") == 1
        assert parse_priority("LOW") == 2
        assert parse_priority(2) == 2
        assert priority_name(0) == "high"
        with pytest.raises(ValueError):
            parse_priority("urgent")

    def test_ramp_and_at_risk(self):
        adm = AdmissionController(slo_s=0.1, window=64)
        for _ in range(32):
            adm.observe(0.01)  # well under
        assert not adm.at_risk and adm.shed_probability == 0.0
        for _ in range(64):
            adm.observe(0.5)  # way over
        assert adm.at_risk
        assert adm.shed_probability == 1.0
        assert adm.risk_episodes == 1

    def test_admit_protects_high_sheds_low(self):
        adm = AdmissionController(slo_s=0.05)
        for _ in range(64):
            adm.observe(1.0)
        assert adm.admit(parse_priority("high"))
        assert not adm.admit(parse_priority("low"))
        snap = adm.snapshot()
        assert snap["shed"]["low"] == 1
        assert snap["submitted"]["high"] == 1
        assert adm.total_shed == 1

    def test_shared_batcher_edf_formation(self):
        flushed = []
        sb = SharedBatcher(max_batch=2, timeout_s=1000.0,
                           flush_fn=flushed.extend, adaptive=False)
        sb.edf = True
        # park 4 frames directly (submit would inline-drain at the
        # window size): B's deadlines are tighter, so the first window
        # is all-B even though A arrived first — and each stream keeps
        # its own relative order (stable selection)
        now = time.monotonic()
        with sb._cv:
            sb._pending.extend([
                ("A", 1, now + 50.0, now), ("A", 2, now + 50.0, now),
                ("B", 3, now + 1.0, now), ("B", 4, now + 1.0, now)])
        sb._drain()
        assert [it[:2] for it in flushed] == [("B", 3), ("B", 4)]
        sb._drain()
        assert [it[:2] for it in flushed[2:]] == [("A", 1), ("A", 2)]

    def test_wait_below_backpressure_and_timeout(self):
        sb = SharedBatcher(max_batch=64, timeout_s=1000.0,
                           flush_fn=lambda items: None, adaptive=False)
        for i in range(4):
            sb.submit_from("A", i)
        assert sb.wait_below("B", 4, timeout_s=0.1)  # other stream
        t0 = time.monotonic()
        assert not sb.wait_below("A", 4, timeout_s=0.2)  # never drains
        assert 0.15 <= time.monotonic() - t0 <= 2.0

    def test_pool_slo_is_pool_level_conflict(self):
        from nnstreamer_tpu.filters.jax_xla import register_model
        from nnstreamer_tpu.runtime.element import NegotiationError

        model = register_model("chaos_adm_conflict", lambda x: x,
                               in_shapes=[(4,)], in_dtypes=np.float32)
        pipes = []
        p1, e1 = _pool_pipe("adm-c1", model, slo_ms=50.0)
        p1.start()
        pipes.append(p1)
        p2, e2 = _pool_pipe("adm-c2", model, slo_ms=80.0)
        try:
            with pytest.raises(Exception) as ei:
                p2.start()
            assert "slo" in str(ei.value).lower() or \
                "conflict" in str(ei.value).lower()
        finally:
            for p in pipes:
                p.stop()

    def test_ingress_stamp_gated_on_active_controller(self):
        from nnstreamer_tpu.filters.jax_xla import register_model
        from nnstreamer_tpu.runtime import admission as adm_mod

        model = register_model("chaos_adm_stamp", lambda x: x * 2.0,
                               in_shapes=[(4,)], in_dtypes=np.float32)
        assert not adm_mod.ACTIVE
        p, els = _pool_pipe("adm-stamp", model, slo_ms=100.0)
        p.start()
        try:
            assert adm_mod.ACTIVE  # armed by the pool attach
            els["src"].push_buffer(Buffer.of(
                np.zeros((1, 4), np.float32), pts=0))
            out = els["sink"].pull(timeout=10)
            assert out is not None
        finally:
            p.stop()
        assert not adm_mod.ACTIVE  # disarmed with the last stream

    def test_shed_posts_counter_and_bus_warning(self):
        from nnstreamer_tpu.filters.jax_xla import register_model

        model = register_model("chaos_adm_shed", lambda x: x + 1.0,
                               in_shapes=[(4,)], in_dtypes=np.float32)
        warns = []
        p_hi, hi = _pool_pipe("adm-hi", model, slo_ms=30.0,
                              priority="high")
        p_lo, lo = _pool_pipe("adm-lo", model, slo_ms=30.0,
                              priority="low")
        p_lo.bus.add_watch(
            lambda m: warns.append(m)
            if m.kind == MessageKind.WARNING else None)
        p_hi.start()
        p_lo.start()
        try:
            entry = hi["flt"].pool
            adm = entry.admission
            # force the at-risk state directly (deterministic — no
            # need to genuinely overload a CI machine)
            for _ in range(64):
                adm.observe(10.0)
            assert adm.shed_probability == 1.0
            for n in range(8):
                lo["src"].push_buffer(Buffer.of(
                    np.zeros((1, 4), np.float32), pts=n))
                hi["src"].push_buffer(Buffer.of(
                    np.zeros((1, 4), np.float32), pts=n))
            deadline = time.monotonic() + 10
            got_hi = 0
            while got_hi < 8 and time.monotonic() < deadline:
                if hi["sink"].pull(timeout=0.2) is not None:
                    got_hi += 1
            assert got_hi == 8  # high never shed
            assert adm.snapshot()["shed"]["low"] > 0
            assert warns and warns[0].data.get("shed") is True
            assert warns[0].data["priority"] == "low"
        finally:
            p_hi.stop()
            p_lo.stop()


def _pool_pipe(name, model, slo_ms=0.0, priority="normal"):
    from nnstreamer_tpu.elements.basic import AppSink, AppSrc, Queue
    from nnstreamer_tpu.elements.filter import TensorFilter

    spec = TensorsSpec.from_shapes([(4,)], np.float32)
    p = Pipeline(name=name)
    src = AppSrc(name="src", spec=spec, max_buffers=64)
    q = Queue(name="q", max_size_buffers=64)
    flt = TensorFilter(name="net", framework="jax-xla", model=model,
                       batch=4, batch_timeout_ms=2.0, batch_buckets="4",
                       share_model=True, slo_ms=slo_ms, priority=priority)
    sink = AppSink(name="sink", max_buffers=64)
    p.add(src, q, flt, sink).link(src, q, flt, sink)
    return p, {"src": src, "q": q, "flt": flt, "sink": sink}


# -- fault-plan-driven recovery coverage --------------------------------------


class TestPoolFaults:
    def test_fail_invoke_fans_out_to_every_sharing_bus(self):
        """SharedBatcher._error_all / the window-failure guard: ONE
        injected fail-invoke on the shared window must error on EVERY
        pipeline that parked a frame in it.

        Window composition is made DETERMINISTIC through the pause
        actuator (runtime/actuators.py): with coalescing paused, both
        streams' frames park in ONE window before the count=1 fault
        installs; resume dispatches that exact 4-frame cross-stream
        window into the fault.  (The old timing-based version let the
        2 ms deadline flush stream A's frames alone ~30% of the time —
        the poisoned window then carried one owner and B never
        errored.)"""
        from nnstreamer_tpu.filters.jax_xla import register_model

        model = register_model("chaos_fanout", lambda x: x * 3.0,
                               in_shapes=[(4,)], in_dtypes=np.float32)
        errs = {"a": [], "b": []}
        pa, ea = _pool_pipe("fan-a", model)
        pb, eb = _pool_pipe("fan-b", model)
        pa.bus.add_watch(lambda m: errs["a"].append(m)
                         if m.kind == MessageKind.ERROR else None)
        pb.bus.add_watch(lambda m: errs["b"].append(m)
                         if m.kind == MessageKind.ERROR else None)
        pa.start()
        pb.start()
        try:
            entry = ea["flt"].pool
            pause = entry.actuators()["coalescing"]
            pause.actuate(0.0)
            # two frames from each stream: with the window paused they
            # ALL park before anything dispatches
            for n in range(2):
                ea["src"].push_buffer(Buffer.of(
                    np.zeros((1, 4), np.float32), pts=n))
                eb["src"].push_buffer(Buffer.of(
                    np.zeros((1, 4), np.float32), pts=n))
            deadline = time.monotonic() + 10
            while entry.batcher.pending < 4 and \
                    time.monotonic() < deadline:
                time.sleep(0.01)
            assert entry.batcher.pending == 4
            # the ONE poisoned dispatch is the resumed 4-frame window
            chaos.install_plan(FaultPlan.parse(
                "seed=1;fail-invoke:count=1,match=pool:"))
            pause.revert()  # resume: drains the composed window
            deadline = time.monotonic() + 10
            while (not errs["a"] or not errs["b"]) and \
                    time.monotonic() < deadline:
                time.sleep(0.02)
            assert errs["a"] and errs["b"], errs
            assert isinstance(errs["a"][0].error, ChaosInvokeError)
        finally:
            chaos.uninstall_plan()
            pa.stop()
            pb.stop()

    def test_per_owner_error_routing_keeps_other_stream_alive(self):
        """A broken downstream in pipeline A (its demux raises) must
        error on A's bus only — B keeps receiving results from the SAME
        shared windows (serving.PoolEntry._dispatch demux guard)."""
        from nnstreamer_tpu.filters.jax_xla import register_model

        model = register_model("chaos_routing", lambda x: x - 1.0,
                               in_shapes=[(4,)], in_dtypes=np.float32)
        errs = {"a": [], "b": []}
        pa, ea = _pool_pipe("route-a", model)
        pb, eb = _pool_pipe("route-b", model)
        pa.bus.add_watch(lambda m: errs["a"].append(m)
                         if m.kind == MessageKind.ERROR else None)
        pb.bus.add_watch(lambda m: errs["b"].append(m)
                         if m.kind == MessageKind.ERROR else None)
        pa.start()
        pb.start()
        try:
            def boom(buf):
                raise RuntimeError("sink down")

            ea["sink"].render = boom  # break A's downstream only
            for n in range(2):
                ea["src"].push_buffer(Buffer.of(
                    np.zeros((1, 4), np.float32), pts=n))
                eb["src"].push_buffer(Buffer.of(
                    np.zeros((1, 4), np.float32), pts=n))
            got_b = 0
            deadline = time.monotonic() + 10
            while got_b < 2 and time.monotonic() < deadline:
                if eb["sink"].pull(timeout=0.2) is not None:
                    got_b += 1
            assert got_b == 2  # B unaffected
            assert errs["a"] and not errs["b"]
        finally:
            pa.stop()
            pb.stop()

    def test_slow_invoke_loses_nothing(self):
        from nnstreamer_tpu.filters.jax_xla import register_model

        model = register_model("chaos_slow", lambda x: x * 5.0,
                               in_shapes=[(4,)], in_dtypes=np.float32)
        p, e = _pool_pipe("slow-a", model)
        p.start()
        try:
            chaos.install_plan(FaultPlan.parse(
                "seed=2;slow-invoke:ms=15,p=0.5,match=pool:"))
            for n in range(12):
                e["src"].push_buffer(Buffer.of(
                    np.zeros((1, 4), np.float32), pts=n))
            got = 0
            deadline = time.monotonic() + 15
            while got < 12 and time.monotonic() < deadline:
                if e["sink"].pull(timeout=0.2) is not None:
                    got += 1
            assert got == 12
            assert chaos.active_plan().counts().get("slow-invoke", 0) > 0
        finally:
            chaos.uninstall_plan()
            p.stop()


# -- FaultPlan-driven query recovery (satellites 2 + 3) ------------------------


def _query_client_pipe(host, port, **kw):
    from nnstreamer_tpu.elements.basic import AppSink, AppSrc

    p = Pipeline(name="chaos-qp")
    src = AppSrc(name="src", spec=SPEC, max_buffers=256)
    kw.setdefault("timeout", 10000)
    kw.setdefault("connect_type", "inproc")
    cli = make("tensor_query_client", el_name="cli", host=host, port=port,
               **kw)
    snk = AppSink(name="out", max_buffers=256)
    p.add(src, cli, snk).link(src, cli, snk)
    return p, src, cli, snk


def _pump_closed_loop(src, cli, snk, n, outstanding):
    """Push ``n`` frames (value = pts = index) with at most
    ``outstanding`` unanswered, so that every frame reaches the wire
    (a burst would be shed at max-request before a fault plan ever saw
    it) and a fault schedule counted in frames is deterministic.
    Returns what was delivered once every frame is delivered, timed out
    or dropped."""
    got, sent = [], 0
    deadline = time.monotonic() + 60
    while len(got) + cli.timeouts + cli.dropped < n and \
            time.monotonic() < deadline:
        while sent < n and sent - len(got) - cli.timeouts \
                - cli.dropped < outstanding:
            src.push_buffer(Buffer.of(
                np.full((1, 4), float(sent), np.float32), pts=sent))
            sent += 1
        b = snk.pull(timeout=0.25)
        if b is not None:
            got.append(b)
    assert sent == n
    return got


class TestQueryFaults:
    def test_resend_at_most_once_unit(self, monkeypatch):
        """Satellite 2 (unit): an in-flight entry that already rode one
        failover resend is expired as a timeout on the NEXT one — never
        resent again (the old deadline-extension made it immortal)."""
        from nnstreamer_tpu.edge import query as query_mod

        cli = make("tensor_query_client", el_name="rcli",
                   host="h", port=1, connect_type="inproc", timeout=500)

        class FakeConn:
            def __init__(self):
                self.sent = []
                self.metrics = None

            def send(self, env):
                self.sent.append(env.seq)
                return True

            def close(self):
                pass

        dead = FakeConn()
        fresh = FakeConn()
        monkeypatch.setattr(query_mod, "connect",
                            lambda *a, **k: fresh)
        now = time.monotonic()
        buf = Buffer.of(np.zeros((1, 4), np.float32))
        cli._conn = dead
        cli.connected_addr = ("h", 1)
        # seq 1 was already resent once (resends=1); seq 2 never was
        cli._inflight[1] = [buf, None, now + 0.5, dead, now, 1]
        cli._inflight[2] = [buf, None, now + 0.5, dead, now, 0]
        cli._failover(dead)
        assert cli._conn is fresh
        assert fresh.sent == [2]          # only the fresh entry resent
        assert 1 not in cli._inflight     # the spent one timed out
        assert cli.timeouts == 1
        assert cli._inflight[2][5] == 1   # its one retry is now used
        cli.stop()

    def test_disconnect_flap_recovers_and_accounts(self):
        """Satellite 2 (end to end): injected disconnects mid-stream —
        the client fails over with backoff, resends in-flight requests
        at most once, and every frame is delivered or visibly timed
        out; EOS is reached (the old behavior could stall it)."""
        from tests.test_query_pipelining import DelayServer

        srv = DelayServer("inproc-chaos-flap", 7301, 0.05).start()
        try:
            p, src, cli, snk = _query_client_pipe(
                "inproc-chaos-flap", 7301, max_request=4, timeout=1500,
                chaos="seed=4;disconnect:every=9,dir=tx")
            n = 24
            with p:
                got = _pump_closed_loop(src, cli, snk, n, 3)
                src.end_of_stream()
                assert p.wait_eos(timeout=30)
                got.extend(iter(lambda: snk.pull(timeout=0.1), None))
            assert cli._metrics.snapshot()["reconnects"] >= 1
            assert len(got) + cli.timeouts + cli.dropped >= n
            # delivered frames still pair with their inputs (x2 server)
            for b in got:
                np.testing.assert_array_equal(
                    b.tensors[0].np(),
                    np.full((1, 4), 2.0 * float(b.pts), np.float32))
        finally:
            srv.stop()

    def test_seqless_drop_diagnostic_via_faultplan(self):
        """Satellite 3: the seq-less silent-drop story, driven by a
        FaultPlan drop on the request path instead of a hand-rolled
        lossy server: the stream stays live, every lost frame surfaces
        as a timeout, and accounting closes."""
        from tests.test_query_pipelining import DelayServer

        srv = DelayServer("inproc-chaos-sldrop", 7302, 0.0,
                          strip_seq=True).start()
        try:
            p, src, cli, snk = _query_client_pipe(
                "inproc-chaos-sldrop", 7302, max_request=2, timeout=400,
                chaos="seed=9;drop:every=7,dir=tx")
            n = 21
            with p:
                got = len(_pump_closed_loop(src, cli, snk, n, 2))
                src.end_of_stream()
                assert p.wait_eos(timeout=30)
                got += sum(1 for _ in iter(
                    lambda: snk.pull(timeout=0.1), None))
            assert cli.timeouts > 0          # drops surfaced, loudly
            assert got + cli.timeouts + cli.dropped >= n
            assert got > 0                   # ...and the stream lived on
        finally:
            srv.stop()

    def test_tombstone_expiry_via_faultplan_delay(self):
        """Satellite 3: tombstone machinery driven by an injected REPLY
        delay — one answer held past the client timeout leaves a
        tombstone that absorbs it when it finally lands; later replies
        keep pairing with the right requests."""
        from tests.test_query_pipelining import DelayServer

        srv = DelayServer("inproc-chaos-tomb", 7303, 0.0,
                          strip_seq=True).start()
        try:
            # delay the reply for request 1 past the 400ms client
            # timeout — injected at the SERVER transport's tx seam
            # (process-wide plan), so the sleep runs on the server's
            # reply thread, not on the client reader that must keep
            # expiring.  tx event 1 is the caps handshake reply; event
            # 2 is the answer to request 0; event 3 (after=2, count=1)
            # is the delayed answer to request 1.
            chaos.install_plan(FaultPlan.parse(
                "seed=1;delay:ms=700,every=1,after=2,count=1,dir=tx,"
                "match=inproc-server"))
            p, src, cli, snk = _query_client_pipe(
                "inproc-chaos-tomb", 7303, max_request=8, timeout=400)
            with p:
                src.push_buffer(Buffer.of(
                    np.zeros((1, 4), np.float32), pts=0))
                first = snk.pull(timeout=5)
                assert first is not None and first.pts == 0
                src.push_buffer(Buffer.of(
                    np.full((1, 4), 1.0, np.float32), pts=1))
                time.sleep(0.5)  # request 1 expires (tombstone parked)
                assert cli.timeouts == 1
                for i in (2, 3):
                    src.push_buffer(Buffer.of(
                        np.full((1, 4), float(i), np.float32), pts=i))
                out = []
                deadline = time.monotonic() + 10
                while len(out) < 2 and time.monotonic() < deadline:
                    b = snk.pull(timeout=0.25)
                    if b is not None:
                        out.append(b)
                src.end_of_stream()
                assert p.wait_eos(timeout=15)
            # the late reply for 1 was absorbed by its tombstone: 2 and
            # 3 pair with THEIR answers, not shifted onto 1's
            assert [b.pts for b in out] == [2, 3]
            for b in out:
                np.testing.assert_array_equal(
                    b.tensors[0].np(),
                    np.full((1, 4), 2.0 * float(b.pts), np.float32))
        finally:
            srv.stop()

    @pytest.mark.parametrize("plan, symptom", [
        ("seed=5;partition:ms=300,every=10,match=cli", "timeouts"),
        ("seed=6;corrupt:every=5,dir=tx,match=cli", "bad_frames"),
        ("seed=7;reorder:every=4,dir=tx,match=cli", "timeouts"),
    ], ids=["partition", "corrupt", "reorder"])
    def test_wire_fault_over_tcp_accounts_every_frame(self, plan,
                                                      symptom):
        """The wire faults the tests above do not drive end to end,
        over a loopback socket (there are no wire bytes to corrupt on
        the inproc transport): the client reaches EOS, every frame sent
        is delivered or counted (timed out, or dropped at max-request),
        what is delivered pairs with its own request, and the fault
        shows in a counter of the link (a request the server refused as
        corrupt, or one overtaken by a later request, times out)."""
        from nnstreamer_tpu.filters.custom import register_custom_easy
        from nnstreamer_tpu.obs.metrics import REGISTRY

        register_custom_easy("chaos_wire_x2", lambda xs: [xs[0] * 2.0],
                             in_spec=SPEC, out_spec=SPEC)
        srv = Pipeline(name="chaos-wire-srv")
        qsrc = make("tensor_query_serversrc", el_name="qsrc",
                    connect_type="tcp", host="127.0.0.1", port=0, id=95)
        flt = make("tensor_filter", el_name="f", framework="custom-easy",
                   model="chaos_wire_x2")
        qsink = make("tensor_query_serversink", el_name="qsink", id=95)
        srv.add(qsrc, flt, qsink).link(qsrc, flt, qsink)
        n = 24
        with srv:
            p, src, cli, snk = _query_client_pipe(
                "127.0.0.1", qsrc.port, connect_type="tcp", timeout=600,
                max_request=3,
                caps="other/tensors,format=static,num_tensors=1,"
                     "dimensions=4:1,types=float32")
            with p:
                installed = chaos.install_plan(FaultPlan.parse(plan))
                got = _pump_closed_loop(src, cli, snk, n, 3)
                # the drain to EOS is not itself under the fault
                chaos.uninstall_plan()
                src.end_of_stream()
                assert p.wait_eos(timeout=30)
                got.extend(iter(lambda: snk.pull(timeout=0.1), None))
                links = [r for r in REGISTRY.snapshot()["links"]
                         if r["link"] in ("cli", "qsrc")]
        assert installed.total_injected > 0
        assert len(got) + cli.timeouts + cli.dropped >= n
        assert got, "the stream did not live through the fault"
        for b in got:
            np.testing.assert_array_equal(
                b.tensors[0].np(),
                np.full((1, 4), 2.0 * float(b.pts), np.float32))
        assert sum(r[symptom] for r in links) > 0


# -- self-healing links (mqtt + edge pub/sub) ---------------------------------


class TestSelfHealingLinks:
    def test_mqttsrc_reconnects_through_broker_restart(self):
        from nnstreamer_tpu.edge.mqtt import MiniBroker, MqttSink, MqttSrc
        from nnstreamer_tpu.elements.basic import AppSink, AppSrc

        broker = MiniBroker()
        port = broker.port
        spec = TensorsSpec.parse("4:1", "float32")
        psrc = Pipeline(name="mq-sub")
        msrc = MqttSrc(name="msrc", port=port, sub_topic="chaos/t",
                       num_buffers=2, sub_timeout=2.0,
                       reconnect_timeout_s=20.0)
        outs = AppSink(name="out", max_buffers=16)
        psrc.add(msrc, outs).link(msrc, outs)
        psrc.start()
        try:
            psink = Pipeline(name="mq-pub")
            asrc = AppSrc(name="src", spec=spec, max_buffers=16)
            msink = MqttSink(name="msink", port=port,
                             pub_topic="chaos/t",
                             reconnect_timeout_s=20.0)
            psink.add(asrc, msink).link(asrc, msink)
            psink.start()
            time.sleep(0.2)  # let the subscription settle
            asrc.push_buffer(Buffer.of(
                np.full((1, 4), 1.0, np.float32), pts=0))
            assert outs.pull(timeout=10) is not None
            # broker restart ON THE SAME PORT: both ends must reconnect
            broker.stop()
            time.sleep(0.3)
            broker = MiniBroker(port=port)
            deadline = time.monotonic() + 20
            got = None
            n = 1
            while got is None and time.monotonic() < deadline:
                asrc.push_buffer(Buffer.of(
                    np.full((1, 4), 2.0, np.float32), pts=n))
                n += 1
                got = outs.pull(timeout=1.0)
            assert got is not None, "no frame after broker restart"
            sub_link = LinkMetrics.get("msrc", f"127.0.0.1:{port}",
                                       kind="mqtt-sub")
            assert sub_link.snapshot()["reconnects"] >= 1
            asrc.end_of_stream()
            psink.stop()
        finally:
            psrc.stop()
            broker.stop()

    def test_edgesrc_reconnects_after_publisher_restart(self):
        from nnstreamer_tpu.elements.basic import AppSink, AppSrc

        spec = TensorsSpec.parse("4:1", "float32")

        def publisher(port):
            p = Pipeline(name="edge-pub")
            src = AppSrc(name="src", spec=spec, max_buffers=16)
            sink = make("edgesink", el_name="esink", host="127.0.0.1",
                        port=port, topic="t")
            p.add(src, sink).link(src, sink)
            p.start()
            return p, src, sink

    # (split so the long body stays readable)
        ppub, psrc_el, esink = publisher(0)
        port = esink.port
        psub = Pipeline(name="edge-sub")
        esrc = make("edgesrc", el_name="esrc", dest_host="127.0.0.1",
                    dest_port=port, topic="t", num_buffers=2,
                    caps="other/tensors,format=static,num_tensors=1,"
                         "dimensions=4:1,types=float32",
                    reconnect_timeout_s=20.0)
        outs = AppSink(name="out", max_buffers=16)
        psub.add(esrc, outs).link(esrc, outs)
        psub.start()
        try:
            time.sleep(0.2)
            psrc_el.push_buffer(Buffer.of(
                np.full((1, 4), 1.0, np.float32), pts=0))
            assert outs.pull(timeout=10) is not None
            # kill the publisher, restart on the SAME port
            ppub.stop()
            time.sleep(0.3)
            ppub, psrc_el, esink = publisher(port)
            deadline = time.monotonic() + 20
            got = None
            n = 1
            while got is None and time.monotonic() < deadline:
                psrc_el.push_buffer(Buffer.of(
                    np.full((1, 4), 2.0, np.float32), pts=n))
                n += 1
                got = outs.pull(timeout=1.0)
            assert got is not None, "no frame after publisher restart"
            assert LinkMetrics.get(
                "esrc", f"127.0.0.1:{port}",
                kind="edge-sub").snapshot()["reconnects"] >= 1
        finally:
            psub.stop()
            ppub.stop()
