"""`runtime/actuators.py` + `obs/control.py` — the actuation plane
(ISSUE-11 surface).

Actuator guards (min/max clamping reported, cooldown rejection,
reversibility restoring the EXACT prior config incl. per-stream queue
limits), the concurrent-actuation-vs-`Pipeline.stop()` race (mirror of
the PR-10 scrape-vs-stop race), the batcher pause/resume seam,
breaker forced transitions (+ the kicked sleep), playbook grammar
(TOML/JSON, malformed files, duplicate names), the controller loop
(alert → playbook → actuation, alert-label target narrowing, cooldown
and guard outcomes, on_resolve revert), the decision audit ring vs the
exported `nns_control_actions_total` (counts equal), the snapshot-v6
`control` table + shape golden companion, `/healthz` control summary,
the nns-top CONTROL section, the strict kill-switch no-op, and the
`nns-ctl` CLI."""

import io
import json
import threading
import time

import numpy as np
import pytest

from nnstreamer_tpu.chaos.retrypolicy import (CLOSED, HALF_OPEN, OPEN,
                                              RetryPolicy)
from nnstreamer_tpu.core import Buffer, TensorsSpec
from nnstreamer_tpu.elements.basic import AppSink, AppSrc, Queue
from nnstreamer_tpu.elements.filter import TensorFilter
from nnstreamer_tpu.filters.jax_xla import register_model, unregister_model
from nnstreamer_tpu.obs import control as control_mod
from nnstreamer_tpu.obs import hooks as obs_hooks
from nnstreamer_tpu.obs.control import (Controller, Playbook,
                                        PlaybookError, control_health,
                                        control_table,
                                        default_playbooks,
                                        lint_playbook, load_playbooks,
                                        parse_playbooks)
from nnstreamer_tpu.obs.metrics import REGISTRY
from nnstreamer_tpu.obs.watch import AlertRule, Watch
from nnstreamer_tpu.runtime import Pipeline
from nnstreamer_tpu.runtime.actuators import (ActuationError, Actuator,
                                              CooldownActive,
                                              find_actuators,
                                              list_actuators)

SHAPE = (4,)


@pytest.fixture(scope="module", autouse=True)
def _model():
    register_model("_t_ctl", lambda x: x + 1.0, in_shapes=[SHAPE],
                   in_dtypes=np.float32)
    yield
    unregister_model("_t_ctl")


def _pool_pipe(name, slo_ms=0.0, priority="normal", batch=4):
    spec = TensorsSpec.from_shapes([SHAPE], np.float32)
    p = Pipeline(name=name)
    src = AppSrc(name="src", spec=spec, max_buffers=64)
    q = Queue(name="q", max_size_buffers=64)
    flt = TensorFilter(name="net", framework="jax-xla", model="_t_ctl",
                       batch=batch, batch_timeout_ms=2.0,
                       batch_buckets=str(batch), share_model=True,
                       slo_ms=slo_ms, priority=priority)
    sink = AppSink(name="sink", max_buffers=64)
    p.add(src, q, flt, sink).link(src, q, flt, sink)
    return p, {"src": src, "q": q, "flt": flt, "sink": sink}


# -- actuator guards (satellite: edge cases) ----------------------------------


def test_actuator_clamps_and_reports():
    v = {"x": 5.0}
    act = Actuator("knob", "pool", "t", get_fn=lambda: v["x"],
                   set_fn=lambda n: v.update(x=n), lo=1.0, hi=10.0,
                   cooldown_s=0.0)
    res = act.actuate(25.0)
    assert res["applied"] == 10.0 and res["clamped"] is True
    assert res["requested"] == 25.0 and v["x"] == 10.0
    res = act.actuate(-3.0)
    assert res["applied"] == 1.0 and res["clamped"] is True
    res = act.actuate(7.0)
    assert res["applied"] == 7.0 and res["clamped"] is False


def test_actuator_cooldown_rejects_then_admits():
    v = {"x": 0.0}
    act = Actuator("knob", "pool", "t", get_fn=lambda: v["x"],
                   set_fn=lambda n: v.update(x=n), cooldown_s=0.2)
    act.actuate(1.0)
    with pytest.raises(CooldownActive):
        act.actuate(2.0)
    assert v["x"] == 1.0  # the rejected write never landed
    time.sleep(0.25)
    assert act.actuate(2.0)["applied"] == 2.0


def test_actuator_revert_restores_exact_prior():
    """Two forward actuations then revert: the knob returns to the
    value BEFORE the first steer, not the intermediate one; revert
    bypasses the cooldown (backing out is always allowed) and a second
    revert is a no-op."""
    v = {"x": 3.0}
    act = Actuator("knob", "pool", "t", get_fn=lambda: v["x"],
                   set_fn=lambda n: v.update(x=n), cooldown_s=0.0)
    act.actuate(5.0)
    act.actuate(9.0)
    act.cooldown_s = 60.0  # revert must not care
    res = act.revert()
    assert res["applied"] == 3.0 and res["prior"] == 9.0
    assert v["x"] == 3.0
    assert act.revert() is None


def test_pool_actuators_bounds_and_revert():
    """The real PoolEntry knobs: window-ms/max-batch clamp to their
    guards, queue-limit restores PER STREAM on revert (the exact-prior
    contract on a non-scalar config)."""
    pa, ea = _pool_pipe("act-a", slo_ms=50.0)
    pb, eb = _pool_pipe("act-b", slo_ms=50.0)
    pa.start()
    pb.start()
    try:
        entry = ea["flt"].pool
        acts = entry.actuators()
        for act in acts.values():
            act.cooldown_s = 0.0
        # max-batch: hi is the largest compiled bucket
        res = acts["max-batch"].actuate(99.0)
        assert res["applied"] == 4.0 and res["clamped"]
        res = acts["max-batch"].actuate(1.0)
        assert entry.batcher.max_batch == 1
        acts["max-batch"].revert()
        assert entry.batcher.max_batch == 4
        # window-ms: floor guard
        res = acts["window-ms"].actuate(0.0)
        assert res["applied"] == 0.1 and res["clamped"]
        acts["window-ms"].revert()
        assert entry.batcher.timeout_s == pytest.approx(0.002)
        # queue-limit: distinct per-stream priors restore exactly
        with entry._lock:
            pols = list(entry._policies.values())
            pols[0].queue_limit = 7
            pols[1].queue_limit = 13
        acts["queue-limit"].actuate(2.0)
        assert {p.queue_limit for p in pols} == {2}
        acts["queue-limit"].revert()
        assert sorted(p.queue_limit for p in pols) == [7, 13]
        # ramp-start clamps into (0.3, 0.99)
        res = acts["ramp-start"].actuate(0.01)
        assert res["applied"] == 0.3 and res["clamped"]
        assert entry.admission.ramp_start == 0.3
        acts["ramp-start"].revert()
        assert entry.admission.ramp_start == pytest.approx(0.7)
    finally:
        pa.stop()
        pb.stop()


def test_window_ms_revert_restores_settle_too():
    """_set_window_ms shrinks the adaptive settle alongside the
    deadline (settle <= timeout invariant); revert must restore BOTH
    — a scalar prior would leave settle collapsed forever while the
    knob reports clean (review finding)."""
    p, e = _pool_pipe("settle")
    p.start()
    try:
        entry = e["flt"].pool
        b = entry.batcher
        act = entry.actuators()["window-ms"]
        act.cooldown_s = 0.0
        settle0, timeout0 = b.settle_s, b.timeout_s
        act.actuate(0.2)  # 0.2 ms deadline collapses settle under it
        assert b.settle_s <= 0.0002
        act.revert()
        assert b.timeout_s == pytest.approx(timeout0)
        assert b.settle_s == pytest.approx(settle0)
    finally:
        p.stop()


def test_actuation_races_pipeline_stop():
    """Actuators hammered from threads while pipelines start, stream
    and stop must never crash: a torn-down window fails the actuation
    with a clean ActuationError (counted, not raised through) — the
    mirror of the PR-10 scrape-vs-stop race."""
    spec = TensorsSpec.from_shapes([SHAPE], np.float32)
    errors = []
    stop_evt = threading.Event()
    outcomes = {"ok": 0, "gone": 0}

    def actuator_thread():
        while not stop_evt.is_set():
            try:
                for act in list_actuators("pool"):
                    try:
                        act.cooldown_s = 0.0
                        act.actuate(5.0 if act.name == "window-ms"
                                    else 2.0)
                        act.revert()
                        outcomes["ok"] += 1
                    except ActuationError:
                        outcomes["gone"] += 1  # stop() won the race
            except Exception as e:  # noqa: BLE001 - the assertion
                errors.append(e)
                return

    threads = [threading.Thread(target=actuator_thread)
               for _ in range(3)]
    for t in threads:
        t.start()
    try:
        for round_i in range(6):
            p, e = _pool_pipe(f"actrace-{round_i}")
            p.start()
            for n in range(4):
                e["src"].push_buffer(Buffer.of(
                    np.zeros(SHAPE, np.float32), pts=n))
            e["src"].end_of_stream()
            p.wait_eos(timeout=10, raise_on_error=False)
            p.stop()
    finally:
        stop_evt.set()
        for t in threads:
            t.join(timeout=10)
    assert not errors, errors
    assert outcomes["ok"] > 0


# -- batcher pause / breaker transitions --------------------------------------


def test_pause_parks_resume_drains_eos_ignores_pause():
    p, e = _pool_pipe("pause-a")
    p.start()
    try:
        entry = e["flt"].pool
        act = entry.actuators()["coalescing"]
        act.cooldown_s = 0.0
        act.actuate(0.0)
        for n in range(6):
            e["src"].push_buffer(Buffer.of(
                np.zeros(SHAPE, np.float32), pts=n))
        deadline = time.monotonic() + 5
        while entry.batcher.pending < 6 and \
                time.monotonic() < deadline:
            time.sleep(0.01)
        assert entry.batcher.pending == 6  # full window did NOT flush
        assert e["sink"].pull(timeout=0.1) is None
        act.actuate(1.0)
        got = 0
        deadline = time.monotonic() + 10
        while got < 6 and time.monotonic() < deadline:
            if e["sink"].pull(timeout=0.2) is not None:
                got += 1
        assert got == 6  # full windows + the timer'd remainder
        # EOS through a paused window: frames still drain (never lost)
        act.actuate(0.0)
        e["src"].push_buffer(Buffer.of(np.zeros(SHAPE, np.float32),
                                       pts=7))
        e["src"].end_of_stream()
        assert p.wait_eos(timeout=10)
        assert e["sink"].pull(timeout=1.0) is not None
    finally:
        p.stop()


def test_breaker_forced_transitions_and_kicked_wait():
    pol = RetryPolicy(name="lnk", fail_threshold=2, open_s=30.0)
    pol.failure(RuntimeError("x"))
    pol.failure(RuntimeError("x"))
    assert pol.state == OPEN
    # a loop sleeping out the 30s open window wakes on the forced probe
    woke = []

    def sleeper():
        t0 = time.monotonic()
        pol.wait(max_s=10.0)
        woke.append(time.monotonic() - t0)

    t = threading.Thread(target=sleeper)
    t.start()
    time.sleep(0.1)
    pol.force_half_open()
    t.join(timeout=5)
    assert woke and woke[0] < 5.0  # not the full max_s
    assert pol.state == HALF_OPEN
    # a force landing BEFORE the wait is not lost either: the delay is
    # computed AFTER the kick clears, so it reflects the forced state
    # (review finding: clear-after-delay erased such a kick and slept
    # the stale open window out)
    pol.failure(RuntimeError("x"))  # half-open probe fails: re-OPEN
    assert pol.state == OPEN
    pol.force_half_open()
    t0 = time.monotonic()
    assert pol.wait(max_s=10.0) is True
    assert time.monotonic() - t0 < 2.0  # backoff, not the open window
    pol.reset()
    assert pol.state == CLOSED and pol.consecutive_failures == 0
    pol.force_open()
    assert pol.state == OPEN
    # the breaker actuator maps values onto the forced transitions
    act = pol.actuators()["breaker"]
    act.cooldown_s = 0.0
    assert act.actuate(1.0)["applied"] == 1.0
    assert pol.state == HALF_OPEN
    assert act.actuate(0.0)["applied"] == 0.0
    assert pol.state == CLOSED
    assert find_actuators("link", "lnk", "breaker")


# -- playbook grammar ---------------------------------------------------------


def test_playbook_parse_and_errors(tmp_path):
    pbs = parse_playbooks({"playbook": [
        {"name": "a", "rule": "slo-burn", "kind": "pool",
         "actuator": "ramp-start", "action": "set", "value": 0.5,
         "cooldown": "2s", "on_resolve": "revert"}]})
    assert pbs[0].cooldown_s == 2.0 and pbs[0].on_resolve == "revert"
    with pytest.raises(PlaybookError, match="unknown key"):
        parse_playbooks([{"name": "a", "rule": "r", "kind": "pool",
                          "actuator": "x", "frobnicate": 1}])
    with pytest.raises(PlaybookError, match="unknown target kind"):
        parse_playbooks([{"name": "a", "rule": "r", "kind": "zray",
                          "actuator": "x", "value": 1}])
    with pytest.raises(PlaybookError, match="unknown action"):
        parse_playbooks([{"name": "a", "rule": "r", "kind": "pool",
                          "actuator": "x", "action": "yeet",
                          "value": 1}])
    # a set/step playbook without an explicit value would silently
    # actuate 0.0 (for coalescing: PAUSE the window it meant to fix)
    with pytest.raises(PlaybookError, match="explicit 'value'"):
        parse_playbooks([{"name": "a", "rule": "r", "kind": "pool",
                          "actuator": "coalescing"}])
    with pytest.raises(PlaybookError, match="duplicate"):
        parse_playbooks([
            {"name": "a", "rule": "r", "kind": "pool",
             "actuator": "x", "value": 1},
            {"name": "a", "rule": "r", "kind": "pool",
             "actuator": "x", "value": 1}])
    with pytest.raises(PlaybookError, match="never moves"):
        parse_playbooks([{"name": "a", "rule": "r", "kind": "pool",
                          "actuator": "x", "action": "step",
                          "value": 0}])
    bad = tmp_path / "bad.json"
    bad.write_text("{nope")
    with pytest.raises(PlaybookError, match="invalid JSON"):
        load_playbooks(str(bad))
    # TOML round-trip (tomllib is 3.11+; JSON is the portable form)
    toml = tmp_path / "pb.toml"
    toml.write_text('[[playbook]]\nname = "t"\nrule = "slo-burn"\n'
                    'kind = "pool"\nactuator = "ramp-start"\n'
                    'value = 0.4\ncooldown = "1s"\n')
    try:
        import tomllib  # noqa: F401
    except ImportError:
        pass
    else:
        assert load_playbooks(str(toml))[0].value == 0.4


def test_lint_playbook_and_default_pack_clean():
    ok = Playbook(name="p", rule="slo-burn", kind="pool",
                  actuator="ramp-start")
    assert lint_playbook(ok, ["slo-burn"]) == []
    bad = Playbook(name="p", rule="slo-burn", kind="pool",
                   actuator="warp-drive")
    assert any("does not exist" in s
               for s in lint_playbook(bad, ["slo-burn"]))
    assert any("never trigger" in s
               for s in lint_playbook(ok, ["other-rule"]))
    from nnstreamer_tpu.obs.watch import default_rules

    names = [r.name for r in default_rules()]
    for pb in default_playbooks():
        assert lint_playbook(pb, names) == [], pb.name


# -- the controller loop ------------------------------------------------------


def _ctl_rig(slo_ms=0.0, rules=None, playbooks=None):
    p, e = _pool_pipe("ctl-rig", slo_ms=slo_ms)
    p.start()
    w = Watch(rules=rules or [], interval_s=0.02)
    ctl = Controller(playbooks=playbooks or [], watch=w,
                     interval_s=0.02)
    return p, e, w, ctl


def test_controller_closes_the_loop_and_reverts_on_resolve():
    """pool-stall fires → playbook resumes coalescing; when the rule
    resolves, a second on_resolve=revert playbook restores the knob it
    steered — all of it visible in the audit ring and the exported
    counter with EQUAL counts."""
    rules = [AlertRule(name="pool-stall", kind="threshold",
                       metric="nns_pool_pending", op=">=", value=6.0)]
    playbooks = [
        Playbook(name="resume", rule="pool-stall", kind="pool",
                 actuator="coalescing", action="set", value=1.0,
                 cooldown_s=0.1),
        Playbook(name="narrow", rule="pool-stall", kind="pool",
                 actuator="window-ms", action="set", value=1.0,
                 cooldown_s=0.1, on_resolve="revert"),
    ]
    before = _counter_total()
    p, e, w, ctl = _ctl_rig(rules=rules, playbooks=playbooks)
    try:
        entry = e["flt"].pool
        pause = entry.actuators()["coalescing"]
        pause.cooldown_s = 0.0
        entry.actuators()["window-ms"].cooldown_s = 0.0
        pause.actuate(0.0)
        for n in range(8):
            e["src"].push_buffer(Buffer.of(
                np.zeros(SHAPE, np.float32), pts=n))
        deadline = time.monotonic() + 5
        while entry.batcher.pending < 8 and \
                time.monotonic() < deadline:
            time.sleep(0.01)
        w.sample_once()  # gauge levels bind on the first tick
        w.sample_once()
        assert any(a["rule"] == "pool-stall" and a["firing"]
                   for a in w.alerts())
        decisions = ctl.tick()
        outcomes = {(d["playbook"], d["outcome"]) for d in decisions}
        assert ("resume", "applied") in outcomes
        assert ("narrow", "applied") in outcomes
        assert not entry.batcher.paused
        assert entry.batcher.timeout_s == pytest.approx(0.001)
        # drain → rule resolves → the narrow playbook reverts its knob
        deadline = time.monotonic() + 10
        while entry.batcher.pending > 0 and \
                time.monotonic() < deadline:
            while e["sink"].pull(timeout=0.05) is not None:
                pass
            time.sleep(0.01)
        w.sample_once()
        w.sample_once()
        decisions = ctl.tick()
        assert ("narrow", "reverted") in {
            (d["playbook"], d["outcome"]) for d in decisions}
        assert entry.batcher.timeout_s == pytest.approx(0.002)
        # audit == exported counter, every outcome included
        assert ctl.actions_total == len(ctl.audit)
        assert _counter_total() - before == ctl.actions_total
        # only the revert-on-resolve playbook retained its actuator;
        # a fire-and-forget playbook holding one would pin the pool
        # for the controller's lifetime (review finding)
        assert ctl._states["resume"].applied == {}
        assert ctl._states["narrow"].applied == {}  # drained by revert
        # the alert's own pool label narrowed the target
        assert all(d["target"] == entry.label() for d in ctl.audit)
    finally:
        ctl.stop()
        w.stop()
        p.stop()


def _counter_total():
    fam = REGISTRY.collect().get("nns_control_actions_total", {})
    return sum(s["value"] for s in fam.get("samples", []))


def test_controller_cooldown_no_target_and_guard_outcomes():
    rules = [AlertRule(name="pool-stall", kind="threshold",
                       metric="nns_pool_pending", op=">=", value=0.0)]

    def firing_watch():
        w = Watch(rules=rules, interval_s=0.02, source=lambda: [
            {"endpoint": "local", "error": None, "snap": {
                "pools": [],
                "metrics": {"nns_pool_pending": {
                    "name": "nns_pool_pending", "kind": "gauge",
                    "help": "", "samples": [
                        {"labels": {"pool": "nowhere:pool"},
                         "value": 9.0}]}}}}])
        w.sample_once()
        w.sample_once()
        return w

    w = firing_watch()
    # no-target: the alert names a pool this process doesn't own
    ctl = Controller(playbooks=[Playbook(
        name="p", rule="pool-stall", kind="pool",
        actuator="coalescing", action="set", value=1.0,
        cooldown_s=10.0)], watch=w, interval_s=0.02)
    d = ctl.tick()
    assert [x["outcome"] for x in d] == ["no-target"]
    # playbook cooldown: the SAME firing episode is not even re-decided
    assert ctl.tick() == []
    w.stop()
    # guard-hold: mfu at the ceiling blocks a widen playbook
    w2 = Watch(rules=rules, interval_s=0.02, source=lambda: [
        {"endpoint": "local", "error": None, "snap": {
            "pools": [],
            "metrics": {
                "nns_pool_pending": {
                    "name": "nns_pool_pending", "kind": "gauge",
                    "help": "", "samples": [{"labels": {},
                                             "value": 9.0}]},
                "nns_mfu": {
                    "name": "nns_mfu", "kind": "gauge", "help": "",
                    "samples": [{"labels": {"source": "m"},
                                 "value": 0.95}]}}}}])
    w2.sample_once()
    w2.sample_once()
    ctl2 = Controller(playbooks=[Playbook(
        name="widen", rule="pool-stall", kind="pool",
        actuator="max-batch", action="step", value=4.0,
        guard="mfu-headroom", cooldown_s=10.0)], watch=w2,
        interval_s=0.02)
    d = ctl2.tick()
    assert [x["outcome"] for x in d] == ["guard-hold"]
    w2.stop()


def _series_snap(kind, families, pools=None):
    """One watch snapshot: ``families`` maps a family name to its
    ``(labels, value)`` samples."""
    return {"pools": pools or [], "metrics": {
        name: {"name": name, "kind": kind, "help": "",
               "samples": [{"labels": labels, "value": value,
                            "name": name + ("_bucket" if "le" in labels
                                            else "")}
                           for labels, value in samples]}
        for name, samples in families.items()}}


@pytest.mark.parametrize("fault", ["window-collapse", "slo-burn",
                                   "breaker-stuck"])
def test_playbook_mends_the_fault_its_rule_names(fault):
    """Fault, alert, actuation, knob back where it belongs, one case a
    remediation beside the stalled window above: a window steered down
    to one frame a dispatch (the rule sees dispatches == frames and the
    playbook reverts the knob), the same under an SLO (the burn rule
    steps the window back open and tightens the shed ramp), and a
    breaker left open (the rule forces the half-open probe).  The
    series the rules read are fed by hand on a fake clock, labelled as
    the live target exports them; every decision is in the audit ring
    and the exported counter alike."""
    p, e = _pool_pipe("mend", slo_ms=50.0)
    p.start()
    before = _counter_total()
    try:
        entry = e["flt"].pool
        pool = {"pool": entry.label()}
        for act in entry.actuators().values():
            act.cooldown_s = 0.0
        if fault == "breaker-stuck":
            pol = RetryPolicy(name="lnk-stuck", fail_threshold=2,
                              open_s=30.0)
            pol.actuators()["breaker"].cooldown_s = 0.0
            pol.failure(RuntimeError("x"))
            pol.failure(RuntimeError("x"))
            assert pol.state == OPEN
        else:
            entry.actuators()["max-batch"].actuate(1.0)
            assert entry.batcher.max_batch == 1
        link = {"link": "lnk-stuck", "peer": "p", "kind": "edge"}
        rules, playbooks, snap = {
            "window-collapse": (
                [AlertRule(name="dispatch-amplification",
                           kind="threshold",
                           metric="nns_pool_dispatches_total",
                           per="nns_pool_frames_total", op=">=",
                           value=0.7)],
                [Playbook(name="widen-window",
                          rule="dispatch-amplification", kind="pool",
                          actuator="max-batch", action="revert",
                          cooldown_s=0.1)],
                lambda t: _series_snap("counter", {
                    "nns_pool_dispatches_total": [(pool, 40.0 * t)],
                    "nns_pool_frames_total": [(pool, 40.0 * t)]})),
            "slo-burn": (
                [AlertRule(name="slo-burn", kind="slo_burn",
                           metric="nns_admission_latency_seconds",
                           fast_s=3.0, slow_s=10.0, budget=0.05,
                           burn=2.0)],
                [Playbook(name="widen-window", rule="slo-burn",
                          kind="pool", actuator="max-batch",
                          action="step", value=3.0, cooldown_s=0.1),
                 Playbook(name="tighten-admission", rule="slo-burn",
                          kind="pool", actuator="ramp-start",
                          action="set", value=0.5, cooldown_s=0.1)],
                # every observation between 100 ms and 1 s: over the
                # pool's 50 ms
                lambda t: _series_snap("histogram", {
                    "nns_admission_latency_seconds": [
                        (dict(pool, le=le), 50.0 * t * over)
                        for le, over in (("0.01", 0), ("0.1", 0),
                                         ("1", 1), ("+Inf", 1))]},
                    pools=[{"pool": entry.label(),
                            "admission": {"slo_ms": 50.0}}])),
            "breaker-stuck": (
                [AlertRule(name="breaker-open", kind="threshold",
                           metric="nns_edge_breaker_state", op=">=",
                           value="open")],
                [Playbook(name="redial-link", rule="breaker-open",
                          kind="link", actuator="breaker", action="set",
                          value=1.0, cooldown_s=0.1)],
                lambda t: _series_snap("gauge", {
                    "nns_edge_breaker_state": [(link, 2.0)]})),
        }[fault]
        clock = {"t": 0}
        w = Watch(rules=rules, interval_s=0.02, source=lambda: [
            {"endpoint": "local", "error": None,
             "snap": snap(clock["t"])}])
        ctl = Controller(playbooks=playbooks, watch=w, interval_s=0.02)
        for t in range(1, 7):
            clock["t"] = t
            w.sample_once(float(t))
        assert any(a["rule"] == rules[0].name and a["firing"]
                   for a in w.alerts())
        decisions = ctl.tick()
        assert {(d["playbook"], d["outcome"]) for d in decisions} == {
            (pb.name, "reverted" if pb.action == "revert" else "applied")
            for pb in playbooks}
        if fault == "breaker-stuck":
            assert pol.state == HALF_OPEN
        else:
            assert entry.batcher.max_batch == 4
        if fault == "slo-burn":
            assert entry.admission.ramp_start == pytest.approx(0.5)
        assert ctl.actions_total == len(ctl.audit) == len(playbooks)
        assert _counter_total() - before == ctl.actions_total
        ctl.stop()
        w.stop()
    finally:
        p.stop()


def test_controller_strictly_inert_when_disabled(monkeypatch):
    p, e = _pool_pipe("inert")
    p.start()
    try:
        before = control_table()["controllers"]
        monkeypatch.setattr(obs_hooks, "DISABLED", True)
        ctl = Controller()
        assert ctl.enabled is False
        assert ctl.start() is False
        assert ctl.tick() == []
        assert ctl.apply("pool", "*", "window-ms", value=5.0) == []
        assert ctl.actions_total == 0 and len(ctl.audit) == 0
        monkeypatch.setattr(obs_hooks, "DISABLED", False)
        assert control_table()["controllers"] == before
    finally:
        p.stop()


# -- export surfaces: snapshot v6, /healthz, nns-top --------------------------


def test_snapshot_control_table_and_health():
    p, e = _pool_pipe("snap6")
    p.start()
    ctl = Controller(playbooks=default_playbooks(), watch=None)
    try:
        entry = e["flt"].pool
        entry.actuators()["window-ms"].cooldown_s = 0.0
        ctl.apply("pool", "*", "window-ms", value=5.0)
        snap = REGISTRY.snapshot()
        assert snap["version"] == 10
        c = snap["control"]
        assert c["controllers"] >= 1
        assert c["actions_total"] >= 1
        assert c["last_action"]["actuator"] == "window-ms"
        assert c["last_action"]["outcome"] == "applied"
        assert any(d["playbook"] == "manual" for d in c["audit"])
        h = control_health()
        assert h["actions_total"] >= 1
        assert h["last_action"]["actuator"] == "window-ms"
        # counter total equals audit total across live controllers
        from nnstreamer_tpu.obs.top import render

        txt = render(snap)
        assert "CONTROL" in txt and "window-ms" in txt \
            and "manual" in txt
    finally:
        ctl.stop()
        p.stop()


def test_healthz_carries_control_summary():
    import urllib.request

    from nnstreamer_tpu.obs.metrics import MetricsServer

    p, e = _pool_pipe("hz6")
    p.start()
    srv = MetricsServer(REGISTRY, port=0)
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/healthz",
                timeout=5) as resp:
            doc = json.loads(resp.read().decode())
        assert "control" in doc
        assert {"controllers", "playbooks", "actions_total",
                "last_action"} <= set(doc["control"])
    finally:
        srv.close()
        p.stop()


# -- the nns-ctl CLI ----------------------------------------------------------


def test_nns_ctl_cli_list_apply_revert():
    from nnstreamer_tpu.obs.control import main as ctl_main

    p, e = _pool_pipe("cli")
    p.start()
    try:
        entry = e["flt"].pool
        for a in entry.actuators().values():
            a.cooldown_s = 0.0
        label = entry.label()
        buf = io.StringIO()
        assert ctl_main(["--list"], out=buf) == 0
        out = buf.getvalue()
        assert "window-ms" in out and label in out
        buf = io.StringIO()
        rc = ctl_main(["--apply", f"pool:{label}:window-ms=5",
                       "--json"], out=buf)
        assert rc == 0
        decisions = json.loads(buf.getvalue())
        assert decisions[0]["outcome"] == "applied"
        assert decisions[0]["applied"] == 5.0
        assert entry.batcher.timeout_s == pytest.approx(0.005)
        buf = io.StringIO()
        rc = ctl_main(["--revert", f"pool:{label}:window-ms",
                       "--json"], out=buf)
        assert rc == 0
        assert entry.batcher.timeout_s == pytest.approx(0.002)
        # an out-of-catalog actuation spec errors cleanly
        assert ctl_main(["--apply", "nonsense"],
                        out=io.StringIO()) == 2
        # audit mode aggregates LIVE controllers (the CLI's one-shot
        # controllers die with their invocation): hold one open
        ctl = Controller(playbooks=[], watch=None)
        ctl.apply("pool", label, "window-ms", value=3.0)
        buf = io.StringIO()
        assert ctl_main(["--audit"], out=buf) == 0
        assert "manual" in buf.getvalue()
        ctl.apply("pool", label, "window-ms", revert=True)
    finally:
        p.stop()


def test_nns_ctl_cli_rejects_bad_playbooks(tmp_path):
    from nnstreamer_tpu.obs.control import main as ctl_main

    bad = tmp_path / "bad.json"
    bad.write_text("{nope")
    assert ctl_main(["--run", "--once", "1",
                     "--playbooks", str(bad)],
                    out=io.StringIO()) == 2
