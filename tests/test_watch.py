"""`obs/watch.py` — alerting watchdog tests (ISSUE-10 surface).

Rule grammar (TOML/JSON, symbolic values, malformed files), the shared
histogram-quantile helper, the bounded series store (rate / level /
windowed quantiles, rate-from-zero for series born mid-run, counter
resets), all three rule kinds (threshold incl. ratio + `for`, dual-
window SLO burn in histogram and counter-ratio mode, robust-z drift
anomaly incl. the bounded baseline window), alert actions (registry
export, flight-recorder trigger exactly once per episode, pipeline-bus
WARNING), the strict kill-switch no-op, fleet mode over the shared
scrape client (endpoint-down), the nns-top ALERTS section, `/healthz`
alerts, the `nns-watch` CLI, and the registry-scrape-vs-`Pipeline.stop`
race (satellite)."""

import io
import json
import threading
import time
import urllib.request

import numpy as np
import pytest

from nnstreamer_tpu.core import TensorsSpec
from nnstreamer_tpu.elements.basic import AppSink, AppSrc, Queue
from nnstreamer_tpu.elements.filter import TensorFilter
from nnstreamer_tpu.filters.jax_xla import register_model, unregister_model
from nnstreamer_tpu.obs.metrics import (MetricsRegistry, REGISTRY,
                                        bucket_quantile)
from nnstreamer_tpu.obs import watch as watch_mod
from nnstreamer_tpu.obs.watch import (AlertRule, RuleError, SeriesStore,
                                      Watch, default_rules, lint_rule,
                                      load_rules, parse_rules)
from nnstreamer_tpu.runtime import Pipeline

SHAPE = (4,)


@pytest.fixture(scope="module", autouse=True)
def _model():
    register_model("_t_watch", lambda x: x * 2.0 + 1.0,
                   in_shapes=[SHAPE], in_dtypes=np.float32)
    yield
    unregister_model("_t_watch")


def _gauge_snap(name, value, labels=None, pools=None):
    return {"pools": pools or [],
            "metrics": {name: {"name": name, "kind": "gauge",
                               "help": "",
                               "samples": [{"labels": labels or {},
                                            "value": value}]}}}


def _counter_snap(name, value, labels=None):
    snap = _gauge_snap(name, value, labels)
    snap["metrics"][name]["kind"] = "counter"
    return snap


def _src(snap_fn):
    return lambda: [{"endpoint": "local", "snap": snap_fn(),
                     "error": None}]


# -- shared histogram-quantile helper (satellite: one definition) ------------


def test_bucket_quantile_interpolates():
    bounds = (1.0, 2.0, 4.0, float("inf"))
    # 10 obs <=1, 10 in (1,2], none above 2
    assert bucket_quantile(bounds, [10, 10, 0, 0], 0.5) == 1.0
    # p75 lands mid-bucket: 5 of 10 into (1,2]
    assert bucket_quantile(bounds, [10, 10, 0, 0], 0.75) == 1.5
    assert bucket_quantile(bounds, [0, 0, 0, 0], 0.99) is None
    # quantile in the +Inf bucket: nothing to interpolate toward
    assert bucket_quantile(bounds, [0, 0, 0, 5], 0.99) is None
    # first-bucket interpolation anchors at 0
    assert bucket_quantile(bounds, [10, 0, 0, 0], 0.5) == 0.5


def test_admission_p99_uses_shared_quantile(monkeypatch):
    """The admission controller's histogram-derived p99 routes through
    the one shared bucket_quantile definition."""
    from nnstreamer_tpu.runtime.admission import AdmissionController

    reg = MetricsRegistry()
    hist = reg.histogram("t_adm", buckets=(0.01, 0.02, 0.04)) \
        .labels()
    ctl = AdmissionController(slo_s=0.05, hist=hist)
    for _ in range(ctl.RECOMPUTE_EVERY * 4):
        ctl.observe(0.015)
    p99 = ctl.p99_s
    assert 0.01 < p99 <= 0.02, p99
    buckets, _s, _n = hist.hist_state()
    assert p99 == pytest.approx(
        bucket_quantile(hist.bucket_bounds, buckets, 0.99))


# -- rule grammar -------------------------------------------------------------


def test_parse_rules_json_and_symbolic(tmp_path):
    path = tmp_path / "rules.json"
    path.write_text(json.dumps({"rule": [
        {"name": "brk", "kind": "threshold",
         "metric": "nns_edge_breaker_state", "op": ">=",
         "value": "open", "for": "10s", "severity": "critical"},
        {"name": "burn", "kind": "slo_burn",
         "metric": "nns_admission_latency_seconds",
         "fast": "500ms", "slow": "2m"},
    ]}))
    rules = load_rules(str(path))
    assert rules[0].value == 2.0 and rules[0].for_s == 10.0
    assert rules[1].fast_s == 0.5 and rules[1].slow_s == 120.0


def test_parse_rules_toml(tmp_path):
    pytest.importorskip("tomllib")
    path = tmp_path / "rules.toml"
    path.write_text(
        '[[rule]]\nname = "brk"\nkind = "threshold"\n'
        'metric = "nns_edge_breaker_state"\nop = ">="\n'
        'value = "open"\nfor = "10s"\n')
    rules = load_rules(str(path))
    assert rules[0].name == "brk" and rules[0].value == 2.0


@pytest.mark.parametrize("doc,msg", [
    ({"rule": [{"name": "r", "kind": "nope", "metric": "nns_mfu"}]},
     "unknown kind"),
    ({"rule": [{"name": "r", "kind": "threshold", "metric": "nns_mfu",
                "frobnicate": 1}]}, "unknown key"),
    ({"rule": [{"name": "r", "kind": "threshold", "metric": "nns_mfu",
                "op": "~"}]}, "unknown op"),
    ({"rule": [{"name": "r", "kind": "threshold", "metric": "nns_mfu",
                "value": "wide-open"}]}, "symbolic"),
    ({"rule": [{"name": "r", "kind": "threshold", "metric": "nns_mfu",
                "for": "10parsecs"}]}, "duration"),
    ({"rule": [{"name": "r", "kind": "threshold", "metric": "nns_mfu"},
               {"name": "r", "kind": "threshold",
                "metric": "nns_mfu"}]}, "duplicate"),
    ({"rule": [{"kind": "threshold", "metric": "nns_mfu"}]}, "name"),
    ({"rule": []}, "no rules"),
    ({}, "no top-level"),
], ids=["kind", "key", "op", "symbol", "duration", "dupe", "noname",
        "empty", "shapeless"])
def test_malformed_rules_raise(doc, msg):
    with pytest.raises(RuleError, match=msg):
        parse_rules(doc)


def test_lint_rule_catalog_checks():
    bad_family = AlertRule(name="r", kind="threshold",
                           metric="nns_never_exported_total")
    assert any("ever exports" in p for p in lint_rule(bad_family))
    bad_signal = AlertRule(name="r", kind="threshold",
                           metric="nns_mfu", signal="rate")
    assert any("does not exist" in p for p in lint_rule(bad_signal))
    burn_gauge = AlertRule(name="r", kind="slo_burn", metric="nns_mfu")
    assert any("gauge" in p for p in lint_rule(burn_gauge))
    burn_counter_noper = AlertRule(
        name="r", kind="slo_burn", metric="nns_admission_shed_total")
    assert any("needs per=" in p for p in lint_rule(burn_counter_noper))
    # unsatisfiable lower-side drift: |z| <= 1/rel_floor on a collapse
    unsat = AlertRule(name="r", kind="anomaly", metric="nns_mfu",
                      z=8.0, side="lower", rel_floor=0.25)
    assert any("never fire" in p for p in lint_rule(unsat))


def test_default_pack_lints_clean():
    rules = default_rules()
    assert len(rules) >= 10
    for r in rules:
        assert lint_rule(r) == [], (r.name, lint_rule(r))


_LINK = {"kind": "query", "link": "qcli", "peer": "srv"}


def _rtt_snap(fast, slow):
    """``nns_edge_rtt_seconds`` with ``fast`` round trips under 1 ms and
    ``slow`` ones between 10 and 100 ms, cumulative."""
    return _hist_snap((fast, fast, fast + slow, fast + slow),
                      name="nns_edge_rtt_seconds", labels=_LINK)


#: rule of the default pack -> (snapshot while healthy, snapshot once
#: the fault shows), each a function of the tick: one symptom per fault
#: class the pack must alarm on (a lost request, a reconnect, a frame
#: the codec refused, a slow link, a slow pool, an errored dispatch)
_SYMPTOMS = {
    "edge-timeouts": (
        lambda t: _counter_snap("nns_edge_timeouts_total", 0.0, _LINK),
        lambda t: _counter_snap("nns_edge_timeouts_total", 3.0, _LINK)),
    "edge-reconnect-flap": (
        lambda t: _counter_snap("nns_edge_reconnects_total", 0.0, _LINK),
        lambda t: _counter_snap("nns_edge_reconnects_total", 1.0, _LINK)),
    "edge-bad-frames": (
        lambda t: _counter_snap("nns_edge_bad_frames_total", 0.0, _LINK),
        lambda t: _counter_snap("nns_edge_bad_frames_total", 2.0, _LINK)),
    "edge-rtt-drift": (
        lambda t: _rtt_snap(10 * t, 0),
        lambda t: _rtt_snap(10 * 14, 10 * (t - 14))),
    "pool-latency-drift": (
        lambda t: _gauge_snap("nns_pool_latency_us", 100.0 + t % 3,
                              {"pool": "jax-xla:m"}),
        lambda t: _gauge_snap("nns_pool_latency_us", 80000.0,
                              {"pool": "jax-xla:m"})),
    "element-errors": (
        lambda t: _counter_snap("nns_element_errors_total", 0.0,
                                {"pipeline": "p", "element": "net"}),
        lambda t: _counter_snap("nns_element_errors_total", 1.0,
                                {"pipeline": "p", "element": "net"})),
}


@pytest.mark.parametrize("rule", sorted(_SYMPTOMS))
def test_default_pack_alarms_on_the_symptom_and_not_before(rule):
    """The pack as shipped, nothing tuned: fourteen healthy ticks on a
    fake clock fire nothing, and the first ticks that show the symptom
    fire the rule that names it."""
    healthy, faulty = _SYMPTOMS[rule]
    state = {"t": 0}
    w = Watch(rules=default_rules(), registry=MetricsRegistry(),
              source=_src(lambda: (healthy if state["t"] <= 14
                                   else faulty)(state["t"])))
    for t in range(1, 15):
        state["t"] = t
        assert w.sample_once(float(t)) == [], f"alarm at healthy tick {t}"
    fired = []
    for t in range(15, 18):
        state["t"] = t
        fired += [ev["rule"] for ev in w.sample_once(float(t))]
    assert rule in fired


# -- series store -------------------------------------------------------------


def test_store_counter_rate_and_reset():
    store = SeriesStore()
    for ts, v in ((1.0, 100.0), (2.0, 110.0), (3.0, 5.0), (4.0, 10.0)):
        store.ingest("local",
                     _counter_snap("nns_edge_timeouts_total", v), ts)
    (_key, s), = store.match("nns_edge_timeouts_total", {})
    rates = [v for _t, v in s.rings["rate"]]
    # first tick = baseline, 100->110 = 10/s, reset skipped, 5->10 = 5/s
    assert rates == [10.0, 5.0]


def test_store_rate_from_zero_for_new_series():
    """A counter born AFTER the store's first tick carries its whole
    value as this window's increments (first error must alarm)."""
    store = SeriesStore()
    empty = {"metrics": {}}
    store.ingest("local", empty, 1.0)
    store.ingest("local",
                 _counter_snap("nns_element_errors_total", 2.0), 2.0)
    (_k, s), = store.match("nns_element_errors_total", {})
    assert [v for _t, v in s.rings["rate"]] == [2.0]
    # but on the store's FIRST tick, history is not news
    store2 = SeriesStore()
    store2.ingest("local",
                  _counter_snap("nns_element_errors_total", 99.0), 1.0)
    (_k, s2), = store2.match("nns_element_errors_total", {})
    assert list(s2.rings["rate"]) == []


def test_store_histogram_windowed_quantiles():
    store = SeriesStore()

    def snap(cums):
        samples = []
        for le, c in zip(("0.001", "0.01", "0.1", "+Inf"), cums):
            samples.append({"labels": {"pool": "p", "le": le},
                            "value": c,
                            "name": "nns_admission_latency_seconds_bucket"})
        return {"metrics": {"nns_admission_latency_seconds": {
            "name": "nns_admission_latency_seconds",
            "kind": "histogram", "help": "", "samples": samples}}}

    store.ingest("local", snap([0, 0, 0, 0]), 1.0)
    store.ingest("local", snap([100, 100, 100, 100]), 2.0)
    (_k, s), = store.match("nns_admission_latency_seconds", {})
    # all 100 obs <= 1ms: p99 interpolates inside the first bucket
    p99 = s.last("p99")[1]
    assert 0 < p99 <= 0.001
    # now 100 more, all in (10ms, 100ms]
    store.ingest("local", snap([100, 100, 200, 200]), 3.0)
    assert 0.01 < s.last("p99")[1] <= 0.1


def test_store_bounded_rings_and_series_cap():
    store = SeriesStore(ring_points=8, max_series=2)
    for i in range(20):
        snap = {"metrics": {"nns_mfu": {
            "name": "nns_mfu", "kind": "gauge", "help": "",
            "samples": [{"labels": {"source": str(i % 4)},
                         "value": 1.0}]}}}
        store.ingest("local", snap, float(i))
    assert len(store) == 2
    assert store.dropped_series > 0
    for _k, s in store.match("nns_mfu", {}):
        assert len(s.rings["level"]) <= 8


# -- threshold rules ----------------------------------------------------------


def test_threshold_for_duration_and_resolve():
    state = {"v": 0.0}
    w = Watch(rules=[AlertRule(name="brk", kind="threshold",
                               metric="nns_edge_breaker_state",
                               op=">=", value="open", for_s=2.0,
                               severity="critical")],
              registry=MetricsRegistry(),
              source=_src(lambda: _gauge_snap(
                  "nns_edge_breaker_state", state["v"],
                  {"link": "l", "peer": "p", "kind": "edge"})))
    assert w.sample_once(1.0) == []
    state["v"] = 2.0
    assert w.sample_once(2.0) == []      # bad, but not for 2s yet
    assert w.sample_once(3.0) == []
    fired = w.sample_once(4.0)           # held 2s: fires
    assert [e["rule"] for e in fired] == ["brk"]
    detail = fired[0]["detail"]
    assert detail["series"] == {"link": "l", "peer": "p",
                                "kind": "edge"}
    assert detail["points"], "offending series snapshot missing"
    state["v"] = 0.0
    assert w.sample_once(5.0) == []
    alerts = {a["rule"]: a for a in w.alerts()}
    assert not alerts["brk"]["firing"] and alerts["brk"]["fired"] == 1


def test_threshold_ratio_queue_saturation():
    def snap(depth):
        return {"metrics": {
            "nns_queue_depth": {
                "name": "nns_queue_depth", "kind": "gauge", "help": "",
                "samples": [{"labels": {"pipeline": "p",
                                        "element": "q"},
                             "value": depth}]},
            "nns_queue_capacity": {
                "name": "nns_queue_capacity", "kind": "gauge",
                "help": "",
                "samples": [{"labels": {"pipeline": "p",
                                        "element": "q"},
                             "value": 10.0}]},
        }}

    state = {"d": 1.0}
    w = Watch(rules=[AlertRule(name="qsat", kind="threshold",
                               metric="nns_queue_depth",
                               per="nns_queue_capacity",
                               op=">=", value=0.9)],
              registry=MetricsRegistry(),
              source=_src(lambda: snap(state["d"])))
    assert w.sample_once(1.0) == []
    state["d"] = 9.0
    assert [e["rule"] for e in w.sample_once(2.0)] == ["qsat"]


# -- anomaly rules ------------------------------------------------------------


def test_anomaly_upper_fires_on_spike_only():
    vals = [100.0, 101.0, 99.0, 100.0, 102.0, 98.0, 100.0, 101.0,
            99.0, 100.0]
    state = {"v": 0.0}
    w = Watch(rules=[AlertRule(name="drift", kind="anomaly",
                               metric="nns_pool_latency_us", z=8.0,
                               side="upper", min_samples=8,
                               rel_floor=0.35)],
              registry=MetricsRegistry(),
              source=_src(lambda: _gauge_snap("nns_pool_latency_us",
                                              state["v"],
                                              {"pool": "x"})))
    now = 0.0
    for v in vals:
        state["v"] = v
        now += 1.0
        assert w.sample_once(now) == [], f"false positive at {v}"
    state["v"] = 104.0  # noise, under the floor
    now += 1.0
    assert w.sample_once(now) == []
    state["v"] = 800.0  # 8x the baseline: decisively out of regime
    now += 1.0
    assert [e["rule"] for e in w.sample_once(now)] == ["drift"]
    assert w.alert_log[-1]["detail"]["zscore"] >= 8.0


def test_anomaly_lower_side_mfu_collapse():
    # NOTE the z/rel_floor pairing: on a lower-side rule the drop is
    # bounded by the series itself (a collapse to 0 is -median), so
    # z*rel_floor must stay < 1 for the rule to be satisfiable — the
    # default pack's mfu-collapse uses the same 3.5 x 0.25
    state = {"v": 0.4}
    w = Watch(rules=[AlertRule(name="mfu", kind="anomaly",
                               metric="nns_mfu", z=3.5, side="lower",
                               min_samples=8, rel_floor=0.25)],
              registry=MetricsRegistry(),
              source=_src(lambda: _gauge_snap("nns_mfu", state["v"],
                                              {"source": "m",
                                               "bucket": "8",
                                               "placement": "tpu"})))
    now = 0.0
    for _ in range(10):
        now += 1.0
        assert w.sample_once(now) == []
    state["v"] = 0.01  # collapse
    now += 1.0
    assert [e["rule"] for e in w.sample_once(now)] == ["mfu"]


def test_anomaly_baseline_window_ages_out_old_regime():
    """Startup values 40x the steady state must age OUT of the
    baseline (bounded baseline_points), not poison the MAD forever."""
    state = {"v": 40000.0}
    w = Watch(rules=[AlertRule(name="drift", kind="anomaly",
                               metric="nns_pool_latency_us", z=8.0,
                               side="upper", min_samples=8,
                               rel_floor=0.35, baseline_points=16)],
              registry=MetricsRegistry(),
              source=_src(lambda: _gauge_snap("nns_pool_latency_us",
                                              state["v"],
                                              {"pool": "x"})))
    now = 0.0
    for _ in range(6):  # compile-decay regime
        now += 1.0
        w.sample_once(now)
        state["v"] *= 0.5
    state["v"] = 300.0  # steady state, 20+ ticks: old regime ages out
    for _ in range(20):
        now += 1.0
        w.sample_once(now)
    state["v"] = 3000.0  # 10x steady: must fire despite the old spikes
    now += 1.0
    assert [e["rule"] for e in w.sample_once(now)] == ["drift"]


def test_stale_series_resolves_alert_and_evicts():
    """A series that stops appearing in snapshots (its pipeline/link
    died) must stop satisfying rules — the alert resolves instead of
    firing forever on the frozen last point — and eventually evicts."""
    present = {"on": True}

    def snap():
        if not present["on"]:
            return {"metrics": {}}
        return _gauge_snap("nns_edge_breaker_state", 2.0,
                           {"link": "l", "peer": "p", "kind": "edge"})

    w = Watch(rules=[AlertRule(name="brk", kind="threshold",
                               metric="nns_edge_breaker_state",
                               op=">=", value="open")],
              registry=MetricsRegistry(), source=_src(snap))
    now = 0.0
    now += 1.0
    assert [e["rule"] for e in w.sample_once(now)] == ["brk"]
    present["on"] = False  # the link's source object is gone
    for _ in range(SeriesStore.STALE_TICKS + 1):
        now += 1.0
        w.sample_once(now)
    alerts = {a["rule"]: a for a in w.alerts()}
    assert not alerts["brk"]["firing"], "stale series kept alert firing"
    for _ in range(SeriesStore.EVICT_TICKS + 1):
        now += 1.0
        w.sample_once(now)
    assert w.store.match("nns_edge_breaker_state", {}) == []
    assert len(w.store) == 0, "ghost series not evicted"


def test_bus_warning_rate_limited_across_episodes(monkeypatch):
    """An oscillating rule fires a new episode per tick; the bus
    WARNING action is limited to one per second while the counter
    still records every episode."""
    from nnstreamer_tpu.runtime.events import MessageKind

    spec = TensorsSpec.from_shapes([SHAPE], np.float32)
    p = Pipeline(name="watch-ratelimit")
    src = AppSrc(name="src", spec=spec, max_buffers=8)
    sink = AppSink(name="out", max_buffers=8)
    p.add(src, sink).link(src, sink)
    warnings = []
    p.bus.add_watch(lambda m: warnings.append(m)
                    if m.kind == MessageKind.WARNING else None)
    p.start()
    try:
        state = {"v": 0.0}
        w = Watch(rules=[AlertRule(name="osc", kind="threshold",
                                   metric="nns_edge_breaker_state",
                                   op=">=", value="open")],
                  registry=REGISTRY,
                  source=_src(lambda: _gauge_snap(
                      "nns_edge_breaker_state", state["v"],
                      {"link": "l", "peer": "rl", "kind": "edge"})))
        now = 0.0
        for i in range(10):  # 5 fire/resolve episodes, back to back
            state["v"] = 2.0 if i % 2 == 0 else 0.0
            now += 0.05
            w.sample_once(now)
        st = w._states["osc"]
        assert st.fired == 5
        assert len(warnings) == 1, [m.data for m in warnings]
    finally:
        src.end_of_stream()
        p.stop()


def test_endpoint_down_rule_name_is_reserved():
    with pytest.raises(RuleError, match="reserved"):
        Watch(rules=[AlertRule(name="endpoint-down", kind="threshold",
                               metric="nns_mfu")])
    assert any("reserved" in p for p in lint_rule(
        AlertRule(name="endpoint-down", kind="threshold",
                  metric="nns_mfu")))


def test_histogram_bucket_layout_change_resyncs_clean():
    """A family whose bucket layout changes mid-run (process restart
    behind the same endpoint) must drop its old-length delta rows —
    no truncated quantiles, no burn-eval crash."""
    store = SeriesStore()

    def snap(les, cums):
        samples = [{"labels": {"pool": "p", "le": le}, "value": c,
                    "name": "nns_admission_latency_seconds_bucket"}
                   for le, c in zip(les, cums)]
        return {"metrics": {"nns_admission_latency_seconds": {
            "name": "nns_admission_latency_seconds",
            "kind": "histogram", "help": "", "samples": samples}}}

    wide = ("0.001", "0.01", "0.1", "1.0", "+Inf")
    store.ingest("local", snap(wide, [0, 0, 0, 0, 0]), 1.0)
    store.ingest("local", snap(wide, [10, 20, 30, 40, 50]), 2.0)
    (_k, s), = store.match("nns_admission_latency_seconds", {})
    assert len(s.raw) == 1
    narrow = ("0.001", "0.01", "+Inf")
    store.ingest("local", snap(narrow, [5, 10, 20]), 3.0)
    assert list(s.raw) == [] and list(s.qwin) == []
    assert s.bounds == (0.001, 0.01, float("inf"))
    store.ingest("local", snap(narrow, [105, 110, 120]), 4.0)
    # quantiles derive from the NEW layout only
    assert len(s.raw) == 1 and len(s.raw[-1][1]) == 3
    assert 0 < s.last("p50")[1] <= 0.001
    assert s.hist_window(10.0, 4.0) == [100.0, 0.0, 0.0]


# -- slo_burn rules -----------------------------------------------------------


def _hist_snap(cums, pools=None, name="nns_admission_latency_seconds",
               labels=None):
    samples = []
    for le, c in zip(("0.001", "0.01", "0.1", "+Inf"), cums):
        samples.append({"labels": dict(labels or {"pool": "p"}, le=le),
                        "value": c, "name": name + "_bucket"})
    return {"pools": pools or [],
            "metrics": {name: {"name": name, "kind": "histogram",
                               "help": "", "samples": samples}}}


def test_burn_histogram_mode_with_pool_slo_hint():
    """slo_ms omitted: derived from the pool's own admission slo-ms in
    the same snapshot."""
    cums = [0, 0, 0, 0]
    pools = [{"pool": "p", "admission": {"slo_ms": 10.0}}]
    w = Watch(rules=[AlertRule(name="burn", kind="slo_burn",
                               metric="nns_admission_latency_seconds",
                               fast_s=3.0, slow_s=10.0, budget=0.01,
                               burn=4.0)],
              registry=MetricsRegistry(),
              source=_src(lambda: _hist_snap(list(cums), pools)))
    now = 0.0
    for _ in range(4):  # clean: all obs under 1ms
        now += 1.0
        cums = [c + 50 for c in cums]
        assert w.sample_once(now) == []
    fired = []
    for _ in range(12):  # 50% of new obs over the 10ms SLO
        now += 1.0
        cums = [cums[0] + 10, cums[1] + 10, cums[2] + 60, cums[3] + 60]
        fired += w.sample_once(now)
    assert fired and fired[0]["rule"] == "burn"
    assert fired[0]["detail"]["err_frac"]["fast"] > 0.04


def test_burn_counter_ratio_mode_shed_over_submitted():
    shed, sub = [0.0], [0.0]

    def snap():
        return {"metrics": {
            "nns_admission_shed_total": {
                "name": "nns_admission_shed_total", "kind": "counter",
                "help": "", "samples": [{"labels": {"pool": "p",
                                                    "priority": "low"},
                                         "value": shed[0]}]},
            "nns_admission_submitted_total": {
                "name": "nns_admission_submitted_total",
                "kind": "counter", "help": "",
                "samples": [{"labels": {"pool": "p",
                                        "priority": "low"},
                             "value": sub[0]}]},
        }}

    w = Watch(rules=[AlertRule(name="shed-burn", kind="slo_burn",
                               metric="nns_admission_shed_total",
                               per="nns_admission_submitted_total",
                               fast_s=3.0, slow_s=10.0, budget=0.05,
                               burn=2.0)],
              registry=MetricsRegistry(), source=_src(snap))
    now = 0.0
    for _ in range(4):  # no sheds
        now += 1.0
        sub[0] += 100
        assert w.sample_once(now) == []
    fired = []
    for _ in range(12):  # 30% shed: err 0.3 >= 2 x 0.05 budget
        now += 1.0
        sub[0] += 100
        shed[0] += 30
        fired += w.sample_once(now)
    assert fired and fired[0]["rule"] == "shed-burn"


# -- actions ------------------------------------------------------------------


def test_alert_export_into_registry_and_top_render():
    from nnstreamer_tpu.obs.top import render

    reg = MetricsRegistry()
    state = {"v": 2.0}
    w = Watch(rules=[AlertRule(name="brk", kind="threshold",
                               metric="nns_edge_breaker_state",
                               op=">=", value="open",
                               severity="critical")],
              registry=reg,
              source=_src(lambda: _gauge_snap(
                  "nns_edge_breaker_state", state["v"],
                  {"link": "l", "peer": "p", "kind": "edge"})))
    w.sample_once(1.0)
    w.sample_once(2.0)
    snap = reg.snapshot()
    fams = snap["metrics"]
    states = {(s["labels"]["rule"], s["labels"]["severity"]):
              s["value"] for s in fams["nns_alert_state"]["samples"]}
    assert states[("brk", "critical")] == 1.0
    fired = {s["labels"]["rule"]: s["value"]
             for s in fams["nns_alerts_fired_total"]["samples"]}
    assert fired["brk"] == 1.0
    table = render(snap)
    assert "ALERT" in table and "brk" in table and "FIRING" in table
    # resolution drops the gauge to 0 and the table shows ok
    state["v"] = 0.0
    w.sample_once(3.0)
    snap = reg.snapshot()
    states = {s["labels"]["rule"]: s["value"]
              for s in snap["metrics"]["nns_alert_state"]["samples"]}
    assert states["brk"] == 0.0
    assert "FIRING" not in render(snap)


def test_firing_alert_triggers_flightrec_once():
    from nnstreamer_tpu.obs.flightrec import FLIGHT

    FLIGHT.clear()
    state = {"v": 2.0}
    w = Watch(rules=[AlertRule(name="brk", kind="threshold",
                               metric="nns_edge_breaker_state",
                               op=">=", value="open")],
              registry=MetricsRegistry(),
              source=_src(lambda: _gauge_snap(
                  "nns_edge_breaker_state", state["v"],
                  {"link": "l", "peer": "p", "kind": "edge"})))
    for t in (1.0, 2.0, 3.0, 4.0):  # stays firing: ONE episode
        w.sample_once(t)
    assert FLIGHT.triggers.get("alert") == 1
    kinds = [e["kind"] for e in FLIGHT.events()]
    assert "alert" in kinds
    # resolve, re-fire: a NEW episode triggers again
    state["v"] = 0.0
    w.sample_once(5.0)
    assert "alert-resolved" in [e["kind"] for e in FLIGHT.events()]
    state["v"] = 2.0
    w.sample_once(6.0)
    assert FLIGHT.triggers.get("alert") == 2
    FLIGHT.clear()


def test_firing_alert_posts_bus_warning():
    from nnstreamer_tpu.runtime.events import MessageKind

    spec = TensorsSpec.from_shapes([SHAPE], np.float32)
    p = Pipeline(name="watch-bus")
    src = AppSrc(name="src", spec=spec, max_buffers=8)
    sink = AppSink(name="out", max_buffers=8)
    p.add(src, sink).link(src, sink)
    warnings = []
    p.bus.add_watch(lambda m: warnings.append(m)
                    if m.kind == MessageKind.WARNING else None)
    p.start()
    try:
        state = {"v": 2.0}
        w = Watch(rules=[AlertRule(name="brk", kind="threshold",
                                   metric="nns_edge_breaker_state",
                                   op=">=", value="open")],
                  registry=REGISTRY,
                  source=_src(lambda: _gauge_snap(
                      "nns_edge_breaker_state", state["v"],
                      {"link": "l", "peer": "p", "kind": "edge"})))
        w.sample_once(1.0)
        assert warnings and warnings[0].data["alert"] == "brk"
        assert warnings[0].source == "nns-watch"
    finally:
        src.end_of_stream()
        p.stop()


# -- kill switch --------------------------------------------------------------


def test_disabled_watch_is_strictly_inert(monkeypatch):
    from nnstreamer_tpu.obs import hooks

    monkeypatch.setattr(hooks, "DISABLED", True)
    reg = MetricsRegistry()
    w = Watch(rules=default_rules(), registry=reg,
              source=_src(lambda: _gauge_snap("nns_mfu", 1.0)))
    assert w.enabled is False
    assert w.start() is False
    assert w._thread is None
    assert w.sample_once() == []
    assert w.samples == 0
    # no export families were even created
    assert "nns_alert_state" not in reg.collect()
    assert len(w.store) == 0


# -- fleet mode ---------------------------------------------------------------


def test_fleet_mode_scrapes_endpoint_and_down_alert():
    from nnstreamer_tpu.obs.metrics import serve_metrics

    reg = MetricsRegistry()
    reg.gauge("nns_mfu", "t", labelnames=("source",)) \
        .labels(source="m").set(0.5)
    srv = reg.serve(port=0)
    try:
        # one live endpoint + one dead one
        dead = "127.0.0.1:1"
        w = Watch(rules=[AlertRule(name="never", kind="threshold",
                                   metric="nns_mfu", op=">",
                                   value=1e9)],
                  registry=MetricsRegistry(),
                  endpoints=[f"127.0.0.1:{srv.port}", dead])
        fired = []
        for i in range(Watch.DOWN_AFTER):
            fired += w.sample_once()
        assert [e["rule"] for e in fired] == ["endpoint-down"]
        assert dead in fired[0]["detail"]["endpoint"]
        # the live endpoint's series landed under ITS endpoint key
        eps = {k[0] for k in w.store._series}
        assert f"127.0.0.1:{srv.port}" in eps
    finally:
        srv.close()


def test_healthz_exposes_alert_summary():
    reg = MetricsRegistry()
    state = {"v": 2.0}
    w = Watch(rules=[AlertRule(name="brk", kind="threshold",
                               metric="nns_edge_breaker_state",
                               op=">=", value="open",
                               severity="critical")],
              registry=reg,
              source=_src(lambda: _gauge_snap(
                  "nns_edge_breaker_state", state["v"],
                  {"link": "l", "peer": "p", "kind": "edge"})))
    w.sample_once(1.0)
    srv = reg.serve(port=0)
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/healthz", timeout=5) as r:
            doc = json.loads(r.read().decode())
        assert doc["alerts"]["firing"] == 1
        assert doc["alerts"]["by_severity"] == {"critical": 1}
        assert doc["alerts"]["rules"] == ["brk"]
    finally:
        srv.close()


# -- CLI ----------------------------------------------------------------------


def test_nns_watch_cli_once(tmp_path):
    from nnstreamer_tpu.obs.watch import main as watch_main

    rules = tmp_path / "rules.json"
    rules.write_text(json.dumps({"rule": [
        {"name": "never", "kind": "threshold", "metric": "nns_mfu",
         "op": ">", "value": 1e9}]}))
    buf = io.StringIO()
    rc = watch_main(["--once", "1", "--interval", "0.01",
                     "--rules", str(rules)], out=buf)
    assert rc == 0
    assert "never" in buf.getvalue() and "ok" in buf.getvalue()
    # malformed rules exit 2
    bad = tmp_path / "bad.json"
    bad.write_text("{not json")
    assert watch_main(["--once", "1", "--rules", str(bad)],
                      out=io.StringIO()) == 2


# -- satellite: registry scrape vs concurrent Pipeline.stop() ----------------


def test_registry_scrape_races_pipeline_stop():
    """snapshot() hammered from another thread while pipelines start,
    stream and stop must never raise and never lose the scrape (the
    weakref unregister can land mid-pull)."""
    spec = TensorsSpec.from_shapes([SHAPE], np.float32)
    errors = []
    stop_evt = threading.Event()
    snaps = [0]

    def scraper():
        while not stop_evt.is_set():
            try:
                snap = REGISTRY.snapshot()
                assert isinstance(snap["pipelines"], list)
                snaps[0] += 1
            except Exception as e:  # noqa: BLE001 - the assertion
                errors.append(e)
                return

    threads = [threading.Thread(target=scraper) for _ in range(3)]
    for t in threads:
        t.start()
    try:
        for round_i in range(6):
            pipes = []
            for j in range(3):
                p = Pipeline(name=f"race-{round_i}-{j}")
                src = AppSrc(name="src", spec=spec, max_buffers=20)
                q = Queue(name="q", max_size_buffers=20)
                flt = TensorFilter(name="net", framework="jax-xla",
                                   model="_t_watch")
                sink = AppSink(name="out", max_buffers=20)
                p.add(src, q, flt, sink).link(src, q, flt, sink)
                p.start()
                pipes.append((p, src, sink))
            for p, src, sink in pipes:
                from nnstreamer_tpu.core import Buffer

                for n in range(4):
                    src.push_buffer(Buffer.of(
                        np.zeros(SHAPE, np.float32), pts=n))
                src.end_of_stream()
            for p, _src, _sink in pipes:
                p.wait_eos(timeout=10, raise_on_error=False)
                p.stop()
    finally:
        stop_evt.set()
        for t in threads:
            t.join(timeout=10)
    assert not errors, errors
    assert snaps[0] > 0


def test_watch_runs_against_live_registry():
    """End-to-end: a watchdog thread sampling the real global registry
    while a pipeline streams — no crashes, series appear, no alerts
    from the default pack on a healthy pipeline."""
    from nnstreamer_tpu.core import Buffer

    spec = TensorsSpec.from_shapes([SHAPE], np.float32)
    p = Pipeline(name="watch-live")
    src = AppSrc(name="src", spec=spec, max_buffers=70)
    q = Queue(name="q", max_size_buffers=70)
    flt = TensorFilter(name="net", framework="jax-xla", model="_t_watch")
    sink = AppSink(name="out", max_buffers=70)
    p.add(src, q, flt, sink).link(src, q, flt, sink)
    w = Watch(rules=default_rules(), interval_s=0.02)
    assert w.start() is True
    p.start()
    try:
        for n in range(64):
            src.push_buffer(Buffer.of(np.zeros(SHAPE, np.float32),
                                      pts=n))
            time.sleep(0.002)
        src.end_of_stream()
        assert p.wait_eos(timeout=30)
    finally:
        p.stop()
        time.sleep(0.1)
        w.stop()
    assert w.samples > 3
    assert len(w.store) > 0
    assert list(w.alert_log) == [], list(w.alert_log)


# -- ISSUE-19: the [store] rules-file section ---------------------------------


def test_parse_store_section_overrides(tmp_path):
    from nnstreamer_tpu.obs.watch import (lint_store, load_store,
                                          parse_store)

    path = tmp_path / "rules.json"
    path.write_text(json.dumps({
        "rule": [{"name": "r", "kind": "threshold",
                  "metric": "nns_mfu"}],
        "store": {"ring_points": 256, "max_series": 1024}}))
    assert load_store(str(path)) == {"ring_points": 256,
                                     "max_series": 1024}
    # absent section: the Watch defaults stand
    assert parse_store({"rule": []}) == {}
    assert lint_store({}) == []
    with pytest.raises(RuleError, match="unknown key"):
        parse_store({"store": {"ring_pints": 256}})
    with pytest.raises(RuleError, match="positive integer"):
        parse_store({"store": {"ring_points": 0}})
    with pytest.raises(RuleError, match="positive integer"):
        parse_store({"store": {"max_series": True}})
    with pytest.raises(RuleError, match="table"):
        parse_store({"store": [256]})


def test_lint_store_flags_unworkable_sizing():
    from nnstreamer_tpu.obs.watch import lint_store

    probs = lint_store({"ring_points": watch_mod.QUANT_WINDOW_TICKS - 1})
    assert any("quantile window" in p for p in probs)
    probs = lint_store({"max_series": 8})
    assert any("max_series" in p for p in probs)
    assert lint_store({"ring_points": 512, "max_series": 4096}) == []


# -- ISSUE-19: rate-from-zero must not resurrect for REBORN series ------------


def test_store_reborn_series_rebases_not_rate_from_zero():
    """A series evicted (source gone for EVICT_TICKS) whose key later
    re-appears carries accumulated HISTORY, not one window's
    increments: it must re-base silently — the rate-from-zero shortcut
    (kept for genuinely new series, pinned above) would manufacture a
    giant phantom spike out of the old cumulative value."""
    store = SeriesStore()
    store.EVICT_TICKS = 2
    store.ingest("local",
                 _counter_snap("nns_edge_timeouts_total", 1000.0), 1.0)
    store.ingest("local",
                 _counter_snap("nns_edge_timeouts_total", 1010.0), 2.0)
    (_k, s), = store.match("nns_edge_timeouts_total", {})
    assert [v for _t, v in s.rings["rate"]] == [10.0]
    # the source disappears long enough to be evicted outright
    for ts in (3.0, 4.0, 5.0, 6.0):
        store.ingest("local", {"metrics": {}}, ts)
    assert len(store) == 0
    # ... then the same key returns with its big cumulative value
    store.ingest("local",
                 _counter_snap("nns_edge_timeouts_total", 1020.0), 7.0)
    (_k, s2), = store.match("nns_edge_timeouts_total", {})
    assert list(s2.rings["rate"]) == []  # re-based, no 1020/s phantom
    # and from there, honest deltas resume
    store.ingest("local",
                 _counter_snap("nns_edge_timeouts_total", 1025.0), 8.0)
    assert [v for _t, v in s2.rings["rate"]] == [5.0]


def test_store_eviction_memory_is_bounded():
    store = SeriesStore()
    store.EVICT_TICKS = 1
    store.EVICT_MEMORY = 4
    for i in range(12):
        snap = _counter_snap("nns_edge_timeouts_total", float(i),
                             {"link": str(i)})
        store.ingest("local", snap, float(i * 10))
        store.ingest("local", {"metrics": {}}, float(i * 10 + 1))
        store.ingest("local", {"metrics": {}}, float(i * 10 + 2))
        store.ingest("local", {"metrics": {}}, float(i * 10 + 3))
    assert len(store._evicted) <= 4


# -- ISSUE-19: the per= denominator label join --------------------------------


def test_ratio_denominator_joins_across_label_schemas():
    """shed{pool,priority,reason} over submitted{pool,priority}: the
    denominator lacks the numerator's `reason` label, so the exact-
    label lookup can never bind — the join must fall back to the
    denominator agreeing on the SHARED labels (this is the default
    pack's own shed-burn shape)."""
    state = {"shed": 0.0, "sub": 0.0}

    def snap():
        return {"pools": [], "metrics": {
            "nns_admission_shed_total": {
                "name": "nns_admission_shed_total", "kind": "counter",
                "help": "", "samples": [
                    {"labels": {"pool": "pl", "priority": "normal",
                                "reason": "slo"},
                     "value": state["shed"]}]},
            "nns_admission_submitted_total": {
                "name": "nns_admission_submitted_total",
                "kind": "counter", "help": "", "samples": [
                    {"labels": {"pool": "pl", "priority": "normal"},
                     "value": state["sub"]}]},
        }}

    w = Watch(rules=[AlertRule(
        name="shed-ratio", kind="threshold",
        metric="nns_admission_shed_total",
        per="nns_admission_submitted_total", op=">=", value=0.4,
        signal="rate")],
        interval_s=1.0, registry=MetricsRegistry(), source=_src(snap))
    fired = []
    for t in range(1, 6):
        state["shed"] = 10.0 * t
        state["sub"] = 20.0 * t
        fired += w.sample_once(float(t))
    assert [ev["rule"] for ev in fired] == ["shed-ratio"]
    assert fired[0]["detail"]["value"] == pytest.approx(0.5)


def test_burn_counter_ratio_binds_across_label_schemas():
    """The same join through the slo_burn path: a shed-vs-submitted
    error budget must compute even though the two families' label sets
    differ (regression for the denominator lookup that silently
    returned None)."""
    state = {"shed": 0.0, "sub": 0.0}

    def snap():
        return {"pools": [], "metrics": {
            "nns_admission_shed_total": {
                "name": "nns_admission_shed_total", "kind": "counter",
                "help": "", "samples": [
                    {"labels": {"pool": "pl", "priority": "normal",
                                "reason": "queue-full"},
                     "value": state["shed"]}]},
            "nns_admission_submitted_total": {
                "name": "nns_admission_submitted_total",
                "kind": "counter", "help": "", "samples": [
                    {"labels": {"pool": "pl", "priority": "normal"},
                     "value": state["sub"]}]},
        }}

    w = Watch(rules=[AlertRule(
        name="shed-burn", kind="slo_burn",
        metric="nns_admission_shed_total",
        per="nns_admission_submitted_total",
        budget=0.05, burn=2.0, fast_s=2.0, slow_s=4.0)],
        interval_s=1.0, registry=MetricsRegistry(), source=_src(snap))
    fired = []
    for t in range(1, 8):
        state["shed"] = 50.0 * t   # 50% of submissions shed: way past
        state["sub"] = 100.0 * t   # a 5% budget at 2x burn
        fired += w.sample_once(float(t))
    assert [ev["rule"] for ev in fired] == ["shed-burn"]
    frac = fired[0]["detail"]["err_frac"]
    assert frac["fast"] == pytest.approx(0.5)
