"""`obs/forecast.py` + the `forecast` watch-rule kind (ISSUE-19
surface).

The robust trend fit (Theil–Sen slope, median-projected level, MAD
band), crossing prediction (ETA within one tick on a clean ramp, flat
series never firing, already-over staying reactive territory, the
noise gate suppressing insignificant slopes, re-convergence after a
step), capacity headroom (MFU path, occupancy fallback, scale-out
clamp), the FORECASTS store, the watch integration (rule grammar,
horizon refusal, histogram skip, `nns_forecast_*` gauges, the firing
transition), the per-pool capacity tick + `/healthz` summary, the
snapshot-v9 `forecasts` table, and the nns-top FORECAST section."""

import json

import pytest

from nnstreamer_tpu.obs import forecast as fc
from nnstreamer_tpu.obs.forecast import (FORECASTS, Forecasts, TrendFit,
                                         capacity_headroom,
                                         fit_trend, forecast_crossing)
from nnstreamer_tpu.obs.metrics import (MetricsRegistry, REGISTRY,
                                        capacity_health)
from nnstreamer_tpu.obs.watch import (AlertRule, RuleError, Watch,
                                      parse_rules)


@pytest.fixture(autouse=True)
def _clean_forecasts():
    FORECASTS.reset()
    yield
    FORECASTS.reset()


def _gauge_snap(name, value, labels=None, pools=None):
    return {"pools": pools or [],
            "metrics": {name: {"name": name, "kind": "gauge",
                               "help": "",
                               "samples": [{"labels": labels or {},
                                            "value": value}]}}}


def _counter_snap(name, value, labels=None, pools=None):
    snap = _gauge_snap(name, value, labels, pools)
    snap["metrics"][name]["kind"] = "counter"
    return snap


def _src(snap_fn):
    return lambda: [{"endpoint": "local", "snap": snap_fn(),
                     "error": None}]


# -- fit_trend ----------------------------------------------------------------


def test_fit_trend_recovers_clean_ramp():
    pts = [(float(t), 3.0 * t + 7.0) for t in range(10)]
    fit = fit_trend(pts)
    assert fit.slope == pytest.approx(3.0)
    assert fit.level == pytest.approx(3.0 * 9 + 7.0)
    assert fit.sigma == pytest.approx(0.0)
    assert fit.n == 10 and fit.t_last == 9.0
    assert fit.at(5.0) == pytest.approx(fit.level + 15.0)


def test_fit_trend_needs_history():
    assert fit_trend([]) is None
    assert fit_trend([(float(t), 1.0)
                      for t in range(fc.MIN_FIT_POINTS - 1)]) is None
    # all points on one timestamp: no pairwise slope exists
    assert fit_trend([(1.0, float(v)) for v in range(8)]) is None


def test_theil_sen_shrugs_off_outliers():
    """A third of the points being garbage moves neither the slope nor
    the level materially — the property the whole predictive layer
    leans on."""
    pts = [(float(t), 2.0 * t) for t in range(12)]
    pts[3] = (3.0, 500.0)
    pts[7] = (7.0, -300.0)
    pts[10] = (10.0, 999.0)
    fit = fit_trend(pts)
    assert fit.slope == pytest.approx(2.0, rel=0.15)
    assert abs(fit.level - 22.0) < 4.0


def test_fit_trend_caps_window():
    pts = [(float(t), float(t)) for t in range(200)]
    assert fit_trend(pts).n == fc.MAX_FIT_POINTS
    assert fit_trend(pts, max_points=8).n == 8


# -- forecast_crossing --------------------------------------------------------


def test_crossing_eta_within_one_tick():
    """Ramp at 1 unit/s sampled at 1 Hz, threshold 10 units ahead: the
    ETA lands within one sampling tick of the true crossing."""
    pts = [(float(t), float(t)) for t in range(8)]
    fit = fit_trend(pts)
    predicted, eta, firing = forecast_crossing(fit, 17.0, ">=", 20.0)
    assert firing
    assert eta == pytest.approx(10.0, abs=1.0)
    assert predicted == pytest.approx(27.0)


def test_already_over_is_reactive_territory():
    fit = TrendFit(slope=1.0, level=50.0, sigma=0.0, n=8, t_last=0.0)
    predicted, eta, firing = forecast_crossing(fit, 40.0, ">=", 10.0)
    assert (eta, firing) == (0.0, False)
    assert predicted == pytest.approx(60.0)


def test_flat_series_never_fires():
    fit = TrendFit(slope=0.0, level=5.0, sigma=0.3, n=16, t_last=0.0)
    predicted, eta, firing = forecast_crossing(fit, 10.0, ">=", 30.0)
    assert (eta, firing) == (None, False)
    assert predicted == pytest.approx(5.0)


def test_trending_away_never_fires():
    fit = TrendFit(slope=-2.0, level=5.0, sigma=0.0, n=8, t_last=0.0)
    _p, eta, firing = forecast_crossing(fit, 10.0, ">=", 30.0)
    assert (eta, firing) == (None, False)
    # the mirror direction: rising series against a "<" rule
    fit = TrendFit(slope=2.0, level=5.0, sigma=0.0, n=8, t_last=0.0)
    _p, eta, firing = forecast_crossing(fit, 1.0, "<=", 30.0)
    assert (eta, firing) == (None, False)


def test_mad_gate_suppresses_insignificant_trend():
    """A slope buried in the residual noise band must not fire even
    when its extrapolation crosses inside the horizon — this is the
    zero-false-positive property."""
    noise = [0.0, 5.0, -5.0, 3.0, -4.0, 4.0, -3.0, 2.0] * 2
    pts = [(float(t), 0.02 * t + noise[t]) for t in range(16)]
    fit = fit_trend(pts)
    sig = abs(fit.slope) * 30.0
    assert sig <= fc.SIGNIFICANCE_SIGMAS * fit.sigma
    _p, _eta, firing = forecast_crossing(fit, fit.level + 0.1, ">=",
                                         30.0)
    assert not firing
    # the same geometry with the noise stripped IS significant
    clean = fit_trend([(float(t), 0.02 * t) for t in range(16)])
    _p, _eta, firing = forecast_crossing(clean, clean.level + 0.1,
                                         ">=", 30.0)
    assert firing


def test_step_reconverges_to_quiet():
    """A level step looks like a ramp only while the window straddles
    it; once the fit window is all post-step, slope is 0 again and the
    forecast goes quiet instead of chasing the step forever."""
    series = [(float(t), 0.0 if t < 10 else 100.0) for t in range(30)]
    fit = fit_trend(series[-16:])
    assert fit.slope == pytest.approx(0.0)
    _p, _eta, firing = forecast_crossing(fit, 500.0, ">=", 30.0)
    assert not firing


# -- capacity_headroom --------------------------------------------------------


def test_capacity_headroom_mfu_path():
    cap = capacity_headroom(100.0, 150.0, mfu=0.2, mfu_ceiling=0.4)
    assert cap["sustainable_fps"] == pytest.approx(200.0)
    assert cap["headroom"] == pytest.approx(0.25)


def test_capacity_headroom_occupancy_fallback_and_clamps():
    cap = capacity_headroom(100.0, 100.0, occupancy=0.5)
    assert cap["sustainable_fps"] == pytest.approx(200.0)
    assert cap["headroom"] == pytest.approx(0.5)
    # an idling pool does not promise 1000x its current rate
    cap = capacity_headroom(10.0, 10.0, mfu=1e-4, mfu_ceiling=0.5)
    assert cap["sustainable_fps"] == pytest.approx(
        10.0 * fc.MAX_SCALE_OUT)
    # predicted overload clamps at -1, not minus-infinity
    cap = capacity_headroom(100.0, 1e6, occupancy=1.0)
    assert cap["headroom"] == -1.0


def test_capacity_headroom_refuses_blind_claims():
    assert capacity_headroom(0.0, 10.0, occupancy=0.5) is None
    assert capacity_headroom(100.0, 10.0) is None
    assert capacity_headroom(100.0, 10.0, mfu=0.0,
                             mfu_ceiling=0.4) is None


# -- the FORECASTS store ------------------------------------------------------


def test_forecasts_store_sorted_snapshot_and_reset():
    st = Forecasts()
    st.update("zz", {"rule": "zz", "firing": False})
    st.update("aa", {"rule": "aa", "firing": True})
    st.update_capacity("pool-b", {"pool": "pool-b", "headroom": 0.5})
    snap = st.snapshot()
    assert [r["rule"] for r in snap["rules"]] == ["aa", "zz"]
    assert snap["capacity"][0]["pool"] == "pool-b"
    # snapshot hands out copies, not live rows
    snap["rules"][0]["firing"] = "mutated"
    assert st.snapshot()["rules"][0]["firing"] is True
    st.reset()
    assert st.snapshot() == {"rules": [], "capacity": []}


# -- rule grammar -------------------------------------------------------------


def test_forecast_rule_grammar_parses_horizon():
    rules = parse_rules({"rule": [
        {"name": "surge", "kind": "forecast",
         "metric": "nns_pool_frames_total", "op": ">=",
         "value": 100.0, "horizon": "30s", "for": "2s"}]})
    assert rules[0].horizon_s == 30.0 and rules[0].for_s == 2.0


def test_forecast_rule_rejects_unordered_op():
    with pytest.raises(RuleError, match="ordered op"):
        AlertRule(name="r", kind="forecast", metric="nns_queue_depth",
                  op="==", value=1.0, horizon_s=30.0)


def test_watch_refuses_horizonless_forecast():
    """Parse stays lenient (nns-lint reports NNS517 at review time);
    the LIVE watchdog refuses to run a forecast with nothing to
    predict across."""
    rule = AlertRule(name="r", kind="forecast",
                     metric="nns_queue_depth", op=">=", value=1.0)
    with pytest.raises(RuleError, match="horizon"):
        Watch(rules=[rule], registry=MetricsRegistry(),
              source=_src(lambda: {"metrics": {}}))


# -- the watch integration ----------------------------------------------------


def test_forecast_rule_fires_ahead_with_eta_and_gauges():
    """A gauge ramping 2 units/s against threshold 60 with a 15 s
    horizon: the rule must fire exactly when the crossing enters the
    horizon (level 30, 15 s early — the predictive lead), publish the
    predicted value + ETA through `nns_forecast_*`, and flip the
    FORECASTS row to firing."""
    state = {"t": 0.0}
    reg = MetricsRegistry()
    rule = AlertRule(name="qd-surge", kind="forecast",
                     metric="nns_queue_depth", op=">=", value=60.0,
                     horizon_s=15.0)
    w = Watch(rules=[rule], interval_s=1.0, registry=reg,
              source=_src(lambda: _gauge_snap(
                  "nns_queue_depth", 2.0 * state["t"],
                  {"element": "q", "pipeline": "p"})))
    fired = []
    for t in range(1, 21):
        state["t"] = float(t)
        fired += [(t, ev) for ev in w.sample_once(float(t))]
        if t == 10:
            # inside the ramp but outside the horizon: exporting, not
            # firing (eta = (60 - 20)/2 = 20 s > 15 s)
            row = FORECASTS.snapshot()["rules"][0]
            assert not row["firing"]
            assert row["eta_s"] == pytest.approx(20.0, abs=1.0)
    assert [t for t, _ev in fired] == [15]
    detail = fired[0][1]["detail"]
    assert detail["eta_s"] == pytest.approx(15.0, abs=1.0)
    assert detail["value"] == pytest.approx(60.0, abs=2.0)
    assert detail["horizon_s"] == 15.0
    snap = reg.snapshot()["metrics"]
    (v,) = snap["nns_forecast_value"]["samples"]
    assert v["labels"] == {"rule": "qd-surge"}
    (eta,) = snap["nns_forecast_eta_seconds"]["samples"]
    assert eta["value"] <= 15.0
    assert FORECASTS.snapshot()["rules"][0]["firing"]


def test_forecast_rule_skips_histogram_series():
    """A forecast bound to a histogram family exports nothing and
    never fires (windowed quantiles re-derive each tick — NNS517
    catches the rule at review time; the evaluator just declines)."""
    def snap():
        samples = []
        for le, c in zip(("0.001", "0.01", "+Inf"), (50, 100, 100)):
            samples.append({"labels": {"pool": "p", "le": le},
                            "value": c,
                            "name": "nns_admission_latency_seconds_bucket"})
        return {"metrics": {"nns_admission_latency_seconds": {
            "name": "nns_admission_latency_seconds",
            "kind": "histogram", "help": "", "samples": samples}}}

    rule = AlertRule(name="h", kind="forecast",
                     metric="nns_admission_latency_seconds", op=">=",
                     value=0.5, horizon_s=30.0)
    w = Watch(rules=[rule], interval_s=1.0, registry=MetricsRegistry(),
              source=_src(snap))
    for t in range(1, 12):
        assert w.sample_once(float(t)) == []
    assert FORECASTS.snapshot()["rules"] == []


def test_capacity_tick_joins_headroom_and_healthz():
    """The per-pool capacity join: a pool pushing a flat 100 frames/s
    at 50% window occupancy sustains ~200 fps — headroom 0.5 through
    the gauge, the FORECASTS capacity row, and `/healthz`'s summary."""
    state = {"t": 0.0}

    def snap():
        pools = [{"pool": "pl", "model": None,
                  "stats": {"avg_batch_occupancy": 4.0},
                  "batcher": {"max_batch": 8}}]
        return _counter_snap("nns_pool_frames_total",
                             100.0 * state["t"], {"pool": "pl"},
                             pools=pools)

    reg = MetricsRegistry()
    w = Watch(rules=[], interval_s=1.0, registry=reg,
              source=_src(snap))
    for t in range(1, 8):
        state["t"] = float(t)
        w.sample_once(float(t))
    (row,) = FORECASTS.snapshot()["capacity"]
    assert row["pool"] == "pl"
    assert row["arrival_fps"] == pytest.approx(100.0)
    assert row["predicted_fps"] == pytest.approx(100.0, rel=0.05)
    assert row["sustainable_fps"] == pytest.approx(200.0)
    assert row["headroom"] == pytest.approx(0.5, abs=0.05)
    # with no forecast rules the default headroom horizon stands
    assert row["horizon_s"] == fc.HEADROOM_HORIZON_S
    (g,) = reg.snapshot()["metrics"]["nns_capacity_headroom"]["samples"]
    assert g["labels"] == {"pool": "pl"}
    assert g["value"] == pytest.approx(0.5, abs=0.05)
    health = capacity_health()
    assert health["pools"] == 1 and health["at_risk"] == []
    assert health["min_headroom"] == pytest.approx(0.5, abs=0.05)


def test_capacity_health_flags_predicted_overload():
    FORECASTS.update_capacity("hot", {"pool": "hot", "headroom": -0.2})
    FORECASTS.update_capacity("cold", {"pool": "cold", "headroom": 0.9})
    health = capacity_health()
    assert health == {"pools": 2, "min_headroom": -0.2,
                      "at_risk": ["hot"]}
    FORECASTS.reset()
    assert capacity_health() == {"pools": 0, "min_headroom": None,
                                 "at_risk": []}


# -- snapshot v9 + nns-top ----------------------------------------------------


def test_snapshot_v9_carries_forecasts_table():
    FORECASTS.update("surge", {
        "rule": "surge", "metric": "nns_pool_frames_total",
        "signal": "rate", "series": {}, "endpoint": "local",
        "value": 120.0, "eta_s": 4.0, "threshold": 100.0, "op": ">=",
        "horizon_s": 30.0, "slope": 2.0, "sigma": 0.1, "firing": True})
    FORECASTS.update_capacity("pl", {
        "pool": "pl", "endpoint": "local", "arrival_fps": 90.0,
        "predicted_fps": 120.0, "horizon_s": 30.0,
        "sustainable_fps": 110.0, "headroom": -0.09})
    snap = REGISTRY.snapshot()
    assert snap["version"] == 10
    assert [r["rule"] for r in snap["forecasts"]["rules"]] == ["surge"]
    assert snap["forecasts"]["capacity"][0]["pool"] == "pl"
    json.dumps(snap["forecasts"])  # wire-safe


def test_top_forecast_section_renders():
    from nnstreamer_tpu.obs.top import render

    FORECASTS.update("surge", {
        "rule": "surge", "metric": "nns_pool_frames_total",
        "signal": "rate", "series": {}, "endpoint": "local",
        "value": 120.0, "eta_s": 4.0, "threshold": 100.0, "op": ">=",
        "horizon_s": 30.0, "slope": 2.0, "sigma": 0.1, "firing": True})
    FORECASTS.update_capacity("pl", {
        "pool": "pl", "endpoint": "local", "arrival_fps": 90.0,
        "predicted_fps": 120.0, "horizon_s": 30.0,
        "sustainable_fps": 110.0, "headroom": -0.09})
    out = render(REGISTRY.snapshot())
    assert "FORECAST" in out and "surge" in out and "FIRING" in out
    assert "capacity" in out and "-9%" in out
