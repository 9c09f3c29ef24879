"""Static analyzer (`nnstreamer_tpu.analyze`) tests.

Covers every diagnostic code at least once, the good-corpus
zero-false-positive guarantee, the caps-dry-run regressions
(rank-flexible dims, framerate 0/1), JSON golden output, and the
satellite runtime fixes (Bus.remove_watch, parser positions,
double-link rejection).
"""

import io
import json
import os
import threading

import numpy as np
import pytest

from nnstreamer_tpu.analyze import (
    CODES,
    Severity,
    analyze_description,
    analyze_pipeline,
    lint_package,
    lint_source,
)
from nnstreamer_tpu.analyze.cli import main as cli_main
from nnstreamer_tpu.core import Buffer, Caps, TensorsSpec
from nnstreamer_tpu.runtime import (
    Bus,
    Pipeline,
    TransformElement,
    make,
    parse_launch,
    register_element,
)
from nnstreamer_tpu.runtime.events import Message, MessageKind
from nnstreamer_tpu.runtime.parser import ParseError

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

GOOD_CAPS = ("other/tensors,format=static,num_tensors=1,"
             "dimensions=3:4:4:1,types=uint8,framerate=30/1")
GOOD = f"appsrc caps={GOOD_CAPS} ! tensor_converter ! tensor_sink"


def codes(diags):
    return {d.code for d in diags}


def above_info(diags):
    return [d for d in diags if d.severity != Severity.INFO]


# -- crafted elements used to reach the rarer codes --------------------------


class _AnyCapsElement(TransformElement):
    """Proposes wildcard caps: downstream fixation must fail (NNS202)."""

    FACTORY = "_t_anycaps"

    def propose_src_caps(self, pad):
        return Caps.any()

    def transform(self, buf):
        return buf


class _RejectElement(TransformElement):
    """caps_negotiated always rejects (NNS204)."""

    FACTORY = "_t_reject"

    def caps_negotiated(self, pad):
        raise ValueError("crafted rejection")

    def transform(self, buf):
        return buf


@pytest.fixture(scope="module", autouse=True)
def _test_factories():
    """The two crafted elements exist only while this module's tests
    run: registered when the module is merely imported (every xdist
    worker imports it while collecting), they stayed in the registry of
    the workers that never ran this fixture's clean-up, and
    ``tests/test_docs.py`` then found two factories without a page."""
    from nnstreamer_tpu.runtime import registry

    register_element("_t_anycaps")(_AnyCapsElement)
    register_element("_t_reject")(_RejectElement)
    yield
    with registry._lock:
        for name in ("_t_anycaps", "_t_reject"):
            registry._factories.pop(name, None)


# -- known-bad corpus: one pipeline per diagnostic code ----------------------

BAD_CORPUS = [
    ("appsrc ! bogus_thing ! tensor_sink", {"NNS100"}),
    (f"appsrc caps={GOOD_CAPS} ! tensor_sink name=s "
     f"appsrc name=b caps={GOOD_CAPS} ! s.sink", {"NNS103"}),
    # dangling src pad + zero sinks
    (f"appsrc caps={GOOD_CAPS} ! tensor_converter", {"NNS102", "NNS106"}),
    # island: unlinked sink pad, unreachable elements, unreached caps
    (f"appsrc caps={GOOD_CAPS} ! tensor_sink "
     "tensor_converter name=lost ! tensor_sink name=s2",
     {"NNS101", "NNS105", "NNS206"}),
    ("tensor_converter name=c1 ! tensor_converter name=c2 ! c1.",
     {"NNS104", "NNS107", "NNS106"}),
    ("tensor_converter ! tensor_sink", {"NNS107"}),
    (f"appsrc caps={GOOD_CAPS} ! other/tensors,format=static,"
     "num_tensors=1,dimensions=3:8:8:1,types=uint8 ! tensor_sink",
     {"NNS201"}),
    (f"appsrc caps={GOOD_CAPS} ! _t_anycaps ! fakesink", {"NNS202"}),
    ("appsrc ! tensor_sink", {"NNS203"}),
    (f"appsrc caps={GOOD_CAPS} ! _t_reject ! tensor_sink", {"NNS204"}),
    (f"appsrc caps={GOOD_CAPS} ! tensor_filter framework=jax-xla "
     "model=/nonexistent/model.pkl ! tensor_sink", {"NNS205"}),
    # fan-in framerate mismatch
    ("appsrc name=a caps=other/tensors,format=static,num_tensors=1,"
     "dimensions=4,types=uint8,framerate=30/1 ! tensor_mux name=m ! "
     "tensor_sink appsrc name=b caps=other/tensors,format=static,"
     "num_tensors=1,dimensions=4,types=uint8,framerate=15/1 ! m.sink_1",
     {"NNS108"}),
    # micro-batching without an upstream thread boundary
    (f"appsrc caps={GOOD_CAPS} ! tensor_filter framework=jax-xla "
     "model=/nonexistent/model.pkl batch=4 ! tensor_sink", {"NNS501"}),
    # micro-batching with per-invoke synchronous latency measurement
    (f"appsrc caps={GOOD_CAPS} ! queue ! tensor_filter framework=jax-xla "
     "model=/nonexistent/model.pkl batch=4 latency=1 ! tensor_sink",
     {"NNS502"}),
    # same jax-xla model opened twice without share-model: 2x HBM
    (f"appsrc caps={GOOD_CAPS} ! tensor_filter framework=jax-xla "
     "model=/nonexistent/model.pkl ! tensor_sink "
     f"appsrc name=b caps={GOOD_CAPS} ! tensor_filter name=f2 "
     "framework=jax-xla model=/nonexistent/model.pkl ! tensor_sink name=s2",
     {"NNS503"}),
    # share-model on a host-side stateful framework
    (f"appsrc caps={GOOD_CAPS} ! queue ! tensor_filter "
     "framework=custom-easy model=nope share-model=true batch=4 ! "
     "tensor_sink", {"NNS504"}),
    # latency=1 behind a queue: the reported number excludes queue
    # residency (batch=1, so neither NNS501 nor NNS502 applies)
    (f"appsrc caps={GOOD_CAPS} ! queue ! tensor_filter "
     "framework=jax-xla model=/nonexistent/model.pkl latency=1 ! "
     "tensor_sink", {"NNS505"}),
    # traced cross-host query link without NTP sync: remote spans are
    # placed by the in-band symmetric-delay estimate alone (caps= set
    # so the dry-run never dials the—nonexistent—server)
    (f"appsrc caps={GOOD_CAPS} ! tensor_query_client caps={GOOD_CAPS} "
     "dest-host=198.51.100.7 dest-port=5432 ! tensor_sink",
     {"NNS506"}),
    # cross-host query link with the in-flight bound disabled: a dead
    # server means unbounded growth and nothing ever times out
    (f"appsrc caps={GOOD_CAPS} ! tensor_query_client caps={GOOD_CAPS} "
     "dest-host=198.51.100.7 dest-port=5432 timeout=0 max-request=0 ! "
     "tensor_sink", {"NNS507"}),
    # mesh micro-batch whose bucket can't split over the data axis:
    # pad slots burn device time on every window (batch=6 over
    # data:4 — and the implied bucket list is just (6,))
    (f"appsrc caps={GOOD_CAPS} ! queue ! tensor_filter "
     "framework=jax-xla model=/nonexistent/model.pkl mesh=data:4 "
     "batch=6 ! tensor_sink", {"NNS509"}),
    # pool-level NNS509: a share-model pool whose cross-pipeline
    # window can't split over the mesh data axis pads on EVERY
    # coalesced window, for every sharer at once
    (f"appsrc caps={GOOD_CAPS} ! queue ! tensor_filter "
     "framework=jax-xla model=/nonexistent/model.pkl mesh=data:4 "
     "batch=6 share-model=true ! tensor_sink", {"NNS512"}),
    # lifecycle: canary grammar must be '<version>:1/N' (2/3 is not a
    # 1-in-N split)
    (f"appsrc caps={GOOD_CAPS} ! queue ! tensor_filter "
     "framework=jax-xla model=/nonexistent/model.pkl batch=4 "
     "share-model=true canary=next:2/3 ! tensor_sink", {"NNS513"}),
    # lifecycle: canary without share-model — one private stream has
    # nothing to split 1-in-N
    (f"appsrc caps={GOOD_CAPS} ! queue ! tensor_filter "
     "framework=jax-xla model=/nonexistent/model.pkl "
     "canary=1/4 ! tensor_sink", {"NNS513"}),
    # residency fence: a host-only converter stage between two
    # device-resident jax-xla filters forces a d2h+h2d pair per frame
    (f"appsrc caps={GOOD_CAPS} ! tensor_filter "
     "framework=jax-xla model=/nonexistent/model.pkl ! "
     "tensor_converter ! tensor_filter name=f2 framework=jax-xla "
     "model=/nonexistent/model.pkl ! tensor_sink", {"NNS514"}),
    # residency fence through transparent plumbing: the queue/tee hop
    # does not hide the host-only python3 filter from the walk
    (f"appsrc caps={GOOD_CAPS} ! tensor_transform mode=typecast "
     "option=float32 ! queue ! tensor_filter framework=python3 "
     "model=cb ! queue ! tensor_filter name=f2 framework=jax-xla "
     "model=/nonexistent/model.pkl ! tensor_sink", {"NNS514"}),
    # fusion blocked by an interposed queue between the transform and
    # an UNBATCHED filter (batch>1 would make the queue load-bearing
    # per NNS501 — see the negative tests)
    (f"appsrc caps={GOOD_CAPS} ! tensor_transform mode=typecast "
     "option=float32 ! queue ! tensor_filter framework=jax-xla "
     "model=/nonexistent/model.pkl ! tensor_decoder "
     "mode=bounding_boxes option1=mobilenet-ssd-postprocess "
     "option7=device ! tensor_sink", {"NNS515"}),
    # fusion blocked by share-model: the pooled instance serves many
    # pipelines, so this pipeline's stages can't bake into it
    (f"appsrc caps={GOOD_CAPS} ! tensor_transform mode=typecast "
     "option=float32 ! tensor_filter framework=jax-xla "
     "model=/nonexistent/model.pkl share-model=true ! tensor_decoder "
     "mode=bounding_boxes option1=mobilenet-ssd-postprocess "
     "option7=device ! tensor_sink", {"NNS515"}),
    # fusion left on the table: the decoder scheme HAS a device render
    # program but option7=device is not set, so the segment pays one
    # dispatch per stage instead of one total
    (f"appsrc caps={GOOD_CAPS} ! tensor_transform mode=typecast "
     "option=float32 ! tensor_filter framework=jax-xla "
     "model=/nonexistent/model.pkl ! tensor_decoder "
     "mode=bounding_boxes option1=mobilenet-ssd-postprocess ! "
     "tensor_sink", {"NNS515"}),
    # pipeline split: two declared stage subsets sharing chips —
    # the stages contend and per-stage attribution is unreliable
    (f"appsrc caps={GOOD_CAPS} ! queue ! tensor_filter name=f1 "
     "framework=jax-xla model=/nonexistent/model.pkl mesh=data:4 "
     "devices=0-3 batch=4 share-model=true ! tensor_sink "
     f"appsrc name=b caps={GOOD_CAPS} ! queue ! tensor_filter name=f2 "
     "framework=jax-xla model=/nonexistent/model.pkl mesh=data:4 "
     "devices=2-5 batch=4 share-model=true ! tensor_sink name=s2",
     {"NNS516"}),
    # cascade offload branch reaching the heavy stage only through a
    # host-only converter (+ the heavy stage missing share-model)
    (f"appsrc caps={GOOD_CAPS} ! tensor_if name=i operator=ge "
     "supplied-value=1 offload=then "
     "i.src_then ! tensor_converter ! tensor_filter name=hv "
     "framework=jax-xla model=/nonexistent/model.pkl mesh=data:4 "
     "devices=4-7 ! tensor_sink "
     "i.src_else ! tensor_sink name=s2", {"NNS516"}),
    # offload grammar: the branch name must be then/else
    (f"appsrc caps={GOOD_CAPS} ! tensor_if name=i offload=both ! "
     "tensor_sink i.src_else ! tensor_sink name=s2", {"NNS516"}),
    # tenancy: tenant= on a private filter — attribution splits the
    # SHARED pool's device-seconds, so nothing is ever billed here
    (f"appsrc caps={GOOD_CAPS} ! queue ! tensor_filter "
     "framework=jax-xla model=/nonexistent/model.pkl tenant=alpha ! "
     "tensor_sink", {"NNS517"}),
]


@pytest.mark.parametrize("desc,expected",
                         BAD_CORPUS, ids=[c for _, e in BAD_CORPUS
                                          for c in [sorted(e)[0]]])
def test_bad_corpus_reports_expected_codes(desc, expected):
    diags, _ = analyze_description(desc)
    assert expected <= codes(diags), \
        f"wanted {expected}, got {[str(d) for d in diags]}"


# -- source lint snippets: one per NNS3xx/NNS4xx code ------------------------

LINT_SNIPPETS = [
    ("""
import time

class P:
    def __init__(self, bus):
        bus.add_watch(self._watch)

    def _watch(self, msg):
        time.sleep(1)
""", {"NNS301"}),
    ("""
class E:
    def emit(self, msg):
        with self._lock:
            self.bus.post(msg)
""", {"NNS302"}),
    ("""
class E:
    def stop(self):
        with self._lock:
            self._thread.join(timeout=5)
""", {"NNS303"}),
    ("""
from nnstreamer_tpu.runtime.registry import register_element

@register_element("padless")
class Padless:
    def chain(self, pad, buf):
        pass
""", {"NNS401"}),
    ("""
import jax
import numpy as np

@jax.jit
def hot(x):
    return np.sum(x, axis=-1)
""", {"NNS402"}),
    ("""
def f():
    try:
        risky()
    except:
        pass
""", {"NNS403"}),
]


@pytest.mark.parametrize("src,expected", LINT_SNIPPETS,
                         ids=[sorted(e)[0] for _, e in LINT_SNIPPETS])
def test_lint_snippets(src, expected):
    assert expected <= codes(lint_source(src))


# -- NNS508 corpus: only fires while obs is globally disabled, so it
# -- runs under its own env-scoped test rather than in BAD_CORPUS ------------

OBS_DISABLED_CORPUS = [
    # stat-sample-interval-ms / latency=1 / latency-report silently
    # no-op under the kill switch (no blocking sample is ever taken)
    (f"appsrc caps={GOOD_CAPS} ! tensor_filter framework=jax-xla "
     "model=/nonexistent/model.pkl stat-sample-interval-ms=100 ! "
     "tensor_sink", {"NNS508"}),
    (f"appsrc caps={GOOD_CAPS} ! tensor_filter framework=jax-xla "
     "model=/nonexistent/model.pkl latency=1 latency-report=true ! "
     "tensor_sink", {"NNS508"}),
    # a traced query client cannot propagate contexts while the tracer
    # can never attach
    (f"appsrc caps={GOOD_CAPS} ! tensor_query_client caps={GOOD_CAPS} "
     "dest-host=198.51.100.7 dest-port=5432 ! tensor_sink",
     {"NNS508"}),
]


@pytest.mark.parametrize("desc,expected", OBS_DISABLED_CORPUS,
                         ids=["stat-interval", "latency", "trace"])
def test_nns508_fires_while_obs_disabled(desc, expected, monkeypatch):
    monkeypatch.setenv("NNS_TPU_OBS_DISABLE", "1")
    diags, _ = analyze_description(desc)
    assert expected <= codes(diags), [str(d) for d in diags]
    d = [x for x in diags if x.code == "NNS508"][0]
    assert d.severity == Severity.WARNING
    assert "NNS_TPU_OBS_DISABLE" in d.message


def test_nns508_negatives(monkeypatch):
    """No NNS508 with obs enabled (whatever the props), and none under
    the kill switch when no obs prop is set."""
    desc = (f"appsrc caps={GOOD_CAPS} ! tensor_filter framework=jax-xla "
            "model=/nonexistent/model.pkl stat-sample-interval-ms=100 ! "
            "tensor_sink")
    monkeypatch.delenv("NNS_TPU_OBS_DISABLE", raising=False)
    diags, _ = analyze_description(desc)
    assert "NNS508" not in codes(diags)
    monkeypatch.setenv("NNS_TPU_OBS_DISABLE", "1")
    plain = (f"appsrc caps={GOOD_CAPS} ! tensor_filter framework=jax-xla "
             "model=/nonexistent/model.pkl ! tensor_sink")
    diags, _ = analyze_description(plain)
    assert "NNS508" not in codes(diags)
    # trace=false on the query client silences the trace variant too
    qc = (f"appsrc caps={GOOD_CAPS} ! tensor_query_client "
          f"caps={GOOD_CAPS} dest-host=198.51.100.7 dest-port=5432 "
          "trace=false ! tensor_sink")
    diags, _ = analyze_description(qc)
    assert "NNS508" not in codes(diags)


# -- NNS510 corpus: watch-rules file validation (file-shaped, not
# -- pipeline-shaped, so it runs under its own tmp-file tests) ---------------

WATCH_RULES_CORPUS = [
    # a family the registry never exports: the rule can never fire
    ({"rule": [{"name": "r", "kind": "threshold",
                "metric": "nns_never_ever_total"}]}, {"NNS510"}),
    # malformed grammar: unknown rule kind
    ({"rule": [{"name": "r", "kind": "frobnicate",
                "metric": "nns_mfu"}]}, {"NNS510"}),
    # a signal the family's kind cannot produce (rate of a gauge)
    ({"rule": [{"name": "r", "kind": "threshold", "metric": "nns_mfu",
                "signal": "rate"}]}, {"NNS510"}),
    # burn on a gauge: neither histogram nor counter-ratio mode binds
    ({"rule": [{"name": "r", "kind": "slo_burn",
                "metric": "nns_queue_depth"}]}, {"NNS510"}),
    # [store] sizing that parses but cannot work: rings too short for
    # any quantile window — same file, still NNS510
    ({"rule": [{"name": "r", "kind": "threshold",
                "metric": "nns_mfu"}],
      "store": {"ring_points": 4}}, {"NNS510"}),
    # forecast without a horizon: nothing to predict across (the live
    # watchdog refuses the set; the lint catches it at review time)
    ({"rule": [{"name": "fc", "kind": "forecast",
                "metric": "nns_queue_depth", "op": ">=",
                "value": 100}]}, {"NNS517"}),
    # a horizon shorter than 3 sampler intervals: too little lookahead
    # to beat the reactive rules
    ({"rule": [{"name": "fc", "kind": "forecast",
                "metric": "nns_queue_depth", "op": ">=", "value": 100,
                "horizon": "1s"}]}, {"NNS517"}),
    # forecast bound to a histogram family: windowed quantiles
    # re-derive each tick — no single series to fit a trend through
    ({"rule": [{"name": "fc", "kind": "forecast",
                "metric": "nns_admission_latency_seconds", "op": ">=",
                "value": 0.5, "horizon": "30s"}]}, {"NNS517"}),
]


@pytest.mark.parametrize("doc,expected", WATCH_RULES_CORPUS,
                         ids=["unknown-family", "bad-grammar",
                              "bad-signal", "burn-gauge", "store-ring",
                              "fc-no-horizon", "fc-short-horizon",
                              "fc-histogram"])
def test_nns510_watch_rules_corpus(doc, expected, tmp_path):
    from nnstreamer_tpu.analyze.watchrules import check_watch_rules

    path = tmp_path / "rules.json"
    path.write_text(json.dumps(doc))
    diags = check_watch_rules(str(path))
    assert expected <= codes(diags), [str(d) for d in diags]
    assert all(d.severity == Severity.WARNING for d in diags)


def test_nns510_negatives(tmp_path, monkeypatch):
    """A well-formed rules file over exported families is clean; the
    env-var form resolves NNS_TPU_WATCH_RULES; unparseable JSON and an
    unreadable path each yield exactly one NNS510."""
    from nnstreamer_tpu.analyze.watchrules import check_watch_rules

    good = tmp_path / "good.json"
    good.write_text(json.dumps({"rule": [
        {"name": "brk", "kind": "threshold",
         "metric": "nns_edge_breaker_state", "op": ">=",
         "value": "open", "for": "10s", "severity": "critical"}]}))
    assert check_watch_rules(str(good)) == []
    # the default pack itself must validate clean through this path
    monkeypatch.setenv("NNS_TPU_WATCH_RULES", str(good))
    assert check_watch_rules(None) == []
    monkeypatch.delenv("NNS_TPU_WATCH_RULES")
    assert [d.code for d in check_watch_rules(None)] == ["NNS510"]
    bad = tmp_path / "bad.json"
    bad.write_text("{not json")
    diags = check_watch_rules(str(bad))
    assert [d.code for d in diags] == ["NNS510"]
    assert "malformed" in diags[0].message
    assert [d.code for d in check_watch_rules(
        str(tmp_path / "missing.json"))] == ["NNS510"]


def test_nns510_cli_flag(tmp_path):
    from nnstreamer_tpu.analyze.cli import main as cli_main

    path = tmp_path / "rules.json"
    path.write_text(json.dumps({"rule": [
        {"name": "r", "kind": "threshold",
         "metric": "nns_never_ever_total"}]}))
    buf = io.StringIO()
    rc = cli_main(["--watch-rules", str(path)], out=buf)
    assert rc == 0 and "NNS510" in buf.getvalue()
    assert cli_main(["--watch-rules", str(path), "--strict"],
                    out=io.StringIO()) == 1
    doc = io.StringIO()
    rc = cli_main(["--watch-rules", str(path), "--json"], out=doc)
    parsed = json.loads(doc.getvalue())
    assert parsed["summary"]["warning"] == 1


def test_nns517_negative_cases(tmp_path):
    """tenant= WITH share-model is the supported shape (no NNS517);
    and a forecast with an ordered op, a sane horizon and a counter/
    gauge family lints clean."""
    desc = (f"appsrc caps={GOOD_CAPS} ! queue ! tensor_filter "
            "framework=jax-xla model=/nonexistent/model.pkl "
            "batch=4 share-model=true tenant=alpha ! tensor_sink")
    diags, _ = analyze_description(desc)
    assert "NNS517" not in codes(diags)
    from nnstreamer_tpu.analyze.watchrules import check_watch_rules

    good = tmp_path / "rules.json"
    good.write_text(json.dumps({"rule": [
        {"name": "surge", "kind": "forecast",
         "metric": "nns_pool_frames_total", "op": ">=",
         "value": 1000, "horizon": "30s", "for": "2s"}]}))
    assert check_watch_rules(str(good)) == []
    # the horizon check scales with the sampler interval it is told
    assert [d.code for d in check_watch_rules(
        str(good), interval_s=20.0)] == ["NNS517"]


# -- NNS518 corpus: host-profiler environment (env-shaped — the lint
# -- reads the same vars the runtime hook does) -------------------------------

PROF_ENV_CORPUS = [
    # profiler armed under the obs kill switch: strictly inert — a
    # silent no-op, the NNS508 family
    ({"NNS_TPU_PROF": "50", "NNS_TPU_OBS_DISABLE": "1"}, {"NNS518"}),
    ({"NNS_TPU_PROF_DEEP_DIR": "/tmp", "NNS_TPU_OBS_DISABLE": "1"},
     {"NNS518"}),
    # an unparsable rate: the profiler will not start
    ({"NNS_TPU_PROF": "fast"}, {"NNS518"}),
    # a rate past the low-overhead envelope
    ({"NNS_TPU_PROF": "1000"}, {"NNS518"}),
]


@pytest.mark.parametrize("env,expected", PROF_ENV_CORPUS,
                         ids=["obs-disabled", "deep-obs-disabled",
                              "bad-hz", "high-hz"])
def test_nns518_prof_env_corpus(env, expected, monkeypatch):
    from nnstreamer_tpu.analyze.watchrules import prof_env_problems

    for var in ("NNS_TPU_PROF", "NNS_TPU_PROF_DEEP_DIR",
                "NNS_TPU_OBS_DISABLE"):
        monkeypatch.delenv(var, raising=False)
    for k, v in env.items():
        monkeypatch.setenv(k, v)
    diags = prof_env_problems()
    assert expected <= codes(diags), [str(d) for d in diags]
    assert all(d.severity == Severity.WARNING for d in diags)


def test_nns518_deep_vs_for_window(tmp_path, monkeypatch):
    """A deep-profile episode longer than a rule's for= window records
    recovery, not the incident — flagged per rule; shorter episodes
    and an unarmed deep profiler stay quiet."""
    from nnstreamer_tpu.analyze.watchrules import check_watch_rules

    rules = tmp_path / "rules.json"
    rules.write_text(json.dumps({"rule": [
        {"name": "qfull", "kind": "threshold",
         "metric": "nns_pool_pending", "op": ">=", "value": 8,
         "for": "1s"}]}))
    monkeypatch.setenv("NNS_TPU_PROF_DEEP_DIR", str(tmp_path))
    monkeypatch.setenv("NNS_TPU_PROF_DEEP_SECONDS", "5")
    diags = check_watch_rules(str(rules))
    assert codes(diags) == {"NNS518"}, [str(d) for d in diags]
    assert "outlasts" in diags[0].message and diags[0].pad == "qfull"
    monkeypatch.setenv("NNS_TPU_PROF_DEEP_SECONDS", "0.5")
    assert check_watch_rules(str(rules)) == []
    monkeypatch.delenv("NNS_TPU_PROF_DEEP_SECONDS")
    # unset seconds falls back to the 2.0 s default (> 1 s window)
    assert codes(check_watch_rules(str(rules))) == {"NNS518"}
    monkeypatch.delenv("NNS_TPU_PROF_DEEP_DIR")
    assert check_watch_rules(str(rules)) == []


def test_nns518_negatives_and_cli_target(monkeypatch):
    """A sane profiler env is clean; with no profiler env at all the
    prof-env target does not even appear (default output stays
    byte-stable); with one set, the CLI gathers it."""
    from nnstreamer_tpu.analyze.cli import main as cli_main
    from nnstreamer_tpu.analyze.watchrules import prof_env_problems

    for var in ("NNS_TPU_PROF", "NNS_TPU_PROF_DEEP_DIR",
                "NNS_TPU_OBS_DISABLE"):
        monkeypatch.delenv(var, raising=False)
    assert prof_env_problems() == []
    monkeypatch.setenv("NNS_TPU_PROF", "47")
    assert prof_env_problems() == []
    buf = io.StringIO()
    cli_main([f"appsrc caps={GOOD_CAPS} ! tensor_sink"], out=buf)
    assert "prof-env" in buf.getvalue()
    monkeypatch.delenv("NNS_TPU_PROF")
    buf = io.StringIO()
    cli_main([f"appsrc caps={GOOD_CAPS} ! tensor_sink"], out=buf)
    assert "prof-env" not in buf.getvalue()
    monkeypatch.setenv("NNS_TPU_PROF", "999")
    assert cli_main([f"appsrc caps={GOOD_CAPS} ! tensor_sink",
                     "--strict"], out=io.StringIO()) == 1


# -- NNS511 corpus: controller-playbook file validation (file-shaped,
# -- like the NNS510 corpus above) --------------------------------------------

CTL_PLAYBOOK_CORPUS = [
    # an actuator nothing exports: the playbook can never act
    ({"playbook": [{"name": "p", "rule": "slo-burn", "kind": "pool",
                    "actuator": "warp-drive", "value": 1}]},
     {"NNS511"}),
    # malformed grammar: unknown target kind
    ({"playbook": [{"name": "p", "rule": "slo-burn",
                    "kind": "frobnicate", "actuator": "ramp-start",
                    "value": 1}]}, {"NNS511"}),
    # malformed grammar: a set/step playbook with no explicit value
    # (would silently actuate the 0.0 default — e.g. PAUSE coalescing)
    ({"playbook": [{"name": "p", "rule": "slo-burn", "kind": "pool",
                    "actuator": "coalescing"}]}, {"NNS511"}),
    # a rule the active rule set never evaluates
    ({"playbook": [{"name": "p", "rule": "no-such-rule",
                    "kind": "pool", "actuator": "ramp-start",
                    "value": 0.5}]}, {"NNS511"}),
    # a double back-out: action=revert plus on_resolve=revert
    ({"playbook": [{"name": "p", "rule": "slo-burn", "kind": "pool",
                    "actuator": "max-batch", "action": "revert",
                    "on_resolve": "revert"}]}, {"NNS511"}),
]


@pytest.mark.parametrize("doc,expected", CTL_PLAYBOOK_CORPUS,
                         ids=["unknown-actuator", "bad-grammar",
                              "missing-value", "unknown-rule",
                              "double-revert"])
def test_nns511_playbook_corpus(doc, expected, tmp_path):
    from nnstreamer_tpu.analyze.ctlplaybooks import check_playbooks

    path = tmp_path / "playbooks.json"
    path.write_text(json.dumps(doc))
    diags = check_playbooks(str(path))
    assert expected <= codes(diags), [str(d) for d in diags]
    assert all(d.severity == Severity.WARNING for d in diags)


def test_nns511_negatives(tmp_path, monkeypatch):
    """The shipped default pack round-trips clean; the env-var form
    resolves NNS_TPU_CTL_PLAYBOOKS; unparseable JSON and an unreadable
    path each yield exactly one NNS511."""
    import dataclasses

    from nnstreamer_tpu.analyze.ctlplaybooks import check_playbooks
    from nnstreamer_tpu.obs.control import default_playbooks

    good = tmp_path / "good.json"
    good.write_text(json.dumps({"playbook": [
        {k: v for k, v in dataclasses.asdict(pb).items() if v != ""}
        for pb in default_playbooks()]}))
    assert check_playbooks(str(good)) == []
    monkeypatch.setenv("NNS_TPU_CTL_PLAYBOOKS", str(good))
    assert check_playbooks(None) == []
    monkeypatch.delenv("NNS_TPU_CTL_PLAYBOOKS")
    assert [d.code for d in check_playbooks(None)] == ["NNS511"]
    bad = tmp_path / "bad.json"
    bad.write_text("{not json")
    diags = check_playbooks(str(bad))
    assert [d.code for d in diags] == ["NNS511"]
    assert "malformed" in diags[0].message
    assert [d.code for d in check_playbooks(
        str(tmp_path / "missing.json"))] == ["NNS511"]


def test_nns511_target_exists_check(tmp_path):
    """A concrete pool target is checked against the SAME invocation's
    analyzed pipelines: matching share-model pool → clean, no match →
    NNS511; with no pipelines analyzed the check stands aside."""
    from nnstreamer_tpu.analyze.cli import main as cli_main

    path = tmp_path / "pb.json"
    path.write_text(json.dumps({"playbook": [
        {"name": "p", "rule": "slo-burn", "kind": "pool",
         "actuator": "ramp-start", "target": "jax-xla:m1",
         "value": 0.5}]}))
    desc = ("appsrc name=s ! tensor_filter framework=jax-xla "
            "model=m1 share-model=true ! appsink")
    buf = io.StringIO()
    rc = cli_main(["--ctl-playbooks", str(path), desc], out=buf)
    assert "NNS511" not in buf.getvalue(), buf.getvalue()
    path2 = tmp_path / "pb2.json"
    path2.write_text(json.dumps({"playbook": [
        {"name": "p", "rule": "slo-burn", "kind": "pool",
         "actuator": "ramp-start", "target": "jax-xla:other",
         "value": 0.5}]}))
    buf = io.StringIO()
    cli_main(["--ctl-playbooks", str(path2), desc], out=buf)
    assert "NNS511" in buf.getvalue()
    assert "matches no share-model pool" in buf.getvalue()
    # no pipelines in the run: unknowable, not wrong
    buf = io.StringIO()
    cli_main(["--ctl-playbooks", str(path2)], out=buf)
    assert "NNS511" not in buf.getvalue()


def test_nns511_cli_flag(tmp_path):
    from nnstreamer_tpu.analyze.cli import main as cli_main

    path = tmp_path / "pb.json"
    path.write_text(json.dumps({"playbook": [
        {"name": "p", "rule": "slo-burn", "kind": "pool",
         "actuator": "warp-drive", "value": 1}]}))
    buf = io.StringIO()
    rc = cli_main(["--ctl-playbooks", str(path)], out=buf)
    assert rc == 0 and "NNS511" in buf.getvalue()
    assert cli_main(["--ctl-playbooks", str(path), "--strict"],
                    out=io.StringIO()) == 1
    doc = io.StringIO()
    cli_main(["--ctl-playbooks", str(path), "--json"], out=doc)
    parsed = json.loads(doc.getvalue())
    assert parsed["summary"]["warning"] == 1


def test_nns511_binds_rules_from_same_invocation(tmp_path):
    """--watch-rules FILE in the same run supplies the rule-name set
    NNS511 binds playbooks against (a custom rule pack must not warn)."""
    from nnstreamer_tpu.analyze.cli import main as cli_main

    rules = tmp_path / "rules.json"
    rules.write_text(json.dumps({"rule": [
        {"name": "my-own-rule", "kind": "threshold",
         "metric": "nns_pool_pending", "op": ">=", "value": 8}]}))
    pb = tmp_path / "pb.json"
    pb.write_text(json.dumps({"playbook": [
        {"name": "p", "rule": "my-own-rule", "kind": "pool",
         "actuator": "coalescing", "value": 1}]}))
    buf = io.StringIO()
    cli_main(["--watch-rules", str(rules),
              "--ctl-playbooks", str(pb)], out=buf)
    assert "NNS511" not in buf.getvalue(), buf.getvalue()


def test_every_code_has_coverage():
    """The catalog is fully exercised: every stable code appears in the
    bad corpus, the lint snippets, the obs-disabled corpus, the
    watch-rules / ctl-playbook corpora above, or the NNS6xx concurrency
    corpus (tests/test_concurrency_lint.py)."""
    from test_concurrency_lint import CONCURRENCY_CORPUS

    covered = set()
    for _, expected in BAD_CORPUS:
        covered |= expected
    for _, expected in LINT_SNIPPETS:
        covered |= expected
    for _, expected in OBS_DISABLED_CORPUS:
        covered |= expected
    for _, expected in WATCH_RULES_CORPUS:
        covered |= expected
    for _, expected in PROF_ENV_CORPUS:
        covered |= expected
    for _, expected in CTL_PLAYBOOK_CORPUS:
        covered |= expected
    for _, expected in CONCURRENCY_CORPUS:
        covered |= expected
    assert covered == set(CODES)


def test_nns514_negative_cases():
    """No sandwich, no warning: a host stage at the head (nothing
    device upstream) or the tail (nothing device downstream) of the
    chain is the normal ingest/render pattern, not a fence; and an
    all-device chain has nothing host-only to flag."""
    head = (f"appsrc caps={GOOD_CAPS} ! tensor_converter ! "
            "tensor_filter framework=jax-xla "
            "model=/nonexistent/model.pkl ! tensor_sink")
    diags, _ = analyze_description(head)
    assert "NNS514" not in codes(diags)
    tail = (f"appsrc caps={GOOD_CAPS} ! tensor_filter framework=jax-xla "
            "model=/nonexistent/model.pkl ! tensor_decoder "
            "mode=image_labeling ! tensor_sink")
    diags, _ = analyze_description(tail)
    assert "NNS514" not in codes(diags)
    all_dev = (f"appsrc caps={GOOD_CAPS} ! tensor_transform "
               "mode=typecast option=float32 ! tensor_filter "
               "framework=jax-xla model=/nonexistent/model.pkl ! "
               "tensor_sink")
    diags, _ = analyze_description(all_dev)
    assert "NNS514" not in codes(diags)
    # positive case renders with element location + hint
    fence = (f"appsrc caps={GOOD_CAPS} ! tensor_filter "
             "framework=jax-xla model=/nonexistent/model.pkl ! "
             "tensor_converter name=fence ! tensor_filter name=f2 "
             "framework=jax-xla model=/nonexistent/model.pkl ! "
             "tensor_sink")
    diags, _ = analyze_description(fence)
    d = [x for x in diags if x.code == "NNS514"]
    assert len(d) == 1 and d[0].element == "fence" and d[0].hint


def test_nns515_negative_cases():
    """NNS515 fires only on a full transform→filter→decoder segment
    broken by a BREAKABLE cause — everything else stays quiet."""
    # the fusable segment itself: direct links, device decoder scheme
    fused = (f"appsrc caps={GOOD_CAPS} ! tensor_transform "
             "mode=typecast option=float32 ! tensor_filter "
             "framework=jax-xla model=/nonexistent/model.pkl ! "
             "tensor_decoder mode=bounding_boxes "
             "option1=mobilenet-ssd-postprocess option7=device ! "
             "tensor_sink")
    diags, _ = analyze_description(fused)
    assert "NNS515" not in codes(diags)
    # no decoder downstream: a transform→filter prologue segment is
    # handled (or not) by fuse_transform_filter; not this lint's shape
    no_dec = (f"appsrc caps={GOOD_CAPS} ! tensor_transform "
              "mode=typecast option=float32 ! queue ! tensor_filter "
              "framework=jax-xla model=/nonexistent/model.pkl ! "
              "tensor_sink")
    diags, _ = analyze_description(no_dec)
    assert "NNS515" not in codes(diags)
    # batch>1: the upstream queue is LOAD-BEARING (NNS501 requires it)
    # — warning would tell the user to break the batching topology
    batched = (f"appsrc caps={GOOD_CAPS} ! tensor_transform "
               "mode=typecast option=float32 ! queue ! tensor_filter "
               "framework=jax-xla model=/nonexistent/model.pkl "
               "batch=4 ! tensor_decoder mode=bounding_boxes "
               "option1=mobilenet-ssd-postprocess option7=device ! "
               "tensor_sink")
    diags, _ = analyze_description(batched)
    assert "NNS515" not in codes(diags)
    # a decoder mode with no device render program could never fuse —
    # nothing breakable to report
    labeling = (f"appsrc caps={GOOD_CAPS} ! tensor_transform "
                "mode=typecast option=float32 ! tensor_filter "
                "framework=jax-xla model=/nonexistent/model.pkl ! "
                "tensor_decoder mode=image_labeling ! tensor_sink")
    diags, _ = analyze_description(labeling)
    assert "NNS515" not in codes(diags)
    # non-jax framework: the fusion pass only captures jax-xla filters
    other_fw = (f"appsrc caps={GOOD_CAPS} ! tensor_transform "
                "mode=typecast option=float32 ! tensor_filter "
                "framework=python3 model=cb share-model=true ! "
                "tensor_decoder mode=bounding_boxes "
                "option1=mobilenet-ssd-postprocess option7=device ! "
                "tensor_sink")
    diags, _ = analyze_description(other_fw)
    assert "NNS515" not in codes(diags)
    # positive case names the whole segment and carries a hint
    tee = (f"appsrc caps={GOOD_CAPS} ! tensor_transform "
           "mode=typecast option=float32 ! tensor_filter name=net "
           "framework=jax-xla model=/nonexistent/model.pkl ! tee "
           "name=t t. ! queue ! tensor_decoder mode=bounding_boxes "
           "option1=mobilenet-ssd-postprocess option7=device ! "
           "tensor_sink t. ! queue ! tensor_sink name=s2")
    diags, _ = analyze_description(tee)
    d = [x for x in diags if x.code == "NNS515"]
    assert len(d) == 1 and d[0].element == "net" and d[0].hint
    assert "queue/tee" in d[0].message


def test_nns516_faces():
    """Each NNS516 face fires precisely: subset overlap, inventory
    excess (jax already up in-proc), the host-interposed offload
    branch, the heavy stage missing share-model, and the offload
    grammar check."""
    import jax

    n_devs = len(jax.devices())  # conftest pins 8 virtual chips
    overlap = (f"appsrc caps={GOOD_CAPS} ! queue ! tensor_filter "
               "name=f1 framework=jax-xla "
               "model=/nonexistent/model.pkl mesh=data:4 devices=0-3 "
               "batch=4 share-model=true ! tensor_sink "
               f"appsrc name=b caps={GOOD_CAPS} ! queue ! "
               "tensor_filter name=f2 framework=jax-xla "
               "model=/nonexistent/model.pkl mesh=data:4 devices=2-5 "
               "batch=4 share-model=true ! tensor_sink name=s2")
    diags, _ = analyze_description(overlap)
    d = [x for x in diags if x.code == "NNS516"]
    assert len(d) == 1 and "overlap" in d[0].message and d[0].hint
    assert "2,3" in d[0].message  # names the shared chips

    over = (f"appsrc caps={GOOD_CAPS} ! queue ! tensor_filter name=f1 "
            "framework=jax-xla model=/nonexistent/model.pkl "
            f"mesh=data:4 devices=0-{n_devs + 3} batch=4 "
            "share-model=true ! tensor_sink")
    diags, _ = analyze_description(over)
    d = [x for x in diags if x.code == "NNS516"]
    assert len(d) == 1 and "inventory" in d[0].message

    fence = (f"appsrc caps={GOOD_CAPS} ! tensor_if name=i operator=ge "
             "supplied-value=1 offload=then "
             "i.src_then ! tensor_converter ! tensor_filter name=hv "
             "framework=jax-xla model=/nonexistent/model.pkl "
             "mesh=data:4 devices=4-7 ! tensor_sink "
             "i.src_else ! tensor_sink name=s2")
    diags, _ = analyze_description(fence)
    d = [x for x in diags if x.code == "NNS516"]
    assert len(d) == 2
    host = [x for x in d if "host-only" in x.message]
    share = [x for x in d if "share-model" in x.message]
    assert len(host) == 1 and host[0].element == "i"
    assert len(share) == 1 and share[0].element == "hv"

    grammar = (f"appsrc caps={GOOD_CAPS} ! tensor_if name=i "
               "offload=both ! tensor_sink "
               "i.src_else ! tensor_sink name=s2")
    diags, _ = analyze_description(grammar)
    d = [x for x in diags if x.code == "NNS516"]
    assert len(d) == 1 and "offload" in d[0].message
    assert d[0].element == "i"


def test_nns516_negative_cases():
    """The WELL-FORMED cascade is quiet: disjoint subsets, the offload
    branch through transparent plumbing only, share-model=true on the
    heavy stage; a single staged filter (no second subset) and an
    un-staged tensor_if are not split topologies at all."""
    clean = (f"appsrc caps={GOOD_CAPS} ! queue ! tensor_filter "
             "name=det framework=jax-xla "
             "model=/nonexistent/model.pkl mesh=data:4 devices=0-3 "
             "batch=4 share-model=true ! tensor_if name=r operator=ge "
             "supplied-value=3 offload=then "
             "r.src_then ! queue ! tensor_filter name=cls "
             "framework=jax-xla model=/nonexistent/model.pkl "
             "mesh=data:4 devices=4-7 batch=4 share-model=true ! "
             "tensor_sink "
             "r.src_else ! tensor_sink name=keep")
    diags, _ = analyze_description(clean)
    assert "NNS516" not in codes(diags)
    # one declared stage alone: nothing to overlap with
    solo = (f"appsrc caps={GOOD_CAPS} ! queue ! tensor_filter "
            "framework=jax-xla model=/nonexistent/model.pkl "
            "mesh=data:4 devices=0-3 batch=4 share-model=true ! "
            "tensor_sink")
    diags, _ = analyze_description(solo)
    assert "NNS516" not in codes(diags)
    # identical subsets on purpose (same pool, two sharers) are NOT an
    # overlap — only partially-shared subsets contend
    same = (f"appsrc caps={GOOD_CAPS} ! queue ! tensor_filter name=f1 "
            "framework=jax-xla model=/nonexistent/model.pkl "
            "mesh=data:4 devices=0-3 batch=4 share-model=true ! "
            "tensor_sink "
            f"appsrc name=b caps={GOOD_CAPS} ! queue ! tensor_filter "
            "name=f2 framework=jax-xla model=/nonexistent/model.pkl "
            "mesh=data:4 devices=0-3 batch=4 share-model=true ! "
            "tensor_sink name=s2")
    diags, _ = analyze_description(same)
    assert "NNS516" not in codes(diags)
    # tensor_if without offload= is plain branching, not a cascade
    plain = (f"appsrc caps={GOOD_CAPS} ! tensor_if name=i operator=ge "
             "supplied-value=1 ! tensor_converter ! tensor_filter "
             "framework=jax-xla model=/nonexistent/model.pkl "
             "mesh=data:4 devices=4-7 share-model=true ! tensor_sink "
             "i.src_else ! tensor_sink name=s2")
    diags, _ = analyze_description(plain)
    assert "NNS516" not in codes(diags)


def test_nns506_suppressed_by_ntp_inproc_or_trace_off():
    """NNS506 is about tracing a cross-host link on an unanchored
    clock: configuring ntp-servers, staying in-process, or disabling
    trace propagation each silence it."""
    base = (f"appsrc caps={GOOD_CAPS} ! tensor_query_client "
            f"caps={GOOD_CAPS} dest-host=198.51.100.7 dest-port=5432")
    for tail in (" ntp-servers=198.51.100.9 ! tensor_sink",
                 " trace=false ! tensor_sink"):
        diags, _ = analyze_description(base + tail)
        assert "NNS506" not in codes(diags), tail
    inproc, _ = analyze_description(
        f"appsrc caps={GOOD_CAPS} ! tensor_query_client "
        f"caps={GOOD_CAPS} connect-type=inproc ! tensor_sink")
    assert "NNS506" not in codes(inproc)
    # and the positive case renders with the element location + hint
    diags, _ = analyze_description(base + " ! tensor_sink")
    d = [x for x in diags if x.code == "NNS506"][0]
    assert d.severity == Severity.INFO
    assert "ntp-servers" in (d.hint or "")


def test_nns513_updatable_without_reload_support():
    """is-updatable on a framework with neither prepare_swap nor a
    RELOAD_MODEL handler: the reload event would raise instead of
    swapping — flagged statically; jax-xla (which implements
    prepare_swap) stays clean."""
    diags, _ = analyze_description(
        f"appsrc caps={GOOD_CAPS} ! tensor_filter "
        "framework=custom-easy model=nope is-updatable=true ! "
        "tensor_sink")
    d = [x for x in diags if x.code == "NNS513"]
    assert d and "prepare_swap" in d[0].message
    clean, _ = analyze_description(
        f"appsrc caps={GOOD_CAPS} ! tensor_filter framework=jax-xla "
        "model=/nonexistent/model.pkl is-updatable=true ! tensor_sink")
    assert "NNS513" not in codes(clean)


def test_nns513_compile_cache_dir(monkeypatch, tmp_path):
    """NNS_TPU_COMPILE_CACHE_DIR pointing nowhere writable silently
    disables the persistent AOT cache — NNS513 warns; a writable dir
    is clean, and pipelines without filters don't care."""
    desc = (f"appsrc caps={GOOD_CAPS} ! tensor_filter "
            "framework=jax-xla model=/nonexistent/model.pkl ! "
            "tensor_sink")
    monkeypatch.setenv("NNS_TPU_COMPILE_CACHE_DIR",
                       str(tmp_path / "missing"))
    diags, _ = analyze_description(desc)
    d = [x for x in diags if x.code == "NNS513"]
    assert d and "NNS_TPU_COMPILE_CACHE_DIR" in d[0].message
    monkeypatch.setenv("NNS_TPU_COMPILE_CACHE_DIR", str(tmp_path))
    diags, _ = analyze_description(desc)
    assert "NNS513" not in codes(diags)
    monkeypatch.delenv("NNS_TPU_COMPILE_CACHE_DIR")
    diags, _ = analyze_description(desc)
    assert "NNS513" not in codes(diags)


def test_nns513_canary_without_watch_rule_cli(tmp_path):
    """The rules face runs in the CLI: a canary= pipeline against the
    default pack (which binds no version-labelled series) warns; a
    rules file with a comparator rule on the canary series is clean."""
    desc = (f"appsrc caps={GOOD_CAPS} ! queue ! tensor_filter "
            "framework=jax-xla model=/nonexistent/model.pkl batch=4 "
            "share-model=true canary=next:1/4 ! tensor_sink")
    buf = io.StringIO()
    cli_main([desc], out=buf)
    out = buf.getvalue()
    assert "canary-rules:" in out and "NNS513" in out, out
    rules = tmp_path / "rules.json"
    rules.write_text(json.dumps({"rule": [
        {"name": "canary-regressed", "kind": "threshold",
         "metric": "nns_model_canary_latency_us",
         "per": "nns_model_baseline_latency_us",
         "op": ">", "value": 1.5, "for": "1s"}]}))
    buf = io.StringIO()
    cli_main([desc, "--watch-rules", str(rules)], out=buf)
    out = buf.getvalue()
    assert "canary-rules:" in out
    # the canary face is clean; (the rules file itself is NNS510-clean)
    assert not [ln for ln in out.splitlines() if "NNS513" in ln], out


def test_nns512_pool_divisibility_and_conflicts():
    """NNS512 is the POOL-level NNS509 (ISSUE-12): share-model sharers
    form one cross-pipeline window, so divisibility is checked per
    pool (union of the sharers' declared buckets), and provably
    conflicting placements — which the runtime refuses with a
    PoolConflictError — are flagged statically."""
    flt = ("tensor_filter framework=jax-xla "
           "model=/nonexistent/model.pkl share-model=true ")
    pre = f"appsrc caps={GOOD_CAPS} ! queue ! "
    # divisible pool window: clean (and no NNS509 double-fire)
    diags, _ = analyze_description(
        pre + flt + "mesh=data:4 batch=8 ! tensor_sink")
    assert "NNS512" not in codes(diags)
    assert "NNS509" not in codes(diags)
    # indivisible pool window: NNS512, NOT NNS509 (the pool check owns
    # share-model windows)
    diags, _ = analyze_description(
        pre + flt + "mesh=data:4 batch=6 ! tensor_sink")
    d = [x for x in diags if x.code == "NNS512"]
    assert d and "NNS509" not in codes(diags)
    assert "6" in d[0].message
    assert "nns_pool_pad_frac" in (d[0].hint or "")
    # two sharers, provably different placements: the static face of
    # the runtime PoolConflictError
    diags, _ = analyze_description(
        pre + flt + "name=f1 mesh=data:4 batch=4 ! tensor_sink  "
        + pre + flt + "name=f2 mesh=data:2 batch=4 ! tensor_sink")
    d = [x for x in diags if x.code == "NNS512"]
    assert d and "PoolConflictError" in d[0].message
    # same spelling, and alias spellings (dp vs replicated), are NOT
    # conflicts; wildcard vs fixed is not PROVABLY different either
    for a, b in (("mesh=data:4 sharding=dp", "mesh=data:4 "
                  "sharding=replicated"),
                 ("mesh=data:-1", "mesh=data:-1"),
                 ("mesh=data:-1", "mesh=data:8")):
        diags, _ = analyze_description(
            pre + flt + f"name=f1 {a} batch=8 ! tensor_sink  "
            + pre + flt + f"name=f2 {b} batch=8 ! tensor_sink")
        conflicts = [x for x in diags if x.code == "NNS512"
                     and "conflict" in x.message]
        assert not conflicts, (a, b, [str(x) for x in conflicts])
    # devices omitted vs an equivalent explicit subset is NOT provably
    # different (a plain mesh lays over the device prefix, which may
    # BE the named subset — the runtime joins them), and subset
    # spellings canonicalize
    for a, b in (("mesh=data:4", "mesh=data:4 devices=0-3"),
                 ("mesh=data:4 devices=0-3",
                  "mesh=data:4 devices=0,1,2,3")):
        diags, _ = analyze_description(
            pre + flt + f"name=f1 {a} batch=8 ! tensor_sink  "
            + pre + flt + f"name=f2 {b} batch=8 ! tensor_sink")
        assert not [x for x in diags if x.code == "NNS512"], (a, b)
    # two EXPLICIT different subsets ARE a conflict
    diags, _ = analyze_description(
        pre + flt + "name=f1 mesh=data:4 devices=0-3 batch=8 ! "
        "tensor_sink  "
        + pre + flt + "name=f2 mesh=data:4 devices=4-7 batch=8 ! "
        "tensor_sink")
    assert [x for x in diags if x.code == "NNS512"]
    # filters split by shared-tensor-filter-key (or custom/IO-spec)
    # open DIFFERENT pools at runtime — different placements across
    # them are NOT a conflict (review fix: grouping mirrors the
    # runtime pool identity, not just the model)
    diags, _ = analyze_description(
        pre + flt + "name=f1 shared-tensor-filter-key=a mesh=data:4 "
        "batch=4 ! tensor_sink  "
        + pre + flt + "name=f2 shared-tensor-filter-key=b mesh=data:2 "
        "batch=4 ! tensor_sink")
    assert not [x for x in diags if x.code == "NNS512"]


def test_nns509_divisible_and_unknown_axis_are_clean():
    """NNS509 only fires when a bucket provably cannot split over a
    statically-known data axis: divisible buckets, batch=1, no mesh,
    and wildcard (-1) axes with no devices= pin are all clean."""
    base = (f"appsrc caps={GOOD_CAPS} ! queue ! tensor_filter "
            "framework=jax-xla model=/nonexistent/model.pkl ")
    for props in ("mesh=data:4 batch=8",            # divisible
                  "mesh=data:4 batch=8 batch-buckets=4,8",
                  "mesh=data:4",                    # batch=1
                  "mesh=data:-1 batch=6",           # unknown axis size
                  "batch=6"):                       # no mesh at all
        diags, _ = analyze_description(base + props + " ! tensor_sink")
        assert "NNS509" not in codes(diags), props
    # an explicit bucket list with ONE bad bucket is enough, and the
    # devices= subset pins a wildcard axis statically
    for props, bad in (
            ("mesh=data:4 batch=8 batch-buckets=4,6,8", "6"),
            ("mesh=data:-1 devices=0-3 batch=6", "6"),
            ("mesh=model:2,data:2 batch=5", "5")):  # named data axis
        diags, _ = analyze_description(base + props + " ! tensor_sink")
        d = [x for x in diags if x.code == "NNS509"]
        assert d, props
        assert d[0].severity == Severity.WARNING
        assert bad in d[0].message, (props, d[0].message)
        assert "nns_mesh_pad_slots_total" in (d[0].hint or "")


def test_nns507_defaults_and_inproc_are_clean():
    """NNS507 is about DISABLED bounds on a cross-host link: the
    defaults (timeout=10000, max-request=8) are bounded, and an inproc
    link has no dead-server failure mode to bound against."""
    base = (f"appsrc caps={GOOD_CAPS} ! tensor_query_client "
            f"caps={GOOD_CAPS} dest-host=198.51.100.7 dest-port=5432")
    diags, _ = analyze_description(base + " ! tensor_sink")
    assert "NNS507" not in codes(diags)
    inproc, _ = analyze_description(
        f"appsrc caps={GOOD_CAPS} ! tensor_query_client "
        f"caps={GOOD_CAPS} connect-type=inproc timeout=0 ! tensor_sink")
    assert "NNS507" not in codes(inproc)
    # each disabled bound alone is enough to warn
    for knob in (" timeout=0", " max-request=0"):
        diags, _ = analyze_description(base + knob + " ! tensor_sink")
        d = [x for x in diags if x.code == "NNS507"]
        assert d, knob
        assert d[0].severity == Severity.WARNING
        assert "max-request" in (d[0].hint or "")


def test_lint_negatives_stay_clean():
    # Condition.wait on the held condition releases the lock: not NNS303
    clean = """
class Q:
    def pop(self):
        with self._cv:
            while not self._dq:
                self._cv.wait(0.05)
"""
    assert codes(lint_source(clean)) == set()
    # string join is not a thread join
    assert codes(lint_source("""
def render(parts, lock):
    with lock:
        return ", ".join(parts)
""")) == set()
    # trace-time shape math is allowed in jitted code
    assert codes(lint_source("""
import jax
import numpy as np

@jax.jit
def hot(x):
    n = int(np.prod(x.shape))
    return x.reshape(n)
""")) == set()


def test_suppressions():
    src = """
def f():
    try:
        risky()
    except:  # nns-lint: disable=NNS403 -- crafted test fixture
        pass
"""
    assert codes(lint_source(src)) == set()
    src_above = """
def f():
    try:
        risky()
    # nns-lint: disable=NNS403 -- reason on the line above
    except:
        pass
"""
    assert codes(lint_source(src_above)) == set()
    src_file = """
# nns-lint: disable-file=NNS403 -- fixture file
def f():
    try:
        risky()
    except:
        pass
"""
    assert codes(lint_source(src_file)) == set()


# -- good corpus: zero false positives ---------------------------------------


def test_good_linear_pipeline_is_clean():
    diags, pipe = analyze_description(GOOD)
    assert diags == []
    assert pipe is not None


def test_good_pipeline_with_registered_model_is_clean():
    from nnstreamer_tpu.filters.jax_xla import register_model, \
        unregister_model

    register_model("_t_analyze_model", lambda x: x.astype("float32") + 1,
                   in_shapes=[(1, 4, 4, 3)], in_dtypes=np.uint8)
    try:
        diags, _ = analyze_description(
            f"appsrc caps={GOOD_CAPS} ! tensor_filter framework=jax-xla "
            "model=_t_analyze_model ! tensor_sink")
        assert diags == [], [str(d) for d in diags]
    finally:
        unregister_model("_t_analyze_model")


def test_good_fan_in_same_rate_is_clean():
    base = ("appsrc name={n} caps=other/tensors,format=static,"
            "num_tensors=1,dimensions=4,types=uint8,framerate=30/1")
    diags, _ = analyze_description(
        base.format(n="a") + " ! tensor_mux name=m ! tensor_sink " +
        base.format(n="b") + " ! m.sink_1")
    assert diags == [], [str(d) for d in diags]


def test_examples_and_doc_corpus_zero_false_positives():
    """Every pipeline in examples/ and every element-doc example analyzes
    without errors or warnings (info is allowed: runtime-registered
    models/specs cannot be proven statically)."""
    from nnstreamer_tpu.analyze.pipelines import default_corpus

    entries = default_corpus(os.path.join(REPO, "examples"))
    assert len(entries) >= 8  # 2 example scripts + 7 doc pipelines
    for entry in entries:
        diags, _ = analyze_description(entry.description,
                                       fragment=entry.fragment)
        bad = above_info(diags)
        assert not bad, f"{entry.label}: {[str(d) for d in bad]}"


def test_self_lint_runs_clean():
    pkg = os.path.join(REPO, "nnstreamer_tpu")
    diags = lint_package(pkg)
    assert diags == [], [str(d) for d in diags]


# -- caps dry-run regressions ------------------------------------------------


def test_dry_run_rank_flexible_dims():
    # 3:4:4:1 vs rank-flexible 3:4:4 intersect (reference rank-flexible
    # compare); the dry run must not flag the link
    diags, _ = analyze_description(
        f"appsrc caps={GOOD_CAPS} ! other/tensors,format=static,"
        "num_tensors=1,dimensions=3:4:4,types=uint8 ! tensor_sink")
    assert diags == [], [str(d) for d in diags]


def test_dry_run_framerate_wildcard():
    # framerate=0/1 is the "any rate" wildcard on either side
    diags, _ = analyze_description(
        f"appsrc caps={GOOD_CAPS} ! other/tensors,framerate=0/1 ! "
        "tensor_sink")
    assert diags == [], [str(d) for d in diags]
    diags, _ = analyze_description(
        "appsrc caps=other/tensors,format=static,num_tensors=1,"
        "dimensions=4,types=uint8,framerate=0/1 ! "
        "other/tensors,framerate=25/1 ! tensor_sink")
    assert diags == [], [str(d) for d in diags]


def test_dry_run_is_pure():
    """The dry run leaves the pipeline unstarted and pad caps untouched,
    and the pipeline still starts normally afterwards."""
    p = parse_launch(GOOD)
    assert analyze_pipeline(p) == []
    assert not p.playing
    for e in p.elements.values():
        for pad in e.sinkpads + e.srcpads:
            assert pad.caps is None and pad.spec is None
    with p:
        assert p.playing
    assert not p.playing


def test_dry_run_names_offending_field():
    diags, _ = analyze_description(
        f"appsrc caps={GOOD_CAPS} ! other/tensors,format=static,"
        "num_tensors=1,dimensions=3:8:8:1,types=uint8 ! tensor_sink")
    [d] = [d for d in diags if d.code == "NNS201"]
    assert "dimensions" in d.message
    assert "3:4:4:1" in d.message and "3:8:8:1" in d.message


# -- CLI ---------------------------------------------------------------------


def test_cli_exit_codes():
    assert cli_main([], out=io.StringIO()) == 2
    assert cli_main([GOOD], out=io.StringIO()) == 0
    assert cli_main(["tensor_converter ! tensor_sink"],
                    out=io.StringIO()) == 1
    # NNS102+NNS106 are warnings: clean exit by default, fail --strict
    warn_only = f"appsrc caps={GOOD_CAPS} ! tensor_converter"
    assert cli_main([warn_only], out=io.StringIO()) == 0
    assert cli_main(["--strict", warn_only], out=io.StringIO()) == 1
    # fragment mode downgrades them to info: clean even under --strict
    assert cli_main(["--strict", "--fragment", warn_only],
                    out=io.StringIO()) == 0


def test_cli_dot_stdout():
    """`--dot` (bare) prints the static Pipeline.to_dot() dump for every
    target that parsed — the never-started graph, so caps stay '?'."""
    buf = io.StringIO()
    rc = cli_main([GOOD, "--dot"], out=buf)
    assert rc == 0
    text = buf.getvalue()
    assert f"// dot: {GOOD}" in text
    assert 'digraph "pipeline"' in text
    assert '"appsrc0" -> "tensor_converter1"' in text
    assert '"tensor_converter1" -> "tensor_sink2"' in text


def test_cli_dot_writes_files(tmp_path):
    d = str(tmp_path / "dots")
    buf = io.StringIO()
    rc = cli_main([GOOD, "--dot", d], out=buf)
    assert rc == 0
    files = os.listdir(d)
    assert len(files) == 1 and files[0].endswith(".dot")
    with open(os.path.join(d, files[0])) as f:
        assert f.read().startswith('digraph "pipeline"')
    assert "wrote" in buf.getvalue()


def test_cli_dot_skips_unparseable_targets(tmp_path):
    d = str(tmp_path / "dots")
    rc = cli_main(["appsrc ! bogus_thing ! tensor_sink", "--dot", d],
                  out=io.StringIO())
    assert rc == 1  # the NNS100 still fails the run
    assert not os.path.isdir(d)  # nothing parsed: nothing dumped


def test_cli_json_golden():
    """--json output is stable and matches the committed golden."""
    buf = io.StringIO()
    rc = cli_main(["--json",
                   "appsrc ! bogus_thing ! tensor_sink",
                   "tensor_converter ! tensor_sink"], out=buf)
    assert rc == 1
    got = json.loads(buf.getvalue())
    golden_path = os.path.join(REPO, "tests", "golden",
                               "analyze_cli.golden.json")
    with open(golden_path) as f:
        golden = json.load(f)
    assert got == golden
    # determinism: a second run byte-matches
    buf2 = io.StringIO()
    cli_main(["--json", "appsrc ! bogus_thing ! tensor_sink",
              "tensor_converter ! tensor_sink"], out=buf2)
    assert buf2.getvalue() == buf.getvalue()


def test_cli_self_flag():
    assert cli_main(["--self", os.path.join(REPO, "nnstreamer_tpu")],
                    out=io.StringIO()) == 0


# -- satellite: Bus.remove_watch + thread safety -----------------------------


def test_bus_remove_watch():
    bus = Bus()
    seen_a, seen_b = [], []
    ha = seen_a.append
    hb = seen_b.append
    bus.add_watch(ha)
    bus.add_watch(hb)
    bus.post(Message(MessageKind.ELEMENT, "x"))
    assert len(seen_a) == len(seen_b) == 1
    assert bus.remove_watch(ha) is True
    assert bus.remove_watch(ha) is False  # already gone
    bus.post(Message(MessageKind.ELEMENT, "x"))
    assert len(seen_a) == 1 and len(seen_b) == 2


def test_bus_remove_watch_bound_method():
    class W:
        def __init__(self):
            self.n = 0

        def on_msg(self, msg):
            self.n += 1

    w = W()
    bus = Bus()
    bus.add_watch(w.on_msg)  # a fresh bound-method object...
    assert bus.remove_watch(w.on_msg) is True  # ...compares equal


def test_bus_watch_mutation_race():
    """add_watch/remove_watch from other threads must never corrupt the
    handler list a concurrent post is iterating."""
    bus = Bus()
    stop = threading.Event()
    errors = []

    def churn():
        def h(msg):
            pass

        while not stop.is_set():
            try:
                bus.add_watch(h)
                bus.remove_watch(h)
            except Exception as e:  # pragma: no cover
                errors.append(e)
                return

    threads = [threading.Thread(target=churn) for _ in range(4)]
    for t in threads:
        t.start()
    for _ in range(2000):
        bus.post(Message(MessageKind.ELEMENT, "race"))
    stop.set()
    for t in threads:
        t.join(timeout=10)
    assert not errors


def test_bus_post_vs_remove_watch_race():
    """ISSUE 16 audit companion: post() iterates a copy-on-write tuple
    snapshot lock-free, so a remove_watch racing two poster threads
    must (a) never corrupt an in-flight delivery and (b) win promptly —
    after remove_watch returns, NO later post may call the handler."""
    bus = Bus()
    stop = threading.Event()
    errors = []
    removed = threading.Event()
    late_calls = []

    def handler(msg):
        if removed.is_set():
            late_calls.append(msg)

    def poster():
        while not stop.is_set():
            try:
                bus.post(Message(MessageKind.ELEMENT, "race"))
            except Exception as e:  # pragma: no cover
                errors.append(e)
                return

    posters = [threading.Thread(target=poster) for _ in range(2)]
    for _ in range(50):
        removed.clear()
        late_calls.clear()
        bus.add_watch(handler)
        for t in posters:
            if not t.is_alive():
                t.start()
        bus.remove_watch(handler)
        removed.set()
        # a delivery that STARTED before the removal may still be
        # draining the old snapshot; one more post must not see it
        bus.post(Message(MessageKind.ELEMENT, "after-remove"))
        assert not any(m.source == "after-remove" for m in late_calls), \
            "handler called by a post issued after remove_watch"
    stop.set()
    for t in posters:
        t.join(timeout=10)
    assert not errors


# -- satellite: parser position info -----------------------------------------


def test_parse_error_positions():
    desc = "appsrc ! nosuchelement ! tensor_sink"
    with pytest.raises(ParseError) as ei:
        parse_launch(desc)
    assert ei.value.pos == desc.index("nosuchelement")
    ctx = ei.value.context(desc)
    caret_line = ctx.splitlines()[1]
    assert caret_line.index("^") == ei.value.pos

    desc2 = "appsrc name=a ! unknownref. ! tensor_sink"
    with pytest.raises(ParseError) as ei:
        parse_launch(desc2)
    assert ei.value.pos == desc2.index("unknownref.")

    with pytest.raises(ParseError) as ei:
        parse_launch('appsrc caps="unterminated')
    assert ei.value.pos == len("appsrc ")


def test_parse_caps_field_position():
    desc = "appsrc ! other/tensors,format=static,badfield ! tensor_sink"
    with pytest.raises(ParseError) as ei:
        parse_launch(desc)
    assert ei.value.pos == desc.index("badfield")


def test_caps_string_error_offsets():
    from nnstreamer_tpu.runtime.parser import parse_caps_string

    with pytest.raises(ParseError) as ei:
        parse_caps_string("other/tensors,oops")
    assert ei.value.pos == len("other/tensors,")


# -- satellite: double-link rejection ----------------------------------------


def test_link_pads_rejects_double_link():
    p = Pipeline()
    src1 = make("appsrc", el_name="s1")
    src2 = make("appsrc", el_name="s2")
    sink = make("tensor_sink", el_name="out")
    p.add(src1, src2, sink)
    p.link_pads("s1", "src", "out", "sink")
    with pytest.raises(ValueError) as ei:
        p.link_pads("s2", "src", "out", "sink")
    msg = str(ei.value)
    assert "already linked" in msg
    assert "s1.src" in msg  # names the existing peer
    # nothing was overwritten
    assert sink.sinkpad.peer is src1.srcpad
    assert src2.srcpad.peer is None


# -- misc --------------------------------------------------------------------


def test_device_src_string_spec():
    el = make("device_src", el_name="d", spec="3:4:4:2/float32,10:2")
    spec = el.output_spec()
    assert isinstance(spec, TensorsSpec)
    assert spec.num_tensors == 2
    assert "float32" in str(spec.tensors[0].dtype)
    assert "uint8" in str(spec.tensors[1].dtype)  # default pattern dtype
    assert spec.tensors[1].dims == (10, 2)


def test_collect_request_pad_autonumbers():
    mux = make("tensor_mux", el_name="m")
    p0 = mux.request_pad("sink_%u")
    p1 = mux.request_pad("sink_%u")
    assert (p0.name, p1.name) == ("sink_0", "sink_1")
    named = mux.request_pad("sink_7")
    assert named.name == "sink_7"


def test_request_pad_names_unique_everywhere():
    """%u templates expand in shared code: every request-pad element
    yields unique names (EOS tracking and get_pad are name-keyed)."""
    for factory, req, attr in [("join", "sink_%u", "sinkpads"),
                               ("tensor_demux", "src_%u", "srcpads"),
                               ("tensor_split", "src_%u", "srcpads"),
                               ("tee", "src_%u", "srcpads")]:
        el = make(factory, el_name=f"u_{factory}")
        a = el.request_pad(req)
        b = el.request_pad(req)
        names = [p.name for p in getattr(el, attr)]
        assert len(names) == len(set(names)), (factory, names)
        assert "%u" not in a.name and "%u" not in b.name, (factory,
                                                           a.name, b.name)


def test_join_two_branches_eos_not_premature():
    """Regression: duplicate 'sink_%u' pad names made join forward EOS
    after the FIRST branch finished, dropping the other branch's tail."""
    caps = ("other/tensors,format=static,num_tensors=1,dimensions=2,"
            "types=uint8,framerate=0/1")
    p = parse_launch(
        f"appsrc name=a caps={caps} ! join name=j ! tensor_sink name=o "
        f"appsrc name=b caps={caps} ! j.")
    assert len({pd.name for pd in p["j"].sinkpads}) == 2
    got = []
    p["o"].connect(lambda buf: got.append(buf.tensors[0].np().tolist()))
    with p:
        p["a"].push_buffer(Buffer.of(np.array([1, 1], np.uint8)))
        p["a"].end_of_stream()  # first branch ends...
        import time

        time.sleep(0.2)
        # ...second branch must still flow
        p["b"].push_buffer(Buffer.of(np.array([2, 2], np.uint8)))
        p["b"].end_of_stream()
        assert p.wait_eos(timeout=30)
    assert [2, 2] in got, got


def test_bus_remove_watch_removes_one_registration():
    bus = Bus()
    seen = []
    h = seen.append
    bus.add_watch(h)
    bus.add_watch(h)  # independent callers both registered the handler
    assert bus.remove_watch(h) is True
    bus.post(Message(MessageKind.ELEMENT, "x"))
    assert len(seen) == 1  # one registration survives
    assert bus.remove_watch(h) is True
    assert bus.remove_watch(h) is False


def test_quoted_caps_token_position():
    desc = 'appsrc ! "other/tensors,badfield" ! tensor_sink'
    with pytest.raises(ParseError) as ei:
        parse_launch(desc)
    assert ei.value.pos == desc.index("badfield")


def test_parse_error_double_link_kind():
    with pytest.raises(ParseError) as ei:
        parse_launch("appsrc name=a ! tensor_sink name=s "
                     "appsrc name=b ! s.sink")
    assert ei.value.kind == "double-link"


def test_lint_blocking_with_item_under_lock():
    src = """
def f(self, path):
    with self._lock:
        with open(path) as fh:
            return fh.read()
"""
    assert "NNS303" in codes(lint_source(src))
