"""`runtime/lifecycle.py` + `runtime/compilecache.py` — the
zero-downtime model lifecycle (ISSUE-14 surface).

Double-buffered hot swap on a live shared pool (staged + warmed
off-path, window-boundary flip, zero frame loss), canary routing with
per-version stats / FIFO demux / error isolation, the promote /
rollback verdict machinery and its actuators (incl. the 3-thread
swap-vs-start/stop race mirroring PR 11's harness), the persistent AOT
compile cache (hit/miss/store, corruption and version-skew fallback,
persist_hit CompileStats accounting), versioned model URIs + orbax
step-dir resolution, snapshot v7 `models` table + `nns_model_*`
export, the nns-top MODELS section, and NNS513's runtime counterparts.
"""

import json
import os
import threading
import time

import numpy as np
import pytest

from nnstreamer_tpu.core import Buffer, TensorsSpec
from nnstreamer_tpu.elements.basic import AppSink, AppSrc, Queue
from nnstreamer_tpu.elements.filter import TensorFilter
from nnstreamer_tpu.filters.api import FilterProps
from nnstreamer_tpu.filters.jax_xla import (JaxXlaFilter,
                                            register_model,
                                            unregister_model)
from nnstreamer_tpu.filters.modeluri import (ModelUriError,
                                             resolve_model_uri,
                                             resolve_model_uri_versioned,
                                             split_model_version)
from nnstreamer_tpu.obs.metrics import REGISTRY
from nnstreamer_tpu.runtime import Pipeline
from nnstreamer_tpu.runtime import compilecache
from nnstreamer_tpu.runtime.actuators import (ActuationError,
                                              find_actuators,
                                              list_actuators)
from nnstreamer_tpu.runtime.lifecycle import (LifecycleError,
                                              parse_canary)
from nnstreamer_tpu.runtime.serving import MODEL_POOL
from nnstreamer_tpu.utils.stats import COMPILE_STATS

SHAPE = (4,)


@pytest.fixture(scope="module", autouse=True)
def _models():
    register_model("_t_lc", lambda x: x + 1.0, in_shapes=[SHAPE],
                   in_dtypes=np.float32)
    register_model("_t_lc_v2", lambda x: x + 3.0, in_shapes=[SHAPE],
                   in_dtypes=np.float32)
    yield
    for n in ("_t_lc", "_t_lc_v2"):
        unregister_model(n)


@pytest.fixture(autouse=True)
def _clean_pool():
    yield
    MODEL_POOL.clear()


def _pool_pipe(name, batch=4, canary="", timeout_ms=2.0,
               sample_ms=10.0):
    spec = TensorsSpec.from_shapes([SHAPE], np.float32)
    p = Pipeline(name=name)
    src = AppSrc(name="src", spec=spec, max_buffers=64)
    q = Queue(name="q", max_size_buffers=64)
    flt = TensorFilter(name="net", framework="jax-xla", model="_t_lc",
                       batch=batch, batch_timeout_ms=timeout_ms,
                       batch_buckets=str(batch), share_model=True,
                       is_updatable=True, canary=canary,
                       stat_sample_interval_ms=sample_ms)
    sink = AppSink(name="sink", max_buffers=256)
    p.add(src, q, flt, sink).link(src, q, flt, sink)
    return p, {"src": src, "q": q, "flt": flt, "sink": sink}


def _push_n(src, n, start=0):
    for i in range(n):
        src.push_buffer(Buffer.of(np.zeros(SHAPE, np.float32),
                                  pts=start + i), timeout=2.0)


def _pull_all(sink, expect, timeout=10.0):
    out, deadline = [], time.monotonic() + timeout
    while len(out) < expect and time.monotonic() < deadline:
        b = sink.pull(timeout=0.2)
        if b is not None:
            out.append(b)
    return out


def _vals(bufs):
    return [float(np.asarray(b.tensors[0].np()).ravel()[0])
            for b in bufs]


# -- canary grammar -----------------------------------------------------------


def test_parse_canary_grammar():
    assert parse_canary("") == ("", 0)
    assert parse_canary("next:1/4") == ("next", 4)
    assert parse_canary("v7:1/2") == ("v7", 2)
    assert parse_canary("1/8") == ("next", 8)
    for bad in ("2/3", "next:2/4", "1/1", "x", "1/0", "next:"):
        with pytest.raises(LifecycleError):
            parse_canary(bad)


# -- versioned model URIs (satellite) -----------------------------------------


def test_split_model_version(tmp_path):
    assert split_model_version("m.pkl@v2") == ("m.pkl", "v2")
    assert split_model_version("plain.pkl") == ("plain.pkl", "")
    assert split_model_version(123) == (123, "")
    # a file literally named with an '@' never splits
    lit = tmp_path / "x@y.pkl"
    lit.write_bytes(b"")
    assert split_model_version(str(lit)) == (str(lit), "")


def test_versioned_file_uri_resolves_with_tag(tmp_path):
    f = tmp_path / "net.pkl"
    f.write_bytes(b"stub")
    model, tag = resolve_model_uri_versioned(f"file://{f}@v2")
    assert model == str(f) and tag == "v2"
    # untagged keeps the old contract
    assert resolve_model_uri(f"file://{f}") == str(f)


def test_versioned_uri_unresolvable_suffix_is_a_clear_error(tmp_path):
    missing = tmp_path / "nope.pkl"
    with pytest.raises(ModelUriError, match="@v9"):
        resolve_model_uri_versioned(f"file://{missing}@v9")
    # a PLAIN string whose base names nothing on disk is a name, not a
    # versioned path: it passes through untouched (an in-process
    # registered model of any framework may contain '@')
    ref = str(missing) + "@v9"
    assert resolve_model_uri_versioned(ref) == (ref, "")


def test_orbax_step_dir_resolution(tmp_path):
    from nnstreamer_tpu.trainers.checkpoint import (latest_step,
                                                    list_steps,
                                                    resolve_step_dir)

    root = tmp_path / "ckpts"
    for step in (100, 200, 250):
        (root / str(step)).mkdir(parents=True)
    assert list_steps(str(root)) == [100, 200, 250]
    assert latest_step(str(root)) == 250
    path, tag = resolve_model_uri_versioned(f"{root}@latest")
    assert path == str(root / "250") and tag == "250"
    path, tag = resolve_model_uri_versioned(f"{root}@100")
    assert path == str(root / "100") and tag == "100"
    with pytest.raises(ModelUriError, match="@999"):
        resolve_model_uri_versioned(f"{root}@999")
    with pytest.raises(ValueError):
        resolve_step_dir(str(root), "not-a-step")


def test_registered_name_with_at_never_splits():
    register_model("_t_lc@weird", lambda x: x * 2.0,
                   in_shapes=[SHAPE], in_dtypes=np.float32)
    try:
        assert resolve_model_uri_versioned("_t_lc@weird") == \
            ("_t_lc@weird", "")
    finally:
        unregister_model("_t_lc@weird")


# -- prepare/commit swap (framework level) ------------------------------------


def test_prepare_swap_builds_warm_shadow_and_commit_flips():
    sp = JaxXlaFilter()
    sp.configure(FilterProps(framework="jax-xla", model="_t_lc"))
    x = np.ones(SHAPE, np.float32)
    sp.invoke_batched([[x]] * 2, 2)
    assert sp.hot_buckets() == (2,)
    before = {(r["kind"], r["bucket"]): r["count"]
              for r in COMPILE_STATS.snapshot()}
    shadow = sp.prepare_swap("_t_lc_v2")
    after = {(r["kind"], r["bucket"]): r["count"]
             for r in COMPILE_STATS.snapshot()}
    # the OLD model still serves: nothing flipped yet
    out = sp.invoke([x])
    assert float(np.asarray(out[0])[0]) == 2.0
    # the shadow's configure compile counts as a reload, and the hot
    # bucket recompiled off-path
    assert after.get(("reload", "0"), 0) - before.get(("reload", "0"),
                                                      0) == 1
    assert after.get(("bucket", "2"), 0) - before.get(("bucket", "2"),
                                                      0) == 1
    sp.commit_swap(shadow)
    out = sp.invoke([x])
    assert float(np.asarray(out[0])[0]) == 4.0
    # the transplanted bucket executable serves without a recompile
    outs = sp.invoke_batched([[x]] * 2, 2)
    assert float(np.asarray(outs[0][0])[0]) == 4.0
    final = {(r["kind"], r["bucket"]): r["count"]
             for r in COMPILE_STATS.snapshot()}
    assert final.get(("bucket", "2")) == after.get(("bucket", "2"))
    sp.close()


def test_prepare_swap_rejects_output_schema_change():
    def wide(x):
        import jax.numpy as jnp

        return jnp.concatenate([x, x])

    register_model("_t_lc_wide", wide, in_shapes=[SHAPE],
                   in_dtypes=np.float32)
    try:
        sp = JaxXlaFilter()
        sp.configure(FilterProps(framework="jax-xla", model="_t_lc"))
        from nnstreamer_tpu.filters.api import FilterError

        with pytest.raises(FilterError, match="output schema"):
            sp.prepare_swap("_t_lc_wide")
        sp.close()
    finally:
        unregister_model("_t_lc_wide")


def test_weights_only_swap_from_params_pytree():
    w0 = {"b": np.float32(1.0)}

    def apply(params, x):
        return x + params["b"]

    register_model("_t_lc_params", apply, params=w0,
                   in_shapes=[SHAPE], in_dtypes=np.float32)
    try:
        sp = JaxXlaFilter()
        sp.configure(FilterProps(framework="jax-xla",
                                 model="_t_lc_params"))
        x = np.zeros(SHAPE, np.float32)
        assert float(np.asarray(sp.invoke([x])[0])[0]) == 1.0
        shadow = sp.prepare_swap({"b": np.float32(9.0)})
        sp.commit_swap(shadow)
        assert float(np.asarray(sp.invoke([x])[0])[0]) == 9.0
        sp.close()
    finally:
        unregister_model("_t_lc_params")


# -- live pool hot swap -------------------------------------------------------


def test_pool_reload_hot_swaps_with_no_frame_loss():
    p, e = _pool_pipe("lc-swap", batch=4, timeout_ms=2.0)
    p.start()
    try:
        entry = e["flt"].pool
        _push_n(e["src"], 8)
        first = _pull_all(e["sink"], 8)
        assert _vals(first) == [1.0] * 8  # baseline x+1 on zeros
        res = entry.reload_model("_t_lc_v2", version="v2")
        assert res["version"] == "v2"
        lc = entry.lifecycle
        assert lc.swaps == 1 and lc.baseline.tag == "v2"
        assert lc.last_swap_stall_s < 1.0
        _push_n(e["src"], 8, start=100)
        swapped = _pull_all(e["sink"], 8)
        assert len(swapped) == 8  # no frame loss across the flip
        assert _vals(swapped) == [3.0] * 8  # v2: x+3 on zeros
        # provenance: the swap landed in the history trail
        assert any(ev["event"] == "swap" and ev["version"] == "v2"
                   for ev in lc.history)
    finally:
        p.stop()
    assert len(MODEL_POOL) == 0


def test_hot_swap_under_load_drops_no_frame_and_flips_once():
    """The swap lands between two windows while three streams keep
    pushing: every frame pushed is delivered, in its stream's order,
    and a stream's outputs change version once and never back."""
    pipes = []
    for i in range(3):
        p, e = _pool_pipe(f"lc-load-{i}", batch=4, timeout_ms=2.0)
        p.start()
        pipes.append((p, e))
    try:
        entry = pipes[0][1]["flt"].pool
        flowing, swapped = threading.Event(), threading.Event()
        pushed = [0, 0, 0]

        def produce(k, src):
            tail = 0        # frames pushed once the swap has returned
            while tail < 16 and pushed[k] < 4000:
                src.push_buffer(Buffer.of(np.zeros(SHAPE, np.float32),
                                          pts=pushed[k]), timeout=10.0)
                pushed[k] += 1
                tail += swapped.is_set()
                if pushed[k] == 8:
                    flowing.set()
                time.sleep(0.001)
            src.end_of_stream()

        def consume(sink, out):
            while True:
                b = sink.pull(timeout=0.2)
                if b is not None:
                    out.append(b)
                elif done.is_set():
                    return

        done = threading.Event()
        outs = [[], [], []]
        threads = [threading.Thread(target=produce, args=(k, e["src"]))
                   for k, (_p, e) in enumerate(pipes)]
        drains = [threading.Thread(target=consume,
                                   args=(e["sink"], outs[k]))
                  for k, (_p, e) in enumerate(pipes)]
        for t in threads + drains:
            t.start()
        assert flowing.wait(timeout=30)
        entry.reload_model("_t_lc_v2", version="v2")   # mid-stream
        swapped.set()
        for t in threads:
            t.join(timeout=60)
            assert not t.is_alive()
        for p, _e in pipes:
            assert p.wait_eos(timeout=30)
        done.set()
        for t in drains:
            t.join(timeout=30)
            assert not t.is_alive()
        for k, got in enumerate(outs):
            assert [b.pts for b in got] == list(range(pushed[k]))
            vals = _vals(got)
            flip = vals.index(3.0)
            assert flip > 0
            assert vals == [1.0] * flip + [3.0] * (len(vals) - flip)
        assert entry.lifecycle.swaps == 1
    finally:
        for p, _e in pipes:
            p.stop()


def test_reload_event_routes_through_pool_and_respects_updatable():
    from nnstreamer_tpu.runtime.events import Event, EventKind

    p, e = _pool_pipe("lc-evt")
    p.start()
    try:
        e["flt"].handle_event(None, Event(
            EventKind.RELOAD_MODEL, data={"model": "_t_lc_v2",
                                          "version": "ev2"}))
        lc = e["flt"].pool.lifecycle
        assert lc.baseline.tag == "ev2" and lc.swaps == 1
    finally:
        p.stop()


def test_reload_event_not_updatable_posts_error():
    from nnstreamer_tpu.runtime.events import Event, EventKind

    spec = TensorsSpec.from_shapes([SHAPE], np.float32)
    p = Pipeline(name="lc-noupd")
    src = AppSrc(name="src", spec=spec)
    flt = TensorFilter(name="net", framework="jax-xla",
                       model="_t_lc", share_model=True)
    sink = AppSink(name="sink")
    p.add(src, flt, sink).link(src, flt, sink)
    errors = []
    from nnstreamer_tpu.runtime.events import MessageKind

    p.bus.add_watch(lambda m: errors.append(m)
                    if m.kind == MessageKind.ERROR else None)
    p.start()
    try:
        flt.handle_event(None, Event(EventKind.RELOAD_MODEL,
                                     data={"model": "_t_lc_v2"}))
        deadline = time.monotonic() + 5
        while not errors and time.monotonic() < deadline:
            time.sleep(0.01)
        assert errors, "expected a not-updatable error on the bus"
        lc = getattr(flt.pool, "_lifecycle", None)
        assert lc is None or lc.swaps == 0
    finally:
        p.stop()


# -- canary routing -----------------------------------------------------------


def _canary_rig(n_pipes=4, canary="next:1/2"):
    pipes = []
    for i in range(n_pipes):
        p, e = _pool_pipe(f"lc-can-{i}", batch=4, canary=canary)
        p.start()
        pipes.append((p, e))
    return pipes


def test_canary_routes_1_in_n_streams_with_per_version_fifo():
    pipes = _canary_rig(n_pipes=4, canary="next:1/2")
    try:
        entry = pipes[0][1]["flt"].pool
        res = entry.reload_model("_t_lc_v2", version="v2")
        assert res == {"version": "v2", "n": 2, "streams": 2}
        lc = entry.lifecycle
        assert lc.canary_active and lc.canary_n == 2
        n = 12
        threads = [threading.Thread(target=_push_n,
                                    args=(e["src"], n))
                   for _p, e in pipes]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        canary_streams = 0
        for _p, e in pipes:
            got = _pull_all(e["sink"], n)
            assert len(got) == n  # zero loss per stream
            # per-stream FIFO: pts strictly in order
            assert [b.pts for b in got] == sorted(b.pts for b in got)
            vals = set(_vals(got))
            # version-homogeneous stream: all frames served by ONE
            # version (1.0 = baseline x+1, 3.0 = canary x+3 on zeros)
            assert vals in ({1.0}, {3.0})
            if vals == {3.0}:
                canary_streams += 1
        assert canary_streams == 2  # exactly 1-in-2 of 4 streams
        summary = lc.summary()
        assert summary["canary_streams"] == 2
        assert summary["canary_frames"] == 2 * n
        # per-version rows land in the snapshot models table
        snap = REGISTRY.snapshot()
        assert snap["version"] == 10
        rows = {r["version"]: r for r in snap["models"]
                if r["pool"] == entry.label()}
        assert rows["v2"]["state"] == "canary"
        assert rows["v2"]["frames"] == 2 * n
        assert rows[lc.baseline.tag]["frames"] >= 2 * n
        lc.promote(force=True)
        assert not lc.canary_active and lc.baseline.tag == "v2"
    finally:
        for p, _e in pipes:
            p.stop()


def test_canary_rollback_restores_baseline_only_serving():
    pipes = _canary_rig(n_pipes=2, canary="next:1/2")
    try:
        entry = pipes[0][1]["flt"].pool
        entry.reload_model("_t_lc_v2", version="v2")
        lc = entry.lifecycle
        assert lc.canary_active
        res = lc.rollback()
        assert res["rolled_back"] and res["canary"]
        assert not lc.canary_active and lc.rollbacks == 1
        for _p, e in pipes:
            _push_n(e["src"], 4)
            got = _pull_all(e["sink"], 4)
            assert set(_vals(got)) == {1.0}  # baseline x+1 on zeros
    finally:
        for p, _e in pipes:
            p.stop()


def test_canary_comparator_alert_rolls_back_through_the_playbook():
    """The automatic verdict, end to end but for the clock: while the
    canary's latency sits beside the baseline's nothing fires; at four
    times the baseline's the comparator rule fires and the rollback
    playbook, aimed by the alert's own pool label, ends the canary."""
    from nnstreamer_tpu.obs.control import Controller, Playbook
    from nnstreamer_tpu.obs.watch import AlertRule, Watch

    pipes = _canary_rig(n_pipes=2, canary="next:1/2")
    try:
        entry = pipes[0][1]["flt"].pool
        entry.reload_model("_t_lc_v2", version="v2")
        lc = entry.lifecycle
        assert lc.canary_active
        for act in lc.actuators().values():
            act.cooldown_s = 0.0
        ratio = {"v": 1.0}

        def gauge(name, value):
            return {"name": name, "kind": "gauge", "help": "",
                    "samples": [{"labels": {"pool": entry.label()},
                                 "value": value}]}

        w = Watch(rules=[AlertRule(
            name="canary-regressed", kind="threshold",
            metric="nns_model_canary_latency_us",
            per="nns_model_baseline_latency_us", op=">", value=3.0,
            severity="critical")], interval_s=0.02, source=lambda: [
            {"endpoint": "local", "error": None, "snap": {
                "pools": [], "metrics": {
                    "nns_model_canary_latency_us": gauge(
                        "nns_model_canary_latency_us",
                        100.0 * ratio["v"]),
                    "nns_model_baseline_latency_us": gauge(
                        "nns_model_baseline_latency_us", 100.0)}}}])
        ctl = Controller(playbooks=[Playbook(
            name="canary-rollback", rule="canary-regressed",
            kind="model", actuator="rollback", action="set", value=1.0,
            cooldown_s=0.1)], watch=w, interval_s=0.02)
        for t in (1.0, 2.0, 3.0):
            assert w.sample_once(t) == []
        assert ctl.tick() == [] and lc.canary_active
        ratio["v"] = 4.0
        fired = w.sample_once(4.0) + w.sample_once(5.0)
        assert [ev["rule"] for ev in fired] == ["canary-regressed"]
        assert [(d["playbook"], d["outcome"]) for d in ctl.tick()] \
            == [("canary-rollback", "applied")]
        assert not lc.canary_active and lc.rollbacks == 1
        for _p, e in pipes:
            _push_n(e["src"], 4)
            assert set(_vals(_pull_all(e["sink"], 4))) == {1.0}
        ctl.stop()
        w.stop()
    finally:
        for p, _e in pipes:
            p.stop()


def test_declared_canary_tag_gates_the_split():
    """`canary=v7:1/2` canaries only version v7: reloading any OTHER
    version cuts over directly (an undeclared version gets no split),
    while `next:1/N` canaries whatever gets staged."""
    pipes = _canary_rig(n_pipes=2, canary="v7:1/2")
    try:
        entry = pipes[0][1]["flt"].pool
        res = entry.reload_model("_t_lc_v2", version="v9")
        lc = entry.lifecycle
        assert not lc.canary_active  # v9 != v7: direct swap
        assert lc.swaps == 1 and res.get("version") == "v9"
        res = entry.reload_model("_t_lc", version="v7")
        assert lc.canary_active and res["n"] == 2  # declared tag
    finally:
        for p, _e in pipes:
            p.stop()


def test_actuator_discovery_does_not_engage_lifecycle_telemetry():
    """`nns-ctl --list` (list_actuators) builds a manager for every
    pool; a merely-discovered pool must NOT grow models rows or a
    lifecycle block — exported state changes only when the lifecycle
    is actually used."""
    p, e = _pool_pipe("lc-disc")
    p.start()
    try:
        entry = e["flt"].pool
        assert find_actuators("model", entry.label(), "swap")
        lc = entry._lifecycle
        assert lc is not None and not lc.engaged
        snap = REGISTRY.snapshot()
        assert not [r for r in snap["models"]
                    if r["pool"] == entry.label()]
        pool_row = [r for r in snap["pools"]
                    if r["pool"] == entry.label()][0]
        assert "lifecycle" not in pool_row
        entry.reload_model("_t_lc_v2")
        assert lc.engaged
        snap = REGISTRY.snapshot()
        assert [r for r in snap["models"]
                if r["pool"] == entry.label()]
    finally:
        p.stop()


def test_promote_refused_before_min_canary_frames():
    pipes = _canary_rig(n_pipes=2, canary="next:1/2")
    try:
        entry = pipes[0][1]["flt"].pool
        entry.reload_model("_t_lc_v2")
        lc = entry.lifecycle
        with pytest.raises(ActuationError, match="frames"):
            lc.promote()
        assert lc.canary_active  # still canarying; verdict deferred
    finally:
        for p, _e in pipes:
            p.stop()


def test_canary_error_isolated_to_canary_streams():
    register_model("_t_lc_boom", lambda x: x + 1.0,
                   in_shapes=[SHAPE], in_dtypes=np.float32)
    try:
        pipes = _canary_rig(n_pipes=2, canary="next:1/2")
        try:
            entry = pipes[0][1]["flt"].pool
            entry.reload_model("_t_lc_boom", version="vboom")
            lc = entry.lifecycle

            # break the canary's executable AFTER staging: every
            # canary window now raises while baseline serving stays
            # untouched
            def boom(*_a, **_k):
                raise RuntimeError("canary exploded")

            lc._canary.subplugin.invoke_batched = boom
            from nnstreamer_tpu.runtime.events import MessageKind

            errors = {i: [] for i in range(2)}
            for i, (p, _e) in enumerate(pipes):
                p.bus.add_watch(
                    lambda m, i=i: errors[i].append(m)
                    if m.kind == MessageKind.ERROR else None)
            canary_idx = [i for i, (_p, e) in enumerate(pipes)
                          if lc.is_canary_stream(e["flt"])]
            assert len(canary_idx) == 1
            for _p, e in pipes:
                _push_n(e["src"], 4)
            base_idx = 1 - canary_idx[0]
            got = _pull_all(pipes[base_idx][1]["sink"], 4)
            assert len(got) == 4 and set(_vals(got)) == {1.0}
            deadline = time.monotonic() + 5
            while not errors[canary_idx[0]] \
                    and time.monotonic() < deadline:
                time.sleep(0.01)
            assert errors[canary_idx[0]], "canary bus got no error"
            assert not errors[base_idx], "baseline bus polluted"
            assert lc._canary.errors >= 1
            # the error series feeds the rollback judge
            assert lc.summary()["canary_errors"] >= 1
        finally:
            for p, _e in pipes:
                p.stop()
    finally:
        unregister_model("_t_lc_boom")


# -- actuators ----------------------------------------------------------------


def test_model_actuators_swap_promote_rollback():
    p, e = _pool_pipe("lc-act")
    p.start()
    try:
        entry = e["flt"].pool
        acts = entry.lifecycle.actuators()
        assert set(acts) == {"swap", "canary", "promote", "rollback"}
        for a in acts.values():
            a.cooldown_s = 0.0
        res = acts["swap"].actuate("_t_lc_v2")
        assert res["applied"] == "_t_lc_v2"
        assert entry.lifecycle.baseline.tag == "v1"
        _push_n(e["src"], 4)
        assert set(_vals(_pull_all(e["sink"], 4))) == {3.0}
        # revert of a swap is a rollback to the retained prior
        acts["swap"].revert()
        assert entry.lifecycle.rollbacks == 1
        _push_n(e["src"], 4, start=50)
        assert set(_vals(_pull_all(e["sink"], 4))) == {1.0}
        # discovery: the model kind lists these knobs
        names = {(a.kind, a.name) for a in list_actuators("model")}
        assert ("model", "swap") in names
        assert find_actuators("model", entry.label(), "rollback")
    finally:
        p.stop()


def test_swap_rollback_actuators_race_pipeline_stop():
    """The PR-11 race harness on the lifecycle knobs: 3 threads
    hammering swap/revert while pipelines start, stream and stop —
    never a crash, torn-down targets fail with a clean
    ActuationError."""
    errors = []
    stop_evt = threading.Event()
    outcomes = {"ok": 0, "gone": 0}

    def actuator_thread():
        while not stop_evt.is_set():
            try:
                for act in list_actuators("model"):
                    if act.name not in ("swap", "rollback"):
                        continue
                    try:
                        act.cooldown_s = 0.0
                        if act.name == "swap":
                            act.actuate("_t_lc_v2")
                            act.revert()
                        else:
                            act.actuate(1.0)
                        outcomes["ok"] += 1
                    except ActuationError:
                        outcomes["gone"] += 1  # stop() won the race
            except Exception as exc:  # noqa: BLE001 - the assertion
                errors.append(exc)
                return

    # one long-lived sharer keeps the pool entry (and its lifecycle)
    # alive across rounds: a swap takes real compile time, so against
    # per-round entries alone EVERY actuation can lose the teardown
    # race and the "ok" leg would assert nothing.  The round pipes
    # still attach/detach streams and stop mid-actuation.
    keeper, ke = _pool_pipe("lc-race-keeper")
    keeper.start()
    threads = [threading.Thread(target=actuator_thread)
               for _ in range(3)]
    for t in threads:
        t.start()
    try:
        for round_i in range(5):
            p, e = _pool_pipe(f"lc-race-{round_i}")
            p.start()
            _push_n(e["src"], 4)
            e["src"].end_of_stream()
            p.wait_eos(timeout=10, raise_on_error=False)
            p.stop()
    finally:
        stop_evt.set()
        for t in threads:
            t.join(timeout=15)
        keeper.stop()
    assert not errors, errors
    assert outcomes["ok"] > 0


# -- persistent AOT compile cache ---------------------------------------------


def _heavyish(name):
    w = np.random.default_rng(3).standard_normal((32, 32)) \
        .astype(np.float32)

    def fn(x):
        import jax.numpy as jnp

        for _ in range(4):
            x = jnp.tanh(x @ w)
        return x

    register_model(name, fn, in_shapes=[(32,)], in_dtypes=np.float32)
    return name


def _persist_hits():
    return sum(r["count"] for r in COMPILE_STATS.snapshot()
               if r["kind"] == "persist_hit")


def test_persistent_cache_hits_and_counts(tmp_path, monkeypatch):
    monkeypatch.setenv("NNS_TPU_COMPILE_CACHE_DIR", str(tmp_path))
    name = _heavyish("_t_lc_pc1")
    try:
        x = np.zeros((32,), np.float32)

        def run():
            sp = JaxXlaFilter()
            sp.configure(FilterProps(framework="jax-xla", model=name))
            sp.invoke([x])[0].block_until_ready()
            outs = sp.invoke_batched([[x]] * 2, 2)
            for o in outs[0]:
                o.block_until_ready()
            sp.close()

        before = compilecache.CACHE_STATS.snapshot()
        hits0 = _persist_hits()
        run()  # populate: misses + stores, no hits
        mid = compilecache.CACHE_STATS.snapshot()
        assert mid["stores"] - before["stores"] == 2
        assert _persist_hits() == hits0
        run()  # fresh instance, warm cache: pure deserialize
        after = compilecache.CACHE_STATS.snapshot()
        assert after["hits"] - mid["hits"] == 2
        assert _persist_hits() - hits0 == 2
        # the registry exports the same persist_hit count
        fam = REGISTRY.collect()["nns_compiles_total"]
        exported = sum(s["value"] for s in fam["samples"]
                       if s["labels"].get("kind") == "persist_hit")
        assert exported == _persist_hits()
        assert len(os.listdir(str(tmp_path))) == 2
    finally:
        unregister_model(name)


def test_cache_load_roundtrips_onto_the_executables_own_devices(
        tmp_path, monkeypatch):
    """A host with several devices (this suite's 8 virtual CPUs; a
    four-chip TPU host) must load a persisted executable back onto the
    devices it was compiled for: a one-device program onto that one
    device — not the default device 0, and not every device of the
    backend — and a mesh program onto its mesh."""
    import jax
    import jax.numpy as jnp

    monkeypatch.setenv("NNS_TPU_COMPILE_CACHE_DIR", str(tmp_path))
    devs = jax.devices()
    assert len(devs) >= 8
    x = np.arange(8, dtype=np.float32)

    one = devs[3]
    lowered = jax.jit(lambda v: jnp.tanh(v) * 2.0).lower(
        jax.ShapeDtypeStruct((8,), np.float32,
                             sharding=jax.sharding.SingleDeviceSharding(
                                 one)))
    assert compilecache.store("one-device", lowered.compile())
    loaded = compilecache.load("one-device", [one])
    out = loaded(jax.device_put(x, one))
    assert out.sharding.device_set == {one}
    np.testing.assert_allclose(np.asarray(out), np.tanh(x) * 2.0,
                               rtol=1e-6)

    mesh = jax.sharding.Mesh(np.array(devs[4:8]), ("data",))
    sh = jax.sharding.NamedSharding(
        mesh, jax.sharding.PartitionSpec("data"))
    lowered = jax.jit(lambda v: v + 1.0, in_shardings=sh,
                      out_shardings=sh).lower(
        jax.ShapeDtypeStruct((8,), np.float32))
    assert compilecache.store("mesh", lowered.compile())
    loaded = compilecache.load("mesh", list(mesh.devices.flat))
    out = loaded(jax.device_put(x, sh))
    assert out.sharding.device_set == set(devs[4:8])
    np.testing.assert_allclose(np.asarray(out), x + 1.0)


def test_persistent_cache_corruption_falls_back(tmp_path, monkeypatch):
    monkeypatch.setenv("NNS_TPU_COMPILE_CACHE_DIR", str(tmp_path))
    name = _heavyish("_t_lc_pc2")
    try:
        x = np.zeros((32,), np.float32)

        def run():
            sp = JaxXlaFilter()
            sp.configure(FilterProps(framework="jax-xla", model=name))
            out = sp.invoke([x])
            out[0].block_until_ready()
            val = float(np.asarray(out[0])[0])
            sp.close()
            return val

        good = run()
        for f in os.listdir(str(tmp_path)):  # corrupt every entry
            with open(os.path.join(str(tmp_path), f), "wb") as fh:
                fh.write(b"not an executable")
        before = compilecache.CACHE_STATS.snapshot()
        assert run() == good  # recompiles, same result
        after = compilecache.CACHE_STATS.snapshot()
        assert after["errors"] > before["errors"]
        # the bad entries were dropped and re-stored
        assert after["stores"] > before["stores"]
    finally:
        unregister_model(name)


def test_persistent_cache_version_skew_misses(tmp_path, monkeypatch):
    """A jax/jaxlib version bump changes the KEY — a skewed process
    simply misses instead of deserializing an incompatible program."""
    monkeypatch.setenv("NNS_TPU_COMPILE_CACHE_DIR", str(tmp_path))
    name = _heavyish("_t_lc_pc3")
    try:
        x = np.zeros((32,), np.float32)
        sp = JaxXlaFilter()
        sp.configure(FilterProps(framework="jax-xla", model=name))
        sp.invoke([x])[0].block_until_ready()
        sp.close()
        n_entries = len(os.listdir(str(tmp_path)))
        monkeypatch.setattr(compilecache, "_versions",
                            lambda: ("99.0.0", "99.0.0"))
        before = compilecache.CACHE_STATS.snapshot()
        sp = JaxXlaFilter()
        sp.configure(FilterProps(framework="jax-xla", model=name))
        sp.invoke([x])[0].block_until_ready()
        sp.close()
        after = compilecache.CACHE_STATS.snapshot()
        assert after["hits"] == before["hits"]  # no cross-version hit
        assert after["misses"] > before["misses"]
        # the skewed build stored under ITS key; both coexist
        assert len(os.listdir(str(tmp_path))) > n_entries
    finally:
        unregister_model(name)


def test_cache_disabled_on_unwritable_dir(tmp_path, monkeypatch):
    monkeypatch.setenv("NNS_TPU_COMPILE_CACHE_DIR",
                       str(tmp_path / "missing"))
    assert compilecache.cache_dir() is None
    assert not compilecache.enabled()
    assert compilecache.load("deadbeef") is None
    monkeypatch.setenv("NNS_TPU_COMPILE_CACHE_DIR", str(tmp_path))
    assert compilecache.cache_dir() == str(tmp_path)


# -- obs surface --------------------------------------------------------------


def test_pool_row_lifecycle_and_comparator_export():
    pipes = _canary_rig(n_pipes=2, canary="next:1/2")
    try:
        entry = pipes[0][1]["flt"].pool
        entry.reload_model("_t_lc_v2", version="v2")
        for _p, e in pipes:
            _push_n(e["src"], 8)
            _pull_all(e["sink"], 8)
        snap = REGISTRY.snapshot()
        pool_row = [r for r in snap["pools"]
                    if r["pool"] == entry.label()][0]
        lcrow = pool_row["lifecycle"]
        assert lcrow["canary_n"] == 2 and lcrow["canary_streams"] == 1
        fams = snap["metrics"]
        assert "nns_model_version_frames_total" in fams
        assert "nns_model_canary_frames_total" in fams
        # the comparator pair exports under the POOL label only
        for fam in ("nns_model_canary_latency_us",
                    "nns_model_baseline_latency_us"):
            if fam in fams:
                for s in fams[fam]["samples"]:
                    assert set(s["labels"]) == {"pool"}
        # nns-top renders the MODELS section
        from nnstreamer_tpu.obs.top import render

        txt = render(snap)
        assert "MODELS" in txt and "canary" in txt
        assert "1/2" in txt
    finally:
        for p, _e in pipes:
            p.stop()


def test_nns_ctl_swap_spec_parses_text_value():
    from nnstreamer_tpu.obs.control import _parse_spec

    kind, target, name, value = _parse_spec(
        "model:jax-xla:_t_lc:swap=file:///m.pkl@v2")
    assert (kind, name) == ("model", "swap")
    assert target == "jax-xla:_t_lc"
    assert value == "file:///m.pkl@v2"
    kind, target, name, value = _parse_spec("model:*:promote=1")
    assert value == 1.0


def test_controller_apply_routes_text_swap_through_audit():
    from nnstreamer_tpu.obs.control import Controller

    p, e = _pool_pipe("lc-ctl")
    p.start()
    try:
        entry = e["flt"].pool
        for a in entry.lifecycle.actuators().values():
            a.cooldown_s = 0.0
        ctl = Controller(playbooks=[])
        out = ctl.apply("model", entry.label(), "swap",
                        value="_t_lc_v2")
        assert out and out[0]["outcome"] == "applied"
        assert entry.lifecycle.swaps == 1
        # the decision landed in the audit ring like any playbook's
        assert any(d["actuator"] == "swap" for d in ctl.audit)
    finally:
        p.stop()
