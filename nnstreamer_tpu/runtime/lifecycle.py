"""Zero-downtime model lifecycle: hot swap, canarying, auto-verdict.

The last missing leg of the serve-measure-steer loop (ROADMAP item 3):
production serving could *measure* everything about a pool but could
not ship a new checkpoint into it — ``share-model`` refused
``is-updatable`` (PR 3), and a reload elsewhere recompiled inline on
the dispatch path.  This module is the model *lifecycle* layer on top
of the serving pool:

- :class:`ModelVersion` / :class:`VersionManager` — a per-
  :class:`~nnstreamer_tpu.runtime.serving.PoolEntry` registry of model
  versions with per-version
  :class:`~nnstreamer_tpu.utils.stats.InvokeStats` and error counts,
  exported as the ``nns_model_version_*`` registry families, the
  snapshot v7 ``models`` table, and the ``nns-top`` MODELS section.

- **Double-buffered hot swap**: :meth:`VersionManager.stage` resolves
  a (possibly versioned — ``filters/modeluri.py``) model reference and
  builds a fully-warmed SHADOW instance off the dispatch path
  (``JaxXlaFilter.prepare_swap``: single-frame + every hot bucket
  executable compiled and first-called) while the old executable keeps
  serving; :meth:`VersionManager.swap` flips atomically at a *window
  boundary* (the batcher's flush serialization lock) — zero dropped
  frames (``tests/test_lifecycle.py``), and the flip stall is a pointer
  swap under that lock (:attr:`VersionManager.last_swap_stall_s`).

- **Canarying with automatic verdict**: ``canary=<tag>:1/N``
  (pool-level ``tensor_filter`` property, or the ``canary`` actuator)
  routes 1-in-N *streams* of the pool to the staged version.  Canary
  windows dispatch through the shadow instance — a failing canary
  errors only its own streams' buses — and export the comparator pair
  ``nns_model_canary_latency_us`` / ``nns_model_baseline_latency_us``
  (+ ``nns_model_canary_errors_total``), so a plain nns-watch
  threshold rule with ``per=`` IS the canary judge, and an nns-ctl
  playbook on the ``promote``/``rollback`` actuators closes the loop
  (promotion and rollback both land in PR 11's decision audit ring).

Every knob is exposed through the actuator API
(``runtime/actuators.py``, kind ``model``): ``swap`` and ``canary``
take the model reference as a TEXT value (``nns-ctl --apply
model:<pool>:swap=file://new.pkl@v2``), ``promote``/``rollback`` are
numeric and playbook-drivable.
"""

from __future__ import annotations

import threading
import time
from typing import Any, Dict, List, Optional, Tuple

from ..utils.log import logi, logw
from ..utils.stats import InvokeStats

#: version states, also exported numerically on
#: ``nns_model_version_state`` (staged=0 serving=1 canary=2 retired=3
#: rolled-back=4)
STATES = ("staged", "serving", "canary", "retired", "rolled-back")

#: default minimum canary frames before ``promote`` is allowed —
#: a canary that served nothing has proven nothing (override per
#: manager, or force=True)
MIN_CANARY_FRAMES = 16


class LifecycleError(ValueError):
    """A lifecycle operation that cannot apply (bad canary grammar,
    nothing staged, premature promote)."""


def parse_canary(spec: str) -> Tuple[str, int]:
    """``"<tag>:1/N"`` → ``(tag, N)``; ``""`` → ``("", 0)`` (no
    canary).  ``tag`` names the version the split applies to — use
    ``next`` for "whatever gets staged next".  The short form
    ``"1/N"`` implies ``next``."""
    s = str(spec or "").strip()
    if not s:
        return "", 0
    tag, sep, ratio = s.rpartition(":")
    if not sep:
        tag, ratio = "", s
    tag = tag.strip() or "next"
    num, sep, den = ratio.partition("/")
    try:
        if not sep or int(num) != 1:
            raise ValueError
        n = int(den)
    except ValueError:
        raise LifecycleError(
            f"canary spec {spec!r}: want '<version>:1/N' (or '1/N'), "
            f"e.g. 'next:1/4' — one in N streams routes to the canary"
        ) from None
    if n < 2:
        raise LifecycleError(
            f"canary spec {spec!r}: N must be >= 2 (1/1 is a full "
            f"swap — use the swap actuator)")
    return tag, n


class ModelVersion:
    """One version of a pool's model: identity + provenance + its own
    serving stats.  ``subplugin`` is the live instance serving this
    version — the pool's shared instance for the baseline, the
    prepared shadow for a staged/canary version."""

    def __init__(self, tag: str, source: str, subplugin: Any,
                 state: str = "staged"):
        self.tag = str(tag)
        self.source = str(source)
        self.subplugin = subplugin
        self.state = state
        self.stats = InvokeStats()
        self.errors = 0  # failed dispatches attributed to this version
        self.staged_wall = time.time()
        self.load_s = 0.0  # off-path load+compile+warm seconds

    def row(self, pool: str, canary_n: int) -> dict:
        s = self.stats.snapshot()
        return {
            "pool": pool,
            "version": self.tag,
            "state": self.state,
            "source": self.source,
            "invokes": s["invokes"],
            "frames": s["frames"],
            "latency_us": s["latency_us"],
            "errors": self.errors,
            "canary_n": canary_n if self.state == "canary" else 0,
            "load_s": round(self.load_s, 6),
            "staged_wall": self.staged_wall,
        }


class VersionManager:
    """Per-PoolEntry double-buffered version registry + the swap /
    canary / promote / rollback state machine.

    Thread model: mutations (stage/swap/promote/rollback/canary
    routing) serialize on ``self._lock``; the FLIP itself additionally
    holds the pool batcher's flush-serialization lock so it lands
    between windows.  The dispatch path only ever reads
    ``self._canary``/``self._assign`` through
    :meth:`partition`/:meth:`subplugin_for` — one dict read, no lock
    ordering against the dispatch."""

    def __init__(self, entry: Any):
        import weakref

        self._entry_ref = weakref.ref(entry)
        self._lock = threading.RLock()
        sp = entry.subplugin
        self.baseline = ModelVersion(
            "v0", self._source_of(sp), sp, state="serving")
        self._canary: Optional[ModelVersion] = None
        self._staged: Optional[ModelVersion] = None
        self.canary_n = 0
        self.default_canary: Tuple[str, int] = ("", 0)  # canary= prop
        self.min_canary_frames = MIN_CANARY_FRAMES
        #: stream routing: id(owner) -> True when the stream rides the
        #: canary version (rebuilt on canary start, extended on attach)
        self._assign: Dict[int, bool] = {}
        self._attach_seq = 0
        self.swaps = 0
        self.promotes = 0
        self.rollbacks = 0
        self._rollback_ref: Optional[ModelVersion] = None
        self.last_swap_stall_s = 0.0
        self.history: List[dict] = []  # bounded swap provenance trail
        self._actuators: Dict[str, Any] = {}
        self._seq = 0  # version sequence for auto tags

    # -- introspection --------------------------------------------------------

    @property
    def entry(self):
        e = self._entry_ref()
        if e is None:
            from .actuators import ActuationError

            raise ActuationError(
                "model lifecycle: the owning pool entry is gone")
        return e

    @staticmethod
    def _source_of(sp: Any) -> str:
        mn = getattr(sp, "model_name", None)
        return str(mn()) if callable(mn) else ""

    @property
    def canary_active(self) -> bool:
        return self._canary is not None and self.canary_n > 1

    @property
    def engaged(self) -> bool:
        """Whether the lifecycle has actually been USED (a stage, swap,
        canary or rollback happened).  Actuator discovery constructs
        managers for every pool; a merely-discovered pool must not
        start exporting version rows — the `models` table stays
        "pools whose lifecycle was engaged" either way."""
        with self._lock:
            return bool(self.swaps or self.promotes or self.rollbacks
                        or self._staged is not None
                        or self._canary is not None
                        or len(self.history))

    def versions(self) -> List[ModelVersion]:
        with self._lock:
            out = [self.baseline]
            if self._canary is not None:
                out.append(self._canary)
            if self._staged is not None and self._staged is not self._canary:
                out.append(self._staged)
            return out

    def snapshot_rows(self) -> List[dict]:
        """The ``models`` table rows of this pool (snapshot v7)."""
        label = self._entry_label()
        with self._lock:
            n = self.canary_n
            rows = [v.row(label, n) for v in self.versions()]
        return rows

    def summary(self) -> dict:
        """Pool-level lifecycle figures (swaps/promotes/rollbacks +
        the live comparator pair) for the registry export."""
        with self._lock:
            out = {
                "swaps": self.swaps,
                "promotes": self.promotes,
                "rollbacks": self.rollbacks,
                "canary_n": self.canary_n if self.canary_active else 0,
                "canary_streams": sum(
                    1 for c in self._assign.values() if c),
                "last_swap_stall_s": self.last_swap_stall_s,
            }
            if self.canary_active:
                out["canary_version"] = self._canary.tag
                out["canary_latency_us"] = self._canary.stats.latency_us
                out["baseline_latency_us"] = self.baseline.stats.latency_us
                out["canary_errors"] = self._canary.errors
                out["canary_frames"] = \
                    self._canary.stats.total_frame_num
        return out

    def _entry_label(self) -> str:
        e = self._entry_ref()
        return e.label() if e is not None else "?"

    def _note(self, event: str, **data) -> None:
        rec = {"event": event, "wall": time.time(), **data}
        with self._lock:
            self.history.append(rec)
            del self.history[:-64]
        from ..obs.flightrec import FLIGHT

        FLIGHT.note("lifecycle", f"{self._entry_label()}:{event}",
                    **{k: v for k, v in data.items()
                       if isinstance(v, (str, int, float, bool))})

    # -- stage ----------------------------------------------------------------

    def stage(self, model: Any, version: str = "",
              warm: bool = True) -> ModelVersion:
        """Load + compile a replacement OFF the dispatch path: resolve
        the (possibly ``@``-versioned) reference, build the warmed
        shadow instance via the framework's ``prepare_swap``, and park
        it as the staged version.  The old executable serves throughout
        — this can take seconds and drops nothing.  Staging again
        replaces a previously staged (un-canaried) version."""
        from ..filters.api import FilterError
        from ..filters.modeluri import resolve_model_uri_versioned
        from .actuators import ActuationError

        entry = self.entry
        resolved, tag = resolve_model_uri_versioned(model)
        if isinstance(resolved, str) and _is_orbax_dir(resolved):
            # orbax checkpoint (step) directory: weights-only swap —
            # load the pytree and keep the serving architecture
            from ..trainers.checkpoint import load_orbax

            source = str(resolved)
            resolved = load_orbax(resolved)
        else:
            source = resolved if isinstance(resolved, str) \
                else getattr(resolved, "name", repr(type(resolved)))
        with self._lock:
            self._seq += 1
            version = str(version or tag or f"v{self._seq}")
        sp = entry.subplugin
        prep_fn = getattr(sp, "prepare_swap", None)
        if not callable(prep_fn):
            raise ActuationError(
                f"{entry.label()}: framework "
                f"{getattr(sp, 'NAME', type(sp).__name__)!r} has no "
                f"prepare_swap — it does not support hot reload "
                f"(nns-lint NNS513 flags is-updatable on it)")
        t0 = time.perf_counter()
        buckets = entry.buckets if entry.batcher is not None else ()
        try:
            shadow = prep_fn(resolved, buckets=buckets, warm=warm)
        except FilterError as e:
            raise ActuationError(
                f"{entry.label()}: staging {source!r} failed: {e}"
            ) from e
        ver = ModelVersion(version, f"{source}@{tag}" if tag else source,
                           shadow)
        ver.load_s = time.perf_counter() - t0
        with self._lock:
            self._staged = ver
        self._note("stage", version=version, source=ver.source,
                   load_s=round(ver.load_s, 4))
        logi("%s: staged model version %s (%s) in %.3fs off-path",
             self._entry_label(), version, ver.source, ver.load_s)
        return ver

    # -- the flip -------------------------------------------------------------

    def _window_boundary(self):
        """Context guard serializing against the pool's in-flight
        window: holding the batcher's flush lock means no window is
        mid-dispatch, so the flip lands BETWEEN windows.  Pools without
        a live batcher (per-frame fallback) flip under the entry lock
        alone — the framework's ``_swap_lock`` already keeps any single
        dispatch consistent."""
        entry = self.entry
        b = entry.batcher
        if b is not None:
            return b._flush_serial_lock
        return threading.Lock()  # uncontended stand-in

    def swap(self, version: Optional[ModelVersion] = None) -> dict:
        """Commit the staged (or given) version as the serving model:
        the double-buffer flip, at a window boundary, stall measured.
        Frames parked in the window simply ride the next dispatch on
        the new version — nothing is dropped, nothing re-queues."""
        from .actuators import ActuationError

        entry = self.entry
        with self._lock:
            ver = version or self._staged
            if ver is None:
                raise ActuationError(
                    f"{entry.label()}: nothing staged to swap in "
                    f"(stage a model first: swap=<model-ref>)")
        sp = entry.subplugin
        # retain the OUTGOING version's executable state BEFORE the
        # flip: post-commit the shared instance serves the new model,
        # so "swap back" needs this holder (commit_swap-compatible)
        prior_state = _swap_state_of(sp)
        t0 = time.perf_counter()
        with self._window_boundary():
            sp.commit_swap(ver.subplugin)
            stall = time.perf_counter() - t0
        with self._lock:
            old = self.baseline
            old.state = "retired"
            old.subplugin = prior_state
            ver.state = "serving"
            # the new baseline serves THROUGH the pool's shared
            # instance; the canary/staged stats carry over so the
            # version's history survives promotion
            nb = ModelVersion(ver.tag, ver.source, sp, state="serving")
            nb.stats = ver.stats
            nb.load_s = ver.load_s
            self.baseline = nb
            self._rollback_ref = old
            if self._staged is ver:
                self._staged = None
            if self._canary is ver:
                self._canary = None
                self.canary_n = 0
                self._assign = {}
            self.swaps += 1
            self.last_swap_stall_s = stall
        self._note("swap", version=ver.tag, source=ver.source,
                   stall_s=round(stall, 6))
        logi("%s: hot-swapped to version %s (%s), flip stall %.3f ms",
             self._entry_label(), ver.tag, ver.source, stall * 1e3)
        return {"version": ver.tag, "stall_s": stall}

    # -- canary ---------------------------------------------------------------

    def start_canary(self, n: int,
                     version: Optional[ModelVersion] = None) -> dict:
        """Route 1-in-``n`` attached streams to the staged version.
        Stream assignment is deterministic (attach order): every
        ``n``-th stream rides the canary; streams attaching later keep
        the same modulus."""
        from .actuators import ActuationError

        entry = self.entry
        n = int(n)
        if n < 2:
            raise ActuationError(
                f"{entry.label()}: canary needs N >= 2 (got {n}); use "
                f"swap for a full cutover")
        with self._lock:
            ver = version or self._staged
            if ver is None:
                raise ActuationError(
                    f"{entry.label()}: nothing staged to canary "
                    f"(stage via swap=<ref> or RELOAD_MODEL first)")
            self._canary = ver
            self._staged = ver  # promote/rollback resolve to it
            ver.state = "canary"
            self.canary_n = n
            self._assign = {}
            self._attach_seq = 0
            for sid in self._stream_ids():
                self._assign[sid] = self._attach_seq % n == n - 1
                self._attach_seq += 1
        routed = sum(1 for c in self._assign.values() if c)
        self._note("canary-start", version=ver.tag, n=n,
                   streams=routed)
        logi("%s: canarying version %s on 1-in-%d streams (%d routed)",
             self._entry_label(), ver.tag, n, routed)
        return {"version": ver.tag, "n": n, "streams": routed}

    def _stream_ids(self) -> List[int]:
        e = self._entry_ref()
        if e is None:
            return []
        with e._lock:
            return list(e._streams.keys())

    def on_attach(self, owner: Any) -> None:
        """Keep the 1-in-N routing law over streams that attach while a
        canary runs."""
        with self._lock:
            if not self.canary_active:
                return
            self._assign[id(owner)] = \
                self._attach_seq % self.canary_n == self.canary_n - 1
            self._attach_seq += 1

    def on_detach(self, owner: Any) -> None:
        with self._lock:
            self._assign.pop(id(owner), None)

    def is_canary_stream(self, owner: Any) -> bool:
        return self.canary_active and self._assign.get(id(owner), False)

    def subplugin_for(self, owner: Any) -> Any:
        """The instance serving ``owner``'s frames — the canary shadow
        for canary-routed streams, the pool's shared instance
        otherwise (the per-frame fallback path reads this)."""
        if self.is_canary_stream(owner):
            c = self._canary
            if c is not None:
                return c.subplugin
        return self.entry.subplugin

    def partition(self, items: List[Any]
                  ) -> List[Tuple[ModelVersion, Any, List[Any]]]:
        """Split one window's ``(owner, buf, ...)`` items into
        per-version groups: ``[(version, subplugin, items), ...]`` in
        baseline-first order.  Per-stream FIFO holds because every
        stream maps to exactly one version."""
        canary = self._canary
        if canary is None or not self.canary_active:
            return [(self.baseline, self.entry.subplugin, items)]
        base_items, canary_items = [], []
        assign = self._assign
        for it in items:
            (canary_items if assign.get(id(it[0]), False)
             else base_items).append(it)
        out = []
        if base_items:
            out.append((self.baseline, self.entry.subplugin, base_items))
        if canary_items:
            out.append((canary, canary.subplugin, canary_items))
        return out or [(self.baseline, self.entry.subplugin, items)]

    # -- verdicts -------------------------------------------------------------

    def promote(self, force: bool = False) -> dict:
        """Commit the canary as the serving version (the healthy
        verdict) — refused until it actually served
        ``min_canary_frames`` unless forced: a canary that saw no
        traffic has proven nothing, and a playbook firing early gets a
        clean retryable failure."""
        from .actuators import ActuationError

        with self._lock:
            ver = self._canary
            if ver is None:
                raise ActuationError(
                    f"{self._entry_label()}: no canary to promote")
            served = ver.stats.total_frame_num
            if not force and served < self.min_canary_frames:
                raise ActuationError(
                    f"{self._entry_label()}: canary {ver.tag} served "
                    f"only {served}/{self.min_canary_frames} frames — "
                    f"not enough evidence to promote (force=1 "
                    f"overrides)")
        res = self.swap(ver)
        with self._lock:
            self._canary = None
            self.canary_n = 0
            self._assign = {}
            self.promotes += 1
        self._note("promote", version=ver.tag, frames=served)
        logi("%s: promoted canary %s after %d frames",
             self._entry_label(), ver.tag, served)
        return dict(res, promoted=True, frames=served)

    def rollback(self) -> dict:
        """The unhealthy verdict: stop routing to the canary and
        discard it (the baseline never stopped serving, so recovery is
        immediate); with no canary active, swap back to the retired
        pre-swap version instead (undo of the last full swap).
        Check-and-mutate happens under ONE lock acquisition, so a
        playbook and a concurrent ``nns-ctl`` firing together roll
        back once, not twice."""
        from .actuators import ActuationError

        prior = None
        with self._lock:
            ver = self._canary
            if ver is not None:
                ver.state = "rolled-back"
                self._canary = None
                if self._staged is ver:
                    self._staged = None
                self.canary_n = 0
                self._assign = {}
                self.rollbacks += 1
            else:
                # pop atomically: two concurrent full-swap rollbacks
                # must not both commit the same prior
                prior = self._rollback_ref
                self._rollback_ref = None
        if ver is not None:
            self._note("rollback", version=ver.tag,
                       errors=ver.errors,
                       frames=ver.stats.total_frame_num)
            logw("%s: rolled back canary %s (errors=%d after %d "
                 "frames) — baseline keeps serving",
                 self._entry_label(), ver.tag, ver.errors,
                 ver.stats.total_frame_num)
            return {"version": ver.tag, "rolled_back": True,
                    "canary": True}
        if prior is not None and prior.subplugin is not None \
                and getattr(prior.subplugin, "_compiled", None) is not None:
            try:
                res = self.swap(prior)
            except Exception:
                with self._lock:  # restore the undo on failure
                    self._rollback_ref = prior
                raise
            with self._lock:
                self.rollbacks += 1
            self._note("rollback", version=prior.tag, full_swap=True)
            return dict(res, rolled_back=True, canary=False)
        raise ActuationError(
            f"{self._entry_label()}: nothing to roll back (no canary "
            f"active, no prior version retained)")

    # -- dispatch-side recording (PoolEntry drives these) ---------------------

    def record(self, version: ModelVersion, latency_s: Optional[float],
               frames: int, streams: int = 1) -> None:
        if latency_s is not None:
            version.stats.record(latency_s, frames=frames,
                                 streams=streams)
        else:
            version.stats.count(frames=frames, streams=streams)

    def record_error(self, version: ModelVersion) -> None:
        with self._lock:
            version.errors += 1

    # -- actuators (runtime/actuators.py kind "model") ------------------------

    def actuators(self) -> Dict[str, Any]:
        """The lifecycle's named knobs on this pool: ``swap`` /
        ``canary`` (text-valued: the model reference), ``promote`` /
        ``rollback`` (numeric, playbook-drivable).  Built once; state
        (cooldowns) persists for the entry's lifetime."""
        with self._lock:
            if self._actuators:
                return self._actuators
        from .actuators import Actuator

        label = self._entry_label()

        def _swap(ref) -> None:
            if isinstance(ref, str) and ref.strip():
                self.stage(ref.strip())
            self.swap()

        def _canary(ref) -> None:
            if isinstance(ref, (int, float)):
                if float(ref) <= 0:
                    # numeric 0 stops the canary without a verdict
                    with self._lock:
                        if self._canary is not None:
                            self._canary.state = "staged"
                            self._staged = self._canary
                        self._canary = None
                        self.canary_n = 0
                        self._assign = {}
                    return
                self.start_canary(int(ref))
                return
            ref = str(ref).strip()
            n = 0
            if ":" in ref and "/" in ref.rsplit(":", 1)[-1]:
                # trailing :1/N ratio on the reference (the version
                # identity rides the reference's own @tag)
                head, _, ratio = ref.rpartition(":")
                try:
                    _, n = parse_canary(ratio)
                    ref = head
                except LifecycleError:
                    n = 0
            if n == 0:
                n = self.default_canary[1] or 2
            if ref:
                self.stage(ref)
            self.start_canary(n)

        built = {
            "swap": Actuator(
                "swap", "model", label,
                get_fn=lambda: self.baseline.tag,
                set_fn=_swap, unit="ref", text=True,
                # revert of a swap IS a rollback: the retained prior
                # executable state flips back (not a re-stage by tag)
                snapshot_fn=lambda: self.baseline.tag,
                restore_fn=lambda prior: self.rollback()),
            "canary": Actuator(
                "canary", "model", label,
                get_fn=lambda: float(self.canary_n),
                set_fn=_canary, unit="ref|1/N", text=True,
                snapshot_fn=lambda: float(self.canary_n),
                restore_fn=lambda prior: _canary(float(prior or 0))),
            "promote": Actuator(
                "promote", "model", label,
                get_fn=lambda: 1.0 if self.canary_active else 0.0,
                set_fn=lambda v: self.promote(force=v >= 2.0)
                if v >= 0.5 else None,
                lo=0.0, hi=2.0, unit="go"),
            "rollback": Actuator(
                "rollback", "model", label,
                get_fn=lambda: 0.0,
                set_fn=lambda v: self.rollback()
                if v >= 0.5 else None,
                lo=0.0, hi=1.0, unit="go"),
        }
        with self._lock:
            if not self._actuators:
                self._actuators = built
            return self._actuators


def _is_orbax_dir(path: str) -> bool:
    import os

    return os.path.isdir(path)


def _swap_state_of(sp: Any) -> Any:
    """Freeze a sub-plugin's live (model, executable, bucket cache)
    into a ``commit_swap``-compatible holder — what a full swap retains
    as its rollback reference."""
    import types

    with sp._swap_lock:
        model, compiled = sp._model, sp._compiled
    with sp._batch_lock:
        batch_exec = dict(sp._batch_exec)
    ns = types.SimpleNamespace(_model=model, _compiled=compiled,
                               _batch_exec=batch_exec)
    ns.model_name = (lambda: model.name if model is not None else "")
    return ns
