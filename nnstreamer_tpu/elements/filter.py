"""``tensor_filter`` — the NN invoke element, and the single-shot invoker.

Parity targets:
- element + dispatch core: /root/reference/gst/nnstreamer/tensor_filter/
  tensor_filter.c (transform hot path :643-880, throttling :511, stats
  :366-468) and tensor_filter_common.c (open_fw :2465, framework
  auto-detection :1224, input/output-combination parsing).
- single-shot: tensor_filter_single.c (invoke without a pipeline).

TPU-native redesign of the hot path: tensors stay ``jax.Array``; ``invoke``
is an async XLA dispatch so the streaming thread pipelines ahead of the
device.  The reference's per-invoke output malloc+memcpy
(tensor_filter.c:760-809) has no equivalent — XLA allocates outputs in HBM
(allocate-in-invoke always on).
"""

from __future__ import annotations

import time
from contextlib import nullcontext as _nullcontext
from fractions import Fraction
from typing import Any, List, Optional, Sequence

from ..chaos import hooks as _chaos_hooks
from ..chaos.plan import apply_invoke_fault
from ..core import Buffer, Caps, Tensor, TensorFormat, TensorsSpec
from ..filters.api import FilterError, FilterProps, FilterSubplugin
from ..filters.registry import detect_framework, find_filter
from ..obs import hooks as _hooks
from ..obs import stagestat as _stagestat
from ..obs import transfer as _xfer
from ..obs.tracer import TRACE_META_KEY
from ..runtime.element import Element, NegotiationError, Pad, StreamError
from ..runtime.events import Event, EventKind, Message, MessageKind
from ..runtime.registry import register_element
from ..runtime.serving import block_all
from ..utils import profile as _profile
from ..utils.stats import InvokeStats


def _parse_combination(s: str) -> Optional[List[int]]:
    if not s:
        return None
    return [int(x) for x in str(s).split(",") if str(x).strip() != ""]


#: meta marker riding a frame that crossed a stage boundary (set at
#: the handoff ingress, consumed — and stripped — at the stage's emit
#: seams so the inter-stage depth decrements exactly once per frame)
_STAGE_META = "nns.stage.handoff"


def _device_ids_of(t: Tensor) -> tuple:
    """Device ids a device-resident tensor currently lives on (empty
    when the runtime can't say — treated as already-local)."""
    try:
        arr = t.jax()
        devs = arr.devices() if callable(getattr(arr, "devices", None)) \
            else {arr.device}
        return tuple(sorted(int(d.id) for d in devs))
    except Exception:  # noqa: BLE001 - telemetry-adjacent: never raise
        return ()


_NO_MARK = _nullcontext()


def _frames_marked(bufs: Sequence[Buffer]):
    """Device-trace correlation for one dispatch: the sampled frames'
    trace ids show up as a ``nns:frames:<ids>`` TraceAnnotation on the
    TensorBoard timeline.  One shared null context unless a
    ``pipeline_trace`` capture is active."""
    if _profile.trace_active():
        return _profile.frame_annotation(_trace_ids(bufs))
    return _NO_MARK


def _trace_ids(bufs: Sequence[Buffer]) -> List[str]:
    """Obs trace ids riding a dispatch's buffers (usually empty: only
    1-in-N sampled frames carry a trace)."""
    out = []
    for b in bufs:
        tr = b.meta.get(TRACE_META_KEY)
        if tr is not None and tr.get("id"):
            out.append(str(tr["id"]))
    return out


@register_element("tensor_filter")
class TensorFilter(Element):
    FACTORY = "tensor_filter"

    def __init__(self, name=None, framework: str = "auto", model: Any = None,
                 accelerator: str = "", custom: str = "",
                 input_combination: str = "", output_combination: str = "",
                 invoke_dynamic: bool = False, is_updatable: bool = False,
                 shared_tensor_filter_key: str = "", latency: int = 0,
                 latency_report: bool = False, inputtype: str = "",
                 input: str = "", outputtype: str = "", output: str = "",
                 mesh: str = "", sharding: str = "", devices: str = "",
                 batch: int = 1, batch_timeout_ms: float = 1.0,
                 batch_buckets: str = "", share_model: bool = False,
                 stat_sample_interval_ms: Optional[float] = None,
                 priority: str = "normal", deadline_ms: float = 0.0,
                 slo_ms: float = 0.0, queue_limit: int = 0,
                 canary: str = "", tenant: str = "", chaos: str = "",
                 **props):
        self.framework = framework
        self.model = model
        self.accelerator = accelerator
        self.custom = custom
        self.input_combination = input_combination
        self.output_combination = output_combination
        self.invoke_dynamic = invoke_dynamic
        self.is_updatable = is_updatable
        self.shared_tensor_filter_key = shared_tensor_filter_key
        self.latency = latency          # 1 = measure synchronously
        self.latency_report = latency_report
        self.inputtype, self.input = inputtype, input
        self.outputtype, self.output = outputtype, output
        # multi-chip: mesh="data:-1" compiles the invoke SPMD over a device
        # mesh (SURVEY.md §7.6 — the pjit answer to remote tensor_filter);
        # devices="0-3" restricts the mesh to a submesh so pipeline stages
        # can occupy disjoint device subsets
        self.mesh = mesh
        self.sharding = sharding
        self.devices = devices
        # dynamic micro-batching (runtime/batching.py): batch>1 coalesces
        # in-flight buffers into ONE XLA dispatch per window; buckets
        # bound the set of compiled shapes; timeout bounds added latency
        self.batch = batch
        self.batch_timeout_ms = batch_timeout_ms
        self.batch_buckets = batch_buckets
        # shared-model serving (runtime/serving.py): share-model=true
        # attaches this element to the process-wide ModelPool — N filters
        # on the same model share ONE sub-plugin instance (one params
        # copy, one executable cache) and, with batch>1, one CROSS-
        # pipeline coalescing window
        self.share_model = share_model
        # observability: cadence of the blocking latency sample —
        # None = the class default STAT_SAMPLE_INTERVAL (so tuning the
        # class attribute still works); shrink for a fresher `nns-top`
        # LAT column, grow to make sampling arbitrarily rare
        self.stat_sample_interval_ms = stat_sample_interval_ms
        # SLO-aware admission (runtime/admission.py, share-model only):
        # priority names this STREAM's class (high/normal/low),
        # deadline-ms its per-frame deadline (0 = the pool SLO),
        # queue-limit bounds its parked frames (0 = 16x batch);
        # slo-ms is POOL-level — >0 arms the admission controller,
        # which sheds sub-high-priority frames while the pool's p99
        # threatens the SLO (every shed counted + bus-warned)
        self.priority = priority
        self.deadline_ms = deadline_ms
        self.slo_ms = slo_ms
        self.queue_limit = queue_limit
        # tenant attribution (obs/tenantstat.py, share-model only):
        # tenant= names who this STREAM's frames are billed to — every
        # pool dispatch splits its device-seconds across tenants by
        # useful-frame occupancy (nns_tenant_* families, snapshot v9
        # tenants table); default tenant "default"
        self.tenant = tenant
        # model lifecycle (runtime/lifecycle.py, share-model only):
        # canary="<version>:1/N" (or "1/N") is POOL-level — a reload
        # routes 1-in-N of the pool's streams to the new version and
        # the watch/playbook pair judges promote-or-rollback, instead
        # of cutting every stream over at once
        self.canary = canary
        # version tag split off a versioned model reference
        # (filters/modeluri.py `model.pkl@v2`) — swap provenance
        self.model_version = ""
        # deterministic fault injection scoped to THIS element (the
        # process-wide NNS_TPU_CHAOS plan applies regardless); grammar
        # in chaos/plan.py, e.g. "seed=7;slow-invoke:ms=20,p=0.1"
        self.chaos = chaos
        super().__init__(name, **props)
        self.add_sink_pad()
        self.add_src_pad()
        self.subplugin: Optional[FilterSubplugin] = None
        self.in_spec: Optional[TensorsSpec] = None
        self.out_spec: Optional[TensorsSpec] = None
        self.invoke_stats = InvokeStats()
        self._in_combi = None
        self._out_combi = None
        self._throttle_interval = 0.0
        self._last_invoke_ts = 0.0
        self._dyn_spec: Optional[TensorsSpec] = None
        self._fused_pre: list = []  # op chains inlined by runtime/fusion.py
        self._fused_post: list = []  # epilogue fns (decoder overlay fusion)
        self._fused_post_decoder = None  # Decoder obj to notify on unfuse
        self._invoke_seq = 0
        self._last_sample_ts = 0.0
        self._last_out: Any = None  # previous invoke's output (drain point)
        self._batcher = None         # MicroBatcher when batch>1 (start())
        self._buckets: tuple = (1,)
        self._pool_entry = None      # serving.PoolEntry (share-model=true)
        self._pool_attached = False  # registered as a live pool stream
        self._pool_batched = False   # frames go through the SharedBatcher
        self._chaos_plan = None      # parsed from the chaos= prop (start)

    #: Sampled invokes block on the outputs so latency/throughput stats
    #: measure device *execution*, not async dispatch (XLA dispatch
    #: returns in ~µs regardless of the computation).  Sampling is
    #: TIME-based — at most one blocking sample per interval — because a
    #: block drains the async run-ahead: a count-based every-Nth rule
    #: would burn a fixed fraction of throughput on stats, whatever the
    #: frame rate.  Unsampled invokes run ahead of
    #: the device.  ``latency=1`` forces every invoke synchronous
    #: (reference prop).  Per element, the ``stat-sample-interval-ms``
    #: property overrides this class-wide default (seconds here, ms on
    #: the property).
    STAT_SAMPLE_INTERVAL = 1.0

    # -- open ----------------------------------------------------------------

    def _user_spec(self, dims: str, types: str) -> Optional[TensorsSpec]:
        if not dims or not types:
            return None
        return TensorsSpec.parse(dims, types)

    def open_fw(self) -> None:
        """Resolve framework + configure the sub-plugin (parity:
        gst_tensor_filter_common_open_fw, tensor_filter_common.c:2465)."""
        if self.subplugin is not None:
            return
        # negotiation asks for the model's schema, so this runs inside
        # <pipeline>/negotiate: weights and state go onto the device
        # and the first program is traced here
        with _profile.span(self.name, "open", setup=True):
            self._open_fw()

    def _open_fw(self) -> None:
        from ..filters.modeluri import resolve_model_uri_versioned

        # scheme-qualified model URIs (mlagent:// analog) resolve first,
        # so extension-based framework detection sees the real target;
        # a `@<tag>` version suffix resolves to (target, tag) and the
        # tag rides along as swap provenance
        self.model, self.model_version = \
            resolve_model_uri_versioned(self.model)
        fw_name = self.framework or "auto"
        if fw_name == "auto":
            fw_name = detect_framework(self.model)
        cls = find_filter(fw_name)
        fprops = FilterProps(
            framework=fw_name, model=self.model,
            accelerator=self.accelerator, custom=self.custom,
            input_spec=self._user_spec(self.input, self.inputtype),
            output_spec=self._user_spec(self.output, self.outputtype),
            shared_key=self.shared_tensor_filter_key or None,
            is_updatable=bool(self.is_updatable),
            latency_report=bool(self.latency_report),
            mesh=str(self.mesh or ""), sharding=str(self.sharding or ""),
            devices=str(self.devices or ""))
        if self.share_model:
            if self.invoke_dynamic:
                raise ValueError(
                    f"{self.name}: share-model=true cannot combine with "
                    "invoke-dynamic (per-buffer reshapes would recompile "
                    "the shared instance under every sharer)")
            # is-updatable IS allowed on a shared pool since the model
            # lifecycle layer (runtime/lifecycle.py): a RELOAD_MODEL
            # event routes through PoolEntry.reload_model — staged +
            # warmed off the dispatch path, flipped at a window
            # boundary (or canaried per the pool's canary= split) for
            # EVERY sharer at once, never one sharer's private swap
            from ..runtime.serving import MODEL_POOL, pool_key
            self._pool_entry = MODEL_POOL.acquire(
                pool_key(fw_name, fprops),
                lambda: cls.open_shared(fprops), cls.close_shared)
            self.subplugin = self._pool_entry.subplugin
        else:
            sp = cls()
            # the sub-plugin's spans (place, dispatch, compile) carry
            # this element's name
            sp.trace_owner = self.name
            sp.configure(fprops)
            if self._fused_pre and hasattr(sp, "set_fused_pre"):
                # fusion pass inlined upstream transform chains into this
                # filter's computation (runtime/fusion.py)
                sp.set_fused_pre(self._fused_pre)
            if self._fused_post and hasattr(sp, "set_fused_post"):
                # fusion pass inlined the downstream decoder's device
                # program as the computation's epilogue
                sp.set_fused_post(self._fused_post)
            self.subplugin = sp
        self.in_spec, self.out_spec = self.subplugin.get_model_info()
        mn = getattr(self.subplugin, "model_name", None)
        if callable(mn):
            # obs join key: this element's nns_invoke_device_seconds
            # series measures executables of this model (obs/xlacost.py
            # scrape-time MFU join)
            from ..obs import xlacost as _xlacost

            _xlacost.map_source(self.name, mn())
        self._in_combi = _parse_combination(self.input_combination)
        # output-combination tokens: iN (input passthrough) / oN (model out)
        self._out_combi = [t.strip() for t in str(
            self.output_combination).split(",") if t.strip()] or None

    def start(self) -> None:
        b = int(self.batch or 1)
        if str(self.chaos or "").strip():
            from ..chaos.plan import FaultPlan

            self._chaos_plan = FaultPlan.parse(str(self.chaos))
        if self._pool_entry is not None:
            # shared-model serving: this element becomes one STREAM of
            # the pool entry.  batch* properties are pool-level — the
            # attach validates them against the settings other sharers
            # fixed, and raises on conflict (caught by Pipeline.start).
            self._pool_batched = self._pool_entry.attach(
                self, b, float(self.batch_timeout_ms), self.batch_buckets,
                slo_ms=float(self.slo_ms or 0.0),
                priority=self.priority,
                deadline_ms=float(self.deadline_ms or 0.0),
                queue_limit=int(self.queue_limit or 0),
                canary=str(self.canary or ""),
                tenant=str(self.tenant or ""))
            self._pool_attached = True
            return
        if b <= 1:
            self._request_placement()
            return
        if self.invoke_dynamic:
            raise ValueError(
                f"{self.name}: batch={b} requires static shapes; "
                "invoke-dynamic streams reshape per buffer and cannot "
                "share a bucketed executable")
        from ..runtime.batching import MicroBatcher, parse_buckets

        self._buckets = parse_buckets(self.batch_buckets, b)
        self._batcher = MicroBatcher(
            max_batch=b, timeout_s=float(self.batch_timeout_ms) / 1e3,
            flush_fn=self._invoke_microbatch, error_fn=self.post_error,
            name=self.name)
        self._batcher.start()

    def _request_placement(self) -> None:
        """Tell the upstream source where the plain ``invoke`` path
        reads its inputs, so that it stages them there and no window is
        placed again (sources start after every other element, with
        negotiation and the fused prologue's recompile behind us, so
        the executable's layout is final).  Sent only when every input
        tensor reaches ``invoke`` as it arrives."""
        if self.subplugin is None or self.invoke_dynamic \
                or self._in_combi is not None:
            return
        layouts = self.subplugin.input_layouts()
        if layouts and any(s is not None for s in layouts):
            self.sinkpad.push_upstream_event(Event.placement(layouts))

    def stop(self) -> None:
        if self._pool_entry is not None:
            from ..runtime.serving import MODEL_POOL

            entry, self._pool_entry = self._pool_entry, None
            self._pool_batched = False
            if self._pool_attached:
                self._pool_attached = False
                try:
                    entry.detach(self)  # flushes THIS stream's parked
                    # frames; survivors keep dispatching on the entry
                except Exception as e:  # noqa: BLE001 - report, keep
                    # stopping: the refcount must still drop
                    self.post_error(e)
            MODEL_POOL.release(entry)
            self.subplugin = None
            return
        if self._batcher is not None:
            try:
                self._batcher.flush()  # drain, best effort: downstream
                # may already be stopping, but frames must not vanish
            except Exception as e:  # noqa: BLE001 - report, keep stopping
                self.post_error(e)
            self._batcher.stop()
            self._batcher = None
        if self.subplugin is not None:
            self.subplugin.close()
            self.subplugin = None

    def on_eos(self) -> None:
        # partial-batch flush BEFORE the EOS event forwards downstream:
        # no frame loss, and sinks see data-then-EOS in order
        if self._pool_entry is not None and self._pool_attached:
            try:
                # per-stream flush: only THIS stream's parked frames
                # must drain; other pipelines' windows stay open
                self._pool_entry.flush_stream(self)
            except Exception as e:  # noqa: BLE001 - same contract as the
                # per-element flush below: report, let EOS propagate
                self.post_error(e)
            return
        if self._batcher is not None:
            try:
                self._batcher.flush()
            except Exception as e:  # noqa: BLE001 - the EOS path has no
                # guarded caller (Queue._loop forwards unguarded): a
                # flush failure must reach the bus, and EOS must still
                # propagate so wait_eos() terminates
                self.post_error(e)

    # -- negotiation ---------------------------------------------------------

    def pad_template_caps(self, pad: Pad) -> Caps:
        if pad.direction.value == "sink":
            if self.invoke_dynamic:
                return Caps.any_tensors()
            try:
                self.open_fw()
            except (FilterError, KeyError, ValueError) as e:
                raise NegotiationError(f"{self.name}: open failed: {e}",
                                       reason="open", sink_pad=pad) from e
            spec = self.in_spec
            if self._in_combi is not None:
                # model sees a subset; pad accepts anything containing it
                return Caps.any_tensors()
            # Preferred: exact model input caps. Fallback: any tensors —
            # caps_negotiated then tries the SET_INPUT_INFO reshape path.
            exact = Caps.from_spec(spec)
            return Caps(structs=exact.structs + Caps.any_tensors().structs)
        return Caps.any_tensors()

    def caps_negotiated(self, pad: Pad) -> None:
        if self.invoke_dynamic:
            return
        self.open_fw()
        spec = pad.spec
        if spec is None or self._in_combi is not None:
            return
        if not spec.is_static():
            # flexible input: per-buffer schemas can't pre-compile an
            # overlay epilogue — withdraw the decoder fusion so the
            # decoder renders for itself (mirror of transform _unfuse)
            if self._fused_post:
                self._fused_post.clear()
                if self._fused_post_decoder is not None:
                    self._fused_post_decoder.fused_upstream = False
            return
        compiled = getattr(self.subplugin, "_compiled", None)
        stale_pre = compiled is not None and \
            (compiled.with_pre != bool(self._fused_pre)
             or getattr(compiled, "with_post", False)
             != bool(self._fused_post))
        if self._fused_pre or self._fused_post or stale_pre:
            # fused prologue: the executable must be specialized to the
            # RAW upstream schema even when it happens to be compatible
            # with the model's declared input; a stale executable whose
            # prologue state no longer matches (element reused after the
            # fusion pass re-derived) must recompile either way
            try:
                self.in_spec, self.out_spec = \
                    self.subplugin.set_input_info(spec)
            except FilterError as e:
                raise NegotiationError(
                    f"{self.name}: fused prologue rejects input "
                    f"{spec}: {e}") from e
            return
        if not spec.is_compatible(self.in_spec):
            if self._shared_by_others():
                # a pooled model must not be recompiled under the other
                # sharers' feet: sharers negotiate identical schemas.
                # Checked HERE because the pool opens the framework
                # instance once per key — the sub-plugin's own ref count
                # cannot see how many elements ride the pool entry.
                raise NegotiationError(
                    f"{self.name}: input {spec} incompatible with the "
                    f"shared model's {self.in_spec}, which "
                    f"{self._pool_entry.refcount - 1} other filter(s) "
                    f"depend on — share-model sharers must negotiate "
                    f"identical input schemas")
            # try a model reshape (SET_INPUT_INFO path)
            try:
                self.in_spec, self.out_spec = \
                    self.subplugin.set_input_info(spec)
            except FilterError as e:
                raise NegotiationError(
                    f"{self.name}: input {spec} incompatible with model "
                    f"{self.in_spec}: {e}") from e

    def _shared_by_others(self) -> bool:
        """Whether other elements currently hold the same pooled model
        (reshaping it would swap the executable under them)."""
        return self._pool_entry is not None and self._pool_entry.refcount > 1

    def propose_src_caps(self, pad: Pad) -> Caps:
        self.open_fw()
        rate = Fraction(0, 1)
        if self.sinkpad.spec is not None:
            rate = self.sinkpad.spec.rate
        if self.invoke_dynamic:
            return Caps.from_spec(TensorsSpec(
                format=TensorFormat.FLEXIBLE, rate=rate))
        out = self.out_spec.with_rate(rate)
        if self._out_combi is not None and self.sinkpad.spec is not None:
            out = self._combined_out_spec(self.sinkpad.spec).with_rate(rate)
        return Caps.from_spec(out)

    def _combined_out_spec(self, in_spec: TensorsSpec) -> TensorsSpec:
        """output-combination 'iN,...,oM,...' merges input passthroughs and
        model outputs (parity: tensor_filter.c:848-880)."""
        tensors = []
        for tok in str(self.output_combination).split(","):
            tok = tok.strip()
            if tok.startswith("i"):
                tensors.append(in_spec.tensors[int(tok[1:])])
            elif tok.startswith("o"):
                tensors.append(self.out_spec.tensors[int(tok[1:])])
        return TensorsSpec(tensors=tuple(tensors))

    # -- hot path ------------------------------------------------------------

    def chain(self, pad: Pad, buf: Buffer) -> None:
        sp = self.subplugin
        if sp is None:
            # checked BEFORE the QoS throttle: a misconfigured filter must
            # report, not silently drop every buffer as "throttled"
            raise StreamError(f"{self.name}: no sub-plugin opened")
        if self._throttled():
            return  # QoS drop (parity: tensor_filter.c:511)
        if self.devices:
            # stage boundary: a frame produced on ANOTHER device subset
            # hands off device-to-device BEFORE it parks in this
            # stage's window — the handoff is part of arriving at the
            # stage, never part of a dispatch
            buf = self._stage_ingress(buf)
        if self._pool_batched and self._pool_entry is not None:
            if self._chaos_plan is not None:
                # element-scoped faults on a pooled stream apply at
                # admission (the pool dispatch belongs to every sharer;
                # the process-wide plan covers it instead)
                apply_invoke_fault(self._chaos_plan, self.name)
            # shared-model serving: park the buffer in the CROSS-pipeline
            # window; the pool dispatch demuxes the result back here
            self._pool_entry.submit(self, buf)
            return
        if self._batcher is not None:
            # micro-batching: park the buffer in the coalescing window;
            # the window flush (full/deadline/EOS) dispatches it
            self._batcher.submit(buf)
            return
        if self._pool_entry is not None:
            # per-frame pooled stream: a live canary may route THIS
            # stream's frames through the staged version's instance
            sp = self._pool_entry.subplugin_for(self)
        # model-path fault seam (unbatched dispatch site): the element
        # plan AND the process-wide plan both apply — NNS_TPU_CHAOS is
        # documented to hold regardless of per-element plans
        if self._chaos_plan is not None:
            apply_invoke_fault(self._chaos_plan, self.name)
        ch = _chaos_hooks.plan
        if ch is not None:
            apply_invoke_fault(ch, self.name)
        tensors = buf.tensors
        if self._in_combi is not None:
            tensors = [tensors[i] for i in self._in_combi]
        if self.invoke_dynamic:
            self._reshape_dynamic(buf)
        device = "tpu" in sp.ACCELERATORS
        # the sample gate opens BEFORE input prep: host-prep is part of
        # what this element spends per dispatch, so the sampled invoke
        # latency (and its phase split) starts here
        sample, t0 = self._sample_gate()
        with _profile.span(self.name, "prep"):
            inputs = [t.jax() if device else t.np() for t in tensors]
        t1 = time.monotonic()
        with _frames_marked((buf,)):
            outputs = sp.invoke(inputs)
        if getattr(sp, "_donate", False):
            # donation consumed the device-resident inputs' HBM
            # buffers: mark exactly the tensors that were PASSED to the
            # dispatch (input-combination may have excluded some — XLA
            # never saw those, so they stay valid) so any re-read (a
            # tee branch, a retained reference) raises
            # DonatedTensorError instead of reading reused memory
            for t in tensors:
                t.mark_donated()
        t2 = self._record_dispatch(outputs, t0, frames=1, sample=sample)
        out_tensors = [Tensor(o) for o in outputs]
        if self._out_combi is not None:
            out_tensors = self._combine_outputs(buf, out_tensors)
        meta = dict(buf.meta)
        if meta.pop(_STAGE_META, None):
            # the handed-off frame leaves the stage: depth decrement
            _stagestat.record_emit(
                self.pipeline.name if self.pipeline is not None else "",
                self.name)
        out = Buffer(tensors=out_tensors, pts=buf.pts, duration=buf.duration,
                     offset=buf.offset, meta=meta,
                     format=TensorFormat.FLEXIBLE if self.invoke_dynamic
                     else TensorFormat.STATIC)
        if sample:
            # cost attribution: phases recorded (and trace marks
            # planted) BEFORE the push — the sink finalizes the trace
            # record inline during it
            t3 = time.monotonic()
            self._attribute_phases(t0, t1, t2, t3, bucket=1)
            tracer = _hooks.tracer
            if tracer is not None:
                tracer.invoke_split([(self.name, out)], t0, t1, t2, t3)
        self.push(out)

    # -- stage boundary (disaggregated pipeline split) -----------------------

    def _stage_ingress(self, buf: Buffer) -> Buffer:
        """Cross-subset handoff INTO this stage: when this filter's
        resolved placement pins an explicit ``devices=`` subset and the
        frame's tensors live on chips OUTSIDE it (the upstream stage's
        subset), route the frame through the device channel's slot
        semantics re-homed onto this stage's devices — a device-to-
        device ICI copy with one byte-exact ``d2d`` ledger row, never a
        host bounce, so ``crossings_per_frame`` stays 0.0 across the
        boundary.  Host/mixed frames pass through untouched (their
        upload is the ordinary ``h2d`` path), as do frames already
        resident on this stage's chips."""
        rp = getattr(self.subplugin, "_placement", None)
        if rp is None or not getattr(rp, "stage", ""):
            return buf
        mine = set(rp.device_ids)
        src_ids: set = set()
        for t in buf.tensors:
            if t.is_device:
                src_ids.update(_device_ids_of(t))
        if not src_ids or src_ids <= mine:
            return buf  # already local to this stage (or host-only)
        from ..edge import devicechannel as _devch
        from ..parallel.placement import subset_label

        if not _devch.eligible(buf):
            return buf  # mixed residency: plain upload path
        nbytes = buf.nbytes
        # re-home onto the WHOLE stage mesh (replicated sharding), not
        # one chip: a jit argument committed to a single device is
        # incompatible with the stage's sharded window dispatch (the
        # batched executable constrains the stacked window over the
        # subset's data axis — committed devices must match the mesh)
        target = rp.mesh.devices.flat[0]
        try:
            import jax

            target = jax.sharding.NamedSharding(
                rp.mesh, jax.sharding.PartitionSpec())
        except Exception:  # noqa: BLE001 - single-chip re-home fallback
            pass
        with _profile.span(self.name, "place"):
            out = _devch.stage_handoff(buf, target,
                                       chan=("stage", self.name))
        out.meta[_STAGE_META] = True
        _stagestat.record_handoff(
            self.pipeline.name if self.pipeline is not None else "",
            self.name, subset_label(src_ids), rp.stage, 1, nbytes)
        return out

    # -- dispatch timing (shared by every invoke path) -----------------------

    def _sample_gate(self):
        """Decide whether this dispatch is a blocking stats sample and, if
        so, drain the async backlog of earlier invokes first — so t0→done
        times ONE dispatch, not the queued N-1 plus this one.  Returns
        ``(sample, t0)``."""
        if _hooks.DISABLED:
            # NNS_TPU_OBS_DISABLE: the dispatch path is FULLY async —
            # no seq/interval bookkeeping, no backlog drain, and (via
            # _record_dispatch) no _last_out retention pinning a
            # window's outputs in HBM.  stat-sample-interval-ms and
            # latency=1 no-op under the kill switch (nns-lint NNS508
            # warns about exactly that combination).
            return False, time.monotonic()
        self._invoke_seq += 1
        now = time.monotonic()
        interval = self.STAT_SAMPLE_INTERVAL \
            if self.stat_sample_interval_ms is None \
            else float(self.stat_sample_interval_ms) / 1e3
        sample = (bool(self.latency) or self._invoke_seq == 1 or
                  now - self._last_sample_ts >= interval)
        if sample and self._last_out is not None:
            with _profile.span(self.name, "sample_fence"):
                block_all([self._last_out])
        return sample, time.monotonic()

    def _record_dispatch(self, outs: List[Any], t0: float,
                         frames: int = 1, sample: bool = True) -> float:
        """Post-invoke bookkeeping shared by the single-frame and
        micro-batched paths: on a sampled dispatch, block on ALL its
        outputs so the recorded time covers device execution (parity:
        tensor_filter.c:389-468 measures the actual invoke — and a
        multi-output model may still be executing earlier outputs when
        the last one resolves); otherwise just count, since unsampled
        invokes would systematically report enqueue time on TPU.  Keeps
        the drain point for the next sample and posts LATENCY messages.
        ``outs`` is the flat list of every output array of the
        dispatch.  Returns the device-done timestamp — the SAME clock
        read the latency was recorded from, so the cost-attribution
        phases partition the recorded latency exactly."""
        if sample:
            with _profile.span(self.name, "sample_fence"):
                block_all(outs)
                sp = self.subplugin
                if sp is not None:
                    # a stateful model's own counters ride in its state:
                    # read here, where the stream is fenced anyway
                    sp.fetch_counters()
            t2 = time.monotonic()
            self.invoke_stats.record(t2 - t0, frames=frames)
            self._last_sample_ts = t2
        else:
            t2 = time.monotonic()
            self.invoke_stats.count(frames=frames)
        # the drain anchor for the NEXT sample — with observability
        # killed there will never be one, so don't pin a window's
        # output in HBM until the stream's next dispatch
        self._last_out = (outs[-1] if outs else None) \
            if not _hooks.DISABLED else None
        if self.latency_report:
            rep = self.invoke_stats.latency_to_report()
            if rep is not None:
                self.post_message(Message(
                    MessageKind.LATENCY, self.name, data={"latency_us": rep}))
        return t2

    def _attribute_phases(self, t0: float, t1: float, t2: float,
                          t3: float, bucket: int) -> None:
        """Record one sampled dispatch's host-prep (t0→t1) / device
        (t1→t2) / host-drain (t2→t3) split into the element's
        InvokeStats and the registry's ``nns_invoke_*`` histograms.
        t2 is the block_until_ready fence ``_record_dispatch``
        returned, so prep + device equals the recorded invoke latency
        by construction."""
        from ..obs.metrics import observe_invoke_phases

        self.invoke_stats.record_phases(t1 - t0, t2 - t1, t3 - t2)
        observe_invoke_phases("element", self.name, bucket,
                              t1 - t0, t2 - t1, t3 - t2)

    def _invoke_microbatch(self, bufs: List[Buffer]) -> None:
        """Window flush: dispatch 1..batch queued buffers as one XLA
        invoke (padded to a bucket), then unbatch the outputs back into
        per-frame Buffers in arrival order, pts/offset/meta preserved.
        Runs on the producer thread (full window) or the coalescer's
        timer thread (deadline/EOS) — never concurrently (MicroBatcher
        serializes flushes)."""
        sp = self.subplugin
        if sp is None:
            raise StreamError(f"{self.name}: no sub-plugin opened")
        # model-path fault seam (micro-batched dispatch site): a
        # fail-invoke loses the whole window, like a real XLA error;
        # element plan and process-wide plan BOTH apply
        if self._chaos_plan is not None:
            apply_invoke_fault(self._chaos_plan, self.name)
        ch = _chaos_hooks.plan
        if ch is not None:
            apply_invoke_fault(ch, self.name)
        # sample gate BEFORE frame prep: host-prep (input gather +
        # conversion for the whole window) is part of the dispatch cost
        sample, t0 = self._sample_gate()
        # transfer-label context for the window: deadline/EOS flushes
        # run on the coalescer's timer thread, which carries no chain
        # context — the window's crossings still belong to this element
        xctx = None
        pushed = _xfer.ACTIVE
        if pushed:
            traces = tuple(
                tr for tr in (b.meta.get(TRACE_META_KEY) for b in bufs)
                if tr is not None) or None
            xctx = _xfer.push_context(
                self.pipeline.name if self.pipeline is not None else "",
                self.name, traces)
        try:
            self._invoke_microbatch_inner(bufs, sample, t0)
        finally:
            if pushed:
                _xfer.pop_context(xctx)

    def _invoke_microbatch_inner(self, bufs: List[Buffer], sample: bool,
                                 t0: float) -> None:
        from ..runtime.batching import pick_bucket

        sp = self.subplugin
        # a deadline flush runs on the coalescer's timer thread, under
        # no chain span: the window's spans carry its number themselves
        window = getattr(self._batcher, "window_seq", None)
        with _profile.span(self.name, "prep", window):
            frames = [self._pool_frame_inputs(buf) for buf in bufs]
            bucket = pick_bucket(len(frames), self._buckets)
        t1 = time.monotonic()
        with _frames_marked(bufs):
            if getattr(sp, "SUPPORTS_BATCH", False):
                outs = sp.invoke_batched(frames, bucket)
            else:
                # framework without a batched entry point: the window
                # still coalesces (ordering, EOS flush, occupancy
                # stats) but each frame dispatches separately
                outs = [sp.invoke(list(f)) for f in frames]
        if getattr(sp, "SUPPORTS_BATCH", False) and \
                getattr(sp, "_donate", False):
            # same donation bookkeeping as the single-frame path (the
            # batched executable donates its window args; pad-slot
            # replays are copies, so only the real frames are
            # consumed), restricted to the input-combination subset
            # actually fed to the dispatch
            for buf in bufs:
                ts = buf.tensors
                if self._in_combi is not None:
                    ts = [ts[i] for i in self._in_combi]
                for t in ts:
                    t.mark_donated()
        t2 = self._record_dispatch([o for out in outs for o in out], t0,
                                   frames=len(bufs), sample=sample)
        if sample:
            tracer = _hooks.tracer
            if tracer is not None:
                # marks planted BEFORE the demux (sinks reached inline
                # finalize the records); each buffer's own demux mark
                # closes its drain span
                tracer.invoke_split([(self.name, b) for b in bufs],
                                    t0, t1, t2)
        with _profile.span(self.name, "demux", window):
            for buf, out in zip(bufs, outs):
                self._pool_emit(buf, out)
        if sample:
            # host-drain of the window: unbatch + per-frame wrap + the
            # downstream handoff of every frame demuxed above
            self._attribute_phases(t0, t1, t2, time.monotonic(),
                                   bucket=bucket)

    # -- serving-pool hooks (runtime/serving.py drives these) ----------------

    def _pool_frame_inputs(self, buf: Buffer) -> List[Any]:
        """Model inputs of one parked frame, input-combination applied.
        Device-resident tensors pass through as jax arrays; host-resident
        ones stay numpy — the batched executable's own arg handling
        transfers them, which is cheaper than a separate per-frame upload
        dispatch ahead of the invoke."""
        tensors = buf.tensors
        if self._in_combi is not None:
            tensors = [tensors[i] for i in self._in_combi]
        return [t.jax() if t.is_device else t.np() for t in tensors]

    def _pool_emit(self, buf: Buffer, out: List[Any]) -> None:
        """Demux one dispatch result onto THIS filter's downstream pad —
        the owner's flush context: output-combination, pts/offset/meta
        preservation, and any downstream failure surfacing on THIS
        element's bus."""
        tracer = _hooks.tracer
        if tracer is not None:
            tracer.batch_demuxed(self, buf)
        out_tensors = [Tensor(o) for o in out]
        if self._out_combi is not None:
            out_tensors = self._combine_outputs(buf, out_tensors)
        meta = dict(buf.meta)
        if meta.pop(_STAGE_META, None):
            # the handed-off frame leaves the stage: depth decrement
            _stagestat.record_emit(
                self.pipeline.name if self.pipeline is not None else "",
                self.name)
        self.push(Buffer(
            tensors=out_tensors, pts=buf.pts, duration=buf.duration,
            offset=buf.offset, meta=meta,
            format=TensorFormat.STATIC))

    def _combine_outputs(self, in_buf: Buffer, outputs: List[Tensor]
                         ) -> List[Tensor]:
        combined = []
        for tok in str(self.output_combination).split(","):
            tok = tok.strip()
            if tok.startswith("i"):
                combined.append(in_buf.tensors[int(tok[1:])])
            elif tok.startswith("o"):
                combined.append(outputs[int(tok[1:])])
        return combined

    def _reshape_dynamic(self, buf: Buffer) -> None:
        spec = buf.spec()
        if self._dyn_spec is not None and spec.is_compatible(self._dyn_spec):
            return
        self.in_spec, self.out_spec = self.subplugin.set_input_info(spec)
        self._dyn_spec = spec

    def _throttled(self) -> bool:
        if self._throttle_interval <= 0:
            return False
        now = time.monotonic()
        if now - self._last_invoke_ts < self._throttle_interval:
            return True
        self._last_invoke_ts = now
        return False

    # -- events --------------------------------------------------------------

    def handle_upstream_event(self, pad: Pad, event: Event) -> None:
        if event.kind == EventKind.QOS_THROTTLE:
            rate = event.data.get("rate")
            self._throttle_interval = float(1 / rate) if rate else 0.0
        super().handle_upstream_event(pad, event)

    def handle_event(self, pad: Pad, event: Event) -> None:
        if event.kind == EventKind.RELOAD_MODEL:
            if self._pool_entry is not None:
                # shared pool: the reload steers the POOL through the
                # lifecycle layer — staged + warmed off the dispatch
                # path, then hot-swapped at a window boundary (or
                # canaried per the pool's canary= declaration)
                if not self.is_updatable:
                    self.post_error(FilterError(
                        f"{self.name}: model is not updatable"))
                    return
                from ..runtime.actuators import ActuationError
                from ..runtime.lifecycle import LifecycleError

                try:
                    self._pool_entry.reload_model(
                        event.data["model"],
                        version=str(event.data.get("version", "")))
                except (FilterError, ActuationError,
                        LifecycleError, ValueError) as e:
                    self.post_error(e)
                return
            try:
                self.subplugin.handle_event(event)
                self.in_spec, self.out_spec = self.subplugin.get_model_info()
            except FilterError as e:
                self.post_error(e)
            return
        super().handle_event(pad, event)

    # -- introspection props -------------------------------------------------

    @property
    def latency_us(self) -> int:
        return self.invoke_stats.latency_us

    @property
    def throughput_milli_fps(self) -> int:
        return self.invoke_stats.throughput_milli_fps

    @property
    def dispatch_milli_fps(self) -> int:
        """1000×XLA dispatches/s — below throughput_milli_fps exactly
        when micro-batching is coalescing."""
        return self.invoke_stats.dispatch_milli_fps

    @property
    def batch_occupancy(self) -> float:
        """Realized mean frames per dispatch (1.0 unbatched)."""
        return self.invoke_stats.avg_batch_occupancy

    # -- serving-pool introspection ------------------------------------------

    @property
    def pool(self):
        """The shared serving-pool entry (``share-model=true``), else
        None.  Its ``stats`` carry the TRUE cross-pipeline dispatch
        counts; this element's own ``invoke_stats`` count the dispatches
        its frames rode in."""
        return self._pool_entry

    @property
    def pool_streams(self) -> int:
        """Streams currently attached to the shared pool entry (0 when
        not sharing)."""
        return self._pool_entry.attached_streams \
            if self._pool_entry is not None else 0

    @property
    def pool_stream_occupancy(self) -> float:
        """Mean distinct pipelines per shared dispatch (0.0 when not
        sharing)."""
        return self._pool_entry.stats.avg_stream_occupancy \
            if self._pool_entry is not None else 0.0

    # -- multi-chip bookkeeping (round-3 verdict #7) -------------------------

    @property
    def num_shards(self) -> int:
        """Mesh size when the sub-plugin compiled over a mesh=; 1 on a
        single device."""
        mesh = getattr(self.subplugin, "_mesh", None)
        return int(mesh.devices.size) if mesh is not None else 1

    @property
    def data_shards(self) -> int:
        """LOCAL batch parallelism of the sub-plugin's placement: the
        per-process share of the data axes (this element's
        ``invoke_stats`` count only this process's frames, so dividing
        them by the global product would understate per-chip
        throughput by the process count on a multi-host placement); 1
        without a mesh.  Falls back to the single ``_data_axis`` view,
        then to the full mesh size, when the sub-plugin predates the
        placement layer."""
        rp = getattr(self.subplugin, "_placement", None)
        if rp is not None:
            return int(rp.local_data_axis_size)
        mesh = getattr(self.subplugin, "_mesh", None)
        if mesh is None:
            return 1
        axis = getattr(self.subplugin, "_data_axis", None)
        if axis is not None:
            try:
                return int(mesh.shape[axis])
            except (KeyError, AttributeError):
                pass
        return int(mesh.devices.size)

    @property
    def throughput_per_shard_milli_fps(self) -> int:
        """Per-chip share of the element's throughput along the DATA
        axis: each chip handles batch/data_shards of every invoke
        (chips on a model-parallel axis all process the same samples,
        so dividing by the full mesh size would understate scaling
        efficiency by the model-axis factor)."""
        return self.invoke_stats.throughput_milli_fps // \
            max(self.data_shards, 1)


class FilterSingle:
    """Invoke a filter sub-plugin without a pipeline (parity:
    tensor_filter_single.c — basis of the ML single-shot API)."""

    def __init__(self, framework: str = "auto", model: Any = None, **kw):
        from ..filters.modeluri import resolve_model_uri

        model = resolve_model_uri(model)
        fw = framework if framework != "auto" else detect_framework(model)
        self.subplugin = find_filter(fw)()
        self.subplugin.configure(FilterProps(framework=fw, model=model, **kw))
        self.in_spec, self.out_spec = self.subplugin.get_model_info()
        self.stats = InvokeStats()

    def invoke(self, inputs: Sequence[Any]) -> List[Any]:
        t0 = time.monotonic()
        out = self.subplugin.invoke(list(inputs))
        # single-shot is a synchronous API: stats cover execution
        block_all(out)
        self.stats.record(time.monotonic() - t0)
        return out

    def set_input_info(self, spec: TensorsSpec) -> None:
        self.in_spec, self.out_spec = self.subplugin.set_input_info(spec)

    def close(self) -> None:
        self.subplugin.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False
