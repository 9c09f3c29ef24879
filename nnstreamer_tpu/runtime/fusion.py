"""Transform↔filter fusion pass (SURVEY.md §7 stage 4).

Before negotiation, every maximal run of ``tensor_transform`` elements
feeding a ``jax-xla`` ``tensor_filter`` is collapsed into the filter's own
XLA computation: the transforms become passthrough nodes and the filter
compiles ``model ∘ t_k ∘ … ∘ t_1`` as ONE jitted program.  This is the
reference's Orc multi-op fusion idea
(/root/reference/gst/nnstreamer/elements/gsttensor_transform.c:473-483,
gsttensor_transform.md:12-14) done the XLA way — the elementwise chain
fuses into the matmul program's prologue, so the separate-elements
pipeline costs the same as a hand-fused model.

Fusion is skipped for a candidate filter when any of these hold (the
pipeline still runs, just unfused): framework isn't jax-xla,
``invoke-dynamic``, input/output-combination in play, a transform mid-run
feeds more than one consumer, or a transform has no static mode.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import List, Optional, Tuple

from ..utils.log import logi


@dataclass(frozen=True)
class FusedSegment:
    """One captured linear segment that lowers as a single XLA program:
    ``transforms → filter [→ decoder]``.  Built by :func:`fuse_pipeline`
    after both passes ran; the descriptor is what the rest of the
    system keys on —

    - ``chain_digest`` is the ordered identity of every non-model stage
      baked into the filter's executable.  The jax-xla sub-plugin folds
      it into the persistent AOT cache key (runtime/compilecache.py),
      which is what lifts the PR-14 exclusion of fused-chain programs:
      two processes building the same segment around the same model hit
      the same cache entry, and a changed transform option or decoder
      config misses instead of wrongly hitting.
    - the element names give bench/obs a stable label for "the windows
      of this segment are ONE dispatch" accounting.
    """

    filter: str
    transforms: Tuple[str, ...] = ()
    decoder: Optional[str] = None
    chain_digest: str = ""

    @property
    def stages(self) -> int:
        """Pipeline stages collapsed into the one dispatch."""
        return len(self.transforms) + 1 + (1 if self.decoder else 0)


def _is_jax_xla(flt) -> bool:
    fw = (flt.framework or "auto")
    if fw == "jax-xla":
        return True
    if fw != "auto":
        return False
    try:
        from ..filters.registry import detect_framework

        return detect_framework(flt.model) == "jax-xla"
    except Exception:
        return False


def fuse_transform_filter(pipeline, enable: bool = True) -> int:
    """Mark fusable transform runs as passthrough and hand their op
    chains to the downstream filter.  Returns the number of filters that
    received a fused prologue.  Always resets previous marks first (an
    element reused in a different topology or a fuse=False pipeline must
    not stay passthrough), then marks only when ``enable``."""
    from ..elements.filter import TensorFilter
    from ..elements.transform import TensorTransform

    for el in pipeline.elements.values():
        if isinstance(el, TensorTransform):
            el._fused = False
            el._fusion_filter = None
        elif isinstance(el, TensorFilter):
            # mutate IN PLACE: an already-opened jax-xla subplugin holds
            # this very list by reference (set_fused_pre) — rebinding
            # would leave a stale prologue baked into its executable
            el._fused_pre.clear()
    if not enable:
        return 0

    fused = 0
    for el in list(pipeline.elements.values()):
        if not isinstance(el, TensorFilter):
            continue
        if el.invoke_dynamic or el.input_combination \
                or el.output_combination:
            continue
        if el.share_model:
            # a pooled instance serves MANY pipelines: baking one
            # pipeline's transform chain into it would corrupt every
            # other sharer's stream
            continue
        if not _is_jax_xla(el):
            continue
        if not el.sinkpads or el.sinkpads[0].peer is None:
            continue
        run: List = []  # (transform, opchain), filter→source order
        up = el.sinkpads[0].peer.element
        while isinstance(up, TensorTransform):
            if up._fused or not up.mode:
                break
            if len(up.srcpads) != 1 or len(up.sinkpads) != 1 \
                    or up.sinkpads[0].peer is None:
                break
            try:
                chain = up._opchain()
            except Exception:
                break
            # the stage scope of this transform inside the fused
            # program (filters/jax_xla._pre_fns): nns.pre/<element>
            chain.scope = up.name
            run.append((up, chain))
            up = up.sinkpads[0].peer.element
        if not run:
            continue
        run.reverse()  # source→filter order
        el._fused_pre[:] = [c for _, c in run]
        for t, _ in run:
            t._fused = True
            # handle to unfuse at negotiation if the stream turns out
            # flexible (per-buffer schemas can't pre-compile a prologue)
            t._fusion_filter = el
        fused += 1
        logi("fused %s into %s (one XLA computation)",
             "+".join(t.name for t, _ in run), el.name, element=el.name)
    return fused


def fuse_filter_decoder(pipeline, enable: bool = True) -> int:
    """Fuse a device-rendering decoder's program INTO its upstream
    jax-xla filter: ``tensor_filter ! tensor_decoder mode=bounding_boxes
    option7=device`` becomes ONE XLA dispatch for
    transform+model+NMS+overlay; the decoder turns into a consumer of
    the ready canvas (round-3 verdict #10).  Same reset-first contract
    as :func:`fuse_transform_filter`."""
    from ..elements.decoder import TensorDecoder
    from ..elements.filter import TensorFilter

    for el in pipeline.elements.values():
        if isinstance(el, TensorFilter):
            el._fused_post.clear()
            el._fused_post_decoder = None
        elif isinstance(el, TensorDecoder):
            dec = getattr(el, "_dec", None)
            if dec is not None and hasattr(dec, "fused_upstream"):
                dec.fused_upstream = False
    if not enable:
        return 0

    fused = 0
    for el in list(pipeline.elements.values()):
        if not isinstance(el, TensorDecoder):
            continue
        if not el.sinkpads or el.sinkpads[0].peer is None:
            continue
        up = el.sinkpads[0].peer.element
        if not isinstance(up, TensorFilter):
            continue
        if up.invoke_dynamic or up.output_combination or up._fused_post \
                or up.share_model:
            continue
        if len(up.srcpads) != 1 or \
                up.srcpads[0].peer is not el.sinkpads[0]:
            continue  # filter output must feed ONLY this decoder
        if not _is_jax_xla(up):
            continue
        try:
            dec = el._decoder()
        except Exception:
            continue
        builder = getattr(dec, "device_post_program", None)
        post = builder() if builder is not None else None
        if post is None:
            continue
        up._fused_post[:] = [post]
        up._fused_post_decoder = dec
        dec.fused_upstream = True
        fused += 1
        logi("fused %s's device overlay into %s (one XLA dispatch for "
             "model+postprocess+overlay)", el.name, up.name,
             element=up.name)
    return fused


def fuse_pipeline(pipeline, enable: bool = True) -> List[FusedSegment]:
    """Whole-graph capture: run both fusion passes, then describe every
    captured linear segment as a :class:`FusedSegment`.  Called by
    ``Pipeline.start()`` before negotiation; the result is stored on
    ``pipeline.fused_segments`` so tests/bench/obs can assert what
    actually collapsed (and the jax-xla instances can key the
    persistent cache off the same digests the descriptor carries).

    The digest is ordered and covers every fused stage: each prologue
    op chain contributes ``_OpChain.digest()`` and a fused decoder
    epilogue contributes the ``chain_digest`` its builder stamped on
    the post fn.  A fused stage WITHOUT a digest poisons the segment's
    digest (set to ``""``) — the sub-plugin then keeps such programs
    out of the persistent cache, preserving the PR-14 invariant that a
    wrong cache hit is impossible."""
    from ..elements.filter import TensorFilter

    fuse_transform_filter(pipeline, enable=enable)
    fuse_filter_decoder(pipeline, enable=enable)
    segments: List[FusedSegment] = []
    if not enable:
        pipeline.fused_segments = segments
        return segments
    for el in pipeline.elements.values():
        if not isinstance(el, TensorFilter):
            continue
        if not el._fused_pre and not el._fused_post:
            continue
        transforms = tuple(
            t.name for t in pipeline.elements.values()
            if getattr(t, "_fusion_filter", None) is el)
        decoder = None
        if el._fused_post_decoder is not None:
            for d in pipeline.elements.values():
                if getattr(d, "_dec", None) is el._fused_post_decoder:
                    decoder = d.name
                    break
        parts: List[str] = []
        ok = True
        for c in el._fused_pre:
            dig = getattr(c, "digest", None)
            if dig is None:
                ok = False
                break
            parts.append("pre:" + c.digest())
        for p in el._fused_post:
            dig = getattr(p, "chain_digest", None)
            if dig is None:
                ok = False
                break
            parts.append("post:" + dig)
        segments.append(FusedSegment(
            filter=el.name, transforms=transforms, decoder=decoder,
            chain_digest=";".join(parts) if ok else ""))
    pipeline.fused_segments = segments
    return segments
