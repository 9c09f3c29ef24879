"""Profiling hooks (jax.profiler traces, per-element annotation) and the
hardware capability probe."""

import glob
import os

import numpy as np

from nnstreamer_tpu.core import Buffer, TensorsSpec
from nnstreamer_tpu.elements.basic import AppSink, AppSrc
from nnstreamer_tpu.runtime import Pipeline
from nnstreamer_tpu.runtime.registry import make
from nnstreamer_tpu.utils import hw
from nnstreamer_tpu.utils.profile import (
    pipeline_trace,
    span,
    trace_active,
)


class TestProfile:
    def test_span_noop_without_trace(self):
        assert not trace_active()
        with span("x"):  # must not touch jax at all
            pass

    def test_pipeline_trace_captures(self, tmp_path):
        log_dir = str(tmp_path / "trace")
        p = Pipeline()
        src = AppSrc(name="src", spec=TensorsSpec.parse("4", "float32"))
        t = make("tensor_transform", el_name="t", mode="arithmetic",
                 option="mul:2.0")
        sink = AppSink(name="out")
        p.add(src, t, sink).link(src, t, sink)
        with pipeline_trace(log_dir):
            assert trace_active()
            with p:
                src.push_buffer(Buffer.of(np.ones(4, np.float32)))
                src.end_of_stream()
                assert p.wait_eos(timeout=60)
        assert not trace_active()
        # a trace directory with at least one event artifact exists
        found = glob.glob(os.path.join(log_dir, "**", "*"), recursive=True)
        assert any(os.path.isfile(f) for f in found)


class TestHwProbe:
    def test_probe_reports_devices(self):
        caps = hw.probe()
        assert caps, "no platforms visible"
        for platform, devs in caps.items():
            assert devs and all("kind" in d for d in devs)

    def test_accelerator_available(self):
        # at least one of cpu/tpu must resolve in any environment
        assert hw.accelerator_available("cpu") or \
            hw.accelerator_available("tpu")
