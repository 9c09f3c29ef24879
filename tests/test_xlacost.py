"""ISSUE-9: XLA cost capture, the scrape-time MFU join, hardware-peak
resolution, and per-shard mesh attribution.

Covers the acceptance tests named by the issue:

- the captured static cost EXACTLY equals ``compiled.cost_analysis()``
  for the same executable;
- the ``nns_mfu`` gauge agrees with an InvokeStats-derived hand
  computation on a fake-clock (deterministic device-seconds) run;
- the imbalance gauge is 0.0 on an even split and positive on a forced
  uneven split;
- the unknown-backend fallback exports intensity but no utilization;

plus the join's bucket mapping, pad accounting and the nns-top MFU /
MESH rendering.
"""

import json

import numpy as np
import pytest

from nnstreamer_tpu.filters.api import FilterProps
from nnstreamer_tpu.filters.jax_xla import JaxXlaFilter, register_model
from nnstreamer_tpu.obs import hwspec
from nnstreamer_tpu.obs.meshstat import (MESH_STATS, shard_device_label,
                                         shard_split)
from nnstreamer_tpu.obs.metrics import REGISTRY, observe_invoke_phases
from nnstreamer_tpu.obs.xlacost import XLA_COST, cost_of, flops_bytes


def _fam_samples(snap, name):
    return snap["metrics"].get(name, {}).get("samples", [])


@pytest.fixture(autouse=True)
def _no_hwspec_override():
    prev = hwspec.set_override(None)
    yield
    hwspec.set_override(prev)


# -- capture exactness --------------------------------------------------------


def test_captured_cost_equals_compiled_cost_analysis():
    """The compile-seam capture (from the jit LOWERING) must report the
    same flops / bytes as a full ``compiled.cost_analysis()`` of the
    same computation — the figures are computation-intrinsic."""
    import jax

    w = np.asarray(np.random.RandomState(3).randn(32, 32), np.float32)
    name = register_model("xc_exact", lambda x: x @ w,
                          in_shapes=[(8, 32)], in_dtypes=np.float32)
    sp = JaxXlaFilter()
    sp.configure(FilterProps(framework="jax-xla", model=name))
    row = XLA_COST.get(name, 0)
    assert row is not None and row["flops"] > 0
    compiled = jax.jit(lambda x: (x @ w,)).lower(
        jax.ShapeDtypeStruct((8, 32), np.float32)).compile()
    ca = cost_of(compiled)
    assert row["flops"] == float(ca["flops"])
    assert row["bytes"] == float(ca["bytes accessed"])
    sp.close()


def test_bucket_executable_captured_per_bucket():
    w = np.asarray(np.random.RandomState(4).randn(16, 16), np.float32)
    name = register_model("xc_bucket", lambda x: x @ w,
                          in_shapes=[(16,)], in_dtypes=np.float32)
    sp = JaxXlaFilter()
    sp.configure(FilterProps(framework="jax-xla", model=name))
    frame = [np.zeros((16,), np.float32)]
    sp.invoke_batched([frame] * 4, 4)
    row1 = XLA_COST.get(name, 0)
    row4 = XLA_COST.get(name, 4)
    assert row4 is not None, "bucket-4 executable not captured"
    # the window program carries ~4x the single-frame work
    assert row4["flops"] > 2 * row1["flops"]
    # ... and exactly the arithmetic of the same function mapped over
    # a window of four, lowered here apart from the filter's own seam
    import jax

    window = jax.jit(jax.vmap(lambda x: x @ w)).lower(
        jax.ShapeDtypeStruct((4, 16), np.float32))
    assert row4["flops"] == float(cost_of(window)["flops"])
    sp.close()


def test_flops_bytes_helper_tolerates_unsupported_stage():
    class Broken:
        def cost_analysis(self):
            raise RuntimeError("unsupported")

    assert cost_of(Broken()) == {}
    assert flops_bytes(Broken()) == (0.0, 0.0)


# -- the scrape-time MFU join -------------------------------------------------


def test_mfu_gauge_matches_hand_computation():
    """Fake-clock run: deterministic device seconds fed through the
    SAME histogram the runtime feeds; the exported nns_mfu must equal
    flops x dispatches / (device_seconds x peak) by hand."""
    hwspec.set_override(hwspec.V5E)
    flops = 3.2e9
    XLA_COST.record("xc_handmodel", 0, "cpu", "cpu",
                    {"flops": flops, "bytes accessed": 1.0e6})
    XLA_COST.map_source("xc_handelem", "xc_handmodel")
    # 5 sampled dispatches, 2 ms device each (the fake clock)
    for _ in range(5):
        observe_invoke_phases("element", "xc_handelem", 1,
                              prep_s=1e-4, device_s=2e-3, drain_s=5e-5)
    snap = REGISTRY.snapshot()
    mfu = [s for s in _fam_samples(snap, "nns_mfu")
           if s["labels"].get("source") == "xc_handelem"]
    assert mfu, "nns_mfu sample missing"
    expected = flops * 5 / (5 * 2e-3 * hwspec.V5E.peak_flops)
    assert mfu[0]["value"] == pytest.approx(expected, rel=1e-9)
    bw = [s for s in _fam_samples(snap, "nns_hbm_bw_util")
          if s["labels"].get("source") == "xc_handelem"]
    assert bw[0]["value"] == pytest.approx(
        1.0e6 * 5 / (5 * 2e-3 * hwspec.V5E.hbm_bw), rel=1e-9)
    # the executables table row carries the same live figure plus the
    # roofline classification against the v5e ridge
    row = [r for r in snap["executables"]
           if r["source"] == "xc_handmodel"][0]
    assert row["mfu"] == pytest.approx(expected, rel=1e-9)
    assert row["bound"] == "compute"  # 3200 flops/byte >> v5e ridge


def test_join_windows_deltas_between_scrapes():
    """The second scrape must derive utilization from the NEW samples
    only (delta window), not the cumulative history."""
    hwspec.set_override(hwspec.V5E)
    XLA_COST.record("xc_winmodel", 0, "cpu", "cpu",
                    {"flops": 1e9, "bytes accessed": 1e6})
    XLA_COST.map_source("xc_winelem", "xc_winmodel")
    observe_invoke_phases("element", "xc_winelem", 1, 0.0, 1e-3, 0.0)
    REGISTRY.snapshot()  # primes the window
    observe_invoke_phases("element", "xc_winelem", 1, 0.0, 4e-3, 0.0)
    snap = REGISTRY.snapshot()
    mfu = [s for s in _fam_samples(snap, "nns_mfu")
           if s["labels"].get("source") == "xc_winelem"][0]
    # window = the single 4 ms dispatch, NOT the (1+4)/2 ms cumulative
    assert mfu["value"] == pytest.approx(
        1e9 / (4e-3 * hwspec.V5E.peak_flops), rel=1e-9)


def test_single_frame_hist_bucket_maps_to_bucket0_executable():
    hwspec.set_override(hwspec.V5E)
    XLA_COST.record("xc_b0model", 0, "cpu", "cpu",
                    {"flops": 5e8, "bytes accessed": 5e5})
    XLA_COST.map_source("xc_b0elem", "xc_b0model")
    # the chain path labels its series bucket=1; the executable row is
    # keyed bucket=0 — the join must bridge them
    observe_invoke_phases("element", "xc_b0elem", 1, 0.0, 1e-3, 0.0)
    snap = REGISTRY.snapshot()
    row = [r for r in snap["executables"]
           if r["source"] == "xc_b0model"][0]
    assert row.get("dispatches_window", 0) >= 1
    assert "mfu" in row


def test_unknown_backend_exports_intensity_only():
    """CPU/unknown hardware: flops/bytes/intensity export (they are
    properties of the program) but no utilization gauge is derived."""
    XLA_COST.record("xc_cpumodel", 0, "cpu", "cpu",
                    {"flops": 1e9, "bytes accessed": 1e6})
    XLA_COST.map_source("xc_cpuelem", "xc_cpumodel")
    observe_invoke_phases("element", "xc_cpuelem", 1, 0.0, 1e-3, 0.0)
    snap = REGISTRY.snapshot()
    row = [r for r in snap["executables"]
           if r["source"] == "xc_cpumodel"][0]
    assert row["intensity_flops_per_byte"] == pytest.approx(1e3)
    assert "mfu" not in row and "hbm_bw_util" not in row
    assert "ridge_flops_per_byte" not in row
    assert not any(s["labels"].get("source") == "xc_cpuelem"
                   for s in _fam_samples(snap, "nns_mfu"))
    # the static gauges still export
    assert any(s["labels"].get("source") == "xc_cpumodel"
               for s in _fam_samples(snap, "nns_executable_flops"))


def test_hwspec_resolution(monkeypatch):
    """The peak table is keyed by ``device_kind``: a v5e chip reports
    "TPU v5 lite"; a TPU kind nobody entered — like the CPU — has no
    peaks and no price, never another generation's."""
    assert hwspec.spec_for_device_kind("TPU v5 lite") is hwspec.V5E
    assert hwspec.spec_for_device_kind("TPU v99") is None
    assert hwspec.spec_for_device_kind("cpu") is None
    assert hwspec.spec_for_device_kind(None) is None
    assert hwspec.V5E.ridge == pytest.approx(197e12 / 819e9)
    monkeypatch.delenv("NNS_TPU_CHIP_HOUR_USD", raising=False)
    assert hwspec.chip_hour_price("TPU v5 lite") == hwspec.V5E.chip_hour_usd
    assert hwspec.chip_hour_price("TPU v99") == 0.0
    assert hwspec.chip_hour_price(None) == 0.0
    prev = hwspec.set_override(hwspec.V5E)
    try:
        assert hwspec.spec_for_device_kind("cpu") is hwspec.V5E
    finally:
        hwspec.set_override(prev)


def test_join_derives_utilization_from_the_rows_device_kind():
    """The join reads the ``device_kind`` captured with the executable:
    a row compiled for a v5e gets an MFU, the same row on an unlisted
    TPU kind gets none — ``platform == "tpu"`` alone decides nothing."""
    for model, elem, kind in (("xc_v5emodel", "xc_v5eelem", "TPU v5 lite"),
                              ("xc_v99model", "xc_v99elem", "TPU v99")):
        XLA_COST.record(model, 0, "tpu", "tpu",
                        {"flops": 1e9, "bytes accessed": 1e6},
                        device_kind=kind)
        XLA_COST.map_source(elem, model)
        observe_invoke_phases("element", elem, 1, 0.0, 1e-3, 0.0)
    snap = REGISTRY.snapshot()
    by_source = {s["labels"].get("source"): s["value"]
                 for s in _fam_samples(snap, "nns_mfu")}
    assert by_source["xc_v5eelem"] == pytest.approx(
        1e9 / (1e-3 * hwspec.V5E.peak_flops), rel=1e-9)
    assert "xc_v99elem" not in by_source


# -- mesh attribution ---------------------------------------------------------


def test_shard_split_even_and_uneven():
    assert shard_split(8, 8, 2) == [4, 4]
    assert shard_split(8, 5, 2) == [4, 1]   # pads land on the tail
    assert shard_split(12, 11, 4) == [3, 3, 3, 2]
    assert shard_split(4, 0, 2) == [0, 0]


def test_shard_device_label_respects_data_axis_position():
    """The device list is the mesh array in C order, so a data shard
    is a contiguous slice only when the data axis LEADS; with
    ``mesh=model:2,data:2`` shard 0 is the strided column {dev0, dev2},
    not the flat half [dev0, dev1]."""
    devs = ["D0", "D1", "D2", "D3"]
    trailing = {"axes": [["model", 2], ["data", 2]], "devices": devs,
                "data_axis": "data", "shards": 2}
    assert shard_device_label(trailing, 0) == "D0+1"  # {D0, D2}
    assert shard_device_label(trailing, 1) == "D1+1"  # {D1, D3}
    leading = {"axes": [["data", 2], ["model", 2]], "devices": devs,
               "data_axis": "data", "shards": 2}
    assert shard_device_label(leading, 0) == "D0+1"   # {D0, D1}
    assert shard_device_label(leading, 1) == "D2+1"   # {D2, D3}
    flat = {"axes": [["data", 4]], "devices": devs,
            "data_axis": "data", "shards": 4}
    assert [shard_device_label(flat, i) for i in range(4)] == devs
    no_data = {"axes": [["model", 2]], "devices": devs[:2],
               "data_axis": "data", "shards": 1}
    assert shard_device_label(no_data, 0) == "D0+1"


def test_imbalance_zero_on_even_split_positive_on_uneven():
    """The issue's acceptance pair, through the REAL jax-xla mesh
    window path: full windows split evenly (imbalance 0.0), a forced
    short window pads and skews the split (imbalance > 0)."""
    w = np.asarray(np.random.RandomState(5).randn(16, 16), np.float32)
    name = register_model("xc_meshmodel", lambda x: x @ w,
                          in_shapes=[(16,)], in_dtypes=np.float32)
    sp = JaxXlaFilter()
    sp.configure(FilterProps(framework="jax-xla", model=name,
                             mesh="data:2"))
    frame = [np.zeros((16,), np.float32)]
    sp.invoke_batched([frame] * 4, 4)   # even: 2 + 2
    row = MESH_STATS.get(name)
    assert row["shards"] == 2
    assert row["shard_frames"] == [2, 2]
    assert row["imbalance"] == 0.0
    assert row["pad_slots"] == 0
    snap = REGISTRY.snapshot()
    imb = [s for s in _fam_samples(snap, "nns_shard_imbalance")
           if s["labels"].get("source") == name]
    assert imb and imb[0]["value"] == 0.0
    sp.invoke_batched([frame] * 3, 4)   # forced uneven: 2 + 1, 1 pad
    row = MESH_STATS.get(name)
    assert row["shard_frames"] == [4, 3]
    assert row["imbalance"] > 0.0
    assert row["pad_slots"] == 1
    assert row["dispatches"] == 2
    snap = REGISTRY.snapshot()
    imb = [s for s in _fam_samples(snap, "nns_shard_imbalance")
           if s["labels"].get("source") == name][0]
    assert imb["value"] == pytest.approx(4 / 3.5 - 1.0)
    pads = [s for s in _fam_samples(snap, "nns_mesh_pad_slots_total")
            if s["labels"].get("source") == name][0]
    assert pads["value"] == 1
    sp.close()


def test_indivisible_window_counts_as_replicated():
    w = np.asarray(np.random.RandomState(6).randn(16, 16), np.float32)
    name = register_model("xc_replmodel", lambda x: x @ w,
                          in_shapes=[(16,)], in_dtypes=np.float32)
    sp = JaxXlaFilter()
    sp.configure(FilterProps(framework="jax-xla", model=name,
                             mesh="data:2"))
    frame = [np.zeros((16,), np.float32)]
    sp.invoke_batched([frame] * 3, 3)  # 3 % 2 != 0: no constraint
    row = MESH_STATS.get(name)
    assert row["replicated_dispatches"] == 1
    assert row["imbalance"] == 0.0  # every chip computed everything
    sp.close()


def test_sharded_model_records_mesh_dispatch():
    import jax

    from nnstreamer_tpu.parallel import ShardedModel, make_mesh

    devs = jax.devices("cpu")[:2]
    mesh = make_mesh("data:2", devices=devs)
    m = ShardedModel(mesh, lambda x: x * 2.0, name="xc_shardedfn")
    m(np.zeros((8, 4), np.float32))
    row = MESH_STATS.get("xc_shardedfn")
    assert row is not None
    assert row["shards"] == 2
    assert row["frames"] == 8
    assert row["shard_frames"] == [4, 4]


# -- rendering ----------------------------------------------------------------


def test_nns_top_renders_mfu_column_and_mesh_section():
    from nnstreamer_tpu.obs.top import render

    base = {"time": 100.0, "pipelines": [{
        "pipeline": "p", "playing": True, "elements": [{
            "element": "net", "factory": "tensor_filter",
            "stats": {"buffers_in": 10, "buffers_out": 10},
            "filter": {"invokes": 10, "frames": 10, "latency_us": 100,
                       "throughput_milli_fps": 1000,
                       "dispatch_milli_fps": 1000,
                       "avg_batch_occupancy": 1.0,
                       "avg_stream_occupancy": 1.0,
                       "attached_streams": 0, "host_prep_us": 5,
                       "device_us": 90, "host_drain_us": 5,
                       "batch": 1, "model": "m1"}}]}],
        "pools": [], "links": [], "compiles": [], "transfers": [],
        "device_memory": [],
        "executables": [{"source": "m1", "bucket": 0,
                         "placement": "mesh(data:2)", "platform": "tpu",
                         "flops": 1e9, "bytes": 1e6,
                         "peak_memory_bytes": 1024,
                         "peak_memory_estimated": True, "compiles": 1,
                         "intensity_flops_per_byte": 1000.0,
                         "mfu": 0.4321}],
        "mesh": [{"source": "m1", "axes": [["data", 2]],
                  "devices": ["TPU:0", "TPU:1"], "data_axis": "data",
                  "shards": 2, "dispatches": 10, "frames": 100,
                  "slots": 104, "pad_slots": 4,
                  "pad_frac": 4 / 104.0, "replicated_dispatches": 0,
                  "shard_frames": [52, 48],
                  "imbalance": 52 / 50.0 - 1.0}]}
    cur = json.loads(json.dumps(base))
    cur["time"] = 101.0
    out = render(cur, base)
    assert "MFU%" in out
    assert "43.21" in out            # the element row's MFU column
    assert "MESH" in out and "TPU:1" in out
    assert "data:2" in out
    # both shard rows render with their frame counts
    assert "52" in out and "48" in out


def test_snapshot_executables_and_mesh_are_lists():
    snap = REGISTRY.snapshot()
    assert isinstance(snap["executables"], list)
    assert isinstance(snap["mesh"], list)
