"""``tools/chunk_trace.py``: the set-up of a token cell up to the end of
prefill with a capture around a stretch of chunks, reduced to device ms
a chunk by stage.  Driven here on the toy twin of ``longcat.decode4k``
on the CPU (a rehearsal: the times mean nothing), and its byte count on
a text written by hand."""

import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tests", "benchmark"))
sys.path.insert(0, os.path.join(REPO, "tools"))

import chunk_trace  # noqa: E402
import test_longcat_cell as cell  # noqa: E402


def test_the_stages_of_a_toy_chunk_add_up(tmp_path, capsys):
    root = cell._add_toy_cell(str(tmp_path / "root"))
    out, text = tmp_path / "chunk.json", tmp_path / "prefill.txt"
    assert chunk_trace.main([
        "--workload", cell.TOY, "--seed", "5400000001", "--first", "2",
        "--chunks", "4", "--cpu", "--root", root, "--out", str(out),
        "--text", str(text)]) == 0
    result = json.loads(out.read_text())
    assert result["chunks"] == [2, 6] and result["executions"] >= 4
    stages = result["stages"]
    for stage in ("moe/combine", "moe/router", "moe/dispatch", "head",
                  "embed"):
        assert stages[stage]["ms_a_chunk"] > 0, stage
    assert not [s for s in stages if s.startswith(("nns.", "layer"))]
    assert sum(s["ms_a_chunk"] for s in stages.values()) == pytest.approx(
        result["device_ms_a_chunk"])
    assert len(result["host_ms_a_chunk"]) == 12       # every chunk fenced
    assert "HloModule" in text.read_text()
    printed = capsys.readouterr().out
    assert "chunks 2..5 of 12" in printed and "moe/combine" in printed


def test_a_cell_without_a_prefill_line_is_refused():
    with pytest.raises(SystemExit, match="no prefill line"):
        chunk_trace.main(["--workload", "vitb16.replay", "--seed", "1",
                          "--cpu"])


HLO = """HloModule jit_f

%fused_computation (p: bf16[8,128]) -> f32[8,128] {
  %p = bf16[8,128]{1,0} parameter(0)
  ROOT %c = f32[8,128]{1,0} convert(%p), metadata={op_name="jit(f)/nns.model/layer00/moe/combine/convert"}
}

ENTRY %main (a: bf16[8,128], b: s32[4]) -> f32[8,128] {
  %a = bf16[8,128]{1,0} parameter(0)
  %b = s32[4]{0} parameter(1)
  %g = bf16[4,128]{1,0} gather(bf16[8,128]{1,0} %a, s32[4]{0} %b), metadata={op_name="jit(f)/nns.model/layer01/moe/combine/gather"}
  %k = f32[8,128]{1,0} custom-call(bf16[8,128]{1,0} %a), custom_call_target="tpu_custom_call", metadata={op_name="jit(f)/nns.model/layer02/moe/combine/jit(weighted_row_sum)/pallas_call"}
  ROOT %f = f32[8,128]{1,0} fusion(bf16[8,128]{1,0} %a), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(f)/nns.model/layer00/moe/combine/convert"}
}
"""


def test_bytes_are_what_the_text_names_outside_fusions():
    """Operands and results of the instructions booked to a stage: the
    gather's 1,024 + 2,048 + 16, the fusion's 4,096 + 2,048 and a
    kernel call's whole operand and result (a custom call is no
    ``call``); a fusion's inside and the parameters not."""
    got = chunk_trace.stage_bytes(HLO)
    assert got == {"nns.model/layer01/moe/combine": 1024 + 2048 + 16,
                   "nns.model/layer00/moe/combine": 4096 + 2048,
                   "nns.model/layer02/moe/combine": 4096 + 2048}
    assert chunk_trace.short("nns.model/layer01/moe/combine") \
        == "moe/combine"
