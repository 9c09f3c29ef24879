#!/usr/bin/env python3
"""Where a prefill chunk's device time goes, by stage of the program.

Drives one token cell of ``BENCHMARK.json`` as ``benchmark/traffic/
cached_replay.py`` does up to the end of prefill (weights, the model's
registration, the seeded chunks, the prefill line started and every
chunk fenced), with a profiler capture around a stretch of chunks, and
reduces the capture with ``benchmark/stages.py`` on the prefill
filter's own ``executable_text()``: device ms a chunk per ``nns.*``
stage (they sum to the busy time), and the bytes the instructions of a
stage name as operands and results in that text (a count that orders;
it does not size: a fusion that reads a row of an operand names all of
it).  Chip only, one process, like the benchmark; no window follows.

    python tools/chunk_trace.py --workload longcat.decode4k --seed 54 \\
        [--first 8] [--chunks 16] [--out chiprun_out/chunk.json] \\
        [--text chiprun_out/prefill.hlo.txt]

Prints the table and one JSON line; PERF.md section 5's "Where a
prefill chunk's time goes" is made of its readings (PRs 48, 49, 54).
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import re
import shutil
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

_SHAPE = re.compile(r"\b(pred|[su](?:8|16|32|64)|bf16|f16|f32|f64)"
                    r"\[([\d,]*)\]")
_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2,
          "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
          "f64": 8}
#: instructions that move nothing of their own
_FREE = re.compile(r" = [^=]*?(?<![\w-])(parameter|get-tuple-element|tuple|"
                   r"bitcast|constant|while|conditional|call)\(")


def stage_bytes(text: str) -> dict:
    """{stage: bytes}: operands and results the text names on the
    instructions booked to a stage, outside fused computations (a
    fusion counts once, by what it is handed and what it returns)."""
    from benchmark import stages

    by_name = stages.stage_map(text)
    totals: dict = {}
    fused = False
    for line in text.splitlines():
        m = stages._COMPUTATION.match(line)
        if m is not None:
            fused = "fused_computation" in m.group(1) \
                or m.group(1).startswith("region_")
            continue
        m = stages._INSTR.match(line)
        if m is None or fused or _FREE.search(line):
            continue
        head = line.split(", metadata=")[0].split(", calls=")[0]
        size = 0
        for kind, dims in _SHAPE.findall(head):
            n = 1
            for d in filter(None, dims.split(",")):
                n *= int(d)
            size += n * _BYTES[kind]
        stage = by_name.get(m.group(1), stages.NO_METADATA)
        totals[stage] = totals.get(stage, 0) + size
    return totals


def short(stage: str) -> str:
    """``nns.model/layer03/moe/combine`` -> ``moe/combine``: a stage
    without the model's root and the layer's number, so that the layers
    add up."""
    parts = [p for p in stage.split("/")
             if not p.startswith("nns.") and not re.fullmatch(
                 r"layer\d+", p)]
    return "/".join(parts) or stage


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--first", type=int, default=8,
                    help="chunks fenced before the capture starts")
    ap.add_argument("--chunks", type=int, default=16,
                    help="chunks fenced inside the capture")
    ap.add_argument("--out", help="write the JSON here too")
    ap.add_argument("--text", help="write the prefill program's text here")
    ap.add_argument("--cpu", action="store_true",
                    help="a rehearsal: no look for a chip, no cache")
    ap.add_argument("--root", default=ROOT,
                    help="the checkout whose BENCHMARK.json names the cell")
    args = ap.parse_args(argv)

    os.environ.setdefault("NNS_TPU_NO_NATIVE", "1")
    from benchmark import BenchmarkError, stages, trace
    from benchmark.run import Loader, Run, find_devices, launch_line

    loader = Loader(args.root)
    cell = loader.entry("workloads", args.workload)
    with open(os.path.join(loader.dir, "peaks.json")) as f:
        peaks = json.load(f)["device_kinds"]
    if not args.cpu:
        from nnstreamer_tpu.utils.jaxcache import enable_compile_cache

        enable_compile_cache()
    devices, _peak = find_devices(int(cell["chips"]), peaks, not args.cpu)
    cfg = loader.config(cell["config"])
    mix = loader.json("traffic", cell["traffic"])
    work = loader.json("workloads", args.workload)
    if "prefill_launch" not in work:
        raise SystemExit(f"{args.workload}: no prefill line (a token cell "
                         "of kind cached_replay has one)")
    out_dir = tempfile.mkdtemp(prefix="nns_chunk_trace_")
    run = Run(loader, cell, cfg, mix, work, args.seed, 0.0, True,
              devices[:1], out_dir, on_chip=devices[0].platform == "tpu")

    from nnstreamer_tpu.runtime import parse_launch

    batch = int(mix["batch"])
    params = run.make_weights()
    model = f"chunk_{cfg['name']}_b{batch}_s{args.seed}"
    run.model.register(cfg, params, batch, model)
    chunks = run.inputs.prefill_chunks(cfg, args.seed)
    first = min(args.first, max(len(chunks) - 1, 0))
    last = min(first + args.chunks, len(chunks))
    prefix = work.get("prefill_prefix", "pf_")
    pre = parse_launch(launch_line(
        {"launch": work["prefill_launch"], "name": work.get("name")},
        cfg, mix, model=model))
    src, sink = pre[prefix + "src"], pre[prefix + "sink"]
    src.frames, src.pool_size = chunks, len(chunks)
    src.num_buffers = len(chunks)
    host_ms = []
    pre.start()
    with contextlib.ExitStack() as capture:
        try:
            done, t_last, started = 0, time.perf_counter(), False
            while done < len(chunks):
                if done == first and not started:
                    capture.enter_context(trace.quiet_pipeline_trace(out_dir))
                    started = True
                buf = sink.pull(timeout=0.5)
                if buf is None:
                    if pre.error is not None:
                        raise BenchmarkError(f"prefill line: {pre.error}")
                    if time.perf_counter() - t_last > 600.0:
                        raise BenchmarkError(f"prefill stopped after {done}")
                    continue
                run.model.fence(buf)
                done += 1
                now = time.perf_counter()
                host_ms.append((now - t_last) * 1e3)
                t_last = now
                if done == last:
                    capture.close()
            text = pre[prefix + "net"].subplugin.executable_text()
        finally:
            capture.close()
            pre.stop()
    run.model.unregister(model)

    where = ({"device_plane": trace.DEVICE_PLANE, "ops_line": trace.OPS_LINE}
             if run.on_chip else
             {"device_plane": "/host:CPU", "ops_line": "tf_XLA"})
    planes = trace.load_xplane(trace.find_xplane(out_dir), prefix, **where)
    seconds = stages.stage_seconds(planes, text, 1, **where)
    runs = sum(len(line["events"]) for plane in planes
               if plane["name"].startswith(where["device_plane"])
               for line in plane["lines"]
               if line["name"] == trace.MODULES_LINE) or (last - first)
    shutil.rmtree(out_dir, ignore_errors=True)
    nbytes = stage_bytes(text)
    table: dict = {}
    for stage, s in seconds.items():
        row = table.setdefault(short(stage), {"ms_a_chunk": 0.0, "bytes": 0})
        row["ms_a_chunk"] += s * 1e3 / runs
    for stage, b in nbytes.items():
        if short(stage) in table:
            table[short(stage)]["bytes"] += b
    total = sum(r["ms_a_chunk"] for r in table.values())
    print(f"{args.workload} seed {args.seed}: chunks {first}..{last - 1} of "
          f"{len(chunks)}, {runs} executions in the capture, "
          f"{total:.3f} device ms a chunk; host clock a fenced chunk "
          f"{sum(host_ms[first:last]) / max(last - first, 1):.1f} ms")
    for name, row in sorted(table.items(),
                            key=lambda kv: -kv[1]["ms_a_chunk"]):
        print(f"  {name:36s} {row['ms_a_chunk']:9.3f} ms "
              f"{100 * row['ms_a_chunk'] / total:5.1f} %  "
              f"{row['bytes'] / 1e9:8.3f} GB named")
    result = {"workload": args.workload, "seed": args.seed,
              "chunks": [first, last], "executions": runs,
              "device_ms_a_chunk": total, "stages": table,
              "host_ms_a_chunk": host_ms,
              "device": devices[0].device_kind}
    print(json.dumps({k: v for k, v in result.items()
                      if k != "host_ms_a_chunk"}))
    for path, body in ((args.out, json.dumps(result, indent=1)),
                       (args.text, text)):
        if path:
            os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
            with open(path, "w") as f:
                f.write(body)
    return 0


if __name__ == "__main__":
    sys.exit(main())
