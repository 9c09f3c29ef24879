"""The benchmark's one command: one process, one cell, one run, one line.

    python3 -m benchmark.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one configuration, one cell, one traffic mix
or one metric sits in a file of its own, found by the name in
``BENCHMARK.json`` (see ``benchmark/README.md``); nothing in this file
names a cell, a configuration or a metric.  The program under test is
driven through the entry points a user calls (``parse_launch``,
``register_model``, element properties, ``appsrc.push_buffer``,
``appsink.pull``); from it the benchmark reads only counters, spans and
kernel names.

Without a TPU whose ``device_kind`` is in ``benchmark/peaks.json``, or
with fewer chips than the cell asks for, it exits non-zero and prints no
result line.
"""

from __future__ import annotations

import time

T_PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:  # `python3 benchmark/run.py` as well as `-m`
    sys.path.insert(0, ROOT)

from benchmark import BenchmarkError  # noqa: E402
from benchmark.inputs import tensors  # noqa: E402


class Loader:
    """Finds the benchmark's files by name under ``root``."""

    def __init__(self, root: str = ROOT):
        self.root = os.path.abspath(root)
        with open(os.path.join(self.root, "BENCHMARK.json")) as f:
            self.manifest = json.load(f)
        self.dir = os.path.join(self.root, self.manifest["paths"][0])

    def entry(self, section: str, name: str) -> dict:
        for row in self.manifest[section]:
            if row["name"] == name:
                return row
        raise BenchmarkError(f"BENCHMARK.json {section} has no {name!r}")

    def json(self, kind: str, name: str) -> dict:
        path = os.path.join(self.dir, kind, name + ".json")
        with open(path) as f:
            return json.load(f)

    def module(self, kind: str, name: str):
        path = os.path.join(self.dir, kind, name + ".py")
        if not os.path.isfile(path):
            raise BenchmarkError(f"no file {path}")
        modname = "benchmark_%s_%s_%x" % (
            kind, name.replace(".", "_"), abs(hash(path)) & 0xFFFFFF)
        if modname in sys.modules:
            return sys.modules[modname]
        spec = importlib.util.spec_from_file_location(modname, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[modname] = mod
        spec.loader.exec_module(mod)
        return mod

    def config(self, name: str) -> dict:
        entry = self.entry("configs", name)
        with open(os.path.join(self.root, entry["file"])) as f:
            return json.load(f)

    def reports(self, metric: dict, cell: str) -> bool:
        """Whether ``cell`` reports ``metric``: its own ``workloads`` list,
        else every cell that reports the end-to-end metric it moves."""
        if "workloads" in metric:
            return cell in metric["workloads"]
        moved = metric.get("moves")
        if moved is None:
            return True
        return self.reports(self.entry("end_to_end", moved), cell)


def cut_faults(cfg: dict, listed: list) -> list:
    """What is wrong with a configuration file's stated cut, as text;
    empty where it holds together.  ``reduced`` is the list
    ``BENCHMARK.json`` gives, key for key.  A file that reduces
    something names keys it has, gives the source's value for each
    under ``published``, says under ``deployment`` how many chips share
    a layer (``chips_per_layer``) and what this chip holds of one
    (``this_chip``), and differs from ``published`` in no other key."""
    reduced = cfg.get("reduced")
    if reduced != listed:
        return [f"reduced is {reduced!r}, BENCHMARK.json lists {listed!r}"]
    if not reduced:
        return []
    faults = [f"reduced key {key!r} is not in the file"
              for key in reduced if key not in cfg]
    published = cfg.get("published")
    if not isinstance(published, dict):
        return faults + ["no `published` object (the source's value of "
                         "each reduced key)"]
    faults += [f"`published` lacks the reduced key {key!r}"
               for key in reduced if key not in published]
    faults += [f"{key!r} is {cfg[key]!r}, published {value!r}, and is "
               "not listed in reduced"
               for key, value in published.items()
               if key not in reduced and key in cfg and cfg[key] != value]
    deployment = cfg.get("deployment")
    if not isinstance(deployment, dict) \
            or not isinstance(deployment.get("chips_per_layer"), int) \
            or deployment["chips_per_layer"] < 1 \
            or not deployment.get("this_chip"):
        faults.append("no `deployment` object with `chips_per_layer` (a "
                      "whole number) and `this_chip` (what it holds)")
    return faults


def launch_line(work: dict, cfg: dict, mix: dict, **values) -> str:
    """A cell's launch line with its placeholders filled in: from the
    caller's ``values``, the mix's parameters, the configuration's own
    ``launch_fields`` and, where the file has them, ``size``
    (``image_size``) and ``transform`` - the first named wins."""
    fields = {key: cfg[name] for key, name in (("size", "image_size"),
                                               ("transform", "transform"))
              if name in cfg}
    fields.update(cfg.get("launch_fields", {}))
    fields.update(mix)
    fields.update(values)
    try:
        return work["launch"].format(**fields)
    except KeyError as e:
        raise BenchmarkError(
            f"the launch line of {work.get('name')!r} asks for "
            f"{{{e.args[0]}}}, which neither the mix, the caller nor "
            f"{cfg.get('name')!r} (launch_fields, image_size, transform) "
            "provides") from None


def find_devices(chips: int, peaks: dict, require_chip: bool):
    """The devices the cell runs on and the peaks of their kind."""
    import jax

    devices = jax.devices()
    first = devices[0]
    if require_chip:
        if first.platform != "tpu":
            raise BenchmarkError(
                f"no accelerator: JAX found platform={first.platform!r} "
                f"({first.device_kind}, {len(devices)} device(s))")
        if first.device_kind not in peaks:
            raise BenchmarkError(
                f"device kind {first.device_kind!r} is not in peaks.json")
    if len(devices) < chips:
        raise BenchmarkError(
            f"the cell asks for {chips} chip(s), JAX found {len(devices)}")
    return devices, peaks.get(first.device_kind)


def memory_peak_bytes(devices) -> int:
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


class Counters:
    """Snapshots of the program's process-wide counters, and deltas."""

    def __init__(self):
        import jax

        self._xla_compiles = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, _seconds, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self._xla_compiles += 1

    def snapshot(self) -> dict:
        from nnstreamer_tpu.obs.transfer import LEDGER
        from nnstreamer_tpu.utils.stats import COMPILE_STATS, DISPATCH_STATS

        ledger = {}
        for row in LEDGER.snapshot():
            key = f"{row['direction']}.{row['reason']}"
            ledger[key + ".bytes"] = ledger.get(key + ".bytes", 0) \
                + row["bytes"]
            ledger[key + ".count"] = ledger.get(key + ".count", 0) \
                + row["count"]
        compiles = COMPILE_STATS.snapshot()
        return {
            "t": time.perf_counter(),
            "dispatch": DISPATCH_STATS.snapshot(),
            "ledger": ledger,
            "compiles": sum(r["count"] for r in compiles
                            if r["kind"] != "aot_fallback"),
            "aot_fallback": sum(r["count"] for r in compiles
                                if r["kind"] == "aot_fallback"),
            "xla_compiles": self._xla_compiles,
        }

    @staticmethod
    def delta(a: dict, b: dict) -> dict:
        def sub(x, y):
            return {k: y.get(k, 0) - x.get(k, 0) for k in set(x) | set(y)}

        return {"seconds": b["t"] - a["t"],
                "dispatch": sub(a["dispatch"], b["dispatch"]),
                "ledger": sub(a["ledger"], b["ledger"]),
                "compiles": b["compiles"] - a["compiles"],
                "aot_fallback": b["aot_fallback"] - a["aot_fallback"],
                "xla_compiles": b["xla_compiles"] - a["xla_compiles"]}


class Run:
    """What a traffic generator is handed: the cell, its files, the
    devices, and the hooks it calls at the window's edges."""

    def __init__(self, loader, cell, cfg, mix, workload, seed, seconds,
                 trace, devices, out_dir, on_chip=True):
        self.loader, self.cell, self.cfg, self.mix = loader, cell, cfg, mix
        self.workload = workload
        self.seed, self.seconds, self.trace = int(seed), float(seconds), trace
        self.devices, self.out_dir = devices, out_dir
        self.chips = int(cell["chips"])
        self.on_chip = on_chip
        self.model = loader.module("models", cfg["model"])
        self.inputs = loader.module("inputs", cfg.get("inputs",
                                                      "image_frames"))
        self.counters = Counters()
        self.t_start = T_PROCESS_START
        self.log = lambda msg: print(f"[bench] {msg}", flush=True)

    def make_weights(self):
        return self.loader.module("weights", self.cfg["weights"]).make(
            self.cfg, self.seed)

    def make_ring(self, slots: int, batch: int) -> list:
        """``slots`` seeded input slots of ``batch`` frames each, from
        the file the configuration names under ``inputs``."""
        return self.inputs.make_ring(self.cfg, self.mix, self.seed,
                                     int(slots), int(batch))

    def launch(self, **values) -> str:
        """The cell's launch line with its placeholders filled in."""
        return launch_line(self.workload, self.cfg, self.mix, **values)


def _device_line(devices, peak: int) -> dict:
    first = devices[0]
    return {"platform": first.platform, "kind": first.device_kind,
            "count": len(devices), "memory_peak_bytes": int(peak)}


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             root: str = ROOT, rehearsal: bool = False,
             details: dict | None = None) -> dict:
    """One run of one cell.  Returns the object of the result line.
    ``rehearsal=True`` (no look for a chip, no compile cache) is for the
    CPU tests in ``tests/benchmark``; the command never passes it.
    ``details``, if given, receives the numbers compared and the sampled
    frames (``benchmark/control.py`` reads the control on the same)."""
    os.environ.setdefault("NNS_TPU_NO_NATIVE", "1")
    loader = Loader(root)
    cell = loader.entry("workloads", workload)
    with open(os.path.join(loader.dir, "peaks.json")) as f:
        peaks = json.load(f)["device_kinds"]

    from nnstreamer_tpu.utils.jaxcache import enable_compile_cache

    # before the first compile
    cache_dir = "off" if rehearsal else enable_compile_cache()
    devices, peak = find_devices(int(cell["chips"]), peaks, not rehearsal)
    cfg = loader.config(cell["config"])
    mix = loader.json("traffic", cell["traffic"])
    work = loader.json("workloads", workload)
    # the trace of this run: under $TMPDIR, removed once reduced
    out_dir = tempfile.mkdtemp(prefix="nns_benchmark_")
    run = Run(loader, cell, cfg, mix, work, seed, seconds, bool(trace),
              devices[:int(cell["chips"])], out_dir,
              on_chip=devices[0].platform == "tpu")
    run.log(f"cell {workload} seed {seed} seconds {seconds} trace "
            f"{int(bool(trace))}; compile cache {cache_dir}; devices "
            f"{len(devices)} x {devices[0].device_kind}")

    traffic = loader.module("traffic", mix["kind"])
    try:
        obs = traffic.run(run)                # set-up, window, drain
    finally:
        shutil.rmtree(out_dir, ignore_errors=True)
    obs["memory_peak_bytes"] = memory_peak_bytes(run.devices)
    obs["chips"] = run.chips
    obs["peaks"] = peak
    obs["cost"] = loader.module("costs", cfg["costs"]).frame_cost(cfg)

    # the output check, after the window and after the program's state
    # is freed; not part of setup_s
    t0 = time.perf_counter()
    reference = loader.module("reference", cfg["reference"])
    sample = obs.pop("sample")
    numbers = reference.check(cfg, run.seed, sample["frames"],
                              sample["served"])
    numbers += obs.pop("stream_checks")
    if details is not None:
        details.update(numbers=numbers, frames=sample["frames"], cfg=cfg,
                       obs=obs)
    correct = True
    for row in numbers:
        ok = bool(row["value"] <= row["limit"])
        correct &= ok
        run.log(f"check {row['name']}: {row['value']:.6g} "
                f"(limit {row['limit']:.6g}) {'ok' if ok else 'FAIL'}")
    run.log(f"check took {time.perf_counter() - t0:.1f} s on "
            f"{len(tensors(sample['frames'])[0])} frames")
    run.log("in the window: compiles %d, aot_fallback %d, xla compiles %d"
            % (obs["window"]["compiles"], obs["window"]["aot_fallback"],
               obs["window"]["xla_compiles"]))

    section = "per_layer" if trace else "end_to_end"
    kind = "readers" if trace else "end_to_end"
    metrics = {}
    for m in loader.manifest[section]:
        if not loader.reports(m, workload):
            continue
        value = _read_metric(loader, kind, m["name"], obs)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    device = _device_line(devices, obs["memory_peak_bytes"])
    line = {"correct": bool(correct), "attempted": int(obs["attempted"]),
            "failed": int(obs["failed"]), "metrics": metrics,
            "device": device}
    if trace:
        device["busy_s"] = obs["trace"]["busy_s"]
        device["window_s"] = obs["trace"]["window_s"]
        line["breakdown"] = {"device_ops": obs["trace"]["device_ops"][:10],
                             "idle_gaps": obs["trace"]["idle_gaps"][:10]}
    # every number compared beside its limit; the line's last key
    line["compared"] = {row["name"]: {"value": float(row["value"]),
                                      "limit": float(row["limit"])}
                        for row in numbers}
    return line


def _read_metric(loader: Loader, kind: str, name: str, obs: dict):
    """A metric is a reader of its own, or a data file that names a
    reader and its parameters."""
    spec_path = os.path.join(loader.dir, "layer_metrics", name + ".json")
    params = {}
    reader = name
    if kind == "readers" and os.path.isfile(spec_path):
        with open(spec_path) as f:
            params = json.load(f)
        reader = params.get("reader", name)
    return loader.module(kind, reader).read(obs, **params.get("args", {}))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        line = run_cell(args.workload, args.seed, args.seconds,
                        bool(args.trace))
    except (BenchmarkError, ImportError, FileNotFoundError) as e:
        print(f"benchmark: {type(e).__name__}: {e}", file=sys.stderr)
        return 1
    print(json.dumps(line), flush=True)
    for name, row in line["compared"].items():       # stderr's last lines
        print(f"compared {name}: {row['value']:.6g} (limit "
              f"{row['limit']:.6g})", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
