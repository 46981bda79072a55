"""Run one cell of the chip benchmark once.

    python3 benchmarks/chip/run.py --workload <name> --seed <n> \\
        --seconds <s> --trace <0|1>

The cell, its configuration file and its traffic mix are found by name in
``BENCHMARK.json`` at the root of the checkout, the configuration's
architecture family in ``families/<family>.py``. One process holds the
chip from start to end: it exits non-zero without a result when the
family has no file, JAX finds no TPU, fewer chips than the cell asks for,
or a device missing from ``peaks.json``. It makes the weights from the
seed, warms every shape, measures for ``--seconds``, then checks what the
timed path produced against the family's plain reference and prints, as
the last
line of standard output, one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics with ``--trace 0``,
its per-layer metrics with ``--trace 1``), ``device`` and, last, the
numbers compared with their limits. The same numbers are the last lines of
standard error.
"""
from __future__ import annotations

import time

T_MONO = time.monotonic()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]


def process_age_s() -> float:
    """Seconds since the kernel started this process."""
    try:
        start = float(open("/proc/self/stat").read().rsplit(")", 1)[1]
                      .split()[19]) / os.sysconf("SC_CLK_TCK")
        return max(float(open("/proc/uptime").read().split()[0]) - start,
                   0.0)
    except (OSError, ValueError, IndexError):
        return 0.0


T_START = T_MONO - process_age_s()


class BenchError(RuntimeError):
    """A run that cannot give a result (exit code 3)."""


def load_cell(name: str):
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise BenchError(f"no workload {name!r}; have {sorted(cells)}")
    cell = cells[name]
    cfg = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    config = json.loads((ROOT / cfg["file"]).read_text())
    mix = json.loads((HERE / "traffic" / f"{cell['traffic']}.json")
                     .read_text())
    limits = json.loads((HERE / "limits" / f"{name}.json").read_text())
    return bench, cell, config, mix, limits


def metrics_for(bench, cell, trace: bool, where=HERE / "metrics"):
    """The cell's end-to-end metrics (``--trace 0``) or per-layer metrics
    (``--trace 1``), each with its reader ``<where>/<name>.py``."""
    reported = {m["name"] for m in bench["end_to_end"]
                if cell["name"] in m.get("workloads", [cell["name"]])}
    if not trace:
        return [(m, None) for m in bench["end_to_end"]
                if m["name"] in reported]
    out = []
    for m in bench["per_layer"]:
        if cell["name"] not in m.get("workloads", [cell["name"]]) \
                or m["moves"] not in reported:
            continue
        path = where / f"{m['name']}.py"
        spec = importlib.util.spec_from_file_location(
            "metric_" + m["name"].replace(".", "_"), path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        out.append((m, mod.read))
    return out


def read_metrics(out, pairs, peak):
    """{name: {value, unit}} of the metrics whose reader (or, for an
    end-to-end metric, the runner) gave a number."""
    metrics = {}
    for m, read in pairs:
        value = out.e2e.get(m["name"]) if read is None else read(out, peak)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    return metrics


def check_device(chips: int):
    """The devices, or BenchError without a TPU or with too few chips."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise BenchError(f"JAX found no TPU (platform "
                         f"{devices[0].platform!r}); this benchmark runs "
                         f"on the chip only")
    if len(devices) < chips:
        raise BenchError(f"the cell asks for {chips} chips, JAX sees "
                         f"{len(devices)}")
    return devices


def device_peak(kind: str):
    table = json.loads((HERE / "peaks.json").read_text())["devices"]
    if kind not in table:
        raise BenchError(f"no peaks for device kind {kind!r} in peaks.json")
    return table[kind]


def judge(readings, limits):
    """{name: (value, limit)} and whether every value is within its limit."""
    checks = {k: (readings[k], limits[k]["limit"]) for k in limits}
    ok = all(math.isfinite(v) and v <= lim for v, lim in checks.values())
    return checks, ok


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        bench, cell, config, mix, limits = load_cell(args.workload)
        sys.path[:0] = [str(ROOT / "src"), str(HERE)]
        os.environ.setdefault("TPU_LOG_DIR", "disabled")
        import families
        family = families.load(config)
        devices = check_device(cell["chips"])
        peak = device_peak(devices[0].device_kind)
    except (BenchError, OSError, LookupError, ValueError) as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 3
    from repro.launch.compile_cache import enable_compile_cache
    import drive
    print(f"compile cache: {enable_compile_cache()}", flush=True)
    import jax
    # every program the run compiles, however quick, is found again next run
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    ctx = drive.Ctx(config=config, mix=mix,
                    seed=args.seed, seconds=args.seconds,
                    trace=bool(args.trace), t_start=T_START, family=family)
    out = drive.RUNNERS[mix["entry"]](ctx)

    metrics = read_metrics(out, metrics_for(bench, cell, bool(args.trace)),
                           peak)
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices),
              "memory_peak_bytes": out.memory_peak_bytes}
    result = {"correct": None, "attempted": out.attempted,
              "failed": out.failed, "metrics": metrics, "device": device}
    if out.summary is not None:
        device["busy_s"] = out.summary.busy_s
        device["window_s"] = out.summary.window_s
        result["breakdown"] = {"device_ops": out.summary.device_ops,
                               "idle_gaps": out.summary.idle_gaps}
    checks, ok = judge(out.readings, limits)
    result["correct"] = ok and out.failed == 0
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in checks.items()}

    for line in out.notes:
        print(line)
    print(f"device: {json.dumps(device)}")
    print(f"end-to-end: {json.dumps(out.e2e)}")
    print(f"window counters: {json.dumps(out.counters)}; work: "
          f"{json.dumps(out.work)}")
    print(f"program readings: {json.dumps(out.program)}")
    if out.summary is not None:
        s = out.summary
        print(f"kernel seconds: {json.dumps(s.kernel_s)}")
        print("program spans: " + json.dumps({
            k: getattr(s, k) for k in (
                "span_count", "scope_s", "unscoped_ops", "idle_in_s",
                "idle_attributed_s", "serve_host_idle_s",
                "dispatch_on_clock")}))
    print(f"readings: {json.dumps(out.readings)}")
    for name, vals in out.control.items():
        print(f"{name}: {json.dumps(vals)}")
    for k, (v, lim) in checks.items():
        print(f"check {k} {v!r} limit {lim!r}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
