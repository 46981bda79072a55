"""Readings that the limits of ``correct`` are set from, on the chip.

    python3 benchmarks/chip/calibrate.py --workload <name> \\
        --seeds <n,n,...> [--seconds <s>]

For each seed, in one process that holds the chip: the cell's runner, as
``run.py`` drives it (set-up, a window of ``--seconds``, 0 for a train
cell, whose checked steps come before the window), with the precision
control and, for a train cell, the planted half-batch fault, both read
against the same float32 reference. One JSON line per seed; the largest
program reading over the seeds is the lower end of a limit, the smallest
control or fault reading the upper end. The benchmark's own runs never run
the control.
"""
from __future__ import annotations

import time

T_MONO = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402

import run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=0.0)
    args = ap.parse_args(argv)
    bench, cell, config, mix, _ = run.load_cell(args.workload)
    sys.path[:0] = [str(run.ROOT / "src"), str(run.HERE)]
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        devices = run.check_device(cell["chips"])
    except run.BenchError as e:
        print(f"calibrate.py: {e}", file=sys.stderr)
        return 3
    from repro.launch.compile_cache import enable_compile_cache
    import drive
    enable_compile_cache()
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.monotonic()
        ctx = drive.Ctx(config=config, mix=mix,
                        seed=seed, seconds=args.seconds, trace=False,
                        t_start=t0, controls=True)
        out = drive.RUNNERS[mix["entry"]](ctx)
        print(json.dumps({"workload": cell["name"], "seed": seed,
                          "device": devices[0].device_kind,
                          "program": out.readings, **out.control,
                          "e2e": out.e2e, "attempted": out.attempted,
                          "failed": out.failed,
                          "memory_peak_bytes": out.memory_peak_bytes,
                          "seconds": time.monotonic() - t0}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
