#!/usr/bin/env python3
"""Run one traced cell of the cell benchmark and keep what the harness drops.

    python3 tools/devtrace_bench.py OUT_DIR --workload put64m-c8 --seed 7 --seconds 51

A by-hand tool (PERF.md section 5 is made with it), not part of the benchmark:
it runs `benchmark/run.py ... --trace 1` unchanged in this process and writes,
under OUT_DIR and named `<workload>.s<seed>`:

  .devtrace.json  control/devtrace.py's reduction of the traced slice: the
                  device's busy/idle, seconds per named program, and the
                  longest idle gaps with the host stages overlapping each
  .ledger.json    every stage-ledger row, codec counter and S3-front counter
                  (get_stream_hops, get_stream_chunks) differenced over the
                  window, the window's facts and its end-to-end numbers
  .xplane.pb      the raw trace, with --keep-xplane (tens of MB)

On the chip: `chiprun -- python3 tools/devtrace_bench.py chiprun_out/dt --workload ...`.
"""

from __future__ import annotations

import json
import os
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


# Counters of minio_tpu.control.metrics.MetricsSys kept beside the codec's.
FRONT_COUNTERS = ("get_stream_hops", "get_stream_chunks")


def main(argv: list[str]) -> int:
    from benchmark.harness import run as bench_run
    from benchmark.harness import server as bench_server
    from benchmark.harness import trace as bench_trace
    from minio_tpu.control import devtrace

    out_dir, rest = argv[0], argv[1:]
    keep_xplane = "--keep-xplane" in rest
    rest = [a for a in rest if a != "--keep-xplane"]
    args = bench_run.parse(rest + ["--trace", "1"])
    base = os.path.join(out_dir, f"{args.workload}.s{args.seed}")
    os.makedirs(out_dir, exist_ok=True)

    find_xplane = bench_trace.find_xplane

    def find_and_keep(log_dir: str) -> str:
        path = find_xplane(log_dir)
        with open(base + ".devtrace.json", "w") as f:
            json.dump(devtrace.summarize(devtrace.load(path)), f, indent=1)
        if keep_xplane:
            shutil.copy(path, base + ".xplane.pb")
        return path

    snapshot = bench_server.Deployment.snapshot

    def snapshot_with_front(dep) -> dict:
        snap = snapshot(dep)
        metrics = getattr(dep.node, "metrics", None)
        snap["front"] = {k: getattr(metrics, k) for k in FRONT_COUNTERS
                         if hasattr(metrics, k)}
        return snap

    result_line = bench_run.result_line

    def result_line_and_keep(cell, out, *a, **kw):
        before, after = out["src"]["window"]
        rows = {}
        for row, h in after["ledger"].items():
            was = before["ledger"].get(row, {})
            rows[row] = {k: h[k] - was.get(k, 0) for k in ("count", "wall_s", "cpu_s")}
        counters = {k: v - before["codec"].get(k, 0) for k, v in after["codec"].items()
                    if isinstance(v, (int, float))}
        front = {k: v - before["front"].get(k, 0) for k, v in after["front"].items()}
        with open(base + ".ledger.json", "w") as f:
            json.dump({"window_s": after["t"] - before["t"], "facts": out["src"]["facts"],
                       "e2e": out["e2e"], "ledger": rows, "codec": counters,
                       "front": front}, f, indent=1)
        return result_line(cell, out, *a, **kw)

    bench_server.Deployment.snapshot = snapshot_with_front
    bench_trace.find_xplane = find_and_keep
    bench_run.result_line = result_line_and_keep
    return bench_run.main(rest + ["--trace", "1"])


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
