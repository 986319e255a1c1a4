#!/usr/bin/env python3
"""Run a loadgen scenario against an in-process cluster or a live endpoint.

Usage:
    python tools/loadgen.py scenarios/mixed_70_30.yaml
    python tools/loadgen.py SPEC --endpoint http://host:9000 \\
        --access-key AK --secret-key SK
    python tools/loadgen.py SPEC --out report.json --metrics-out report.prom

Without --endpoint, a real multi-node cluster (shape from the spec's
`cluster` block, overridable with --nodes/--drives) is built in-process on
temp-dir drives, driven, and torn down. The final stdout line is the whole
report as ONE JSON object (the BENCH contract: tools/perf_gate.py --slo
consumes it); --out additionally writes it pretty-printed.

Exit 0: ran and every declared SLO held. Exit 1: ran but an SLO was
violated (or the compare block failed to reproduce). Exit 2: could not
run (bad spec, cluster failed to build).
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _log(msg: str) -> None:
    print(f"loadgen: {msg}", file=sys.stderr)


def main(argv: list[str]) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("spec", help="scenario YAML/JSON path")
    ap.add_argument("--endpoint", action="append", default=[],
                    help="live S3 endpoint URL (repeatable for multi-node)")
    ap.add_argument("--access-key", default="")
    ap.add_argument("--secret-key", default="")
    ap.add_argument("--nodes", type=int, default=0, help="override spec cluster.nodes")
    ap.add_argument("--drives", type=int, default=0,
                    help="override spec cluster.drives_per_node")
    ap.add_argument("--seed", type=int, default=None, help="override spec seed")
    ap.add_argument("--profile", action="store_true",
                    help="arm the continuous profiling plane and embed its "
                         "summary (gil_load, role stacks, copy ledger) in "
                         "the report (same as `profile: true` in the spec)")
    ap.add_argument("--out", default="", help="write pretty report JSON here")
    ap.add_argument("--metrics-out", default="",
                    help="write Prometheus exposition of the report here")
    args = ap.parse_args(argv)

    # Sample trace-span publication so high concurrency doesn't flood the
    # hub/slow-ring (the perf ledger still sees every request).
    os.environ.setdefault("MTPU_TRACE_SAMPLE", "0.1")

    from minio_tpu.loadgen.runner import ScenarioRunner
    from minio_tpu.loadgen.spec import SpecError, load_scenario
    from minio_tpu.loadgen.target import EndpointAdmin, InProcessAdmin, S3Target

    try:
        scenario = load_scenario(args.spec)
    except SpecError as e:
        _log(f"bad spec: {e}")
        return 2
    if args.seed is not None:
        scenario.seed = args.seed
    if args.nodes:
        scenario.nodes = args.nodes
    if args.drives:
        scenario.drives_per_node = args.drives
    if args.profile:
        scenario.profile = True

    cluster = None
    workdir = ""
    try:
        if args.endpoint:
            if not args.access_key or not args.secret_key:
                _log("--endpoint needs --access-key and --secret-key")
                return 2
            target = S3Target(args.endpoint, args.access_key, args.secret_key)
            admin = EndpointAdmin(target)
            _log(f"target: live endpoint(s) {args.endpoint}")
        else:
            from minio_tpu.loadgen.cluster import InProcessCluster

            # Spec-declared env knobs (e.g. MTPU_MEMCACHE_MB for the hot-read
            # tier) must be live before the nodes build. setdefault: the
            # operator's explicit environment wins over the spec.
            for k, v in scenario.env.items():
                os.environ.setdefault(k, v)
            workdir = tempfile.mkdtemp(prefix="mtpu-loadgen-")
            _log(
                f"building in-process cluster: {scenario.nodes} nodes x "
                f"{scenario.drives_per_node} drives x {scenario.pools} pool(s) "
                f"under {workdir}"
            )
            try:
                cluster = InProcessCluster(
                    workdir, scenario.nodes, scenario.drives_per_node,
                    pools=scenario.pools,
                )
            except RuntimeError as e:
                _log(str(e))
                return 2
            target = S3Target(cluster.urls, cluster.root_user, cluster.root_password)
            admin = InProcessAdmin(cluster)

        report = ScenarioRunner(scenario, target, admin, log=_log).run()

        from minio_tpu.runtime import probe_status

        probe = probe_status()
        if probe is not None:
            report["probe_cached"] = probe.cached
    finally:
        if cluster is not None:
            cluster.stop()
        if workdir:
            shutil.rmtree(workdir, ignore_errors=True)

    if args.out:
        with open(args.out, "w") as f:
            json.dump(report, f, indent=2, sort_keys=True)
        _log(f"report written to {args.out}")
    if args.metrics_out:
        from minio_tpu.loadgen.report import render_prometheus

        with open(args.metrics_out, "w") as f:
            f.write(render_prometheus(report))
        _log(f"metrics written to {args.metrics_out}")

    print(json.dumps(report, sort_keys=True))

    slo_ok = all(
        row.get("ok", True) for row in report.get("slo", {}).values()
    )
    cmp = report.get("compare")
    cmp_blocks = cmp if isinstance(cmp, list) else [cmp] if isinstance(cmp, dict) else []
    cmp_ok = all(b.get("reproduced", True) for b in cmp_blocks)
    loss = report.get("acked_object_loss")
    loss_ok = loss.get("ok", True) if isinstance(loss, dict) else True
    cache_slo = report.get("cache_slo")
    cache_ok = cache_slo.get("ok", True) if isinstance(cache_slo, dict) else True
    pools_blk = report.get("pools")
    pools_ok = pools_blk.get("ok", True) if isinstance(pools_blk, dict) else True
    if not slo_ok:
        _log("SLO VIOLATED (see report.slo)")
    if not cmp_ok:
        _log("compare block did not reproduce (see report.compare)")
    if not loss_ok:
        _log(
            f"ACKED OBJECT LOSS: {loss.get('get_miss_count')} GET(s) hit "
            "NoSuchKey on a prepopulated, never-deleted key"
        )
    if not cache_ok:
        _log("cache hit-ratio promise missed (see report.cache_slo)")
    if not pools_ok:
        _log(
            f"pool(s) {pools_blk.get('require_drained')} did not drain within "
            f"{pools_blk.get('max_drain_s')}s (see report.pools)"
        )
    return 0 if slo_ok and cmp_ok and loss_ok and cache_ok and pools_ok else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
