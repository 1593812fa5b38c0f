"""Find the highest rate an open-loop cell sustains: one sweep, on the chip.

    python3 benchmarks/onchip/sweep.py --workload <open cell> \\
        --rates 4000,8000,... --seconds <s> --seed <n>

Sets the cell up once, then drives its open loop at each rate in turn.
A rate is sustained when the backlog does not grow (the median queue
wait, due time to flush, of the window's last quarter of arrivals is no
more than twice that of its first quarter plus one dispatch's time) and
a query waits, on median, no longer than one dispatch takes (the
window's seconds over its dispatches).  The frontend pads every chunk
of a flush to ``max_batch``, so a flush with more than ``max_batch``
queries due costs whole extra dispatches; above the rate at which a
two-chunk flush gathers more than ``max_batch`` new queries, one slow
flush leaves the frontend in two-chunk flushes for good: the backlog
no longer grows, but every query waits a dispatch longer.  One JSON
line per rate.  The benchmark's own runs never run this: the cell's
rate is fixed in its traffic file, at about four fifths of the highest
rate sustained here.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(ROOT, "src"))


def sustained(wait_s: np.ndarray, flush_s: float) -> bool:
    q = max(wait_s.size // 4, 1)
    first, last = np.median(wait_s[:q]), np.median(wait_s[-q:])
    return bool(last <= 2 * first + flush_s
                and np.median(wait_s) <= flush_s)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--rates", required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--seed", type=int, default=0)
    args = p.parse_args(argv)

    import run
    from onchip_harness import cell, registry
    from onchip_harness.stream import QueryStream
    entry = registry.cell(args.workload, registry.benchmark())
    run.check_platform(entry["chips"])
    run.enable_compile_cache()
    config = registry.load_config(entry["config"])
    traffic = registry.load_traffic(entry["traffic"])
    db, fe, data, _, k = cell.setup(config, traffic, args.seed)
    for rate in (float(r) for r in args.rates.split(",")):
        stream = QueryStream(data, traffic, args.seed)
        win = cell.window(fe, stream, dict(traffic, rate=rate),
                          args.seconds, k)
        lat = win.latency_s * 1e3
        flush_s = win.seconds / max(win.batches, 1)
        print(json.dumps({
            "rate": rate, "queries": win.n_queries,
            "qps": float(win.answered.sum()) / win.seconds,
            "p50_ms": float(np.percentile(lat, 50)),
            "p99_ms": float(np.percentile(lat, 99)),
            "batch_fill": float(win.answered.sum())
            / max(win.batches * win.max_batch, 1),
            "wait_first_ms": float(np.median(
                win.queue_wait_s[: max(win.queue_wait_s.size // 4, 1)])) * 1e3,
            "wait_last_ms": float(np.median(
                win.queue_wait_s[-max(win.queue_wait_s.size // 4, 1):])) * 1e3,
            "wait_p50_ms": float(np.median(win.queue_wait_s)) * 1e3,
            "dispatch_ms": flush_s * 1e3,
            "sustained": sustained(win.queue_wait_s, flush_s)}), flush=True)
    db.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
