"""Run one cell of the on-chip benchmark once.

    python3 benchmarks/onchip/run.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

From the root of a checkout, on a machine whose JAX sees a TPU.  The
cell (an entry of ``workloads`` in BENCHMARK.json) names a configuration
(``configs/<config>.json``) and a traffic mix (``traffic/<mix>.json``);
``--seed`` draws the query stream.  Set-up loads the stored graph and
builds none (``onchip_harness.cell``); the window drives the frontend of
``db.serve()`` for ``--seconds``; then every answer of the window is
checked against exact k-NN.

Standard error carries the run's log and, as its last lines, each number
compared beside its limit.  The last line of standard output is one JSON
object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's
end-to-end metrics, or with ``--trace 1`` its per-layer metrics, from
the window's counts and a profiler trace of its last seconds),
``device``, with ``--trace 1`` ``breakdown``, and last ``checks``.  Any platform but a TPU, or fewer
chips than the cell asks for, ends the run with exit code 2 and no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
NO_CHIP = 2


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def check_platform(chips: int) -> None:
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found platform {devs[0].platform!r}")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX found "
                     f"{len(devs)}")


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache at one fixed place inside the
    checkout, every program cached, however quickly it compiled."""
    import jax
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    from repro.compile_cache import enable
    path = enable()
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def metrics(kind: str, specs: list[dict], cell_name: str, ctx) -> dict:
    from onchip_harness import registry
    out = {}
    for m in registry.metrics_for(cell_name, specs):
        value = registry.load_reader(kind, m["name"])(ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def result_line(outcome, bench: dict, cell_name: str, trace: bool) -> dict:
    import jax
    dev = jax.devices()[0]
    ctx = outcome.context
    kind, specs = (("layers", bench["per_layer"]) if trace
                   else ("end_to_end", bench["end_to_end"]))
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": outcome.memory_peak_bytes}
    line = {"correct": outcome.correct, "attempted": outcome.attempted,
            "failed": outcome.failed,
            "metrics": metrics(kind, specs, cell_name, ctx),
            "device": device}
    if trace and ctx.trace is not None:
        device["busy_s"] = ctx.trace["busy_s"]
        device["window_s"] = ctx.trace["window_s"]
        line["breakdown"] = ctx.trace["breakdown"]
    line["checks"] = outcome.checks
    return line


def main(argv=None, *, require_chip: bool = True) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)

    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        print(f"run: no system under test at {ROOT}/src/repro",
              file=sys.stderr)
        return NO_CHIP
    sys.path.insert(0, HERE)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from onchip_harness import cell, correct, registry

    bench = registry.benchmark()
    entry = registry.cell(args.workload, bench)
    if require_chip:
        try:
            check_platform(entry["chips"])
        except NoChip as e:
            print(f"run: {e}", file=sys.stderr)
            return NO_CHIP
    cell.log(f"compile cache: {enable_compile_cache()}")
    import jax
    kind = jax.devices()[0].device_kind
    with open(os.path.join(HERE, "peaks.json")) as f:
        peaks = json.load(f)["devices"]
    if require_chip and kind not in peaks:
        print(f"run: no peaks for device kind {kind!r} in peaks.json",
              file=sys.stderr)
        return NO_CHIP
    outcome = cell.run(
        args.workload, registry.load_config(entry["config"]),
        registry.load_traffic(entry["traffic"]), args.seed, args.seconds,
        bool(args.trace), T_START,
        correct.load_limits(args.workload, HERE), peaks.get(kind, {}))
    line = result_line(outcome, bench, args.workload, bool(args.trace))
    for name, c in outcome.checks.items():
        cell.log(f"check {name}: {c['value']!r} limit {c['limit']!r}")
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
