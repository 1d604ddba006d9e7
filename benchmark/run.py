"""The benchmark's command::

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One run of one cell of ``BENCHMARK.json`` on the machine it is started on. It
runs on a TPU or not at all: any other platform, or fewer chips than the cell
asks for, exits 2 with no result line, and no option or environment variable
changes that. The last line of standard output is the result object;
everything else goes to earlier lines or to ``benchmark_out/``.

``--control 1`` (never passed by the driver) also reads, on the same prompts
and served tokens, the gaps of a float8 reference: the comparison's control.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

OUT_DIR = os.path.join(ROOT, "benchmark_out")


def configure_jax() -> str:
    """The compile cache at the fixed path the program's own rule gives
    (``JAX_COMPILATION_CACHE_DIR`` if set, else ``<checkout>/.jax_cache``),
    and every executable kept, however quickly it compiled."""
    import jax

    from edgellm_tpu.utils.startup import configure_compile_cache

    path = configure_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def result_object(cell, record: dict, trace: bool) -> dict:
    """The contract's last line, from a run's record."""
    import jax

    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = m.reader(record)
        if value is not None:
            metrics[m.name] = {"value": float(value), "unit": m.unit}
    dev = jax.devices()
    device = {"platform": dev[0].platform, "kind": dev[0].device_kind,
              "count": len(dev), "memory_peak_bytes": record["peak_bytes"]}
    out = {"correct": bool(record["correct"]),
           "attempted": int(record["attempted"]),
           "failed": int(record["failed"]), "metrics": metrics,
           "device": device}
    if trace and record.get("trace"):
        from benchmark import trace_reduce

        device["busy_s"] = record["trace"]["busy_s"]
        device["window_s"] = record["trace"]["window_s"]
        out["breakdown"] = trace_reduce.breakdown(record["trace"])
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from benchmark.cell import load_cell

    cell = load_cell(args.workload)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"benchmark: {cell.name} needs {cell.chips} TPU chip(s); JAX "
              f"found {len(devices)} x {devices[0].platform!r}. Nothing was "
              f"run and there is no fallback.", file=sys.stderr)
        return 2
    cache = configure_jax()
    print(f"cell {cell.name}: seed {args.seed}, {args.seconds} s, trace "
          f"{args.trace}; compile cache {cache}", flush=True)

    run_dir = os.path.join(OUT_DIR, cell.name)
    trace_dir = os.path.join(run_dir, "trace")
    if args.trace:
        import shutil

        shutil.rmtree(trace_dir, ignore_errors=True)
    os.makedirs(run_dir, exist_ok=True)

    def dump(name, obj):
        with open(os.path.join(run_dir, name), "w") as f:
            json.dump(obj, f, indent=1, default=str)

    env = {"t_start": T_START, "trace": bool(args.trace),
           "trace_dir": trace_dir, "control": bool(args.control),
           "dump": dump}
    record = cell.kind.run(cell, args.seed, args.seconds, env)
    line = result_object(cell, record, bool(args.trace))
    print(f"total {time.monotonic() - T_START:.1f} s", flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
