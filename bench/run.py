"""Run one cell of the benchmark once, on the chip JAX finds.

    python -m bench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything is found by name from ``BENCHMARK.json``: the cell's
configuration in ``bench/configs/<config>.json`` (whose generator is
``bench/generators/<generator>.py``), its traffic mix in
``bench/traffic/<traffic>.json`` (whose op is ``bench/ops/<op>.py``), and
each metric's reader in ``bench/metrics/<metric>.py``. With ``--trace 0`` the
result carries the cell's end-to-end metrics, with ``--trace 1`` its
per-layer metrics, read from a profiled window.

The last line of stdout is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` (``busy_s`` and ``window_s`` too when
traced), ``breakdown`` when traced, and last ``checks``: each number
compared with the references, beside its limit. The checks are also the
last lines of stderr. Where JAX finds no TPU, or fewer chips than the
cell asks for, it exits 2 and prints no result.
"""
from __future__ import annotations

import time

STARTED = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

# libtpu logs under /tmp unless told otherwise; a run keeps to its
# checkout, HOME and TMPDIR
os.environ.setdefault("TPU_LOG_DIR", "disabled")

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "bench")


def load_json(*parts: str) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def cell_spec(spec: dict, name: str) -> dict:
    for w in spec["workloads"]:
        if w["name"] == name:
            return w
    raise SystemExit(f"bench.run: no workload {name!r} in BENCHMARK.json")


def cell_metrics(spec: dict, cell: str, traced: bool) -> list[dict]:
    """The metrics a run of ``cell`` reports: end-to-end ones untraced,
    per-layer ones traced; a metric with ``workloads`` only in those."""
    group = spec["per_layer" if traced else "end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]


def reader(name: str):
    """``bench/metrics/<name>.py``'s ``read(run) -> float | None``."""
    from bench import drive
    return drive.plugin("metrics", name).read


def execute(cell: str, seed: int, seconds: float, traced: bool, *,
            spec: dict, config: dict, traffic: dict, started: float):
    """Everything after the look for a chip: set-up, window, comparison
    and metrics. Returns (result, run). Tests call this on the CPU with
    their own small configuration."""
    import jax

    from bench import drive, instrument
    from bench import trace as tr

    run = drive.Run(cell=cell, config=config, traffic=traffic, seed=seed,
                    seconds=seconds, trace=traced, started=started,
                    limits=traffic.get("limits", {}),
                    clock=instrument.CompileClock())
    devices = jax.devices()
    run.device_kind = devices[0].device_kind
    with tempfile.TemporaryDirectory(prefix="bench-") as tmp:
        drive.plugin("ops", traffic["op"]).run(run, tmp)
    metrics = {}
    for m in cell_metrics(spec, cell, traced):
        value = reader(m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": run.memory_peak_bytes}
    result = {"correct": run.correct, "attempted": run.attempted,
              "failed": run.failed, "metrics": metrics, "device": device}
    if traced:
        busy, window = tr.busy_seconds(run.events)
        device.update(busy_s=busy, window_s=window)
        result["breakdown"] = {
            "device_ops": tr.top(tr.program_seconds(
                run.events, tr.windows(run.events))),
            "idle_gaps": tr.top(tr.idle_gaps(run.events))}
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in run.checks.items()}
    return result, run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    spec = load_json(ROOT, "BENCHMARK.json")
    cell = cell_spec(spec, args.workload)
    config = load_json(BENCH, "configs", cell["config"] + ".json")
    traffic = load_json(BENCH, "traffic", cell["traffic"] + ".json")

    sys.path[:0] = [os.path.join(ROOT, "src")]
    import jax
    from repro import api
    cache = api.enable_compile_cache()
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        print(f"bench.run: {args.workload} needs {cell['chips']} TPU "
              f"chip(s); JAX found {len(devices)} {devices[0].platform} "
              f"device(s)", file=sys.stderr)
        return 2
    print(f"bench.run: {args.workload} seed {args.seed} on "
          f"{devices[0].device_kind} x {len(devices)} (jax "
          f"{jax.__version__}); compile cache {cache}", file=sys.stderr)
    result, run = execute(args.workload, args.seed, args.seconds,
                          bool(args.trace), spec=spec, config=config,
                          traffic=traffic, started=STARTED)
    for line in run.notes:
        print(line, file=sys.stderr)
    print(f"setup_s {run.setup_s:.3f}; window {run.window_s:.3f} s; "
          f"{run.compiles_in_window} programs traced in the window; "
          f"{run.clock.summary()}", file=sys.stderr)
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
