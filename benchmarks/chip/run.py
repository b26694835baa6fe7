#!/usr/bin/env python3
"""Chip benchmark of one node's served path.

    python3 benchmarks/chip/run.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

Runs the cell ``<name>`` of ``BENCHMARK.json`` on this machine's chip:
builds the node (configs/), brings it to a steady state under the cell's
traffic (traffic/), measures for ``--seconds``, then checks a sample of
the served tokens against the float32 reference (verdict.py).  With
``--trace 0`` it reports the cell's end-to-end metrics, with ``--trace 1``
its per-layer metrics from a profiler trace of the window (metrics/).

The last line of standard output is one JSON object; earlier lines carry
counts (samples, generator lateness, peak bytes, compiles in the window,
prefix-cache hits).  The numbers compared, each beside its limit, are the
last lines of standard error.  Without a TPU, or with fewer chips than the
cell asks for, it exits 2 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from dataclasses import dataclass  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
# libtpu would log to a fixed path under /tmp; nothing here reads it
os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, str(HERE.parents[1] / "src"))

import cells  # noqa: E402


@dataclass
class Run:
    """What a metric reader sees."""
    spec: dict
    win: object
    red: object
    peaks: dict
    setup_s: float


def log(*parts):
    print("bench:", *parts, flush=True)


def use_compile_cache(jax):
    """Every program goes to the persistent cache inside the checkout, at
    a fixed path, so that only a cell's first run there compiles."""
    jax.config.update("jax_compilation_cache_dir",
                      str(cells.ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def _profile_options():
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0      # host spans only, no per-call events
    return opts


class CompileCount:
    def __init__(self, jax):
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, secs, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1


def _serve(spec, mix, seed, seconds, trace, device, metrics, pk, check,
           compiles, t_start, plant):
    """Set-up, window and metrics.  Everything of the node lives in this
    frame, so that it is freed when the frame returns."""
    import jax

    import devtrace
    import node
    import serve
    import traffic as gen
    eng, sched = node.build(spec, seed, device, check=check)
    if plant is not None:
        plant(eng, sched)
    mixed = gen.generate(mix, spec["vocab_size"], sched.max_active, seed)
    watch = serve.settle(sched, mixed, spec["vocab_size"], seed)
    # what set-up made lives to the end: keep the collector off it
    gc.collect()
    gc.freeze()
    setup_s = time.perf_counter() - t_start
    n0 = compiles.n if compiles else 0
    red = None
    if trace:
        tdir = Path(tempfile.mkdtemp(prefix="bench_trace_"))
        try:
            serve.instrument(sched)
            jax.profiler.start_trace(str(tdir),
                                     profiler_options=_profile_options())
            try:
                with jax.profiler.TraceAnnotation(devtrace.WINDOW):
                    win = serve.run_window(sched, mixed, seconds, watch,
                                           jax.profiler.TraceAnnotation)
                    jax.block_until_ready(eng.arena)
            finally:
                jax.profiler.stop_trace()
            red = devtrace.reduce(devtrace.load(devtrace.find_xplane(tdir),
                                                serve.SPANS))
        finally:
            shutil.rmtree(tdir, ignore_errors=True)
    else:
        win = serve.run_window(sched, mixed, seconds, watch)
        jax.block_until_ready(eng.arena)
    in_window = (compiles.n - n0) if compiles else 0
    stats = device.memory_stats() or {}
    pc = eng.prefix_cache
    gaps = sum(max(0, len(t) - 1) for t in win.tokens.values())
    log(f"window {win.elapsed!r} s (deadline {win.seconds!r} s): "
        f"{win.token_count()} tokens, {gaps} gaps, {len(win.worked)} "
        f"requests served, {len(win.due)} due, {len(win.finished)} "
        f"finished, {len(win.admitted)} admitted, {len(win.decode_ctx)} "
        f"decode dispatches, {win.prefill_dispatches} prefill dispatches "
        f"({win.prefill_tokens} tokens), {len(sched.queue)} queued and "
        f"{len(sched.active)} decoding at the close")
    dec = sorted(took for _, took, p in win.steps if not p)
    adm = [(round(at, 3), took) for at, took, p in win.steps if p]
    if dec:
        med = dec[len(dec) // 2]
        slow = [(round(at, 3), took) for at, took, p in win.steps
                if not p and took > 1.3 * med]
        log(f"steps: {len(dec)} decode-only, {sum(dec)!r} s, median "
            f"{med!r} s, slower than 1.3 x median (start s, took s): "
            f"{slow!r}; with admission (start s, took s): {adm!r}; "
            f"outside steps "
            f"{win.elapsed - sum(dec) - sum(t for _, t in adm)!r} s")
    if win.late:
        log(f"generator lateness: max {max(win.late)!r} s, mean "
            f"{sum(win.late) / len(win.late)!r} s over {len(win.late)}")
    log(f"compiles inside the window: {in_window}")
    log(f"prefix cache: hits {pc.hits} misses {pc.misses} hit_tokens "
        f"{pc.hit_tokens} of {pc.total_tokens} looked up")
    log(f"memory: peak_bytes_in_use {stats.get('peak_bytes_in_use')} "
        f"bytes_limit {stats.get('bytes_limit')}")
    log(f"setup_s {setup_s!r}")
    run = Run(spec, win, red, pk, setup_s)
    out = {}
    for m in metrics:
        v = cells.reader(m["name"])(run)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    prompts = {r.rid: r.tokens for r in
               mixed.in_flight + mixed.backlog + [r for _, r in mixed.timed]}
    result = {"correct": False,
              "attempted": len(win.due) if win.due else len(win.worked),
              "failed": 0, "metrics": out,
              "device": {"memory_peak_bytes": stats.get("peak_bytes_in_use")}}
    if red is not None:
        result["device"].update(busy_s=red.busy_s, window_s=red.window_s)
        result["breakdown"] = {"device_ops": red.device_ops,
                               "idle_gaps": red.idle_gaps}
    return result, prompts, win.served


def measure(spec, mix, seed, seconds, trace, device, limits, metrics, pk,
            check=True, compiles=None, t_start=T_START, control=False,
            plant=None):
    """One run of a cell on ``device``: the result object, with the device
    entry holding what only a run knows (peak bytes; busy and window
    seconds when traced).  ``metrics``: the metric entries to report;
    ``control``: judge the float8 control too; ``plant(engine,
    scheduler)``: a fault planted in the node (faults.py)."""
    import verdict
    result, prompts, served = _serve(spec, mix, seed, seconds, trace,
                                     device, metrics, pk, check, compiles,
                                     t_start, plant)
    # the node is gone: the reference takes the chip
    gc.unfreeze()
    gc.collect()
    log(f"memory after freeing the node: bytes_in_use "
        f"{(device.memory_stats() or {}).get('bytes_in_use')}")
    t = time.perf_counter()
    v = verdict.judge(spec, seed, prompts, served,
                      spec["deployment"]["max_len"], limits, control)
    log(f"reference check took {time.perf_counter() - t!r} s")
    result["correct"] = v["correct"]
    if "control" in v:
        result["control"] = v["control"]
    result["checks"] = v["checks"]
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench = cells.load_benchmark()
    cell = cells.workload(bench, args.workload)
    spec = cells.config(bench, cell["config"])
    mix = cells.traffic(cell["traffic"])
    import verdict
    limits = verdict.load_limits(cell["name"])
    section = "per_layer" if args.trace else "end_to_end"
    metrics = cells.reported(bench, cell["name"], section)

    import jax
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu" or len(devices) < cell["chips"]:
        print(f"bench: needs {cell['chips']} TPU chip(s); JAX has "
              f"{len(devices)} {platform!r} device(s)", file=sys.stderr)
        return 2
    import arith
    kind = devices[0].device_kind
    pk = arith.peaks(kind)
    use_compile_cache(jax)
    compiles = CompileCount(jax)
    log(f"workload {cell['name']} seed {args.seed} seconds {args.seconds} "
        f"trace {args.trace} on {len(devices)} x {kind!r}")
    result = measure(spec, mix, args.seed, args.seconds,
                     args.trace, devices[0], limits, metrics, pk,
                     compiles=compiles)
    result["device"] = {"platform": platform, "kind": kind,
                        "count": len(devices), **result["device"]}
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} {c['rule']} {c['limit']!r}",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
