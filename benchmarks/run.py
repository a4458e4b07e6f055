#!/usr/bin/env python3
"""benchmarks/run.py — one run of one cell of ``BENCHMARK.json``.

    python3 benchmarks/run.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

Loads, warms up, measures for ``--seconds``, prints earlier lines
freely and LAST one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics with
``--trace 0``, its per-layer metrics with ``--trace 1``) and
``device``. No CPU branch: without a TPU, or with fewer chips than the
cell asks for, it exits non-zero before it builds anything.

Nothing about one cell lives here. The cell's entry in
``BENCHMARK.json`` names its configuration and traffic mix, and the
files are found by those names:

    benchmarks/workloads/<cell>.json      job, engine or trainer sizes,
                                          the metrics it reports
    benchmarks/configs/<config>.json      published keys, ``builder``
    benchmarks/traffic/<traffic>.json     parameters of the mix
    benchmarks/layer_metrics/<metric>.json  ``source.reader`` + its spec
    benchmarks/jobs/<job>.py, models/<builder>.py,
    reference/<builder>.py, readers/<reader>.py
"""
from __future__ import annotations

import time

_T_START = time.perf_counter()   # set-up is counted from here

import argparse
import importlib
import json
import os
import sys
import types

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def load_json(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def load_cell(name):
    """The manifest entry of a cell and its three data files."""
    manifest = load_json("BENCHMARK.json")
    entry = next((w for w in manifest["workloads"] if w["name"] == name), None)
    if entry is None:
        raise SystemExit(f"benchmarks: no workload {name!r} in BENCHMARK.json")
    return (manifest, entry,
            load_json("benchmarks", "workloads", f"{name}.json"),
            load_json("benchmarks", "configs", f"{entry['config']}.json"),
            load_json("benchmarks", "traffic", f"{entry['traffic']}.json"))


def layer_metrics(names, obs):
    """Each named per-layer metric through its own reader; a reader
    that finds nothing to read leaves the metric out."""
    out = {}
    for name in names:
        spec = load_json("benchmarks", "layer_metrics", f"{name}.json")
        reader = importlib.import_module(
            f"benchmarks.readers.{spec['source']['reader']}")
        value = reader.read(spec["source"], obs)
        if value is not None:
            out[name] = {"value": float(value), "unit": spec["unit"]}
    return out


def measure(name, seed, seconds, trace, files, devices):
    """One run of a cell on ``devices``; returns the last line's object.
    ``files`` is ``load_cell``'s tuple, so the benchmark's own tests can
    rehearse a job at a toy size."""
    manifest, entry, cell, config, mix = files
    import jax

    import paddle_tpu as paddle
    from benchmarks import harness, peaks

    cache_dir = paddle.jit.place_compile_cache()
    # every program is cached, however quickly it compiled
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    ctx = types.SimpleNamespace(
        name=name, seed=seed, seconds=seconds, trace=bool(trace), root=ROOT,
        cell=cell, config=config, mix=mix, devices=devices,
        peaks=peaks.peaks_for(devices[0].device_kind),
        compiles=harness.CompileCounter(), setup_s=None,
        builder=importlib.import_module(
            f"benchmarks.models.{config['builder']}"),
        reference=importlib.import_module(
            f"benchmarks.reference.{config['builder']}"))

    def window_opens():
        ctx.setup_s = time.perf_counter() - _T_START
        harness.line("window_opens", setup_s=ctx.setup_s,
                     **ctx.compiles.snapshot())
        return time.perf_counter()

    ctx.window_opens = window_opens
    harness.line("start", workload=name, seed=seed, seconds=seconds,
                 trace=trace, compile_cache=cache_dir,
                 cache_entries=len(os.listdir(cache_dir))
                 if os.path.isdir(cache_dir) else 0)
    res = importlib.import_module(f"benchmarks.jobs.{cell['job']}").run(ctx)

    device = harness.device_record(devices)
    out = {"correct": res["correct"], "attempted": res["attempted"],
           "failed": res["failed"]}
    if trace:
        obs = dict(res["obs"], peaks=ctx.peaks)
        tw = res.get("trace")
        path = tw.xplane() if tw is not None else None
        if path:
            from benchmarks.readers import device_trace

            obs["trace"] = device_trace.summarize(
                device_trace.reduce_planes(device_trace.load(path)),
                tw.window_s)
        if obs.get("trace"):
            device["busy_s"] = obs["trace"]["busy_s"]
            device["window_s"] = obs["trace"]["window_s"]
            out["breakdown"] = obs["trace"]["breakdown"]
            harness.line("trace", modules=obs["trace"]["modules"],
                         busy_s=device["busy_s"], window_s=device["window_s"])
        out["metrics"] = layer_metrics(cell["per_layer"], obs)
    else:
        values = dict(res["end_to_end"], setup_s=ctx.setup_s)
        units = {m["name"]: m["unit"] for m in manifest["end_to_end"]}
        out["metrics"] = {n: {"value": float(values[n]), "unit": units[n]}
                          for n in cell["end_to_end"]
                          if n in values and n in units}
    out["device"] = device
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    files = load_cell(args.workload)
    chips = files[1]["chips"]

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < chips:
        print(f"benchmarks: {args.workload} needs {chips} TPU chip(s), jax "
              f"found {devices}; there is no CPU branch", file=sys.stderr)
        return 2
    out = measure(args.workload, args.seed, args.seconds, args.trace, files,
                  devices[:chips])
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
