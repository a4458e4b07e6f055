#!/usr/bin/env python3
"""One traced run of a benchmark cell that also reads per-layer metrics
the cell does not list: how an "unlisted" reading in ``PERF.md`` is made
and made again.

    python3 tools/cell_metrics.py --workload <cell> --seed <n> \\
        --seconds <s> [--also m1,m2,...]

A metric is listed for a cell in ``per_layer`` of
``benchmarks/workloads/<cell>.json``, which only a ``benchmark`` PR may
edit, so a metric file a later PR adds (``benchmarks/layer_metrics/``)
is read by no cell until then. This loads the cell as ``benchmarks/
run.py`` does, extends that list IN MEMORY with ``--also`` (default:
every metric file the cell does not list; a reader that finds nothing
in this cell leaves its metric out), calls ``run.measure(..., trace=1)``
unchanged and prints its last line. It edits no file. On the chip only,
like ``run.py``.
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmarks import run  # noqa: E402


def unlisted(cell):
    """Every metric file the cell's own list does not name."""
    names = sorted(
        os.path.basename(p)[:-len(".json")] for p in glob.glob(
            os.path.join(ROOT, "benchmarks", "layer_metrics", "*.json")))
    return [n for n in names if n not in cell["per_layer"]]


def measure(name, seed, seconds, also, files, devices):
    """``run.measure`` of the cell, traced, with ``also`` read too;
    returns its last line's object."""
    manifest, entry, cell, config, mix = files
    also = unlisted(cell) if also is None else \
        [n for n in also if n not in cell["per_layer"]]
    for n in also:      # before the run, not after it
        run.load_json("benchmarks", "layer_metrics", f"{n}.json")
    cell = dict(cell, per_layer=cell["per_layer"] + also)
    return run.measure(name, seed, seconds, 1,
                       (manifest, entry, cell, config, mix), devices)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--also", default=None,
                    help="comma-separated metric names (default: every "
                         "metric file the cell does not list)")
    args = ap.parse_args(argv)
    files = run.load_cell(args.workload)
    chips = files[1]["chips"]

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < chips:
        print(f"cell_metrics: {args.workload} needs {chips} TPU chip(s), "
              f"jax found {devices}; there is no CPU branch", file=sys.stderr)
        return 2
    also = None if args.also is None else \
        [n for n in args.also.split(",") if n]
    out = measure(args.workload, args.seed, args.seconds, also, files,
                  devices[:chips])
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
