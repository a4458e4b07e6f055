"""Run a python payload in a subprocess with an n-device virtual CPU mesh.

jax backend init is process-global and irreversible; once a process has
claimed the real TPU chip (or a 1-device CPU platform), the only way to
get an n-device mesh is a fresh interpreter, started with
``JAX_PLATFORMS=cpu`` and the device-count flag in its environment (the
payload also sets both in-process before any backend touch, as
tests/conftest.py does). This helper is the single home of that recipe
(used by ``bench.py --lower-7b`` and
``__graft_entry__.dryrun_multichip``).
"""
from __future__ import annotations

import os
import re
import subprocess
import sys
import threading


def _pump(pipe, sink, chunks):
    """Forward a child pipe line-by-line: echo to ``sink`` immediately
    (flushed — this is what makes phase-OK lines survive a driver
    timeout) while accumulating for the returned CompletedProcess."""
    for line in iter(pipe.readline, ""):
        chunks.append(line)
        if sink is not None:
            sink.write(line)
            sink.flush()
    pipe.close()


def run_in_virtual_cpu_mesh(n_devices: int, payload: str, cwd: str,
                            timeout: int = 1800, stream: bool = False):
    """Execute ``payload`` (python source) in a subprocess that sees
    ``n_devices`` CPU devices. The payload runs AFTER the cpu-platform
    bootstrap. Returns a CompletedProcess (output captured either way).

    ``stream=True`` additionally forwards the child's stdout/stderr to
    this process line-by-line AS IT IS PRODUCED (child runs python -u,
    parent flushes per line). The multichip dryrun uses this so every
    completed phase's OK line is already on the driver's stdout if a
    wall-clock limit kills the run mid-phase — with the old
    capture-then-echo shape, a timeout recorded ZERO phases even when
    three had finished (round-5 postmortem)."""
    env = dict(os.environ)
    flags = re.sub(
        r"--xla_force_host_platform_device_count=\d+", "",
        env.get("XLA_FLAGS", ""),
    )
    flags = (
        flags + f" --xla_force_host_platform_device_count={n_devices}"
    ).strip()
    env["XLA_FLAGS"] = flags
    env["JAX_PLATFORMS"] = "cpu"
    code = (
        f"import os; os.environ['XLA_FLAGS'] = {flags!r}; "
        "import jax; jax.config.update('jax_platforms', 'cpu'); "
        + payload
    )
    argv = [sys.executable, "-u", "-c", code]  # -u: no block buffering
    if not stream:
        return subprocess.run(
            argv, cwd=cwd, env=env,
            capture_output=True, text=True, timeout=timeout,
        )
    proc = subprocess.Popen(
        argv, cwd=cwd, env=env, text=True,
        stdout=subprocess.PIPE, stderr=subprocess.PIPE,
    )
    out_chunks, err_chunks = [], []
    threads = [
        threading.Thread(
            target=_pump, args=(proc.stdout, sys.stdout, out_chunks),
            daemon=True,
        ),
        threading.Thread(
            target=_pump, args=(proc.stderr, sys.stderr, err_chunks),
            daemon=True,
        ),
    ]
    for t in threads:
        t.start()
    try:
        rc = proc.wait(timeout=timeout)
    except subprocess.TimeoutExpired:
        proc.kill()
        proc.wait()
        for t in threads:
            t.join(timeout=5)
        raise subprocess.TimeoutExpired(
            argv, timeout, output="".join(out_chunks),
            stderr="".join(err_chunks),
        ) from None
    for t in threads:
        t.join(timeout=5)
    return subprocess.CompletedProcess(
        argv, rc, stdout="".join(out_chunks),
        stderr="".join(err_chunks),
    )
