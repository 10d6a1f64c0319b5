#!/usr/bin/env python3
"""One run of one cell of the benchmark.

    python3 benchmarks/run.py --workload <name> --seed <n> \\
        --seconds <s> --trace <0|1>

starts one ``ray_tpu`` session whose driver (this process) never
imports jax, drives the cell through ray_tpu's front door (HTTP POSTs
to the serve proxy's socket, or ``train.Trainer.run``), and prints as
its last line of standard output one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` and, traced,
``breakdown``; the numbers ``correct`` was decided on come last, under
``compared``. With no TPU (or fewer chips than the cell asks for) it
exits non-zero and prints no result.
"""

from __future__ import annotations

import time

T_START = time.time()       # process start: where setup_s is counted from

import argparse             # noqa: E402
import contextlib           # noqa: E402
import json                 # noqa: E402
import math                 # noqa: E402
import os                   # noqa: E402
import sys                  # noqa: E402
import tempfile             # noqa: E402
import threading            # noqa: E402
import traceback            # noqa: E402
import urllib.error         # noqa: E402
import urllib.request       # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import loader, readers, traffic as traffic_mod  # noqa: E402
from benchmarks.loader import BenchmarkError               # noqa: E402

PHASES = []                 # [name, seconds since T_START] of set-up
LATE_ANSWER_S = 60.0        # how long past the close an answer is awaited
# libtpu pins a host buffer for transfers when its client starts: 4 GiB
# unless told otherwise, which on a host without transparent hugepages
# takes 6 to 12 s and was the whole spread of setup_s (PERF.md section
# 2). No cell moves more than this in one transfer; a mix that does
# asks for more under "premapped_buffer_bytes".
PREMAPPED_BUFFER_BYTES = 512 << 20


def mark(phase: str) -> None:
    """A stamp at the end of a phase of set-up, for the line's
    ``setup_phases`` (which the driver ignores)."""
    PHASES.append([phase, time.time() - T_START])


def require_device(seen: dict, platform: str, chips: int) -> None:
    """No result on another platform or on fewer chips than the cell
    asks for. ``seen`` is what jax reports inside the worker that holds
    the lease: it is started under ``JAX_PLATFORMS=tpu,cpu``, so where
    jax finds no TPU it raises there and the run ends before this; a
    child that asked jax first would cost every run a second start of
    the TPU client, 12 to 20 s (PERF.md)."""
    if seen["platform"] != platform or seen["count"] < chips:
        raise BenchmarkError(
            f"the cell needs {chips} {platform} chip(s); jax found {seen}")


def http_json(url: str, body=None, timeout: float = 900.0):
    data = None if body is None else json.dumps(body).encode()
    with urllib.request.urlopen(
            urllib.request.Request(url, data=data), timeout=timeout) as r:
        return json.loads(r.read())


def dump_worker_logs(session_dir: str, tail: int = 4000) -> None:
    log_dir = os.path.join(session_dir, "logs")
    for name in sorted(n for n in os.listdir(log_dir) if n.endswith(".log")):
        with open(os.path.join(log_dir, name), "rb") as f:
            f.seek(0, os.SEEK_END)
            f.seek(max(0, f.tell() - tail))
            text = f.read().decode(errors="replace").strip()
        if text:
            print(f"--- {name}\n{text}", file=sys.stderr)


def _proc_stat(pid):
    """(state, parent pid, start time in clock ticks) from /proc, or
    None once the process is gone."""
    try:
        with open(f"/proc/{pid}/stat") as f:
            fields = f.read().rsplit(")", 1)[1].split()
    except OSError:
        return None
    return fields[0], int(fields[1]), fields[19]


def descendants() -> dict:
    """pid -> start time of every process that descends from this one."""
    stats = {int(e): _proc_stat(e) for e in os.listdir("/proc")
             if e.isdigit()}
    found, frontier = {}, {os.getpid()}
    while frontier:
        frontier = {pid for pid, st in stats.items()
                    if st and st[1] in frontier and pid not in found}
        found.update((pid, stats[pid][2]) for pid in frontier)
    return found


def wait_until_ended(started: dict, patience_s: float = 30.0) -> None:
    """The session kills its workers and goes on; a killed worker needs
    seconds to let go of the chip (its main thread shows as a zombie
    while its other threads still run). Wait until each has ended and
    been collected, so that a run leaves no process behind: orphans come
    to this process, which is their subreaper."""
    left = dict(started)
    began = time.time()
    deadline = began + patience_s
    while left:
        for pid, born in list(left.items()):
            stat = _proc_stat(pid)
            if stat is None or stat[2] != born:
                del left[pid]
                continue
            with contextlib.suppress(ChildProcessError):   # not ours yet
                if os.waitpid(pid, os.WNOHANG)[0] == pid:
                    del left[pid]
        if not left or time.time() >= deadline:
            break
        time.sleep(0.1)
    print(f"waited {time.time() - began:.1f} s for {len(started)} "
          f"process(es) of the session to end", file=sys.stderr)
    for pid in left:
        print(f"pid {pid} outlived the session by {patience_s:.0f} s",
              file=sys.stderr)
        with contextlib.suppress(OSError):
            os.kill(pid, 9)


def adopt_orphans() -> None:
    """Make this process the one that orphaned descendants are handed to
    (prctl PR_SET_CHILD_SUBREAPER), so that it can collect a worker
    whose own parent, the session's worker template, ended first."""
    import ctypes

    ctypes.CDLL(None, use_errno=True).prctl(36, 1, 0, 0, 0)


@contextlib.contextmanager
def session(chips: int, premapped_buffer_bytes=None):
    """One ray_tpu session. Its files live under this run's TMPDIR, its
    workers find the benchmark on PYTHONPATH and start libtpu with the
    premapped buffer the cell needs, and their log tails go to stderr
    when the body fails."""
    os.environ["TPU_PREMAPPED_BUFFER_SIZE"] = str(int(
        premapped_buffer_bytes or PREMAPPED_BUFFER_BYTES))
    os.environ.setdefault(
        "RAY_TPU_TMPDIR", os.path.join(tempfile.gettempdir(), "ray_tpu"))
    os.environ["PYTHONPATH"] = os.pathsep.join(
        [ROOT] + [p for p in os.environ.get("PYTHONPATH", "").split(
            os.pathsep) if p and p != ROOT])
    import ray_tpu

    adopt_orphans()
    info = ray_tpu.init(num_cpus=4, num_tpus=chips, log_to_driver=False)
    mark("session")
    try:
        yield info
    except BaseException:
        dump_worker_logs(info["session_dir"])
        raise
    finally:
        started = descendants()
        ray_tpu.shutdown()
        wait_until_ended(started)


# ------------------------------------------------------------------ serve

def _post_request(url: str, item: dict, seed: int, vocab: int,
                  record: dict) -> None:
    prompt = traffic_mod.prompt_tokens(seed, item["index"],
                                       item["prompt_len"], vocab)
    record.update(item, sent=time.perf_counter(), ok=False)
    try:
        answer = http_json(url, {"prompt": prompt,
                                 "max_tokens": item["max_tokens"]})
        record["done"] = time.perf_counter()
        tokens = answer["tokens"]
        record.update(answer, ok=(
            len(tokens) == item["max_tokens"]
            and all(isinstance(t, int) and 0 <= t < vocab for t in tokens)))
    except (OSError, ValueError, KeyError) as e:   # urllib's errors are
        record["done"] = time.perf_counter()       # OSErrors: a 503 or
        record["error"] = repr(e)                  # 500 is a failed request


def closed_loop(url, plan, mix, seed, vocab, t0, t_end) -> list:
    records, lock = [], threading.Lock()

    def client():
        while True:
            with lock:
                if time.perf_counter() >= t_end or len(records) >= len(plan):
                    return
                record = {}
                item = plan[len(records)]
                records.append(record)
            _post_request(url, item, seed, vocab, record)

    threads = [threading.Thread(target=client, daemon=True)
               for _ in range(int(mix["clients"]))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(max(0.0, t_end + LATE_ANSWER_S - time.perf_counter()))
    return records


def open_loop(url, plan, seed, vocab, t0, t_end) -> list:
    records, threads = [], []
    for item in plan:
        due = t0 + item["due_s"]
        delay = due - time.perf_counter()
        if delay > 0:
            time.sleep(delay)
        record = {"due": due}
        records.append(record)
        t = threading.Thread(target=_post_request, daemon=True,
                             args=(url, item, seed, vocab, record))
        t.start()
        threads.append(t)
    for t in threads:
        t.join(max(0.0, t_end + LATE_ANSWER_S - time.perf_counter()))
    return records


def deploy_lm(run: dict, lm_class=None) -> str:
    """Deploy the cell's replica and warm it up, which counts as set-up:
    every slot and every prompt length of the mix once, through the
    front door. Returns the replica's URL."""
    from benchmarks.worker import BenchLM
    from ray_tpu import serve

    mix, seed = run["traffic"], run["seed"]
    vocab = int(run["config"]["vocab_size"])
    serve.deployment(
        lm_class or BenchLM, name="lm",
        ray_actor_options={"num_tpus": run["chips"]}).deploy(
            run["config"], mix, seed, run["family"])
    url = f"http://{serve.get_http_address()}/lm"
    mark("replica")         # TPU worker, TPU client, weights from the seed
    lengths = mix["prompt_lengths"]
    n_warm = max(int(mix["slots"]), len(lengths))
    warm = [{"index": -1 - i, "prompt_len": lengths[i % len(lengths)],
             "max_tokens": 3, "due_s": None} for i in range(n_warm)]
    records = [{} for _ in warm]
    threads = [threading.Thread(target=_post_request,
                                args=(url, w, seed, vocab, r))
               for w, r in zip(warm, records)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    if not all(r["ok"] for r in records):
        raise BenchmarkError(f"a warm-up request failed: {records}")
    mark("warm_up")
    return url


def drive_serve(run: dict, lm_class=None) -> dict:
    from ray_tpu import serve

    mix, seed, seconds = run["traffic"], run["seed"], run["seconds"]
    vocab = int(run["config"]["vocab_size"])
    serve.start()
    try:
        url = deploy_lm(run, lm_class)
        programs = http_json(url + "/programs")["prefill_mosaic_calls"]
        before = http_json(url + "/stats?t0=inf")
        require_device(before["device"], run["platform"], run["chips"])
        mark("programs")

        plan = traffic_mod.serve_plan(mix, seed, seconds)
        tracer = None
        if run["trace"]:
            slice_s = min(float(mix["trace_seconds"]), seconds / 2.0)
            tracer = threading.Timer(
                seconds / 4.0, http_json,
                args=(url + "/trace", {"seconds": slice_s}))
        setup_s = time.time() - T_START
        t0 = time.perf_counter()
        t_end = t0 + seconds
        if tracer:
            tracer.start()
        if mix["loop"] == "closed":
            records = closed_loop(url, plan, mix, seed, vocab, t0, t_end)
        else:
            records = open_loop(url, plan, seed, vocab, t0, t_end)
        if tracer:
            tracer.join()
        describe = "&describe=1" if run.get("describe") else ""
        after = http_json(f"{url}/stats?t0={t0!r}&t1={t_end!r}{describe}")

        finished = [r for r in records if r.get("ok")]
        sample = traffic_mod.check_sample(seed, finished,
                                          int(mix["check_requests"]))
        for r in sample:
            r["prompt"] = traffic_mod.prompt_tokens(
                seed, r["index"], r["prompt_len"], vocab)
        check = http_json(url + "/check", {
            "requests": [{"prompt": r.pop("prompt"), "tokens": r["tokens"]}
                         for r in sample],
            "control": run.get("control")})
    finally:
        serve.shutdown()
    return {"kind": "serve", "setup_s": setup_s, "window": [t0, t_end],
            "requests": records, "programs": programs,
            "decode_before": before["decode"], "decode_after": after["decode"],
            "steps": after["steps"], "prefills": after["prefills"],
            "compiles": after["compiles"], "trace": after["trace"],
            "device": after["device"],
            "memory_peak_bytes": after["memory_peak_bytes"],
            "setup_stamps": before.get("setup_stamps", []),
            "cache_misses": before.get("cache_misses"),
            "check": check}


# ------------------------------------------------------------------ train

def drive_train(run: dict, train_func=None) -> dict:
    from benchmarks import worker
    from ray_tpu import train

    reports = []            # [arrival time, phase, step, loss]

    class Collect(train.TrainingCallback):
        def handle_result(self, results, **info):
            now = time.perf_counter()
            reports.extend([now, r["phase"], r["step"], r["loss"]]
                           for r in results)

    trainer = train.Trainer(num_workers=1, use_tpu=True)
    try:
        result = trainer.run(train_func or worker.train_func,
                             {k: run[k] for k in (
                                 "config", "family", "traffic", "seed",
                                 "seconds", "trace", "control", "describe")},
                             callbacks=[Collect()])[0]
    finally:
        trainer.shutdown()
    marks = [r[0] for r in reports if r[1] == "window"]
    if not marks:
        raise BenchmarkError("the worker never announced the window")
    t0 = marks[0]
    since_start = time.time() - time.perf_counter() - T_START
    checked = [r[0] + since_start for r in reports if r[1] == "check"]
    PHASES.extend([["first_step", checked[0]],      # cell built, one step
                   ["checked_steps", checked[-1]]] if checked else [])
    result.update(kind="train", reports=reports, window=[t0, t0 + run["seconds"]],
                  setup_s=time.time() - T_START
                  - (time.perf_counter() - t0))
    return result


# ------------------------------------------------------------- the result

def decide(run: dict, obs: dict) -> dict:
    """``correct``, ``attempted``, ``failed`` and the numbers compared,
    each beside its limit."""
    limits = run["traffic"]["limits"]
    compared, faults = {}, []
    if obs["kind"] == "serve":
        due = [r for r in obs["requests"] if "sent" in r]
        attempted, failed = len(due), sum(not r.get("ok") for r in due)
        # refused or errored is failed; only an answer that never came
        # or that says the wrong thing is for ``correct``
        wrong = sum(1 for r in due if "done" not in r
                    or ("tokens" in r and not r["ok"]))
        gaps = obs["check"]["served_gaps"]
        numbers = {"served_logit_gap": max(gaps) if gaps else math.inf,
                   "answers_wrong": wrong}
        if obs["device"]["platform"] == "tpu" and not all(
                n > 0 for n in obs["programs"].values()):
            faults.append(f"a prefill program holds no Mosaic call: "
                          f"{obs['programs']}")
    else:
        lo, hi = obs["window"]
        attempted = sum(1 for t, phase, *_ in obs["reports"]
                        if phase == "run" and lo <= t <= hi)
        bad = [r for r in obs["reports"] if not math.isfinite(r[3])]
        failed = len(bad)
        numbers = dict(obs["check"], steps_failed=failed)
        if obs["device"]["platform"] == "tpu" and not obs["mosaic_calls"]:
            faults.append("the train step holds no Mosaic call")
    if obs["compiles"]:
        faults.append(f"{len(obs['compiles'])} compiles or cache loads "
                      f"inside the window")
    for name, limit in limits.items():    # a number the mix gives no
        value = numbers[name]             # limit is read, not compared
        compared[name] = {"value": value, "limit": limit}
        if not value <= limit:          # a NaN fails too
            faults.append(f"{name} {value} is over its limit {limit}")
    if attempted == 0:
        faults.append("nothing was attempted in the window")
    return {"correct": not faults, "attempted": attempted, "failed": failed,
            "compared": compared, "faults": faults}


def run_cell(bench: dict, cell_name: str, seed: int, seconds: float,
             trace: bool, platform: str = "tpu", *, control=None,
             describe=False, lm_class=None, train_func=None) -> dict:
    """One run of one cell on ``platform``: returns the result line as
    a dict."""
    cell = loader.find_cell(bench, cell_name)
    run = {"cell": cell_name, "seed": int(seed), "seconds": float(seconds),
           "trace": bool(trace), "chips": int(cell["chips"]),
           "control": control, "describe": describe, "platform": platform,
           "config": loader.load_config(bench, cell["config"]),
           "traffic": loader.load_traffic(bench, cell["traffic"])}
    run["family"] = loader.find_family(bench, run["config"])
    with session(run["chips"], run["traffic"].get("premapped_buffer_bytes")):
        if run["traffic"]["kind"] == "serve":
            obs = drive_serve(run, lm_class)
        else:
            obs = drive_train(run, train_func)
    seen = obs["device"]
    require_device(seen, platform, run["chips"])
    obs.update(run=run, seconds=run["seconds"])
    verdict = decide(run, obs)
    line = {"correct": verdict["correct"], "attempted": verdict["attempted"],
            "failed": verdict["failed"],
            "metrics": loader.read_metrics(bench, cell_name, trace, obs),
            "device": {**seen,
                       "memory_peak_bytes": obs["memory_peak_bytes"]}}
    if trace:
        traced = obs["trace"]
        line["device"].update(busy_s=traced["busy_s"],
                              window_s=traced["window_s"])
        line["breakdown"] = {"device_ops": traced["device_ops"],
                             "idle_gaps": traced["idle_gaps"]}
        # how much of each kind the slice caught: the traced readings
        # swing with it (PERF.md section 7)
        line["slice"] = {kind: len(readers.started_in_slice(
            obs.get(kind, []), traced)) for kind in ("steps", "prefills")}
        if describe:
            line["describe"] = traced.get("describe")
    # what the check read besides the numbers compared (calibration)
    extra = {k: v for k, v in obs["check"].items()
             if k not in verdict["compared"] and k != "served_gaps"}
    extra.update(obs.get("calibration", {}))
    PHASES.extend([name, at - T_START]
                  for name, at in obs.get("setup_stamps", []))
    PHASES.sort(key=lambda p: p[1])
    line.update(setup_s=obs["setup_s"], setup_phases=PHASES,
                setup_cache_misses=obs.get("cache_misses"), check=extra,
                faults=verdict["faults"],
                compared=verdict["compared"])
    return line


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), required=True)
    parser.add_argument("--control", default=None,
                        help="calibration only: also read the reference "
                             "in these lower precisions (int8,fp8)")
    parser.add_argument("--describe", action="store_true",
                        help="with --trace 1: add the trace's plane, line "
                             "and event names to the line")
    args = parser.parse_args(argv)
    try:
        if not os.path.isdir(os.path.join(ROOT, "ray_tpu")):
            raise BenchmarkError(f"the program (ray_tpu/) is not in {ROOT}")
        line = run_cell(loader.load_benchmark(), args.workload, args.seed,
                        args.seconds, bool(args.trace), control=args.control,
                        describe=args.describe)
    except BenchmarkError as e:
        print(f"NO RESULT: {e}", file=sys.stderr)
        return 1
    except Exception:       # the session's own: a leased worker in which
        traceback.print_exc()               # jax found no TPU raises there
        print("NO RESULT: the run ended in the error above", file=sys.stderr)
        return 1
    for name, number in line["compared"].items():
        print(f"compared {name}: {number['value']} (limit "
              f"{number['limit']})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
