"""Run a cell several times as the driver's check does, and say after each
run what it left behind; a run that stands has its state written down while
it stands.

    python tools/watch_runs.py --workload <cell> --seeds 1,2,3 [--trace 1]
        [--root .bench_copy/parent] [--tag parent]

Each seed is one `benchmark/run.py --workload <cell> --seed <n> --seconds
<run_seconds> --trace <t>` from `--root` (this checkout by default), one
after the other. For each run one line on stdout: exit code, seconds, the
result line's `correct` / `failed` / `attempted` / `tokens_per_s` /
`setup_s` / `memory_peak_bytes`, and HOW MANY PROCESSES THE MACHINE HOLDS
THAT IT DID NOT HOLD BEFORE THE RUN: at the run's end, 2 s and 10 s later
(each named; whatever is still there after 10 s is killed, so that the next
run finds the chip free). A checker that cuts `run.py` at a time limit ends
it without its clean-up and the server, a session of its own, stays: that is
what "left a process running" has meant so far (PERF.md section 7.5), and it
takes a run that stands.

Under `chiprun_out/watch/`, per run: `<tag>.out` / `.err` (the run's own),
`<tag>.cpu.jsonl` (every 6 s: the CPU seconds of the server's and the
backend's threads summed by thread name, and their resident sets: a process
that waits for a device that does not answer burns none), and, once a run
is older than `--stall-after` seconds (760: a cold run ends by 530, a warm
one by 230), `<tag>.stall.txt`: the machine's processes, the backend's
threads by name, and the last 120 lines of the server's log.

A cold start is the caller's to give: `JAX_COMPILATION_CACHE_DIR=<an empty
directory>` in the environment, which `run.py` hands on (this script sets
none: tests/test_chip_bringup.py). `--cpu-rehearsal` goes on to `run.py` and
proves this script without a chip (every run then exits 3). Imports no JAX:
the chip belongs to the backend each run starts.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PS = ["ps", "-eo", "pid,ppid,pgid,sid,stat,etime,rss,args", "ww"]


def ps_rows() -> list:
    out = subprocess.run(PS, capture_output=True, text=True).stdout
    return [r for r in out.splitlines()[1:] if " ps -eo " not in r]


def pids() -> set:
    return {int(r.split()[0]) for r in ps_rows()}


def newcomers(before: set) -> list:
    return [r[:300] for r in ps_rows()
            if int(r.split()[0]) not in before | {os.getpid()}]


def thread_cpu(pid: str) -> dict:
    """{thread name: [user ticks, system ticks, threads]} of one process."""
    by_name: dict = {}
    for t in os.listdir(f"/proc/{pid}/task"):
        with open(f"/proc/{pid}/task/{t}/stat") as f:
            raw = f.read()
        rest = raw[raw.rindex(")") + 2:].split()
        a = by_name.setdefault(raw[raw.index("(") + 1:raw.rindex(")")],
                               [0, 0, 0])
        a[0] += int(rest[11])
        a[1] += int(rest[12])
        a[2] += 1
    return by_name


def sample(f) -> None:
    rec = {"t": time.time(), "procs": {}}
    for row in ps_rows():
        if "localai_tpu" not in row:
            continue
        pid = row.split()[0]
        kind = "backend" if "localai_tpu.backend" in row else "server"
        try:
            with open(f"/proc/{pid}/status") as g:
                rss = next((x.split(":", 1)[1].strip() for x in g
                            if x.startswith("VmRSS")), "")
            rec["procs"][kind] = {"threads": thread_cpu(pid), "rss": rss}
        except (OSError, ValueError):
            pass                      # the process ended under the reader
    f.write(json.dumps(rec) + "\n")
    f.flush()


def write_stall(path: str, log: str) -> None:
    with open(path, "a") as f:
        f.write(f"==== {time.strftime('%H:%M:%S')}\n" + "\n".join(ps_rows())
                + "\n")
        for row in ps_rows():
            if "localai_tpu" not in row:
                continue
            try:
                names = thread_cpu(row.split()[0])
            except (OSError, ValueError):
                continue
            f.write(f"---- {row[:160]}\n" + "".join(
                f"  {n:24s} x{c[2]:3d}  user {c[0] / 100:.2f} s  "
                f"system {c[1] / 100:.2f} s\n"
                for n, c in sorted(names.items())))
        try:
            with open(log, errors="replace") as g:
                f.write("---- the server's log, last 120 lines\n"
                        + "".join(g.readlines()[-120:]))
        except OSError as e:
            f.write(f"(no server log: {e})\n")


def brief(out_path: str) -> str:
    last = ""
    with open(out_path) as f:
        for line in f:
            if line.startswith("{"):
                last = line
    try:
        d = json.loads(last)
    except ValueError:
        return "no result line"
    e2e = (d.get("end_to_end_traced") or d.get("metrics")
           or d.get("rehearsal_metrics") or {})
    return (f"correct={d['correct']} failed={d['failed']} "
            f"attempted={d['attempted']} tokens_per_s="
            f"{(e2e.get('tokens_per_s') or {}).get('value')} setup_s="
            f"{(e2e.get('setup_s') or {}).get('value')} peak="
            f"{d['device']['memory_peak_bytes']}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--root", default=HERE)
    ap.add_argument("--tag", default="change")
    ap.add_argument("--stall-after", type=float, default=760.0)
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="pass it on to run.py: proves this script on the "
                         "CPU at a tiny geometry (every run exits 3)")
    args = ap.parse_args()
    root = os.path.abspath(args.root)
    out_dir = os.path.join(HERE, "chiprun_out", "watch")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        seconds = json.load(f)["run_seconds"]
    log = os.path.join(root, ".bench_work", args.workload, "server.log")
    for seed in args.seeds.split(","):
        tag = f"{args.tag}_{args.workload.split('.')[0]}_{seed}_t{args.trace}"
        base = os.path.join(out_dir, tag)
        before = pids()
        t0 = time.monotonic()
        with open(base + ".out", "w") as fo, open(base + ".err", "w") as fe, \
                open(base + ".cpu.jsonl", "w") as fc:
            proc = subprocess.Popen(
                [sys.executable, "benchmark/run.py", "--workload",
                 args.workload, "--seed", seed, "--seconds", str(seconds),
                 "--trace", str(args.trace)]
                + (["--cpu-rehearsal"] if args.cpu_rehearsal else []),
                cwd=root, stdout=fo, stderr=fe)
            next_stall, ticks = args.stall_after, 0
            while proc.poll() is None:
                time.sleep(2.0)
                ticks += 1
                if ticks % 3 == 0:
                    sample(fc)
                if time.monotonic() - t0 > next_stall:
                    next_stall += args.stall_after
                    write_stall(base + ".stall.txt", log)
        took = time.monotonic() - t0
        left = [newcomers(before)]
        for pause in (2.0, 8.0):
            time.sleep(pause)
            left.append(newcomers(before))
        print(f"RUN {tag} rc={proc.returncode} took={took:.1f}s "
              f"{brief(base + '.out')} left_at_end={len(left[0])} "
              f"after_2s={len(left[1])} after_10s={len(left[2])}", flush=True)
        for row in left[0]:
            print(f"   LEFT {row}", flush=True)
        for row in left[2]:
            print(f"   STILL THERE, killed: {row}", flush=True)
            try:
                os.kill(int(row.split()[0]), 9)
            except OSError:
                pass
        if left[2]:
            time.sleep(5.0)
    return 0


if __name__ == "__main__":
    sys.exit(main())
