"""tools/watch_runs.py: what it says a run left behind, and what it reads of
a result line. No JAX, no server: the pieces alone."""
import json
import os
import subprocess
import sys
import time

import pytest

from tools import watch_runs


def test_a_process_started_after_the_listing_is_a_newcomer_and_one_before_is_not():
    old = subprocess.Popen([sys.executable, "-c", "import time; time.sleep(30)"])
    try:
        before = watch_runs.pids()
        new = subprocess.Popen(
            [sys.executable, "-c", "import time; time.sleep(30)"])
        try:
            time.sleep(0.2)
            seen = {int(r.split()[0]) for r in watch_runs.newcomers(before)}
            assert new.pid in seen
            assert old.pid not in seen and os.getpid() not in seen
        finally:
            new.kill()
            new.wait()
        assert new.pid not in {
            int(r.split()[0]) for r in watch_runs.newcomers(before)}
    finally:
        old.kill()
        old.wait()


def test_thread_cpu_sums_a_process_s_threads_by_name():
    by_name = watch_runs.thread_cpu(str(os.getpid()))
    assert sum(c[2] for c in by_name.values()) >= 1
    assert all(c[0] >= 0 and c[1] >= 0 for c in by_name.values())


def test_a_sample_names_the_server_and_the_backend_by_their_command_lines(tmp_path):
    # the words on the command line are what the sampler goes by
    child = subprocess.Popen([sys.executable, "-c",
                              "import time; time.sleep(30)",
                              "-m", "localai_tpu.backend"])
    try:
        time.sleep(0.2)
        path = tmp_path / "cpu.jsonl"
        with open(path, "w") as f:
            watch_runs.sample(f)
        rec = json.loads(path.read_text())
        assert rec["procs"]["backend"]["threads"]
        assert rec["procs"]["backend"]["rss"].endswith("kB")
    finally:
        child.kill()
        child.wait()


LINE = {"correct": True, "failed": 0, "attempted": 68,
        "device": {"memory_peak_bytes": 12914875904}}


@pytest.mark.parametrize("key", ["metrics", "end_to_end_traced",
                                 "rehearsal_metrics"])
def test_the_brief_reads_the_end_to_end_numbers_wherever_the_line_keeps_them(
        tmp_path, key):
    line = dict(LINE, **{key: {"tokens_per_s": {"value": 1432.01},
                               "setup_s": {"value": 158.4}}})
    if key == "end_to_end_traced":
        line["metrics"] = {"decode_step_ms.over": {"value": 14.8}}
    p = tmp_path / "run.out"
    p.write_text("[bench +  1.0s] loaded\n" + json.dumps(line) + "\n")
    said = watch_runs.brief(str(p))
    assert "tokens_per_s=1432.01" in said and "setup_s=158.4" in said
    assert "correct=True" in said and "peak=12914875904" in said


def test_a_run_without_a_result_line_is_said_to_have_none(tmp_path):
    p = tmp_path / "run.out"
    p.write_text("[bench +  1.0s] loaded\n")
    assert watch_runs.brief(str(p)) == "no result line"


def test_the_state_of_a_run_that_stands_holds_the_processes_and_the_log(tmp_path):
    log = tmp_path / "server.log"
    log.write_text("".join(f"line {i}\n" for i in range(200)))
    out = tmp_path / "stall.txt"
    watch_runs.write_stall(str(out), str(log))
    text = out.read_text()
    assert "line 199" in text and "line 79\n" not in text
    assert str(os.getpid()) in text
    watch_runs.write_stall(str(out), str(tmp_path / "none.log"))
    assert "no server log" in out.read_text()
