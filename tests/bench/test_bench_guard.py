"""The measurement path reports nothing unless the seat is on the chip the
cell asks for, and refuses to run outside a checkout."""
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

from bench import run  # noqa: E402

CPU = dict(platform="cpu", kind="cpu", count=1)
TPU = dict(platform="tpu", kind="TPU v5 lite", count=1)


def test_seat_off_the_tpu_is_refused():
    with pytest.raises(run.Refused):
        run.check_seat(CPU, "tpu", 1)
    run.check_seat(TPU, "tpu", 1)


def test_too_few_chips_is_refused():
    with pytest.raises(run.Refused):
        run.check_seat(TPU, "tpu", 4)


def test_refused_run_prints_no_result(monkeypatch, capsys):
    def refuse(args, cell):
        raise run.Refused("the seat is on cpu, not tpu")

    monkeypatch.setattr(run, "measure", refuse)
    rc = run.main(["--workload", "g500-s16.bfs-batch", "--seed", "1",
                   "--seconds", "1", "--trace", "1"])
    assert rc == 1
    assert capsys.readouterr().out == ""


def test_benchmark_files_alone_do_not_run(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "g500-s16.bfs-batch", "--seed", "1", "--seconds",
                        "1", "--trace", "0"], cwd=tmp_path,
                       capture_output=True, text=True, timeout=60)
    assert p.returncode != 0 and p.stdout == ""
