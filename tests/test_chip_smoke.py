"""chip_smoke.py's contract, as far as a machine without the chip can show
it: the tiny CPU run passes every phase (server and, on the eight virtual
devices, both mesh tiers), and the run fails — non-zero, no result line —
without a TPU it was not told to do without, when a phase raises, on an
oracle mismatch, and when a degrade counter moves."""

import json
import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402


def _result_lines(stdout: str) -> list:
    return [ln for ln in stdout.splitlines() if ln.startswith('{"ok"')]


def test_every_phase_passes_at_tiny_size_on_the_cpu(capsys):
    assert chip_smoke.main(["--cpu"]) == 0
    out = capsys.readouterr().out
    for ph in ("identity", "data", "query q3", "query q7", "query q19",
               "server", "mesh"):
        assert f"phase {ph}: ok" in out, out[-2000:]
    assert "mesh stage: tier=stage" in out
    assert "mesh mesh-whole: tier=mesh-whole" in out
    last = json.loads(out.strip().splitlines()[-1])
    assert last["ok"] is True
    assert last["device"]["platform"] == "cpu"
    assert last["device"]["count"] >= chip_smoke.MESH_DEVICES


def test_without_a_tpu_and_without_the_cpu_argument_it_fails():
    r = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")],
                       env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=REPO,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert not _result_lines(r.stdout), r.stdout
    assert "platform is 'cpu', not 'tpu'" in r.stderr


def test_alone_in_a_directory_it_fails(tmp_path):
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    r = subprocess.run([sys.executable, "chip_smoke.py", "--cpu"], env=env,
                       cwd=tmp_path, capture_output=True, text=True,
                       timeout=300)
    assert r.returncode != 0
    assert not _result_lines(r.stdout), r.stdout
    assert "spark_tpu" in r.stderr


def test_a_phase_that_raises_ends_the_run_with_the_phase_named(
        monkeypatch, capsys):
    def boom(scale):
        raise RuntimeError("datagen exploded")

    monkeypatch.setattr(chip_smoke, "generate_tables", boom)
    with pytest.raises(RuntimeError, match="datagen exploded"):
        chip_smoke.main(["--cpu"])
    cap = capsys.readouterr()
    assert "phase data: FAILED" in cap.err
    assert not _result_lines(cap.out)


def test_an_oracle_mismatch_fails(monkeypatch, capsys):
    real = chip_smoke.oracle_rows

    def off_by_one_row(tables):
        return {q: rows[:-1] for q, rows in real(tables).items()}

    monkeypatch.setattr(chip_smoke, "oracle_rows", off_by_one_row)
    with pytest.raises(chip_smoke.SmokeFailure, match="vs sqlite oracle"):
        chip_smoke.main(["--cpu"])
    assert not _result_lines(capsys.readouterr().out)


def test_a_degraded_whole_tier_fails_even_though_the_rows_are_right(
        monkeypatch, capsys):
    """An injected dispatch fault degrades the whole-query program to the
    stage tier, which still answers correctly — exactly what the smoke
    must not let pass."""
    from spark_tpu.utils import faults

    monkeypatch.setattr(chip_smoke, "SESSION_CONF", {
        "spark.tpu.compile.whole.minRows": 0,       # whole tier, tiny data
        "spark.tpu.faults.enabled": "true",
        "spark.tpu.faults.points": "kernel.dispatch=once@whole_query"})
    try:
        with pytest.raises(chip_smoke.SmokeFailure,
                           match="whole_query.runtime_degraded"):
            chip_smoke.main(["--cpu"])
    finally:
        faults.reset()
    assert not _result_lines(capsys.readouterr().out)
