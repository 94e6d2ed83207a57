"""``chip_smoke.py`` at the smoke config on the CPU: the rehearsal of the chip
run, so the script's phases and checks keep working between chip runs."""
import json
import os
import subprocess
import sys
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

import chip_smoke  # noqa: E402


def test_refuses_without_a_tpu(capsys):
    assert chip_smoke.main([]) != 0
    out = capsys.readouterr().out
    assert not any(line.startswith("{") for line in out.splitlines())


def test_one_chip_phase_passes_at_smoke_size(capsys):
    check = chip_smoke.Checks()
    chip_smoke.one_chip_phase(True, 0, check)
    out = capsys.readouterr().out
    assert check.failed == []
    for name in ("main_path_impl", "finished", "pallas_vs_stream_tokens",
                 "pallas_vs_stream_logits", "finite", "teacher_forced_pallas",
                 "teacher_forced_stream"):
        assert f"check {name}: PASS" in out


def _req(rid, generated, rows):
    return SimpleNamespace(rid=rid, generated=generated,
                           logits_trace=[np.asarray(r, np.float32) for r in rows])


def test_compare_streams_reports_where_streams_part():
    rows = [[4.0, 1.0, 0.0], [0.0, 2.0, 1.0], [4.0, 0.0, 0.0]]
    ref = [_req(0, [0, 1, 0], rows), _req(1, [0, 1, 0], rows)]
    parts, err = chip_smoke.compare_streams(ref, [_req(0, [0, 1, 0], rows),
                                                 _req(1, [0, 1, 0], rows)])
    assert parts == [] and err == 0.0
    # request 1 parts at token 1: the reference's pick scores 2.0 and the
    # other's 1.0 in the reference's row; the row after the parting step
    # (another context) is not compared
    other = [_req(0, [0, 1, 0], rows),
             _req(1, [0, 2, 0], [[4.0, 1.0, 0.0], [0.0, 1.9, 2.0], [9.0, 0.0, 0.0]])]
    parts, err = chip_smoke.compare_streams(ref, other)
    assert [(r, t) for r, t, _ in parts] == [(1, 1)]
    assert parts[0][2] == pytest.approx(0.5)
    assert err == pytest.approx(1.0 / 2.0)


def test_trace_exercises_multi_chunk_prefill_without_budget_splits():
    specs, max_seq = chip_smoke.trace_for(True, 0)
    lens = [len(p) for p, _, _ in specs]
    assert min(lens) == chip_smoke.PROMPT_MIN and max(lens) == chip_smoke.PROMPT_MAX
    assert max(lens) + chip_smoke.GEN <= max_seq
    # a prompt's chunks all run before the next request arrives
    arrivals = [a for _, _, a in specs]
    assert all(b - a >= -(-max(lens) // chip_smoke.CHUNK)
               for a, b in zip(arrivals, arrivals[1:]))


def test_four_chip_phase_passes_on_four_host_devices(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    proc = subprocess.run(
        [sys.executable, str(ROOT / "chip_smoke.py"), "--smoke", "--chips", "4"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    for name in ("replica_placement", "routed_vs_one_engine", "tp4_vs_one_chip"):
        assert f"check {name}: PASS" in proc.stdout
    last = proc.stdout.strip().splitlines()[-1]
    with pytest.raises(json.JSONDecodeError):
        json.loads(last)  # a rehearsal prints no result line
