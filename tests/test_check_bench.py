"""The benchmark artifact gate (``scripts/check_bench.py``) must pass on
the committed artifacts and *demonstrably fail* on each class of defect
it guards against: unknown/missing keys, a false parity flag, and a cut
regression beyond tolerance.  Pure stdlib — runs in the docs lane."""
import json
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parent.parent
SCRIPT = ROOT / "scripts" / "check_bench.py"


def _run(*args):
    return subprocess.run([sys.executable, str(SCRIPT), *args],
                          capture_output=True, text=True)


def test_committed_artifacts_pass():
    proc = _run()
    assert proc.returncode == 0, proc.stderr
    assert "OK" in proc.stdout


@pytest.fixture()
def dirs(tmp_path):
    base, cand = tmp_path / "base", tmp_path / "cand"
    base.mkdir(), cand.mkdir()
    src = ROOT / "BENCH_population.json"
    shutil.copy(src, base / src.name)
    shutil.copy(src, cand / src.name)
    return base, cand


def _mutate(path: Path, fn):
    data = json.loads(path.read_text())
    fn(data)
    path.write_text(json.dumps(data))


def test_clean_comparison_passes(dirs):
    base, cand = dirs
    proc = _run("--baseline", str(base), "--candidate", str(cand))
    assert proc.returncode == 0, proc.stderr


def test_unknown_key_fails(dirs):
    base, cand = dirs
    _mutate(cand / "BENCH_population.json",
            lambda d: d.update(surprise_field=1))
    proc = _run("--baseline", str(base), "--candidate", str(cand))
    assert proc.returncode == 1
    assert "unknown keys" in proc.stderr


def test_missing_required_key_fails(dirs):
    base, cand = dirs
    _mutate(cand / "BENCH_population.json",
            lambda d: d.pop("cuts_equal"))
    proc = _run("--baseline", str(base), "--candidate", str(cand))
    assert proc.returncode == 1
    assert "missing required" in proc.stderr


def test_false_parity_flag_fails(dirs):
    base, cand = dirs
    _mutate(cand / "BENCH_population.json",
            lambda d: d["shard"].update(cuts_equal=False))
    proc = _run("--baseline", str(base), "--candidate", str(cand))
    assert proc.returncode == 1
    assert "parity flag" in proc.stderr


def test_cut_regression_fails(dirs):
    base, cand = dirs

    def inflate(d):
        d["per_member_cuts"] = [c * 1.5 for c in d["per_member_cuts"]]
    _mutate(cand / "BENCH_population.json", inflate)
    proc = _run("--baseline", str(base), "--candidate", str(cand))
    assert proc.returncode == 1
    assert "cut regression" in proc.stderr


def test_cut_within_tolerance_passes(dirs):
    base, cand = dirs

    def nudge(d):
        d["per_member_cuts"] = [c * 1.01 for c in d["per_member_cuts"]]
    _mutate(cand / "BENCH_population.json", nudge)
    proc = _run("--baseline", str(base), "--candidate", str(cand),
                "--tolerance", "0.02")
    assert proc.returncode == 0, proc.stderr


def test_unregistered_artifact_fails(tmp_path):
    base, cand = tmp_path / "base", tmp_path / "cand"
    base.mkdir(), cand.mkdir()
    (cand / "BENCH_mystery.json").write_text("{}")
    proc = _run("--baseline", str(base), "--candidate", str(cand))
    assert proc.returncode == 1
    assert "no schema registered" in proc.stderr


@pytest.mark.parametrize("name,block,backend,ok", [
    ("BENCH_service.json", "multi_device", "tpu", True),
    ("BENCH_service.json", "single_device", "tpu", True),
    ("BENCH_service.json", "multi_device", "cpu", False),
    ("BENCH_incremental.json", "multi_device", "tpu", True),
    ("BENCH_incremental.json", "multi_device", "cpu", False),
    ("BENCH_modelshard.json", "forced", "tpu", True),
    ("BENCH_modelshard.json", "forced", "cpu", False),
])
def test_chip_record_may_skip_forced_block(tmp_path, name, block, backend,
                                           ok):
    """A chip run records no forced-host-device rows: the gate accepts
    a null block only when another block names a non-CPU backend, and
    still checks the parity flags of the blocks that are there."""
    base, cand = tmp_path / "base", tmp_path / "cand"
    base.mkdir(), cand.mkdir()
    data = json.loads((ROOT / name).read_text())
    if name == "BENCH_modelshard.json":
        data["local"] = dict(data["forced"], devices=4)
    kept = next(b for b in ("single_device", "multi_device", "local")
                if b in data and b != block)
    data[kept]["backend"] = backend
    data[block] = None
    (cand / name).write_text(json.dumps(data))
    proc = _run("--baseline", str(base), "--candidate", str(cand))
    assert (proc.returncode == 0) == ok, proc.stderr
    if not ok:
        assert "only a chip run may skip it" in proc.stderr

    if ok:   # the remaining block's parity flags are still enforced
        if name == "BENCH_modelshard.json":
            data[kept]["parity_gate"]["bit_equal"] = False
        else:
            data[kept]["rows"][0][
                "cuts_equal" if name == "BENCH_service.json"
                else "migration_within_budget"] = False
        (cand / name).write_text(json.dumps(data))
        proc = _run("--baseline", str(base), "--candidate", str(cand))
        assert proc.returncode == 1
        assert "parity flag" in proc.stderr


def test_record_with_no_device_rows_fails(tmp_path):
    base, cand = tmp_path / "base", tmp_path / "cand"
    base.mkdir(), cand.mkdir()
    data = json.loads((ROOT / "BENCH_service.json").read_text())
    data["single_device"] = data["multi_device"] = None
    (cand / "BENCH_service.json").write_text(json.dumps(data))
    proc = _run("--baseline", str(base), "--candidate", str(cand))
    assert proc.returncode == 1
    assert "no rows were recorded" in proc.stderr
