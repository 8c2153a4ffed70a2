"""The benchmark writers' chip branches, driven on the CPU with the
backend and the device count stubbed: on an accelerator they must start
no forced-host-device child, record only the local rows, and write a
record that the artifact gate (``scripts/check_bench.py``) accepts."""
import importlib
import json
import subprocess
import sys
from pathlib import Path

import jax
import pytest

ROOT = Path(__file__).resolve().parent.parent


def _local_block(name: str, key: str, devices: int) -> dict:
    block = json.loads((ROOT / name).read_text())[key]
    return dict(block, devices=devices, backend="tpu")


@pytest.mark.parametrize("module,writer,artifact,template,devices", [
    ("service", "bench_service", "BENCH_service.json", "single_device", 1),
    ("service", "bench_service", "BENCH_service.json", "multi_device", 4),
    ("incremental", "bench_incremental", "BENCH_incremental.json",
     "single_device", 1),
    ("incremental", "bench_incremental", "BENCH_incremental.json",
     "multi_device", 4),
    ("modelshard", "bench_modelshard", "BENCH_modelshard.json", "forced", 4),
])
def test_chip_writer_skips_forced_rows(monkeypatch, tmp_path, module, writer,
                                       artifact, template, devices):
    mod = importlib.import_module(f"benchmarks.{module}")
    block = _local_block(artifact, template, devices)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax, "local_devices", lambda: [object()] * devices)
    monkeypatch.setattr(mod, "measure_rows", lambda *a, **kw: block)

    def no_child(*a, **kw):
        raise AssertionError("forced-host-device child started on a chip")
    monkeypatch.setattr(mod, "_rows_subprocess", no_child)
    for var in ("REPRO_POP_MESH_MODEL", "REPRO_DEVICE_MEM_BUDGET"):
        monkeypatch.setenv(var, "")            # restored after the test

    out = tmp_path / "cand"
    out.mkdir()
    log = tmp_path / "log.txt"
    with open(log, "w") as f:
        record = getattr(mod, writer)(out=f, json_path=str(out / artifact))
    assert "forced-host-device rows skipped" in log.read_text()
    blocks = {k: v for k, v in record.items()
              if k in ("single_device", "multi_device", "forced", "local")}
    assert [v for v in blocks.values() if v is not None] == [block]

    base = tmp_path / "base"
    base.mkdir()
    proc = subprocess.run([sys.executable, str(ROOT / "scripts" /
                                               "check_bench.py"),
                           "--baseline", str(base), "--candidate", str(out)],
                          capture_output=True, text=True)
    assert proc.returncode == 0, proc.stderr


def test_modelshard_on_one_chip_refuses(monkeypatch):
    """A model axis of 2 needs two devices; one chip cannot run it and
    must not fall back to a forced-host-device child."""
    mod = importlib.import_module("benchmarks.modelshard")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax, "local_devices", lambda: [object()])

    def no_child(*a, **kw):
        raise AssertionError("forced-host-device child started on a chip")
    monkeypatch.setattr(mod, "_rows_subprocess", no_child)
    with pytest.raises(SystemExit, match="2 local devices"):
        mod.bench_modelshard(out=sys.stdout, json_path=None)
