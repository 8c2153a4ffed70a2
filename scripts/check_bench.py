#!/usr/bin/env python3
"""Benchmark artifact gate (CI; pure stdlib, no jax needed).

Two modes:

1. **Committed mode** (no arguments) — validate every `BENCH_*.json`
   committed at the repo root: the top-level key set must match the
   schema recorded here (a writer growing or renaming fields without
   updating this table and `docs/reference.md` fails CI instead of
   silently drifting), every parity flag the writer asserts-before-write
   must actually be `true` in the artifact, and every file must have a
   row in the `docs/reference.md` artifact table.
2. **Regression mode** (`--baseline DIR --candidate DIR`) — validate
   the candidate artifacts as above, then compare every cut-like
   numeric field against the same-named baseline artifact: a candidate
   cut more than `--tolerance` (relative) above the baseline fails.
   Wall-clock fields are NOT compared (CI machines are too noisy);
   cuts are deterministic at fixed seeds, so a cut regression is a
   code regression.

Parity-flag paths use `.` for dict descent and `[*]` for "every list
element" (`sweep[*].exact` = the `exact` bit of every sweep row).

Device blocks (`device_blocks` below) hold the rows of one device
setting, each recording its `backend`.  On an accelerator the writer
cannot start the forced-host-device child, so the block that child
would fill is `null` (or, if optional, absent): that is accepted only
when another block of the same record names a non-CPU backend, and the
parity paths under the missing block are skipped.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent

# filename -> {required: top-level keys that must be present,
#              optional: additionally allowed top-level keys,
#              parity: dotted flag paths that must be truthy,
#              device_blocks: row blocks that may be missing on a chip}
SCHEMAS = {
    "BENCH_population.json": {
        "required": {"alpha", "batched_wall_s", "bench", "cuts_equal",
                     "design", "eps", "fm_node_limit", "k", "levels",
                     "looped_wall_s", "lp_iters", "m", "n",
                     "per_member_cuts", "shard", "speedup"},
        "optional": set(),
        "parity": ["cuts_equal", "shard.cuts_equal"],
    },
    "BENCH_gain.json": {
        "required": {"backend", "bench", "design", "interpret", "m", "n",
                     "pins", "reps", "sweep"},
        "optional": set(),
        "parity": ["sweep[*].exact"],
    },
    "BENCH_coarsen.json": {
        "required": {"backend", "bench", "design", "device_levels",
                     "device_speedup", "device_wall_s", "host_levels",
                     "host_wall_s", "interpret", "k", "m", "n", "note",
                     "pins", "rating_path", "reps"},
        "optional": set(),
        "parity": [],  # tie-breaking differs by design; see the note
    },
    "BENCH_mutation.json": {
        "required": {"alpha_flagged", "backend", "batched_wall_s", "bench",
                     "design", "eps", "interpret", "k",
                     "legacy_per_member_wall_s", "looped_wall_s", "m", "n",
                     "note", "parts_equal", "per_member_cuts", "pins",
                     "speedup", "speedup_vs_legacy"},
        "optional": set(),
        "parity": ["parts_equal"],
    },
    "BENCH_service.json": {
        "required": {"alpha", "bench", "cuts_equal", "lp_iters",
                     "multi_device", "note", "nreq", "offered_loads_rps",
                     "scale", "single_device", "slots"},
        "optional": set(),
        "parity": ["cuts_equal", "single_device.rows[*].cuts_equal",
                   "multi_device.rows[*].cuts_equal"],
        "device_blocks": {"single_device", "multi_device"},
    },
    "BENCH_robustness.json": {
        "required": {"alpha", "backend", "baseline_makespan_s", "bench",
                     "devices", "lp_iters", "note", "nreq", "runs",
                     "slots"},
        "optional": set(),
        "parity": ["runs[*].cuts_equal_all"],
    },
    "BENCH_modelshard.json": {
        "required": {"bench", "budget_bytes", "forced", "note"},
        "optional": {"local"},   # rows measured on >= 2 local devices
        "parity": ["forced.parity_gate.bit_equal",
                   "local.parity_gate.bit_equal"],
        "device_blocks": {"forced", "local"},
    },
    "BENCH_incremental.json": {
        "required": {"alpha", "bench", "drift_magnitude", "k", "lp_iters",
                     "migration_frac", "multi_device", "note", "scale",
                     "single_device", "steps"},
        "optional": set(),
        "parity": ["single_device.rows[*].migration_within_budget",
                   "multi_device.rows[*].migration_within_budget",
                   "single_device.summary.all_within_budget",
                   "multi_device.summary.all_within_budget"],
        "device_blocks": {"single_device", "multi_device"},
    },
    "BENCH_sched.json": {
        "required": {"bench", "note", "policy", "rows", "seed", "smoke",
                     "summary"},
        "optional": set(),
        "parity": ["rows[*].replay_equal"],
    },
}


def _walk_flag(obj, parts, path, errors, filename):
    """Resolve one parity-flag path; every terminal value must be truthy."""
    if not parts:
        if obj is not True:
            errors.append(f"{filename}: parity flag {path} is {obj!r}, "
                          "expected true")
        return
    head, rest = parts[0], parts[1:]
    if head == "[*]":
        if not isinstance(obj, list):
            errors.append(f"{filename}: parity path {path} expects a list "
                          f"at [*], found {type(obj).__name__}")
            return
        if not obj:
            errors.append(f"{filename}: parity path {path} hit an empty "
                          "list — nothing was asserted")
            return
        for item in obj:
            _walk_flag(item, rest, path, errors, filename)
        return
    if not isinstance(obj, dict) or head not in obj:
        errors.append(f"{filename}: parity path {path} missing key "
                      f"{head!r}")
        return
    _walk_flag(obj[head], rest, path, errors, filename)


def _flag_parts(path: str):
    parts = []
    for seg in path.split("."):
        if seg.endswith("[*]"):
            parts.extend([seg[:-3], "[*]"])
        else:
            parts.append(seg)
    return parts


def _missing_device_blocks(data: dict, schema: dict, name: str,
                           errors: list) -> set:
    """Device blocks that are null or absent, checked against the
    record's backends (see the module docstring)."""
    blocks = schema.get("device_blocks", set())
    missing = {b for b in blocks if data.get(b) is None}
    if not missing:
        return missing
    backends = {data[b].get("backend") for b in blocks - missing
                if isinstance(data[b], dict)}
    if not backends:
        errors.append(f"{name}: every device block {sorted(blocks)} is "
                      "missing — no rows were recorded")
    elif backends <= {"cpu"}:
        for b in sorted(missing & schema["required"]):
            errors.append(f"{name}: device block {b!r} is null on a cpu "
                          "record — only a chip run may skip it")
    return missing


def validate_file(path: Path, errors: list) -> dict:
    name = path.name
    schema = SCHEMAS.get(name)
    if schema is None:
        errors.append(f"{name}: no schema registered in "
                      "scripts/check_bench.py (add one alongside the "
                      "writer and a docs/reference.md row)")
        return {}
    try:
        data = json.loads(path.read_text())
    except (OSError, json.JSONDecodeError) as exc:
        errors.append(f"{name}: unreadable ({exc})")
        return {}
    keys = set(data)
    missing = schema["required"] - keys
    unknown = keys - schema["required"] - schema["optional"]
    if missing:
        errors.append(f"{name}: missing required keys {sorted(missing)}")
    if unknown:
        errors.append(f"{name}: unknown keys {sorted(unknown)} — update "
                      "the schema here and the docs/reference.md table")
    skipped = _missing_device_blocks(data, schema, name, errors)
    for flag in schema["parity"]:
        parts = _flag_parts(flag)
        if parts[0] not in skipped:
            _walk_flag(data, parts, flag, errors, name)
    return data


def _cut_leaves(obj, path=""):
    """Yield (dotted_path, value) for every numeric leaf whose key names
    a cut (lower-is-better, deterministic at fixed seeds).  Ratios and
    booleans are excluded; list elements are indexed positionally."""
    if isinstance(obj, dict):
        for k, v in obj.items():
            yield from _cut_leaves(v, f"{path}.{k}" if path else k)
    elif isinstance(obj, list):
        for i, v in enumerate(obj):
            yield from _cut_leaves(v, f"{path}[{i}]")
    else:
        leaf = path.rsplit(".", 1)[-1]
        leaf = leaf.split("[", 1)[0]
        if ("cut" in leaf.lower() and "ratio" not in leaf.lower()
                and isinstance(obj, (int, float))
                and not isinstance(obj, bool)):
            yield path, float(obj)


def compare_cuts(name: str, baseline: dict, candidate: dict,
                 tolerance: float, errors: list) -> int:
    base = dict(_cut_leaves(baseline))
    cand = dict(_cut_leaves(candidate))
    compared = 0
    for path, bval in sorted(base.items()):
        if path not in cand:
            continue  # row-shape changes are the schema check's problem
        compared += 1
        cval = cand[path]
        if bval >= 0 and cval > bval * (1.0 + tolerance):
            errors.append(
                f"{name}: cut regression at {path}: {cval:g} vs baseline "
                f"{bval:g} (tolerance {tolerance:.0%})")
    return compared


def check_docs_rows(names, errors):
    ref = ROOT / "docs" / "reference.md"
    text = ref.read_text() if ref.exists() else ""
    for name in names:
        if f"`{name}`" not in text:
            errors.append(f"{name}: no row in docs/reference.md's "
                          "BENCH_*.json artifact table")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--baseline", type=Path, default=None,
                    help="directory of baseline BENCH_*.json artifacts")
    ap.add_argument("--candidate", type=Path, default=None,
                    help="directory of candidate BENCH_*.json artifacts")
    ap.add_argument("--tolerance", type=float, default=0.02,
                    help="relative cut-regression tolerance (default 2%%)")
    args = ap.parse_args(argv)
    if (args.baseline is None) != (args.candidate is None):
        ap.error("--baseline and --candidate must be given together")

    errors: list = []
    if args.candidate is None:
        files = sorted(ROOT.glob("BENCH_*.json"))
        if not files:
            errors.append("no BENCH_*.json artifacts at the repo root")
        for path in files:
            validate_file(path, errors)
        check_docs_rows([p.name for p in files], errors)
        checked = len(files)
    else:
        files = sorted(args.candidate.glob("BENCH_*.json"))
        if not files:
            errors.append(f"no BENCH_*.json artifacts in {args.candidate}")
        checked = 0
        for path in files:
            cand = validate_file(path, errors)
            base_path = args.baseline / path.name
            if not base_path.exists():
                print(f"note: {path.name} has no baseline, schema-only")
                continue
            try:
                base = json.loads(base_path.read_text())
            except (OSError, json.JSONDecodeError) as exc:
                errors.append(f"{path.name}: baseline unreadable ({exc})")
                continue
            checked += compare_cuts(path.name, base, cand,
                                    args.tolerance, errors)

    if errors:
        for err in errors:
            print(f"FAIL: {err}", file=sys.stderr)
        print(f"\ncheck_bench: {len(errors)} error(s)", file=sys.stderr)
        return 1
    mode = ("committed artifacts"
            if args.candidate is None else "cut comparisons")
    print(f"check_bench: OK ({checked} {mode})")
    return 0


if __name__ == "__main__":
    sys.exit(main())
