"""Dynamic-audit implementations behind the ``tools.lint`` front door.

``python -m tools.lint --records [ROOT]`` and ``--ckpt DIR`` run the
same checks the standalone CLIs (``tools/record_check.py``,
``tools/ckpt_fsck.py``) expose — those files are now thin shims over
this module, so the audit logic has exactly one home and the linter is
the single entry point for "is this tree/record-store/checkpoint-dir
sound?".

Imports of ``singa_tpu`` happen lazily inside the functions: the static
rules must stay runnable (and fast) on machines where jax is absent or
slow to initialize.
"""

from __future__ import annotations

import glob
import json
import os
import sys
from typing import List, Optional, Tuple

_REPO_ROOT = os.path.abspath(
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", ".."))


def _ensure_repo_on_path() -> None:
    if _REPO_ROOT not in sys.path:
        sys.path.insert(0, _REPO_ROOT)


def _load_json(path: str):
    try:
        with open(path) as f:
            return json.load(f), None
    except json.JSONDecodeError as e:
        return None, f"{path}: not valid JSON ({e.msg} at line {e.lineno})"
    except OSError as e:
        return None, f"{path}: unreadable ({e})"


def check_records_root(root: str) -> List[str]:
    """Validate every committed telemetry record under ``root`` against
    the obs schema; returns error strings ([] = all valid).

    Covers ``tpu_session*.json`` / ``*_session.json`` (session docs, v1
    strict / legacy structural), ``BENCH_*.json`` / ``MULTICHIP_*.json``
    (driver records) and ``runs/records.jsonl`` (the RunRecord store:
    every line strictly valid, no duplicate keys)."""
    _ensure_repo_on_path()
    from singa_tpu.obs import record as obs_record
    from singa_tpu.obs import schema

    errors: List[str] = []

    def run(validator, path):
        doc, err = _load_json(path)
        if err:
            errors.append(err)
            return
        errors.extend(schema.collect_errors(validator, doc, path))

    for path in sorted(glob.glob(os.path.join(root, "tpu_session*.json"))):
        run(schema.validate_session_doc, path)
    for path in sorted(glob.glob(os.path.join(root, "*_session.json"))):
        if os.path.basename(path).startswith("tpu_session"):
            continue  # already covered by the pattern above
        run(schema.validate_session_doc, path)
    for path in sorted(glob.glob(os.path.join(root, "BENCH_*.json"))):
        run(schema.validate_bench_doc, path)
    for path in sorted(glob.glob(os.path.join(root, "MULTICHIP_*.json"))):
        run(schema.validate_multichip_doc, path)

    store = os.path.join(root, obs_record.DEFAULT_STORE)
    if os.path.exists(store):
        errors.extend(obs_record.RunRecord(store).validate())
        errors.extend(_check_flight_refs(store))
    errors.extend(_check_incident_dumps(root))
    errors.extend(_check_autotune(root, store))
    return errors


def _check_autotune(root: str, store: str,
                    table: Optional[str] = None) -> List[str]:
    """The autotune layer's record hygiene (ISSUE 14): every committed
    ``autotune_sweep`` entry's knob NAMES must be registered in
    ``singa_tpu.autotune.knobs.KNOBS`` (the schema checks shape; a
    typo'd knob would otherwise fit a predictor on noise), and the
    committed best-config table — when one exists — must validate
    against the current schema version AND cite only run_ids that
    exist in the store (a best point must reference its measured
    evidence; a stale-version table fails loudly instead of silently
    steering configs).  ``table`` overrides the committed location so
    ``tools.autotune check --table`` can vet a CANDIDATE table against
    the same store before it is committed."""
    _ensure_repo_on_path()
    from singa_tpu.autotune import knobs as at_knobs
    from singa_tpu.autotune import table as at_table
    from singa_tpu.obs import record as obs_record
    from singa_tpu.obs import schema

    errors: List[str] = []
    run_ids: Optional[set] = None
    if os.path.exists(store):
        try:
            entries = obs_record.RunRecord(store).entries()
        except schema.SchemaError:
            # the store lint above already reported it; run_ids stays
            # None so the table check below does not pile spurious
            # 'cites a run_id which does not exist' errors on top of
            # the one real store error
            entries = []
        else:
            run_ids = {e["run_id"] for e in entries}
        for e in entries:
            if e["kind"] != "autotune_sweep":
                continue
            p = e["payload"]
            ctx = f"{store}: {e['run_id']}"
            errors.extend(at_knobs.validate_knobs(
                p.get("domain"), p.get("knobs"), ctx=ctx))

    table = table or os.path.join(root, at_table.DEFAULT_TABLE)
    if os.path.exists(table):
        doc, err = _load_json(table)
        if err:
            errors.append(err)
        else:
            errors.extend(at_table.validate_table(
                doc, ctx=table, store_run_ids=run_ids))
    return errors


def _check_flight_refs(store: str) -> List[str]:
    """Every ``flight_ref`` carried by a store entry must point at an
    existing, parseable flight dump (path relative to the store's
    directory) — a ref into nothing would strand the postmortem the
    whole flight-recorder machinery exists to serve."""
    _ensure_repo_on_path()
    from singa_tpu.obs import record as obs_record
    from singa_tpu.obs import schema
    from tools import obsq

    errors: List[str] = []
    try:
        entries = obs_record.RunRecord(store).entries()
    except schema.SchemaError:
        return []          # the store lint above already reported it
    store_dir = os.path.dirname(os.path.abspath(store))
    for e in entries:
        ref = (e.get("payload") or {}).get("flight_ref")
        if not isinstance(ref, str) or not ref:
            continue
        path = os.path.join(store_dir, ref)
        if not os.path.exists(path):
            errors.append(f"{store}: {e['run_id']}: flight_ref {ref!r} "
                          f"points at a missing dump file")
            continue
        try:
            obsq.load_events(path)
        except ValueError as exc:
            errors.append(f"{store}: {e['run_id']}: flight_ref {ref!r}: "
                          f"{exc}")
    return errors


def _check_incident_dumps(root: str) -> List[str]:
    """Every committed flight dump under ``runs/incidents/`` must parse
    as an event-per-line file (partial/truncated dumps fail here, not
    in a postmortem)."""
    _ensure_repo_on_path()
    from tools import obsq

    errors: List[str] = []
    for path in sorted(glob.glob(os.path.join(root, "runs", "incidents",
                                              "*.jsonl"))):
        try:
            obsq.load_events(path)
        except ValueError as exc:
            errors.append(str(exc))
    return errors


def fsck_ckpt_dir(directory: str) -> Tuple[List[str], List[str]]:
    """Audit one checkpoint directory against the commit-marker
    contract; returns (errors, warnings).

    The checks ARE the loader's checks — ``AsyncCheckpointManager.
    verify`` for the marker/size/sha contract and ``utils.checkpoint``'s
    decode + manifest enforcement — so the auditor and the restore path
    can never disagree about what "intact" means."""
    _ensure_repo_on_path()
    from singa_tpu.train import ckpt as train_ckpt
    from singa_tpu.utils import checkpoint

    errors: List[str] = []
    warns: List[str] = []
    if not os.path.isdir(directory):
        return [f"{directory}: not a directory"], []
    for tmp in glob.glob(os.path.join(directory, "*.tmp")):
        warns.append(f"{tmp}: stray temp file (interrupted write)")

    mgr = train_ckpt.AsyncCheckpointManager(directory)
    steps = mgr.steps()
    committed = {mgr.path(s) for s in steps}
    for marker in glob.glob(os.path.join(directory, "ckpt_*.npz"
                                         + train_ckpt.COMMIT_SUFFIX)):
        path = marker[:-len(train_ckpt.COMMIT_SUFFIX)]
        if path not in committed:
            # steps() couldn't parse the name, so restore can't see it
            errors.append(f"{marker}: unparsable marker name (invisible "
                          f"to restore)")
            committed.add(path)

    for step in steps:
        path = mgr.path(step)
        try:
            mgr.verify(step)
        except train_ckpt.CheckpointCorrupt as e:
            errors.append(str(e))
            continue
        # committed and byte-intact: the payload must also decode and
        # self-agree (array manifest vs members, opt moments vs slots)
        try:
            arrays, aux = checkpoint.load_arrays(path)
            checkpoint.check_opt_manifest(arrays, aux)
        except Exception as e:
            errors.append(f"{path}: committed but undecodable "
                          f"({type(e).__name__}: {e})")

    npzs = set(glob.glob(os.path.join(directory, "ckpt_*.npz")))
    for path in sorted(npzs - committed):
        warns.append(f"{path}: no commit marker (uncommitted — ignored "
                     f"at load)")
    return errors, warns


def records_main(root: str) -> int:
    """CLI body shared by ``tools.lint --records`` and the
    ``record_check.py`` shim: 0 = all valid, 1 = named errors printed."""
    root = os.path.abspath(root)
    errors = check_records_root(root)
    if errors:
        for e in errors:
            print(f"record_check: {e}", file=sys.stderr)
        print(f"record_check: {len(errors)} error(s) in {root}",
              file=sys.stderr)
        return 1
    print(f"record_check: all records valid in {root}")
    return 0


def ckpt_main(dirs: List[str]) -> int:
    """CLI body shared by ``tools.lint --ckpt`` and the
    ``ckpt_fsck.py`` shim: 0 = every committed checkpoint intact
    (warnings allowed), 1 = errors printed one per line."""
    all_errors: List[str] = []
    for d in dirs:
        errors, warns = fsck_ckpt_dir(os.path.abspath(d))
        for w in warns:
            print(f"ckpt_fsck: warning: {w}", file=sys.stderr)
        all_errors.extend(errors)
    if all_errors:
        for e in all_errors:
            print(f"ckpt_fsck: {e}", file=sys.stderr)
        print(f"ckpt_fsck: {len(all_errors)} error(s)", file=sys.stderr)
        return 1
    print("ckpt_fsck: all committed checkpoints intact")
    return 0
