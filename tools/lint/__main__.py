"""``python -m tools.lint`` — the one audit front door.

Static (explicit paths)::

    python -m tools.lint singa_tpu tools          # lint trees/files
    python -m tools.lint --json singa_tpu         # machine-readable
    python -m tools.lint --select SGL005 singa_tpu
    python -m tools.lint --list-rules

Full audit (no paths, no mode flags): static rules over the repo's own
trees (``singa_tpu``, ``tools``), the concurrency thread-model gate
(conclint, ``tools/lint/conc.py``), the process-mesh gate (proclint,
``tools/lint/proc.py``), AND the compiled-program gates — HLO
structure (hloaudit) plus cost/memory (hlocost), off ONE shared
lowering::

    python -m tools.lint

Dynamic audits (same checks the old standalone CLIs ran)::

    python -m tools.lint --records [ROOT]         # telemetry records
    python -m tools.lint --ckpt DIR [DIR ...]     # checkpoint fsck
    python -m tools.lint --hlo                    # structure + cost gates
    python -m tools.lint --hlo --update-baselines # reviewed re-baseline
    python -m tools.lint --conc                   # thread-model gate
    python -m tools.lint --conc --update-baselines  # reviewed re-model
    python -m tools.lint --proc                   # process-mesh gate
    python -m tools.lint --proc --update-baselines  # reviewed re-model

``--select`` filters audit modes too (``--select hlo``,
``--select cost``, ``--select conc``, ``--select records``, or mixed
with SGL codes in the full audit).

Exit codes: 0 clean, 1 findings/errors, 2 usage error.
"""

from __future__ import annotations

import argparse
import os
import sys
from typing import List, Optional

from . import RULES, render_human, render_json, run_paths
from . import audit

#: ``--records`` with no value means "the repo root" — a sentinel the
#: user cannot type, so an explicit ``--records .`` still means cwd
_RECORDS_DEFAULT = "\0repo-root"

#: the dynamic-audit modes --select/--list-rules enumerate alongside
#: the SGL rules; ckpt needs its DIR argument so it is flag-only
_AUDIT_MODES = {
    "records": "validate telemetry records (sessions, BENCH/MULTICHIP "
               "docs, runs/records.jsonl) — also via --records [ROOT]",
    "ckpt": "checkpoint-directory fsck (commit markers, manifests) — "
            "via --ckpt DIR [DIR ...] only, it needs the directory",
    "conc": "concurrency thread-model gate (conclint): diff the "
            "discovered thread roots + cross-thread attribute table "
            "against tools/lint/data/conc/model.json — also via "
            "--conc (re-baseline with --conc --update-baselines)",
    "proc": "process-mesh gate (proclint): diff the discovered spawn/"
            "signal/reap/socket model against tools/lint/data/proc/"
            "model.json AND cross-check the worker RPC dispatch table "
            "vs. call sites vs. _OP_TIMEOUTS — also via --proc "
            "(re-baseline with --proc --update-baselines)",
    "hlo": "compiled-program structural gate: lower the flagship train/"
           "prefill/decode programs and diff collectives, donation, "
           "entry parameters, un-fused logits vs tools/lint/data/hlo/ "
           "— also via --hlo (which runs the cost gate too, off ONE "
           "shared lowering)",
    "cost": "compiled-program cost gate (hlocost): flops, lost "
            "donation bytes, collective wire bytes vs "
            "tools/lint/data/hlo/cost/ — shares the hlo mode's lowering",
}

#: the trees the bare full-audit invocation lints (repo-relative) —
#: the same set the tier-1 repo-is-clean gate pins
_DEFAULT_TREES = ("singa_tpu", "tools")


def _list_rules() -> str:
    from .conc import CONC_GATE_CODES
    from .cost import COST_CODES
    from .proc import PROC_GATE_CODES
    from .framework import RETIRED_CODES
    from .hlo import HLO_CODES
    lines = ["singalint rules:"]
    for code, cls in RULES.items():
        lines.append(f"  {code}  {cls.name:<17} {cls.description}")
    lines.append("  SGL000 suppression-hygiene  a '# singalint: "
                 "disable=CODE' without a reason, or naming an unknown "
                 "code, is itself a finding and cannot be suppressed")
    for code, successor in sorted(RETIRED_CODES.items()):
        lines.append(f"  {code}  (retired)          superseded by "
                     f"{successor}; a disable={code} suppression fails "
                     f"loudly with a migration hint")
    lines.append("conc gate finding codes (the committed thread-model "
                 "baseline, tools/lint/conc.py; re-baseline via "
                 "--conc --update-baselines):")
    for code, (name, desc) in CONC_GATE_CODES.items():
        lines.append(f"  {code}  {name:<21} {desc}")
    lines.append("proc gate finding codes (the committed process-model "
                 "baseline + RPC-protocol cross-check, "
                 "tools/lint/proc.py; re-baseline via "
                 "--proc --update-baselines):")
    for code, (name, desc) in PROC_GATE_CODES.items():
        lines.append(f"  {code}  {name:<21} {desc}")
    lines.append("audit modes (run via their flag, or --select MODE):")
    for mode, desc in _AUDIT_MODES.items():
        lines.append(f"  {mode:<7} {desc}")
    lines.append("hlo gate finding codes (named finding per drifted "
                 "metric; waive per-baseline via a 'suppress' entry "
                 "with a reason):")
    for code, (name, desc) in HLO_CODES.items():
        lines.append(f"  {code}  {name:<21} {desc}")
    lines.append("cost gate finding codes (relative tolerance per "
                 "metric; same per-baseline waiver contract):")
    for code, (name, desc) in COST_CODES.items():
        lines.append(f"  {code}  {name:<21} {desc}")
    return "\n".join(lines)


def main(argv: Optional[List[str]] = None) -> int:
    parser = argparse.ArgumentParser(
        prog="python -m tools.lint",
        description="singalint: AST invariant linter + dynamic audits "
                    "(records, ckpt, hlo); bare invocation runs the "
                    "full audit: static rules + the HLO gate")
    parser.add_argument("paths", nargs="*",
                        help="files or directories to lint (static "
                             "rules); omit everything for the full "
                             "audit (static + HLO gate)")
    parser.add_argument("--json", action="store_true",
                        help="emit findings as JSON")
    parser.add_argument("--select", metavar="CODES",
                        help="comma-separated rule codes and/or audit "
                             "modes (records, hlo) to run "
                             "(default: all)")
    parser.add_argument("--list-rules", action="store_true",
                        help="print the rule + audit-mode catalogue "
                             "and exit")
    parser.add_argument("--records", nargs="?", const=_RECORDS_DEFAULT,
                        metavar="ROOT", default=None,
                        help="validate telemetry records under ROOT "
                             "(default: repo root) instead of linting")
    parser.add_argument("--ckpt", nargs="+", metavar="DIR", default=None,
                        help="fsck checkpoint directories instead of "
                             "linting")
    parser.add_argument("--hlo", action="store_true",
                        help="run the compiled-program gates (structure "
                             "AND cost, off one shared lowering) against "
                             "tools/lint/data/hlo/ baselines")
    parser.add_argument("--conc", action="store_true",
                        help="run the concurrency thread-model gate "
                             "(conclint) against "
                             "tools/lint/data/conc/model.json")
    parser.add_argument("--proc", action="store_true",
                        help="run the process-mesh gate (proclint): "
                             "spawn/signal/reap/socket model vs "
                             "tools/lint/data/proc/model.json, plus "
                             "the RPC-protocol cross-check")
    parser.add_argument("--update-baselines", action="store_true",
                        help="rewrite the committed baselines, printing "
                             "a human-readable diff to review: with "
                             "--conc the thread model; otherwise the "
                             "HLO structure + cost baselines (implies "
                             "--hlo)")
    args = parser.parse_args(argv)

    if args.list_rules:
        print(_list_rules())
        return 0
    if args.update_baselines and not (args.conc or args.proc):
        args.hlo = True
    mode_flags = [f for f, on in (("--records", args.records is not None),
                                  ("--ckpt", args.ckpt is not None),
                                  ("--hlo", args.hlo),
                                  ("--conc", args.conc),
                                  ("--proc", args.proc)) if on]
    if len(mode_flags) > 1:
        parser.error(f"{' and '.join(mode_flags)} are separate audit "
                     f"modes")
    if mode_flags and args.paths:
        parser.error("audit modes take no lint paths — run the static "
                     "lint as a separate invocation")

    # --select: SGL codes and/or audit-mode names
    codes = None
    selected_modes: List[str] = []
    if args.select:
        raw = [c.strip() for c in args.select.split(",") if c.strip()]
        selected_modes = [c for c in raw if c in _AUDIT_MODES]
        codes = [c for c in raw if c in RULES]
        unknown = [c for c in raw if c not in RULES
                   and c not in _AUDIT_MODES]
        if unknown:
            from .framework import RETIRED_CODES
            retired = [f"{c} was retired — use {RETIRED_CODES[c]}"
                       for c in unknown if c in RETIRED_CODES]
            parser.error(f"unknown rule code(s)/mode(s): "
                         f"{', '.join(unknown)} (see --list-rules"
                         + (f"; {'; '.join(retired)}" if retired else "")
                         + ")")
        if "ckpt" in selected_modes:
            parser.error("the ckpt audit needs its directories — run "
                         "it as --ckpt DIR [DIR ...]")
        if selected_modes and (args.paths or mode_flags):
            parser.error("--select with audit-mode names applies to "
                         "the bare full-audit invocation only")

    if args.records is not None:
        root = (audit._REPO_ROOT if args.records == _RECORDS_DEFAULT
                else args.records)
        return audit.records_main(root)
    if args.ckpt is not None:
        return audit.ckpt_main(args.ckpt)
    if args.conc:
        from . import conc
        if args.update_baselines:
            print(conc.update_model_baseline())
            print(f"conclint: thread-model baseline updated at "
                  f"{conc.MODEL_PATH} — review the diff above")
            return 0
        findings = conc.gate_findings()
        print(render_json(findings) if args.json
              else render_human(findings).replace("singalint:",
                                                  "conclint:"))
        return 1 if findings else 0
    if args.proc:
        from . import proc
        if args.update_baselines:
            print(proc.update_model_baseline())
            print(f"proclint: process-model baseline updated at "
                  f"{proc.MODEL_PATH} — review the diff above")
            return 0
        findings = proc.audit_findings()
        print(render_json(findings) if args.json
              else render_human(findings).replace("singalint:",
                                                  "proclint:"))
        return 1 if findings else 0
    if args.hlo:
        from .hlo import hlo_main
        try:
            return hlo_main(update=args.update_baselines,
                            json_out=args.json)
        except RuntimeError as e:
            parser.error(str(e))

    if not args.paths:
        # the full audit: static rules over the repo trees + the
        # concurrency thread-model gate (conclint) + the process-mesh
        # gate (proclint) + the compiled-program gates (or the
        # --select'ed subset) — the structure and cost gates always
        # share ONE lowering pass, and the conc/proc gates reuse the
        # static pass's parse cache
        run_static = codes is None or bool(codes)
        run_hlo = not args.select or "hlo" in selected_modes
        run_cost = not args.select or "cost" in selected_modes
        run_conc = not args.select or "conc" in selected_modes
        run_proc = not args.select or "proc" in selected_modes
        run_records = "records" in selected_modes
        rc = 0
        findings = []
        if run_static:
            trees = [os.path.join(audit._REPO_ROOT, t)
                     for t in _DEFAULT_TREES]
            try:
                findings = run_paths(trees, codes)
            except ValueError as e:
                parser.error(str(e))
        if run_conc:
            from . import conc
            findings = sorted(
                findings + conc.gate_findings(),
                key=lambda f: (f.path, f.line, f.col, f.code))
        if run_proc:
            from . import proc
            findings = sorted(
                findings + proc.audit_findings(),
                key=lambda f: (f.path, f.line, f.col, f.code))
        if run_static or run_conc or run_proc:
            # with --json AND a gate half, the static findings merge
            # into the gate's single document — stdout must stay ONE
            # parseable JSON object
            if not (args.json and (run_hlo or run_cost)):
                print(render_json(findings) if args.json
                      else render_human(findings))
            rc = max(rc, 1 if findings else 0)
        if run_records:
            rc = max(rc, audit.records_main(audit._REPO_ROOT))
        if run_hlo or run_cost:
            from .hlo import hlo_main
            try:
                rc = max(rc, hlo_main(
                    json_out=args.json, structure=run_hlo,
                    cost_gate=run_cost,
                    static_findings=findings if args.json else None))
            except RuntimeError as e:
                parser.error(str(e))
        return rc

    try:
        findings = run_paths(args.paths, codes)
    except ValueError as e:
        # a typo'd or renamed path must not read as "clean"
        parser.error(str(e))
    print(render_json(findings) if args.json else render_human(findings))
    return 1 if findings else 0


if __name__ == "__main__":
    # die silently when the consumer closes the pipe (… | head)
    import signal
    if hasattr(signal, "SIGPIPE"):
        signal.signal(signal.SIGPIPE, signal.SIG_DFL)
    raise SystemExit(main())
