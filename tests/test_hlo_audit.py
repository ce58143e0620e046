"""hloaudit + hlocost (ISSUES 7 & 9) — the compiled-program invariant
gates (tools/lint/hlo.py structure, tools/lint/cost.py cost), tier-1
lean.

The invariants under test are the gates' contract:
  * the committed baselines under tools/lint/data/hlo/ (structure) and
    tools/lint/data/hlo/cost/ (cost) are CLEAN against a fresh lowering
    of all eight flagship programs, one case a program — so a change of
    ours that moves a collective, a donation, an entry parameter, the
    fused CE loss, a flop count or a wire byte fails CI with a named
    finding until it is reviewed via ``--update-baselines``.  What the
    compiler owns (fusion counts, opcode histograms, ``while`` bodies,
    HBM and peak bytes) is not compared: it moves with an XLA version
    and no code of ours;
  * the seeded regressions are each caught with a named finding and
    exit 1: a defused CE chunk (HLO008), a moved collective (HLO004), a
    raised CE-chunk count (COST002), a broken KV-arena donation (HLO005
    and COST004), a changed mesh size and a silent f32 fallback of the
    int8 ring (COST005) — and ``--update-baselines`` round-trips with a
    human-readable metric diff;
  * ``--hlo`` runs BOTH gates off ONE lowering pass per program
    (counted via a stub) — the "lower once, audit twice" contract that
    keeps the combined lane inside its tier-1 budget;
  * baseline waivers follow the singalint suppression contract in both
    families (reason REQUIRED, unknown codes are findings, the hygiene
    code unwaivable);
  * the ``hlo_audit`` record kind roundtrips through the obs schema.

Budget discipline: ONE module fixture lowers all eight programs; every
other test summarizes texts or diffs summaries in memory.  The defused
and many-chunk train-step variants are the only extra compiles (tiny
1-block config — the cheap lowering).
"""

import json
import os
import re

import pytest

from tools.lint import cost, hlo
from tools.lint.__main__ import main as lint_main

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


@pytest.fixture(scope="module")
def texts():
    """All eight flagship programs (incl. train_step_dp2_int8, the
    error-feedback int8-ring DP step) lowered ONCE — the file's whole
    compile budget (plus the two seeded train-step variants); tests
    share and never mutate it."""
    return hlo.lower_flagship_texts()


@pytest.fixture(scope="module")
def summaries(texts):
    return hlo.flagship_summaries(texts=texts)


@pytest.fixture(scope="module")
def costs(texts):
    return cost.cost_summaries(texts)


@pytest.fixture()
def stub_lowering(texts, monkeypatch):
    """Route the CLI's single lowering call to the fixture texts and
    count how often it happens."""
    calls = []

    def fake_lower(programs=None):
        calls.append(programs)
        return dict(texts)

    monkeypatch.setattr(hlo, "lower_flagship_texts", fake_lower)
    return calls


def codes_of(findings):
    return [f.code for f in findings]


# ---------------------------------------------------------------------------
# the tier-1 gates: committed baselines are clean
# ---------------------------------------------------------------------------

def _of(findings, program):
    """The findings filed against one program's baseline file."""
    return [f for f in findings
            if os.path.basename(f.path) == f"{program}.json"]


@pytest.fixture(scope="module")
def gate(summaries):
    return hlo.gate_findings(summaries)


@pytest.fixture(scope="module")
def cost_gate(costs):
    return cost.cost_gate_findings(costs)


@pytest.mark.parametrize("program", hlo.FLAGSHIP_PROGRAMS)
def test_committed_baselines_are_clean(gate, program):
    """`python -m tools.lint --hlo` structure half exits 0 on this
    tree: the lowered flagship program matches tools/lint/data/hlo/
    exactly.  A finding here means OUR code changed the program's
    collectives, donation or interface — review it, then re-baseline
    with `--hlo --update-baselines` (docs/static-analysis.md has the
    policy)."""
    findings = _of(gate, program)
    assert findings == [], "\n".join(f.render() for f in findings)


@pytest.mark.parametrize("program", hlo.FLAGSHIP_PROGRAMS)
def test_committed_cost_baselines_are_clean(cost_gate, program):
    """The cost half of the same gate: flops, donated bytes and wire
    bytes of the program within tolerance of tools/lint/data/hlo/cost/."""
    findings = _of(cost_gate, program)
    assert findings == [], "\n".join(f.render() for f in findings)


@pytest.mark.parametrize("program", hlo.FLAGSHIP_PROGRAMS)
def test_summaries_encode_the_flagship_invariants(summaries, program):
    """The metrics the gate protects are non-vacuous in the baselines:
    the train step DOES donate params/opt state and holds no un-fused
    logits, the DP step DOES carry collectives, and the serve programs
    DO donate the KV arena."""
    s = summaries[program]
    assert s["schema"] == hlo.SUMMARY_SCHEMA
    assert s["program"] == program
    assert s["entry_params"] > 0
    assert ("unfused_logits" in s) == program.startswith("train_step")
    decode_donated = summaries["decode"]["donated_outputs"]
    if program == "train_step":
        assert s["donated_outputs"] > 0
        assert s["collective_ops"] == []
        assert s["unfused_logits"] is False
    elif program == "train_step_dp2":
        assert "all-reduce" in s["collective_ops"]
        assert s["unfused_logits"] is False
    elif program == "train_step_dp2_int8":
        # the int8-ring DP step's sync IS a ring: collective-permute
        # hops + the int8 all-gather (plus the absmax-consensus
        # all-reduces), and the error-feedback residuals ride the
        # donated opt state
        assert "collective-permute" in s["collective_ops"]
        assert "all-gather" in s["collective_ops"]
        assert s["donated_outputs"] > \
            summaries["train_step_dp2"]["donated_outputs"]
        assert s["unfused_logits"] is False
    elif program in ("prefill_chunk", "decode"):
        assert s["donated_outputs"] > 0
        assert s["collective_ops"] == []
    elif program == "verify":
        # the speculative verify round donates BOTH arenas (target +
        # draft block pools are updated in place), collective-free
        assert s["donated_outputs"] > decode_donated
        assert s["collective_ops"] == []
    elif program == "handoff_gather":
        # the disagg handoff gather reads the arena without consuming it
        assert s["donated_outputs"] == 0
        assert s["collective_ops"] == []
    else:
        # the int8-arena decode donates MORE outputs than f32 decode —
        # the QuantKV arena flattens into codes + scale leaves, all in
        # place
        assert program == "decode_int8"
        assert s["donated_outputs"] > decode_donated
        assert s["collective_ops"] == []


@pytest.mark.parametrize("program", hlo.FLAGSHIP_PROGRAMS)
def test_cost_summaries_encode_the_flagship_invariants(costs, program):
    """The cost metrics are non-vacuous and mutually consistent: real
    flops everywhere, per-participant DP flops exactly half the
    single-device step (the batch splits two ways), wire bytes only in
    the DP programs (= the f32 gradient payload under the ring model's
    2(P-1)/P factor), donated bytes on every donating program."""
    s = costs[program]
    assert s["schema"] == cost.COST_SCHEMA
    assert s["program"] == program
    assert set(cost.GATED_FIELDS) <= set(s)
    assert s["hbm_bytes"] > 0
    assert s["peak_bytes"] > 0
    if program == "handoff_gather":
        # the one legitimately flop-free program: a pure KV block
        # gather (the disagg handoff source) moves bytes, not math. It
        # must NOT donate: a failed handoff has to leave the source
        # arena valid for the router to re-route
        assert s["flops"] == 0
        assert s["wire_bytes"] == 0
        assert s["donated_bytes"] == 0
        return
    assert s["flops"] > 0
    if program == "train_step":
        assert s["flops"] == 2 * costs["train_step_dp2"]["flops"]
        assert s["wire_bytes"] == 0
        # donation is weighed, not just counted
        assert s["donated_bytes"] > 0
    elif program == "train_step_dp2":
        assert s["wire_bytes"] > 0
    elif program == "train_step_dp2_int8":
        # ISSUE-10 acceptance, enforced in tier-1: the int8-ring DP
        # step moves >= 3x fewer collective wire bytes per participant
        # than the f32 DP step — same matmul flops (quantize is
        # elementwise; the flops model counts dots), the win is pure
        # wire
        assert 0 < s["wire_bytes"] * 3 <= \
            costs["train_step_dp2"]["wire_bytes"]
        assert s["flops"] == costs["train_step_dp2"]["flops"]
    elif program == "verify":
        # one verify dispatch packs k+1 draft steps plus a (k+1)-token
        # target window: more math per dispatch than the one-token
        # decode program — the whole point of ISSUE 13
        assert s["flops"] > 2 * costs["decode"]["flops"]
        assert s["wire_bytes"] == 0
    elif program == "decode_int8":
        # ISSUE-17 acceptance: the int8-KV decode moves FEWER modeled
        # HBM bytes than the f32-arena decode, and its int8 arena
        # donates fewer bytes than the f32 arena it replaces
        assert s["hbm_bytes"] < costs["decode"]["hbm_bytes"]
        assert 0 < s["donated_bytes"] < costs["decode"]["donated_bytes"]
    else:
        assert program in ("prefill_chunk", "decode")
        assert s["donated_bytes"] > 0


# ---------------------------------------------------------------------------
# the shared-lowering contract ("lower once, audit twice")
# ---------------------------------------------------------------------------

def test_hlo_and_cost_gates_share_one_lowering(stub_lowering, capsys):
    """`--hlo` runs the structure gate AND the cost gate from ONE
    lowering pass per program — the compile cost that keeps the
    combined audit lane within its tier-1 budget (the fifth program,
    train_step_dp2_int8, rides the same single pass).  A second
    lower_flagship_texts() call here would double it."""
    assert lint_main(["--hlo"]) == 0
    assert stub_lowering == [None], (
        f"expected exactly one lowering pass for the combined "
        f"structure+cost audit, saw {len(stub_lowering)}")
    assert "hlo_audit: clean" in capsys.readouterr().out


# ---------------------------------------------------------------------------
# seeded structural regressions (the ISSUE-7 acceptance scenarios)
# ---------------------------------------------------------------------------

def test_defused_ce_chunk_is_flagged_with_exit_1(texts, summaries,
                                                 monkeypatch):
    """A train step whose CE-chunk fusion is broken (fused_loss=False —
    the [B, T, V] logits materialize again) must fail the gate: exit 1
    and a named HLO008 un-fused-logits finding for train_step."""
    txt = hlo.lower_train_step(fused_loss=False)
    broken = dict(summaries)
    broken["train_step"] = hlo.summarize_hlo(txt, "train_step")
    findings = hlo.gate_findings(broken)
    assert broken["train_step"]["unfused_logits"] is True
    assert codes_of(findings) == ["HLO008"]
    assert "[train_step]" in findings[0].message
    assert "un-fused logits drifted" in findings[0].message
    # and through the front door: `python -m tools.lint --hlo` exits 1
    # on the defused TEXT
    broken_texts = dict(texts, train_step=txt)
    monkeypatch.setattr(hlo, "lower_flagship_texts",
                        lambda programs=None: broken_texts)
    assert lint_main(["--hlo"]) == 1


def test_moved_collective_is_flagged_with_exit_1(texts, summaries,
                                                 monkeypatch, capsys):
    """A collective migrating between the entry computation and a loop
    body (the overlap path) must fail the gate with the named HLO004
    placement finding."""
    real = summaries["train_step_dp2"]
    moved = dict(summaries)
    assert real["collectives"] > 0
    moved["train_step_dp2"] = dict(
        real, collectives_in_loop=real["collectives"])
    findings = hlo.gate_findings(moved)
    assert codes_of(findings) == ["HLO004"]
    assert "collective placement drifted" in findings[0].message
    monkeypatch.setattr(hlo, "lower_flagship_texts",
                        lambda programs=None: dict(texts))
    monkeypatch.setattr(hlo, "flagship_summaries",
                        lambda programs=None, texts=None: moved)
    assert lint_main(["--hlo", "--json"]) == 1
    doc = json.loads(capsys.readouterr().out)
    assert doc["count"] == 1
    assert doc["findings"][0]["code"] == "HLO004"


# ---------------------------------------------------------------------------
# seeded cost regressions (the ISSUE-9 acceptance scenarios)
# ---------------------------------------------------------------------------

def test_raised_ce_chunk_count_drifts_flops(texts, costs,
                                                    monkeypatch, capsys):
    """Acceptance seed 1: lowering the train step with 8-row CE chunks
    (4 scan iterations instead of 1) changes analytic flops beyond
    tolerance — a named COST002 finding, exit 1 through the front door, and --update-baselines round-trips with a
    human-readable metric diff."""
    txt = hlo.lower_train_step(ce_chunk=8)
    chunked = dict(costs)
    chunked["train_step"] = cost.summarize_cost(txt, "train_step")
    findings = cost.cost_gate_findings(chunked)
    assert codes_of(findings) == ["COST002"]
    flops_f = [f for f in findings if f.code == "COST002"][0]
    assert "analytic flops drifted" in flops_f.message
    assert "%" in flops_f.message and "tolerance" in flops_f.message
    # front door: exit 1 on the chunked TEXT
    chunk_texts = dict(texts, train_step=txt)
    monkeypatch.setattr(hlo, "lower_flagship_texts",
                        lambda programs=None: chunk_texts)
    assert lint_main(["--hlo"]) == 1
    assert "COST002" in capsys.readouterr().out
    # --update-baselines accepts it with a reviewable diff...
    import tempfile
    with tempfile.TemporaryDirectory() as d:
        diff = cost.update_cost_baselines(costs, d)
        assert "NEW cost baseline" in diff
        diff2 = cost.update_cost_baselines(chunked, d)
        assert "COST002" in diff2
        assert "cost unchanged" in diff2       # the other programs
        # ...and the gate is clean against the accepted numbers
        assert cost.cost_gate_findings(chunked, d) == []


def test_broken_kv_arena_donation_inflates_peak(texts, summaries, costs):
    """Acceptance seed 2: stripping the decode program's
    input_output_alias (the KV-arena donation) zeroes its donated
    bytes — the arena now needs a fresh allocation on top of the
    still-live argument every dispatch — and the gate names it COST004
    with the byte cost, and HLO005 with the lost alias entries."""
    stripped = re.sub(r"input_output_alias=\{.*?\},\s*", "",
                      texts["decode"], count=1)
    broken = dict(costs)
    broken["decode"] = cost.summarize_cost(stripped, "decode")
    assert broken["decode"]["donated_bytes"] == 0
    assert costs["decode"]["donated_bytes"] > 0
    findings = cost.cost_gate_findings(broken)
    assert codes_of(findings) == ["COST004"]
    msg = findings[0].message
    assert "donation was LOST" in msg
    assert f"{costs['decode']['donated_bytes']:,} B" in msg
    lost = dict(summaries, decode=hlo.summarize_hlo(stripped, "decode"))
    structural = hlo.gate_findings(lost)
    assert codes_of(structural) == ["HLO005"]
    assert "LOST" in structural[0].message
    # the train step's params/opt-state donation is big enough that the
    # modeled liveness peak itself inflates too
    tstripped = re.sub(r"input_output_alias=\{.*?\},\s*", "",
                       texts["train_step"], count=1)
    tbroken = cost.summarize_cost(tstripped, "train_step")
    assert tbroken["peak_bytes"] > costs["train_step"]["peak_bytes"]


def test_changed_mesh_size_shifts_wire_bytes(texts, costs, monkeypatch,
                                             capsys):
    """Acceptance seed 3: the same all-reduces over a 4-way group
    instead of 2-way shift per-participant wire bytes by the ring
    factor (2(P-1)/P: 1.0 -> 1.5, +50%) — named COST005, exit 1."""
    mesh4 = texts["train_step_dp2"].replace(
        "replica_groups={{0,1}}", "replica_groups={{0,1,2,3}}")
    assert mesh4 != texts["train_step_dp2"]
    shifted = dict(costs)
    shifted["train_step_dp2"] = cost.summarize_cost(mesh4,
                                                    "train_step_dp2")
    assert shifted["train_step_dp2"]["wire_bytes"] == pytest.approx(
        1.5 * costs["train_step_dp2"]["wire_bytes"], rel=1e-6)
    findings = cost.cost_gate_findings(shifted)
    assert codes_of(findings) == ["COST005"]
    assert "wire bytes" in findings[0].message
    mesh_texts = dict(texts, train_step_dp2=mesh4)
    monkeypatch.setattr(hlo, "lower_flagship_texts",
                        lambda programs=None: mesh_texts)
    assert lint_main(["--hlo", "--json"]) == 1
    doc = json.loads(capsys.readouterr().out)
    assert [f["code"] for f in doc["findings"]] == ["COST005"]


def test_silent_f32_fallback_fails_the_wire_gate(texts, costs,
                                                 monkeypatch, capsys):
    """ISSUE-10 acceptance seed: a regression that silently falls back
    to f32 collectives in the int8-ring mode (modeled by the f32 DP
    lowering standing in for train_step_dp2_int8) blows the committed
    wire_bytes baseline ~4x past COST005's 1% tolerance — a NAMED
    COST005 finding on train_step_dp2_int8 and exit 1 through the
    front door.  The >=3x win is enforced, not just claimed."""
    fallen = dict(costs)
    fallen["train_step_dp2_int8"] = dict(
        cost.summarize_cost(texts["train_step_dp2"], "train_step_dp2_int8"))
    assert fallen["train_step_dp2_int8"]["wire_bytes"] >= \
        3 * costs["train_step_dp2_int8"]["wire_bytes"]
    findings = cost.cost_gate_findings(fallen)
    hits = [f for f in findings if f.code == "COST005"
            and "[train_step_dp2_int8]" in f.message]
    assert hits, codes_of(findings)
    assert "wire bytes" in hits[0].message
    # front door: the f32-fallback TEXT fails the combined gate with
    # exit 1 (the structural half names the vanished ring ops too)
    fallen_texts = dict(texts,
                        train_step_dp2_int8=texts["train_step_dp2"])
    monkeypatch.setattr(hlo, "lower_flagship_texts",
                        lambda programs=None: fallen_texts)
    assert lint_main(["--hlo", "--json"]) == 1
    doc = json.loads(capsys.readouterr().out)
    assert "COST005" in {f["code"] for f in doc["findings"]}


# ---------------------------------------------------------------------------
# --update-baselines roundtrip + waiver contract (in-memory, no compiles)
# ---------------------------------------------------------------------------

def test_update_baselines_roundtrip(summaries, tmp_path):
    d = str(tmp_path / "hlo")
    diff = hlo.update_baselines(summaries, d)
    assert "NEW baseline" in diff
    assert sorted(os.listdir(d)) == sorted(
        f"{p}.json" for p in hlo.FLAGSHIP_PROGRAMS)
    assert hlo.gate_findings(summaries, d) == []

    # a lost donation drifts exactly one named metric...
    mutated = dict(summaries)
    mutated["decode"] = dict(summaries["decode"], donated_outputs=0)
    findings = hlo.gate_findings(mutated, d)
    assert codes_of(findings) == ["HLO005"]
    assert "LOST" in findings[0].message
    # ...and one reviewed update command accepts it, with a diff
    diff2 = hlo.update_baselines(mutated, d)
    assert "HLO005" in diff2 and "unchanged" in diff2
    assert hlo.gate_findings(mutated, d) == []

    # stale/missing baselines are loud in both directions
    only = {"decode": mutated["decode"]}
    stale = hlo.gate_findings(only, d)
    assert codes_of(stale) == ["HLO001"] * (len(hlo.FLAGSHIP_PROGRAMS) - 1)
    missing = hlo.gate_findings(summaries, str(tmp_path / "empty"))
    assert codes_of(missing) == ["HLO001"] * len(hlo.FLAGSHIP_PROGRAMS)
    assert all("--update-baselines" in f.message for f in missing)


def test_cost_update_prunes_stale_and_reports_missing(costs, tmp_path):
    """The cost gate mirrors the structural program-set contract:
    missing baselines, stale baselines and removals are all loud."""
    d = str(tmp_path / "cost")
    missing = cost.cost_gate_findings(costs, d)
    assert codes_of(missing) == ["COST001"] * len(hlo.FLAGSHIP_PROGRAMS)
    cost.update_cost_baselines(costs, d)
    assert cost.cost_gate_findings(costs, d) == []
    subset = {p: s for p, s in costs.items() if p != "decode"}
    stale = cost.cost_gate_findings(subset, d)
    assert codes_of(stale) == ["COST001"]
    diff = cost.update_cost_baselines(subset, d)
    assert "REMOVED" in diff
    assert not os.path.exists(os.path.join(d, "decode.json"))
    assert cost.cost_gate_findings(subset, d) == []


def test_update_preserves_waivers_and_prunes_stale(summaries, tmp_path):
    d = str(tmp_path / "hlo")
    hlo.update_baselines(summaries, d)
    # hand-add a waiver, then re-update: the waiver survives
    path = os.path.join(d, "decode.json")
    doc = json.load(open(path))
    doc["suppress"] = {"HLO003": "tracked upstream XLA churn"}
    json.dump(doc, open(path, "w"))
    hlo.update_baselines(summaries, d)
    assert json.load(open(path))["suppress"] == \
        {"HLO003": "tracked upstream XLA churn"}
    # a program that stops being lowered loses its baseline, loudly
    subset = {p: s for p, s in summaries.items() if p != "decode"}
    diff = hlo.update_baselines(subset, d)
    assert "REMOVED" in diff
    assert not os.path.exists(path)
    assert hlo.gate_findings(subset, d) == []


def test_baseline_waiver_contract(summaries, tmp_path):
    """A waived metric stays quiet WITH a reason; an empty reason or an
    unknown code is itself a finding (HLO000) — the singalint
    suppression contract, ported to baselines."""
    d = str(tmp_path / "hlo")
    hlo.update_baselines(summaries, d)
    path = os.path.join(d, "decode.json")
    mutated = dict(summaries)
    mutated["decode"] = dict(summaries["decode"], donated_outputs=0)

    doc = json.load(open(path))
    doc["suppress"] = {"HLO005": "arena aliasing unsupported here"}
    json.dump(doc, open(path, "w"))
    assert hlo.gate_findings(mutated, d) == []

    doc["suppress"] = {"HLO005": "   "}
    json.dump(doc, open(path, "w"))
    out = hlo.gate_findings(mutated, d)
    assert codes_of(out) == ["HLO000", "HLO005"]
    assert "no reason" in out[0].message

    doc["suppress"] = {"HLO942": "because"}
    json.dump(doc, open(path, "w"))
    out = hlo.gate_findings(mutated, d)
    assert "HLO000" in codes_of(out) and "HLO005" in codes_of(out)
    assert "HLO942" in out[0].message


def test_cost_baseline_waiver_contract(costs, tmp_path):
    """The SAME waiver contract on the cost family: COST000 hygiene,
    reasons required, unknown codes loud — one shared implementation
    (hlo._baseline_suppressions) so the two families cannot drift."""
    d = str(tmp_path / "cost")
    cost.update_cost_baselines(costs, d)
    path = os.path.join(d, "train_step_dp2.json")
    mutated = dict(costs)
    mutated["train_step_dp2"] = dict(costs["train_step_dp2"],
                                     wire_bytes=0)

    doc = json.load(open(path))
    doc["suppress"] = {"COST005": "wire model tracked upstream"}
    json.dump(doc, open(path, "w"))
    assert cost.cost_gate_findings(mutated, d) == []

    doc["suppress"] = {"COST005": ""}
    json.dump(doc, open(path, "w"))
    out = cost.cost_gate_findings(mutated, d)
    assert codes_of(out) == ["COST000", "COST005"]

    doc["suppress"] = {"COST942": "because"}
    json.dump(doc, open(path, "w"))
    out = cost.cost_gate_findings(mutated, d)
    assert "COST000" in codes_of(out)
    assert "COST942" in out[0].message


# ---------------------------------------------------------------------------
# CLI exit codes + JSON schema (front door, lowering stubbed)
# ---------------------------------------------------------------------------

def test_cli_clean_exit_0_and_json_payload(costs, stub_lowering, capsys):
    assert lint_main(["--hlo"]) == 0
    assert "hlo_audit: clean" in capsys.readouterr().out
    assert lint_main(["--hlo", "--json"]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["version"] == 1 and doc["count"] == 0
    assert doc["findings"] == []
    # the drift-history payload rides the JSON output (bench.py appends
    # it to the record store) — now extended with the cost numerics
    assert doc["hlo"]["programs"] == len(hlo.FLAGSHIP_PROGRAMS)
    assert doc["hlo"]["drifted"] == 0
    for k in ("fusions", "collectives", "while_loops",
              "flops", "hbm_bytes", "peak_bytes", "wire_bytes"):
        assert isinstance(doc["hlo"][k], int) and doc["hlo"][k] >= 0
    assert doc["hlo"]["flops"] == sum(s["flops"] for s in costs.values())
    assert doc["hlo"]["peak_bytes"] == max(s["peak_bytes"]
                                           for s in costs.values())
    assert set(doc["hlo"]["cost_per_program"]) == set(costs)


def test_cli_update_baselines_prints_reviewable_diff(stub_lowering,
                                                     monkeypatch,
                                                     tmp_path, capsys):
    monkeypatch.setattr(hlo, "BASELINE_DIR", str(tmp_path / "hlo"))
    monkeypatch.setattr(cost, "COST_BASELINE_DIR",
                        str(tmp_path / "hlo" / "cost"))
    assert lint_main(["--hlo", "--update-baselines"]) == 0
    out = capsys.readouterr().out
    assert "NEW baseline" in out and "NEW cost baseline" in out
    assert "baselines updated" in out
    assert lint_main(["--hlo"]) == 0


# ---------------------------------------------------------------------------
# the tools/hlo_audit.py shim (deprecated standalone CLI)
# ---------------------------------------------------------------------------

def test_hlo_audit_shim_forwards_and_points_at_front_door(monkeypatch,
                                                          capsys):
    """ISSUE-9 satellite: the shim forwards --update-baselines/--json
    and the exit code through to hlo_main unchanged, and prints the
    one-line deprecation pointer to `python -m tools.lint --hlo`."""
    from tools import hlo_audit as shim
    seen = []

    def fake_hlo_main(update=False, json_out=False, **kw):
        seen.append((update, json_out))
        return 7

    monkeypatch.setattr(shim, "hlo_main", fake_hlo_main)
    assert shim.main(["--update-baselines"]) == 7
    assert shim.main(["--json"]) == 7
    assert seen == [(True, False), (False, True)]
    assert "tools.lint --hlo" in capsys.readouterr().err


# ---------------------------------------------------------------------------
# the hlo_audit record kind (drift + cost history in runs/records.jsonl)
# ---------------------------------------------------------------------------

def test_hlo_audit_record_schema_roundtrip(summaries, costs, tmp_path):
    """An hlo_audit store entry with the EXTENDED cost numerics
    validates end-to-end (the record_check CI contract); one missing a
    cost field is named-field rejected — zeros cannot silently stand in
    for measurements."""
    from singa_tpu.obs import record as obs_record
    from singa_tpu.obs import schema

    payload = hlo.audit_payload(summaries, [], costs)
    assert payload["programs"] == len(summaries)
    assert payload["flops"] > 0 and payload["hbm_bytes"] > 0
    assert payload["peak_bytes"] > 0 and payload["wire_bytes"] > 0
    store = obs_record.RunRecord(str(tmp_path / "records.jsonl"))
    entry = obs_record.new_entry("hlo_audit", "cpu", True, "cpu",
                                 payload=payload)
    store.append(entry)
    assert store.validate() == []
    # a payload built WITHOUT the cost pass omits the cost fields and
    # is rejected — it cannot masquerade as a full audit record
    bare = dict(entry)
    bare["payload"] = hlo.audit_payload(summaries, [])
    with pytest.raises(schema.SchemaError,
                       match="flops|hbm_bytes|peak_bytes|wire_bytes"):
        schema.validate_entry(bare)
    bad = dict(entry)
    bad["payload"] = {"programs": 4}
    with pytest.raises(schema.SchemaError, match="drifted|fusions"):
        schema.validate_entry(bad)


# ---------------------------------------------------------------------------
# the cost parser itself (pure text — no lowering)
# ---------------------------------------------------------------------------

class TestCostParser:
    def test_shape_bytes(self):
        assert cost.shape_bytes("f32[2,16]{1,0}") == 2 * 16 * 4
        assert cost.shape_bytes("bf16[8]") == 16
        assert cost.shape_bytes("s32[]") == 4
        assert cost.shape_bytes(
            "(s32[], f32[30,256]{1,0}, pred[4]{0})") == 4 + 30*256*4 + 4

    def test_dot_flops_and_trip_weighting(self):
        text = """HloModule m, is_scheduled=true

%body (p: (s32[], f32[8,16])) -> (s32[], f32[8,16]) {
  %p = (s32[], f32[8,16]{1,0}) parameter(0)
  %g0 = s32[] get-tuple-element((s32[], f32[8,16]{1,0}) %p), index=0
  %g1 = f32[8,16]{1,0} get-tuple-element((s32[], f32[8,16]{1,0}) %p), index=1
  %w = f32[16,16]{1,0} constant({...})
  %d = f32[8,16]{1,0} dot(f32[8,16]{1,0} %g1, f32[16,16]{1,0} %w), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  ROOT %t = (s32[], f32[8,16]{1,0}) tuple(s32[] %g0, f32[8,16]{1,0} %d)
}

%cond (p: (s32[], f32[8,16])) -> pred[] {
  %p = (s32[], f32[8,16]{1,0}) parameter(0)
  %g0 = s32[] get-tuple-element((s32[], f32[8,16]{1,0}) %p), index=0
  %c = s32[] constant(4)
  ROOT %lt = pred[] compare(s32[] %g0, s32[] %c), direction=LT
}

ENTRY %main (a: f32[8,16]) -> (s32[], f32[8,16]) {
  %a = f32[8,16]{1,0} parameter(0)
  %z = s32[] constant(0)
  %t0 = (s32[], f32[8,16]{1,0}) tuple(s32[] %z, f32[8,16]{1,0} %a)
  ROOT %w = (s32[], f32[8,16]{1,0}) while((s32[], f32[8,16]{1,0}) %t0), condition=%cond, body=%body, backend_config={"known_trip_count":{"n":"4"}}
}
"""
        s = cost.summarize_cost(text, "t")
        # one (8,16)x(16,16) dot = 2*8*16*16 flops, x4 trips
        assert s["flops"] == 4 * 2 * 8 * 16 * 16

    def test_wire_factor_needs_real_group(self):
        text = """HloModule m, is_scheduled=true

ENTRY %main (a: f32[64]) -> f32[64] {
  %a = f32[64]{0} parameter(0)
  ROOT %ar = f32[64]{0} all-reduce(f32[64]{0} %a), channel_id=1, replica_groups={{0,1,2,3}}, to_apply=%add
}
"""
        s = cost.summarize_cost(text, "t")
        # ring all-reduce over P=4: 2*(4-1)/4 * 256 B
        assert s["wire_bytes"] == int(round(1.5 * 256))

    def test_unknown_dtype_counts_nothing(self):
        assert cost.shape_bytes("mystery[4,4]") == 0


# ---------------------------------------------------------------------------
# the shared jit-cache helper (no jax)
# ---------------------------------------------------------------------------

class _FakeJitted:
    def __init__(self, n):
        self._n = n

    def _cache_size(self):
        return self._n


class _FakeEngine:
    def __init__(self, counts):
        self._c = counts

    def compiled_counts(self):
        return self._c


class TestAssertProgramCount:
    def test_engine_form(self):
        hlo.assert_program_count(_FakeEngine((1, 1)), (1, 1))
        with pytest.raises(AssertionError, match="no-recompile"):
            hlo.assert_program_count(_FakeEngine((1, 2)), (1, 1))

    def test_function_forms(self):
        hlo.assert_program_count(_FakeJitted(1), 1)
        hlo.assert_program_count([_FakeJitted(1), _FakeJitted(2)], (1, 2))
        with pytest.raises(AssertionError, match="expected \\(1, 1\\)"):
            hlo.assert_program_count([_FakeJitted(1), _FakeJitted(2)],
                                     (1, 1))
