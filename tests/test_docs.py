"""Docs stay true: README code snippets must compile and their repro
imports must resolve, and every ``repro.*`` dotted name the docs mention
must point at something that actually exists.  Cheap to run, so it lives
in the fast lane — a rename that orphans the docs fails CI, not a reader.
"""
from __future__ import annotations

import importlib
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
README = os.path.join(REPO, "README.md")
ARCH = os.path.join(REPO, "docs", "architecture.md")
WIRE = os.path.join(REPO, "docs", "wire_format.md")
OPS = os.path.join(REPO, "docs", "operations.md")

FENCE = re.compile(r"```python\n(.*?)```", re.DOTALL)
DOTTED = re.compile(r"\brepro(?:\.[A-Za-z_][A-Za-z0-9_]*)+")


def _read(path: str) -> str:
    with open(path, encoding="utf-8") as f:
        return f.read()


def _python_blocks(path: str) -> list[str]:
    return FENCE.findall(_read(path))


def _resolves(dotted: str) -> bool:
    """True iff ``dotted`` is an importable module, or an attribute
    (class/function) reachable from its longest importable prefix."""
    parts = dotted.split(".")
    for split in range(len(parts), 0, -1):
        mod_name = ".".join(parts[:split])
        try:
            obj = importlib.import_module(mod_name)
        except ImportError:
            continue
        for attr in parts[split:]:
            if not hasattr(obj, attr):
                return False
            obj = getattr(obj, attr)
        return True
    return False


class TestReadme:
    def test_exists_with_core_sections(self):
        text = _read(README)
        for needle in ("Quickstart", "Backend matrix", "tier-1",
                       "BENCH_baseline.json", "Re-baselining"):
            assert needle in text, f"README lost its {needle!r} section"

    def test_python_snippets_compile(self):
        blocks = _python_blocks(README)
        assert blocks, "README has no python snippets to check"
        for i, block in enumerate(blocks):
            compile(block, f"README.md[block {i}]", "exec")

    def test_snippet_imports_execute(self):
        """Every import line in README python blocks must actually import
        (the snippet API surface exists)."""
        lines = [
            ln for block in _python_blocks(README)
            for ln in block.splitlines()
            if re.match(r"\s*(import repro|from repro[\w.]* import)", ln)
        ]
        assert lines, "README snippets import nothing from repro?"
        ns: dict = {}
        for ln in lines:
            exec(ln.strip(), ns)  # noqa: S102 — repo-controlled docs text

    def test_dotted_references_resolve(self):
        missing = [d for d in sorted(set(DOTTED.findall(_read(README))))
                   if not _resolves(d)]
        assert not missing, f"README references nonexistent: {missing}"


class TestArchitectureDoc:
    def test_exists_with_cross_reference(self):
        text = _read(ARCH)
        for needle in ("Eq. 5", "Eq. 6", "Eq. 7", "cross-reference",
                       "FleetAggregator", "wire_format.md",
                       "repro.telemetry.transport",
                       # the closed-loop hop: causes don't just get
                       # reported, they feed the guarded policy engine
                       "PolicyEngine", "repro.ft.policy", "Actuator",
                       "guardrail"):
            assert needle in text

    def test_fan_in_tree_hop(self):
        """The tree-topology hop diagram (ISSUE 7): the architecture doc
        must keep the fan-in layer and its two load-bearing invariants."""
        text = _read(ARCH)
        for needle in ("fan-in tree hop", "TreeAggregator",
                       "ForwardedDelta", "BRDF", "AggregatorJournal",
                       'ack="drain"', "Verbatim inner payloads",
                       "Failover = redelivery", "duplicate_drops",
                       "byte-identical to star"):
            assert needle in text, f"architecture.md lost {needle!r}"

    def test_scenario_engine_hop(self):
        """The scenario-engine hop (ISSUE 9): the architecture doc must
        keep the layer that exercises everything above it in concert,
        and its two load-bearing properties."""
        text = _read(ARCH)
        for needle in ("scenario engine hop", "repro.anomaly.scenario",
                       "ScenarioEngine", "SimLink", "SimClock",
                       "SCENARIO_LIBRARY", "carriage",
                       "PYTHONHASHSEED-independent",
                       "rows_sent == rows_ingested + rows_lost_crash",
                       "byte-for-byte", "scenarios` lane"):
            assert needle in text, f"architecture.md lost {needle!r}"

    def test_forecast_hop(self):
        """The forecast hop (ISSUE 10): the architecture doc must keep
        the predictive layer and its load-bearing contracts — recurrent
        serve, honest value gate, candidates outside the dedup
        stream."""
        text = _read(ARCH)
        for needle in ("forecast hop", "repro.core.forecast",
                       "export_episodes", "forecast_ssd",
                       "forecast_step", "predicted_straggler",
                       "pack_sequences(length=1)", "forecast_rule",
                       "scale/forecast_infer_16384", "per-feature",
                       "byte-identical"):
            assert needle in text, f"architecture.md lost {needle!r}"

    def test_dotted_references_resolve(self):
        missing = [d for d in sorted(set(DOTTED.findall(_read(ARCH))))
                   if not _resolves(d)]
        assert not missing, f"architecture.md references nonexistent: {missing}"


class TestWireFormatDoc:
    """docs/wire_format.md is the *normative* spec: the sections a codec
    implementer needs must exist, and every dotted name must resolve."""

    def test_exists_with_normative_sections(self):
        text = _read(WIRE)
        for needle in ("BRD1", "BRD2", "BRD3", "present", "DEFLATE",
                       "XOR", "Changed mask", "boot", "seq",
                       "At-least-once", "WireFormatError",
                       "DATA", "ACK", "trailing bytes"):
            assert needle in text, f"wire_format.md lost {needle!r}"

    def test_both_versions_specified(self):
        text = _read(WIRE)
        assert ("Version 1" in text and "Version 2" in text
                and "Version 3" in text)

    def test_attribution_block_specified(self):
        """The v3 attribution block (ISSUE 8) is normative: an
        implementer must find the auto-select rule, the v2-reader
        compatibility statement, and the encode/decode error posture."""
        text = _read(WIRE)
        for needle in ("attribution block", "causes",
                       "auto-select", "byte-identical",
                       "v2-reader compatibility", "estimated_recovery_s",
                       "cumulative_recovery_s", "ValueError",
                       "repro.core.whatif.WhatIfReplayer"):
            assert needle in text, f"wire_format.md lost {needle!r}"

    def test_forwarded_envelope_specified(self):
        """The BRDF forwarded-delta frame (ISSUE 7) is normative too: an
        implementer must find the magic, header fields, depth cap, and
        the dual-granularity dedup rule here."""
        text = _read(WIRE)
        for needle in ("Forwarded delta envelopes", "BRDF",
                       "ForwardedDelta", "sizes", "MAX_FORWARD_DEPTH",
                       "is_forwarded", "verbatim", "envelope"):
            assert needle in text, f"wire_format.md lost {needle!r}"

    def test_dotted_references_resolve(self):
        missing = [d for d in sorted(set(DOTTED.findall(_read(WIRE))))
                   if not _resolves(d)]
        assert not missing, f"wire_format.md references nonexistent: {missing}"

    def test_cross_referenced(self):
        assert "wire_format.md" in _read(ARCH)
        assert "operations.md" in _read(WIRE)


class TestOperationsDoc:
    def test_exists_with_ops_sections(self):
        text = _read(OPS)
        for needle in ("lease", "dropout", "severity",
                       "Re-baselining is deliberate", "BENCH_current.json",
                       "BENCH_baseline.json", "fleet_demo.py",
                       "--fleet-listen", "--fleet-connect",
                       "at-least-once", "duplicate_drops"):
            assert needle.lower() in text.lower(), (
                f"operations.md lost {needle!r}"
            )

    def test_closed_loop_mitigation_section(self):
        """The mitigation ops guide must keep its three load-bearing
        parts: rule syntax, guardrail tuning, reading the audit log."""
        text = _read(OPS)
        for needle in ("Closed-loop mitigation", "Rule syntax",
                       "Guardrail tuning", "Reading the audit log",
                       "--mitigate", "--mitigate-dry-run", "--policy",
                       "--audit-log", "min_recurrence", "cooldown",
                       "min_fleet", "flap", "rollback", "verify_steps",
                       "suppress", "actuator_noop", "dry-run",
                       "ab_compare", "fault_tolerance_demo.py"):
            assert needle.lower() in text.lower(), (
                f"operations.md lost {needle!r}"
            )

    def test_fan_in_tree_deployment_section(self):
        """The tree deployment guide (ISSUE 7) must keep the parts an
        operator needs: role wiring flags, fanout sizing, journal
        placement, and the adaptive lease formula's knobs."""
        text = _read(OPS)
        for needle in ("Deploying a fan-in tree", "--fleet-role",
                       "--fleet-parent", "--fleet-journal", "fanout",
                       "journal", "Compaction", "effective_lease",
                       "lease_ceiling", "lease_multiplier",
                       "Diagnosis", "TypeError"):
            assert needle in text, f"operations.md lost {needle!r}"

    def test_recovery_ranking_section(self):
        """The what-if attribution ops guide (ISSUE 8): an operator must
        find how causes are priced, how the policy ranks and
        budget-floors by the price, and the honest caveat about
        concurrent stragglers."""
        text = _read(OPS)
        for needle in ("Reading the recovery ranking", "attribution=True",
                       "estimated_recovery_s", "cumulative_recovery_s",
                       "min_recovery_s", "peer mean", "critical path",
                       "last_stage_recovery", "whatif_recovery",
                       "scale/whatif_replay_16384", "exclusive"):
            assert needle in text, f"operations.md lost {needle!r}"

    def test_authoring_a_scenario_section(self):
        """The scenario cookbook (ISSUE 9): an operator must find the
        script format, the incident kinds, the determinism rules, and
        the golden re-pinning workflow."""
        text = _read(OPS)
        for needle in ("Authoring a scenario", "Script format",
                       "Incident", "LinkProfile", "run_scenario",
                       "rack_degrade", "agg_restart", "clock_skew",
                       "restart_after", "ordered=False", "reorder_window",
                       "rows_sent == rows_ingested + rows_lost_crash",
                       "--repin", "--check", "--trace-dir", "--budget",
                       "scenario_<name>.golden",
                       "Re-pinning is deliberate",
                       "scale/scenario_rack_degrade_1024"):
            assert needle in text, f"operations.md lost {needle!r}"

    def test_forecast_driven_mitigation_section(self):
        """The forecast ops guide (ISSUE 10): an operator must find how
        to train on scenario episodes, how to read and bound risk
        alarms, the honest value gate, and the opt-in policy wiring."""
        text = _read(OPS)
        for needle in ("Forecast-driven mitigation", "--forecast",
                       "--forecast-risk", "predicted_straggler",
                       "export_episodes", "risk_threshold", "min_history",
                       "hold_steps", "forecast_rule", "DEFAULT_RULES",
                       "evaluate_forecaster", "lead_time_curve",
                       "score_auc", "byte-identical",
                       "episodes_<name>.golden", "--episodes",
                       "scale/forecast_infer_16384", "forecast_step",
                       "pack_sequences"):
            assert needle in text, f"operations.md lost {needle!r}"

    def test_tracing_the_aggregator_section(self):
        """The tracing guide: how spans switch on, every span name, and
        the counters an operator watches."""
        text = _read(OPS)
        for needle in ("Tracing the aggregator", "repro.spans.enable()",
                       "jax.profiler", "bigroots.ingest", "bigroots.decode",
                       "bigroots.merge", "bigroots.sketch", "bigroots.step",
                       "bigroots.prep", "bigroots.quantiles",
                       "bigroots.gates", "bigroots.emit", "bigroots.whatif",
                       "repro.serve.FleetAggregator.counters",
                       "sketch_rows", "stragglers_packed",
                       "gate_rows_launched", "seen_walked",
                       "pending_forwards"):
            assert needle in text, f"operations.md lost {needle!r}"

    def test_readme_links_here_for_rebaseline(self):
        """The re-baseline workflow moved here; the README must keep a
        pointer instead of a divergent copy."""
        readme = _read(README)
        assert "docs/operations.md" in readme
        assert "Re-baselining" in readme

    def test_dotted_references_resolve(self):
        missing = [d for d in sorted(set(DOTTED.findall(_read(OPS))))
                   if not _resolves(d)]
        assert not missing, f"operations.md references nonexistent: {missing}"


class TestHelpMatchesDocs:
    """The docstring pass: help() on the public API must mention the
    behaviors the docs advertise."""

    @pytest.mark.parametrize("obj_path, needles", [
        ("repro.core.BigRootsAnalyzer", ("backend", "analyze_fleet", "merge")),
        ("repro.core.TraceStore", ("merge", "add_row")),
        ("repro.core.SlidingStageWindow", ("merge", "add_rows", "advance")),
        ("repro.core.TraceStore.merge", ("column", "vocabulary")),
        ("repro.core.SlidingStageWindow.merge", ("watermark", "sketch",
                                                 "byte-identical")),
        ("repro.core.BigRootsAnalyzer.analyze_fleet", ("batched", "backend")),
        ("repro.serve.FleetAggregator", ("StepDelta", "merged", "step",
                                         "lease", "dark")),
        ("repro.serve.TreeAggregator", ("forward", "verbatim", "journal",
                                        "boot", "recover")),
        ("repro.serve.Diagnosis", ("local", "fleet", "forward",
                                   "ServeEngine", "tick")),
        ("repro.serve.AggregatorJournal", ("snapshot", "compact",
                                           "recover", "unacked",
                                           "watermark")),
        ("repro.telemetry.ForwardedDelta", ("BRDF", "envelope", "verbatim",
                                            "re-stamp", "duplicate")),
        ("repro.telemetry.Endpoint", ("tcp", "unix", "shm", "parse",
                                      "listen", "connect")),
        ("repro.telemetry.StepDelta", ("wire", "stage")),
        ("repro.telemetry.StepTelemetry.drain_delta", ("present", "drain")),
        ("repro.telemetry.StepDelta.to_bytes", ("version", "deflate",
                                                "stateless")),
        ("repro.telemetry.StepDelta.from_bytes", ("truncated",
                                                  "WireFormatError")),
        ("repro.telemetry.DeltaClient", ("resend", "ack", "reconnect",
                                         "bounded")),
        ("repro.telemetry.DeltaServer", ("ack", "drain", "thread")),
        ("repro.telemetry.ShmRing", ("producer", "consumer", "cursor")),
        ("repro.ft.PolicyEngine", ("guardrail", "dry_run", "actuator",
                                   "audit")),
        ("repro.ft.policy", ("cooldown", "rate limit", "flap",
                             "rollback", "audit log", "dry_run")),
        ("repro.ft.Rule", ("scope", "recurrence", "target")),
        ("repro.ft.Actuator", ("apply", "rollback", "actuator_noop")),
        ("repro.ft.GuardrailConfig", ("tuning",)),
        ("repro.core.WhatIfReplayer", ("counterfactual", "critical-path",
                                       "attribution=None", "stages()")),
        ("repro.core.Attribution", ("peer mean", "critical-path",
                                    "estimated_recovery_s",
                                    "throughput_delta")),
        ("repro.anomaly.loop.whatif_recovery", ("joint", "ab_compare",
                                                "prediction")),
        ("repro.ft.supervisor", ("backoff", "jitter", "healthy")),
        ("repro.anomaly.ClosedLoopSim", ("stage", "policy", "cordoned")),
        ("repro.anomaly.loop", ("ab_compare", "step (stage) time",
                                "dry_run")),
        ("repro.anomaly.scenario", ("discrete-event", "byte-identical",
                                    "golden", "carriage", "scenarios")),
        ("repro.anomaly.ScenarioEngine", ("determinism", "seeded",
                                          "PYTHONHASHSEED", "injected")),
        ("repro.anomaly.scenario.SimLink", ("at-least-once", "resend",
                                            "socket-vs-sim")),
        ("repro.anomaly.scenario.LinkProfile", ("ordered", "loss",
                                                "reorder_window")),
        ("repro.core.Forecaster", ("recurrence", "predicted_straggler",
                                   "risk_threshold", "hold", "frozen",
                                   "min_history")),
        ("repro.core.forecast", ("candidates", "byte", "roc",
                                 "lead_time_curve")),
        ("repro.core.lead_time_curve", ("precision", "median", "earliest")),
        ("repro.anomaly.scenario.export_episodes", ("label", "horizon",
                                                    "byte", "gate space")),
        ("repro.models.forecast_ssd", ("exact-rounding", "byte-identical",
                                       "fixed op order", "allclose")),
        ("repro.models.forecast_ssd.forecast_step", ("recurrence",
                                                     "h = 0",
                                                     "freeze")),
        ("repro.ft.forecast_rule", ("opt-in", "DEFAULT_RULES",
                                    "predicted_straggler")),
    ])
    def test_docstring_covers(self, obj_path, needles):
        parts = obj_path.split(".")
        obj = importlib.import_module(".".join(parts[:2]))
        for attr in parts[2:]:
            obj = getattr(obj, attr)
        doc = (obj.__doc__ or "").lower()
        for needle in needles:
            assert needle.lower() in doc, (
                f"help({obj_path}) no longer mentions {needle!r}"
            )
