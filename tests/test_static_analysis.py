"""Tier-1 gate + regression suite for the framework lint
(``paddle_tpu.analysis`` / ``tools/analyze.py``).

Three layers:

* **fixture corpus** (``tests/fixtures/analysis/``) — every rule must flag
  its known-bad fixture and stay silent on the known-good twin;
* **the gate** — the full suite over the live package must report zero
  non-baseline findings in under 10 seconds, with no stale baseline
  entries and a real one-line justification on every entry;
* **regressions** for the real findings this lint surfaced and fixed:
  the ``RoutedRequest._attach`` state race, the undeclared
  ``FLAGS_selected_devices``, the four dead flags, and the documented
  GIL-atomic bump pattern.
"""
import json
import os
import subprocess
import sys
import threading

import numpy as np
import pytest

from paddle_tpu import analysis
from paddle_tpu.analysis.common import SourceFile, load_baseline

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURES = os.path.join("tests", "fixtures", "analysis")
BASELINE = os.path.join(REPO, "tools", "analysis_baseline.json")


def _fixture_corpus(*names, support=()):
    """Fixture files with relpaths faked into the analyzed tree (the
    corpus default excludes tests/), plus real support modules the
    registry analyzer resolves against."""
    corpus = []
    for name in names:
        path = os.path.join(REPO, FIXTURES, name + ".py")
        with open(path, "r", encoding="utf-8") as f:
            corpus.append(SourceFile(
                path, f"paddle_tpu/serving/_fixture_{name}.py", f.read()))
    for rel in support:
        with open(os.path.join(REPO, rel), "r", encoding="utf-8") as f:
            corpus.append(SourceFile(rel, rel, f.read()))
    return corpus


def _rules(corpus, full_corpus=False):
    report = analysis.run_analysis(corpus=corpus, root=REPO,
                                   full_corpus=full_corpus)
    return [f.rule for f in report.findings]


# ----------------------------------------------------------- fixture corpus

FIXTURE_CASES = [
    ("unguarded-mutation", "concurrency_unguarded", ()),
    ("lock-order-cycle", "concurrency_lock_order", ()),
    ("blocking-call-in-lock", "concurrency_blocking", ()),
    ("traced-branch", "compiled_traced_branch", ()),
    ("traced-cast", "compiled_traced_cast", ()),
    ("mutable-global-capture", "compiled_mutable_global", ()),
    ("shape-from-data", "compiled_shape_from_data", ()),
    ("use-after-donate", "compiled_donation", ()),
    # the PR 10 speculative verify-k shape: donated-pool rollback and
    # traced acceptance branching (serving/spec_decode.py's two hazards)
    ("use-after-donate", "compiled_spec_verify", ()),
    ("traced-branch", "compiled_spec_verify", ()),
    # the quantized-serving dequant shape: host-cast scale and
    # data-dependent quantization support (quantization.quantize_kv /
    # cache_views.scatter_rows must stay all-array math)
    ("traced-cast", "compiled_quant", ()),
    ("shape-from-data", "compiled_quant", ()),
    # the ISSUE 12 per-slot sampling shape: traced branch on a per-slot
    # top-k and data-dependent constraint-mask indexing
    # (serving.sampling.sample_tokens must stay all-array math)
    ("traced-branch", "compiled_sampling", ()),
    ("shape-from-data", "compiled_sampling", ()),
    # the ISSUE 13 paged-kernel dispatch shape: data-dependent workload
    # from a block table's contents and a traced branch on the filled
    # block count (ops.paged_attention / engine views must key on the
    # table's static shape only)
    ("shape-from-data", "compiled_paged", ()),
    ("traced-branch", "compiled_paged", ()),
    # the ISSUE 14 mesh shape: a Python branch on a per-device traced
    # value (lax.axis_index — the mesh-aware tracedness extension) and a
    # mesh-committed pool donated into the sharded step then read again
    # (the donation rule over NamedSharding-placed buffers)
    ("traced-branch", "compiled_mesh", ()),
    ("use-after-donate", "compiled_mesh", ()),
    # the ISSUE 15 tiered-restore shape: a traced branch on tier
    # residency and a host np.asarray of the donated pool inside the
    # restore program (engine._get_restore must keep residency host-side
    # and the scatter all-array)
    ("traced-branch", "compiled_tiered", ()),
    ("traced-cast", "compiled_tiered", ()),
    # the ISSUE 16 SPMD-kernel shape: the model-axis degree recovered as
    # a traced per-device value (lax.psum of 1), host-cast into a
    # per-shard head count and Python-branched on (headwise_shard_map
    # must read the STATIC mesh shape / local q.shape instead)
    ("traced-cast", "compiled_spmd_kernel", ()),
    ("traced-branch", "compiled_spmd_kernel", ()),
    ("undefined-flag", "registry_flags",
     ("paddle_tpu/core/flags.py",)),
    ("unknown-metric-key", "registry_metrics",
     ("paddle_tpu/serving/metrics.py",
      "paddle_tpu/serving/telemetry.py")),
    # the ISSUE 17 observability shape: telemetry from INSIDE a compiled
    # region — a trace-time-baked clock read smuggled out through a
    # float() cast of a traced value (timestamps + histogram records
    # belong AROUND the dispatch; docs/observability.md overhead policy)
    ("traced-cast", "compiled_telemetry",
     ("paddle_tpu/serving/telemetry.py",)),
    # the ISSUE 18 process-worker shapes: (a) poll-RPC serialization from
    # inside the compiled decode step — the token tail int()-cast under
    # trace instead of materialized around the dispatch; (b) the
    # WorkerHandle pending-RPC table registered under the handle lock but
    # popped lock-free in the reader loop (a strand-the-caller race)
    ("traced-cast", "compiled_worker", ()),
    ("unguarded-mutation", "concurrency_worker", ()),
    # the ISSUE 19 disagg shapes: (a) restore-ahead prefetch deciding
    # published-chain residency INSIDE the compiled restore — a traced
    # branch on the residency mask plus a host int() of the traced chain
    # length (the planner's radix walk is host-side; the restore must
    # stay the one shared scatter); (b) the handoff claim-and-flip done
    # lock-free while the pump/watchdog movers race on the same FINISH
    ("traced-branch", "compiled_disagg", ()),
    ("traced-cast", "compiled_disagg", ()),
    ("unguarded-mutation", "concurrency_disagg", ()),
    # the ISSUE 20 crash-safe-gateway shapes: (a) WAL record serialization
    # from inside the compiled decode step — the token delta int()-cast
    # under trace instead of materialized once per commit batch around
    # the dispatch; (b) the per-stream journal high-water mark advanced
    # lock-free while the finalizer's terminal sweep reads it under the
    # stream lock (a journal-the-same-token-twice race)
    ("traced-cast", "compiled_wal", ()),
    ("unguarded-mutation", "concurrency_wal", ()),
    ("broad-except", "hygiene_broad_except", ()),
]


@pytest.mark.parametrize("rule,stem,support",
                         FIXTURE_CASES, ids=[c[0] for c in FIXTURE_CASES])
def test_rule_flags_bad_fixture(rule, stem, support):
    rules = _rules(_fixture_corpus(stem + "_bad", support=support))
    assert rule in rules, f"{rule} missed its known-bad fixture: {rules}"


@pytest.mark.parametrize("rule,stem,support",
                         FIXTURE_CASES, ids=[c[0] for c in FIXTURE_CASES])
def test_rule_passes_good_fixture(rule, stem, support):
    rules = _rules(_fixture_corpus(stem + "_good", support=support))
    assert rule not in rules, \
        f"{rule} false-positived on its known-good twin"


def test_bad_fixtures_are_specific():
    """A bad fixture must trip (at least) its own rule, not collateral
    noise from unrelated analyzers — one seeded defect class per file."""
    for rule, stem, support in FIXTURE_CASES:
        rules = set(_rules(_fixture_corpus(stem + "_bad", support=support)))
        allowed = {rule}
        if stem.startswith("compiled_traced"):
            # casts and branches legitimately co-occur in trace hazards
            allowed |= {"traced-branch", "traced-cast"}
        if stem == "compiled_spec_verify":
            # this fixture deliberately seeds BOTH spec-decode hazards:
            # donated-pool rollback + traced acceptance branching
            allowed |= {"use-after-donate", "traced-branch"}
        if stem == "compiled_quant":
            # deliberately seeds BOTH dequant hazards: host-cast scale +
            # data-dependent support
            allowed |= {"traced-cast", "shape-from-data"}
        if stem == "compiled_sampling":
            # deliberately seeds BOTH sampling hazards: traced top-k
            # branch + data-dependent mask shape
            allowed |= {"traced-branch", "shape-from-data"}
        if stem == "compiled_paged":
            # deliberately seeds BOTH paged-dispatch hazards: table-
            # content shape + traced block-count branch (the int() cast
            # feeding it legitimately co-fires traced-cast)
            allowed |= {"shape-from-data", "traced-branch", "traced-cast"}
        if stem == "compiled_mesh":
            # deliberately seeds BOTH mesh hazards: per-device traced
            # branch + donated sharded pool read-back
            allowed |= {"traced-branch", "use-after-donate"}
        if stem == "compiled_tiered":
            # deliberately seeds BOTH restore hazards: traced residency
            # branch + host np.asarray of the donated pool
            allowed |= {"traced-branch", "traced-cast"}
        if stem == "compiled_spmd_kernel":
            # deliberately seeds BOTH SPMD-kernel hazards: host-cast of
            # the traced axis degree + the head-count branch it feeds
            allowed |= {"traced-cast", "traced-branch"}
        if stem == "compiled_disagg":
            # deliberately seeds BOTH prefetch-restore hazards: traced
            # residency branch + host int() of the traced chain length
            allowed |= {"traced-branch", "traced-cast"}
        assert rules <= allowed, (stem, rules)


def test_dead_flag_detection_synthetic():
    """dead-flag needs a full corpus view; prove it on a synthetic
    registry: one flag read by a user module, one zombie."""
    flags_src = (
        "def define_flag(name, default, doc=''):\n    pass\n"
        "define_flag('live_flag', 1, 'read below')\n"
        "define_flag('zombie_flag', 1, 'read by nothing')\n")
    user_src = ("from paddle_tpu.core import flags\n"
                "x = flags.flag('live_flag')\n")
    corpus = [
        SourceFile("<mem>", "paddle_tpu/core/flags.py", flags_src),
        SourceFile("<mem>", "paddle_tpu/user.py", user_src),
    ]
    report = analysis.run_analysis(corpus=corpus, root=REPO,
                                   full_corpus=True)
    dead = [f for f in report.findings if f.rule == "dead-flag"]
    assert len(dead) == 1 and "zombie_flag" in dead[0].message


def test_suppression_requires_reason():
    src = ("def f(x):\n"
           "    try:\n"
           "        return x()\n"
           "    except Exception:  # analysis: allow(broad-except)\n"
           "        return None\n")
    corpus = [SourceFile("<mem>", "paddle_tpu/serving/_r.py", src)]
    report = analysis.run_analysis(corpus=corpus, root=REPO,
                                   full_corpus=False)
    rules = [f.rule for f in report.findings]
    assert "suppression-missing-reason" in rules
    assert "broad-except" not in rules  # suppressed, but flagged as bare


# ------------------------------------------------------------------ the gate

@pytest.fixture(scope="module")
def gate_report():
    return analysis.run_analysis(root=REPO)


def test_gate_zero_nonbaseline_findings(gate_report):
    baseline = load_baseline(BASELINE)
    new, stale = gate_report.apply_baseline(baseline)
    assert not new, "non-baseline findings:\n" + "\n".join(
        str(f) for f in new)
    assert not stale, (
        "stale baseline entries (match nothing — remove them):\n"
        + "\n".join(f"[{e.rule}] {e.path} :: {e.scope}" for e in stale))


def test_gate_no_parse_errors(gate_report):
    assert not gate_report.parse_errors


def test_gate_fast_enough(gate_report):
    # the whole point of a tier-1 gate: the full suite stays cheap
    assert gate_report.elapsed < 10.0, gate_report.elapsed


def test_baseline_entries_all_justified():
    with open(BASELINE, "r", encoding="utf-8") as f:
        data = json.load(f)
    assert data.get("entries"), "baseline should exist (may be empty list)"
    for e in data["entries"]:
        why = e.get("why", "")
        assert why and "TODO" not in why, (
            f"baseline entry [{e['rule']}] {e['path']} :: {e['scope']} "
            f"has no real justification")


def test_inline_suppressions_all_carry_reasons(gate_report):
    # every suppression that fired carried a reason (the ones that did
    # not would have surfaced as suppression-missing-reason findings)
    assert all(f.rule != "suppression-missing-reason"
               for f in gate_report.findings)
    assert gate_report.suppressed, "expected inline allow()s in the tree"


def test_cli_gate_subprocess():
    """tools/analyze.py runs standalone (no jax import) and exits 0."""
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "analyze.py")],
        capture_output=True, text=True, timeout=120, cwd=REPO)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "0 finding(s)" in out.stdout


def test_cli_update_baseline_refuses_subset_runs():
    """Rewriting the baseline from a subset view would silently delete
    every entry for files outside the scanned corpus (with their
    hand-written justifications) — the CLI must refuse."""
    before = open(BASELINE, "rb").read()
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "analyze.py"),
         "paddle_tpu/serving", "--update-baseline"],
        capture_output=True, text=True, timeout=120, cwd=REPO)
    assert out.returncode == 2, out.stdout + out.stderr
    assert "requires a full run" in out.stderr
    assert open(BASELINE, "rb").read() == before


def test_cli_rule_filter_and_json():
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "analyze.py"),
         "--rules", "undefined-flag", "--json", "paddle_tpu/core",
         "paddle_tpu/distributed"],
        capture_output=True, text=True, timeout=120, cwd=REPO)
    assert out.returncode == 0, out.stdout + out.stderr
    payload = json.loads(out.stdout)
    assert payload["findings"] == []


# ---------------------------------------------- regressions (real findings)

def test_flags_selected_devices_resolves():
    """Real finding: FLAGS_selected_devices was referenced by the
    launcher/ParallelEnv with no define_flag declaration."""
    from paddle_tpu.core import flags
    assert flags.get_flags("FLAGS_selected_devices") is not None
    assert "selected_devices" in flags.all_flags()


def test_dead_flags_deleted():
    """Real finding: four flags nothing read. They must stay gone (the
    dead-flag rule keeps them from coming back silently)."""
    from paddle_tpu.core import flags
    for name in ("benchmark", "tracer_mkldnn_ops_on",
                 "allocator_strategy", "use_stream_safe_allocator"):
        with pytest.raises(KeyError):
            flags.get_flags(name)


def test_registry_lint_proves_all_flags_resolve(gate_report):
    assert not any(f.rule in ("undefined-flag", "dead-flag")
                   for f in gate_report.findings)


def test_attach_never_resurrects_finalized_request():
    """Real finding (unguarded-mutation): RoutedRequest._attach mutated
    ``state`` outside the lock — a _finalize racing between its check and
    its set was overwritten back to RUNNING. The transition now happens
    under the lock; a finalized handle must stay terminal through a late
    _attach (the exact submit-vs-cancel interleaving of the race)."""
    from paddle_tpu.serving.gateway.router import RoutedRequest
    from paddle_tpu.serving.scheduler import Request, RequestState
    from paddle_tpu.core import resilience

    class _Rep:
        idx, generation = 0, 0

    rr = RoutedRequest(pool=None, prompt=np.array([1, 2], np.int32),
                       max_new_tokens=4, stop_token_id=None,
                       tenant="t", priority=0,
                       deadline=resilience.Deadline.after(None),
                       request_id="race")
    backend = Request(np.array([1, 2], np.int32))
    rr._finalize(RequestState.CANCELLED)
    rr._attach(backend, _Rep(), 0)
    assert rr.state == RequestState.CANCELLED
    assert rr.finished and rr.done_event.is_set()


def test_concurrency_lint_clean_on_router_and_metrics(gate_report):
    """Regression for the fixed/triaged unguarded-mutation findings: the
    router and the metrics modules stay clean (reintroducing the _attach
    pattern or an unannotated helper mutation fails here)."""
    assert not any(
        f.rule == "unguarded-mutation"
        and ("serving/gateway" in f.path or "serving/metrics" in f.path)
        for f in gate_report.findings)


def test_gil_atomic_bump_is_allowed_pattern():
    """The documented GIL-atomic single-key bump (metrics.bump /
    resilience.bump / compile_cache.bump) is an allowed pattern, not a
    finding — asserted against the real modules."""
    report = analysis.run_analysis(
        ["paddle_tpu/serving/metrics.py", "paddle_tpu/core/resilience.py",
         "paddle_tpu/core/compile_cache.py"],
        root=REPO, full_corpus=False)
    assert not any(f.rule == "unguarded-mutation"
                   for f in report.findings), report.findings


def test_documented_namespaces_cover_runtime_keys():
    """The namespace registries match what the modules actually emit."""
    from paddle_tpu.serving import metrics
    from paddle_tpu.core import resilience
    metrics.bump("requests.finished", 0)
    for key in metrics.stats():
        ns = key.split(".", 1)[0]
        assert ns in metrics.DOCUMENTED_NAMESPACES, key
    resilience.bump("retry.retries", 0)
    for key in resilience.stats():
        ns = key.split(".", 1)[0]
        assert ns in resilience.DOCUMENTED_NAMESPACES, key
