"""raydp-lint framework tests: each checker catches its seeded-violation
fixture and stays clean on the fixed version; suppression syntax and the CLI
exit-code contract hold; and the repo itself passes the gate CI enforces."""

import json
import os
import subprocess
import sys

import pytest

from tools.analyze.core import load_project, render_report, run_rules
from tools.analyze.rules import ALL_RULES, rules_by_name

FIXTURES = os.path.join(os.path.dirname(__file__), "analyze_fixtures")
REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_rule(rule_name, *files):
    project = load_project([os.path.join(FIXTURES, f) for f in files])
    findings = run_rules(project, [rules_by_name()[rule_name]()])
    return [f for f in findings if not f.suppressed and f.rule == rule_name]


# ---------------------------------------------------------------------------
# per-rule: seeded fixture caught, fixed fixture clean
# ---------------------------------------------------------------------------


def test_donation_aliasing_catches_seed():
    found = run_rule("donation-aliasing", "donation_bad.py")
    assert len(found) >= 2  # params AND opt_state reach the donated jit
    assert all("externally-owned" in f.message for f in found)
    assert any("_restore_checkpoint" in f.message for f in found)


def test_donation_aliasing_clean_on_fixed():
    assert run_rule("donation-aliasing", "donation_good.py") == []


def test_rpc_protocol_catches_seed():
    found = run_rule("rpc-protocol", "rpc_bad.py")
    messages = "\n".join(f.message for f in found)
    assert "unknown op 'object_pvt'" in messages
    assert "arity mismatch for op 'object_put'" in messages
    assert "dead handler MiniServer.handle_never_called" in messages
    # two distinct arity mistakes: unexpected kwarg and missing required
    assert sum("arity mismatch" in f.message for f in found) == 2


def test_rpc_protocol_clean_on_fixed():
    assert run_rule("rpc-protocol", "rpc_good.py") == []


def test_rpc_protocol_actor_plane_catches_seed():
    """The actor-dispatch half of the rule: ``handle.<m>.remote(...)`` call
    sites (incl. through ``.options(...)``) are checked against the
    project-wide method inventory — covers run_plan/run_tasks/run_shuffle
    and the SPMD worker ops."""
    found = run_rule("rpc-protocol", "actor_bad.py")
    messages = "\n".join(f.message for f in found)
    assert "unknown actor method 'run_plann'" in messages
    assert sum("actor arity mismatch" in f.message for f in found) == 2


def test_rpc_protocol_actor_plane_clean_on_fixed():
    assert run_rule("rpc-protocol", "actor_good.py") == []


def test_swallowed_exceptions_catches_seed():
    found = run_rule("swallowed-exceptions", "swallowed_bad.py")
    assert len(found) == 2  # the pass handler and the continue handler


def test_swallowed_exceptions_clean_on_fixed():
    assert run_rule("swallowed-exceptions", "swallowed_good.py") == []


def test_guarded_by_catches_seed():
    found = run_rule("guarded-by", "guarded_bad.py")
    lines = sorted(f.line for f in found)
    # the off-lock attr read, the closure read, and the off-lock global
    # read from a class with no guarded attrs of its own
    assert len(found) == 3
    assert sum("self._lock" in f.message for f in found) == 2
    assert sum("_cache_lock" in f.message for f in found) == 1
    # the with-guarded accesses on other lines are NOT flagged
    src = open(os.path.join(FIXTURES, "guarded_bad.py")).read().splitlines()
    for line in lines:
        assert "BUG" in src[line - 1]


def test_guarded_by_clean_on_fixed():
    assert run_rule("guarded-by", "guarded_good.py") == []


def test_lock_order_catches_seed():
    found = run_rule("lock-order", "lockorder_bad.py")
    assert len(found) == 2
    messages = "\n".join(f.message for f in found)
    # the Condition alias (Registry.cond wraps Registry.lock) must collapse
    # to ONE lock node, so the flush() path inverts against ingest()
    assert "Registry.lock" in messages and "_flush_lock" in messages
    # the guarded-by-held interprocedural edge supplies one direction of the
    # Pool inversion
    assert "Pool._slots_lock" in messages
    assert "guarded-by annotation" in messages
    # both acquisition paths are in the finding
    assert all("->" in f.message and " at " in f.message for f in found)


def test_lock_order_clean_on_fixed():
    assert run_rule("lock-order", "lockorder_good.py") == []


def test_blocking_under_lock_catches_seed():
    found = run_rule("blocking-under-lock", "blocking_bad.py")
    messages = "\n".join(f.message for f in found)
    assert len(found) == 7
    for marker in (
        "control-plane RPC 'rpc(...)'",
        "'time.sleep(...)'",
        "unbounded '.wait()'",
        "future '.result(...)'",
        "jax 'block_until_ready(...)'",
        "subprocess '.communicate(...)'",
        "'subprocess.run(...)'",
    ):
        assert marker in messages, marker
    # every finding names the held lock and where it was acquired
    assert all("while holding" in f.message for f in found)


def test_blocking_under_lock_clean_on_fixed():
    assert run_rule("blocking-under-lock", "blocking_good.py") == []


def test_print_diagnostics_catches_seed():
    found = run_rule("print-diagnostics", "print_bad.py")
    kinds = "\n".join(f.message for f in found)
    assert len(found) == 2
    assert "print()" in kinds and "print_exc" in kinds


def test_metric_registry_catches_seed():
    """Read-without-writer: the reporter reads `etlfx.rows_ingest` but the
    instrumentation site says `etlfx.rows_ingested`."""
    found = run_rule("metric-registry", "metricreg_bad.py")
    assert len(found) == 1
    assert "etlfx.rows_ingest" in found[0].message
    assert "nobody writes" in found[0].message


def test_metric_registry_clean_on_fixed():
    """Dynamic `tenant.<ns>.` reads and `.p99` fan-out reads resolve to
    their writers — no false positives on the fixed fixture."""
    assert run_rule("metric-registry", "metricreg_good.py") == []


def test_conf_registry_catches_seed():
    found = run_rule("conf-registry", "confreg_bad.py")
    assert len(found) == 1
    assert "etlfx.window_rows" in found[0].message
    assert "no explicit default" in found[0].message


def test_conf_registry_clean_on_fixed():
    """One declaring site is enough — the second bare read of the same key
    is not flagged."""
    assert run_rule("conf-registry", "confreg_good.py") == []


def test_env_registry_catches_seed():
    """env-registry runs only on full-surface sweeps (package + the tools'
    reader side in scope): the fixture's undocumented RAYDP_TPU_ETLFX_FIXTURE_FLAG read is
    the single finding against the real docs tree."""
    from tools.analyze.__main__ import config_excludes

    project = load_project(
        [
            os.path.join(REPO_ROOT, "raydp_tpu"),
            os.path.join(REPO_ROOT, "tools", "trace_analyze.py"),
            os.path.join(REPO_ROOT, "tests", "conftest.py"),
            os.path.join(FIXTURES, "envreg_bad.py"),
        ],
        root=REPO_ROOT,
        exclude=config_excludes(REPO_ROOT),
    )
    findings = run_rules(project, [rules_by_name()["env-registry"]()])
    active = [f for f in findings if not f.suppressed]
    assert len(active) == 1, "\n".join(f.render() for f in active)
    assert "RAYDP_TPU_ETLFX_FIXTURE_FLAG" in active[0].message


def test_env_registry_clean_on_fixed():
    from tools.analyze.__main__ import config_excludes

    project = load_project(
        [
            os.path.join(REPO_ROOT, "raydp_tpu"),
            os.path.join(REPO_ROOT, "tools", "trace_analyze.py"),
            os.path.join(REPO_ROOT, "tests", "conftest.py"),
            os.path.join(FIXTURES, "envreg_good.py"),
        ],
        root=REPO_ROOT,
        exclude=config_excludes(REPO_ROOT),
    )
    findings = run_rules(project, [rules_by_name()["env-registry"]()])
    active = [f for f in findings if not f.suppressed]
    assert active == [], "\n".join(f.render() for f in active)


def test_env_registry_skips_partial_sweeps():
    """Without the full-surface markers in scope the rule stays silent — a
    one-file sweep must not demand the docs describe it."""
    assert run_rule("env-registry", "envreg_bad.py") == []


def test_rpc_error_safety_catches_seed():
    found = run_rule("rpc-error-safety", "rpcerr_bad.py")
    assert len(found) == 1
    assert "FetchPlanError" in found[0].message
    assert "unpickling" in found[0].message


def test_rpc_error_safety_clean_on_fixed():
    """Builtins, bare re-raises, and types imported from outside the project
    are all fine inside an RPC-served file."""
    assert run_rule("rpc-error-safety", "rpcerr_good.py") == []


def test_rpc_error_safety_pickle_contract():
    """The cluster/common.py half: a required __init__ arg not forwarded to
    super().__init__ is lost across BaseException.__reduce__ (the
    TenantQuotaError.tenant contract); forwarding through the message
    f-string satisfies it."""
    found = run_rule("rpc-error-safety", os.path.join("cluster", "common.py"))
    assert len(found) == 1
    assert "QuotaExceeded" in found[0].message
    assert "tenant" in found[0].message


def test_except_order_catches_seed():
    found = run_rule("except-order", "exceptorder_bad.py")
    messages = "\n".join(f.message for f in found)
    assert len(found) == 3
    # divergent cleanup: the narrow miss path never discards the socket
    assert "never touches `sock`" in messages
    # redundant tuple member
    assert "`ConnectionError` is redundant" in messages
    # unreachable handler behind its superclass
    assert "unreachable" in messages and "FileNotFoundError ⊆ OSError" in messages


def test_except_order_clean_on_fixed():
    assert run_rule("except-order", "exceptorder_good.py") == []


# ---------------------------------------------------------------------------
# white-box: the shared surface-extraction pass
# ---------------------------------------------------------------------------


def test_surfaces_dynamic_tenant_prefix_resolves():
    """f-string holes become single-segment wildcards: the write pattern
    `tenant.<*>.etlfx_rows` unifies with any concrete tenant read."""
    from tools.analyze.surfaces import patterns_match

    project = load_project([os.path.join(FIXTURES, "metricreg_good.py")])
    surf = project.surfaces()
    assert "tenant.<*>.etlfx_rows" in surf.write_patterns()
    assert patterns_match("tenant.dashboards.etlfx_rows",
                          "tenant.<*>.etlfx_rows")
    assert not patterns_match("tenant.a.b.etlfx_rows",
                              "tenant.<*>.etlfx_rows")  # one segment only


def test_surfaces_fanout_suffix_strips_to_instrument():
    """`etlfx.stage_ms.p99` is a fan-out series of the histogram — the read
    resolves to the instrumentation site, no false positive."""
    from tools.analyze.surfaces import strip_fanout

    project = load_project([os.path.join(FIXTURES, "metricreg_good.py")])
    surf = project.surfaces()
    assert strip_fanout("etlfx.stage_ms.p99") == "etlfx.stage_ms"
    assert strip_fanout("etlfx.stage_ms") == "etlfx.stage_ms"
    assert surf.has_writer("etlfx.stage_ms.p99")


def test_surfaces_read_without_writer_detected():
    """The typo'd read has no producer even though its family has writers in
    scope — exactly the condition the metric-registry rule gates on."""
    project = load_project([os.path.join(FIXTURES, "metricreg_bad.py")])
    surf = project.surfaces()
    assert "etlfx" in surf.write_families()
    assert not surf.has_writer("etlfx.rows_ingest")
    assert surf.has_writer("etlfx.rows_ingested")


def test_metric_registry_mutation_check():
    """The acceptance-criteria drill: rename `serve.p99_ms` at its
    batcher.py instrumentation site and metric-registry must fail the build
    from three directions — the doc row goes dead, the autoscaler's reads go
    writerless, and the renamed write is undocumented."""
    from tools.analyze.__main__ import config_excludes
    from tools.analyze.core import Project, SourceFile

    project = load_project(
        [
            os.path.join(REPO_ROOT, "raydp_tpu"),
            os.path.join(REPO_ROOT, "tools"),
            os.path.join(REPO_ROOT, "examples"),
            os.path.join(REPO_ROOT, "tests", "conftest.py"),
        ],
        root=REPO_ROOT,
        exclude=config_excludes(REPO_ROOT),
    )
    target = os.path.join("raydp_tpu", "serve", "batcher.py")
    src = project.file(target)
    assert src is not None and '"serve.p99_ms"' in src.text
    mutated = SourceFile(
        src.path, src.display_path,
        src.text.replace('"serve.p99_ms"', '"serve.p99_millis"'),
    )
    files = [mutated if f.display_path == target else f for f in project.files]
    findings = run_rules(
        Project(files, root=REPO_ROOT),
        [rules_by_name()["metric-registry"]()],
    )
    active = [f for f in findings if not f.suppressed]
    rendered = "\n".join(f.render() for f in active)
    assert any("docs row describes metric `serve.p99_ms`" in f.message
               for f in active), rendered
    assert any("`serve.p99_ms` is read here" in f.message
               for f in active), rendered
    assert any("`serve.p99_millis` is instrumented here" in f.message
               for f in active), rendered


# ---------------------------------------------------------------------------
# suppression budget gate
# ---------------------------------------------------------------------------


def test_suppression_stats_counts_by_rule(tmp_path):
    from tools.analyze.__main__ import suppression_stats

    path = tmp_path / "sup.py"
    path.write_text(
        "print('a')  # raydp-lint: disable=print-diagnostics (x)\n"
        "print('b')  # raydp-lint: disable=print-diagnostics (y)\n"
        "print('c')\n"
    )
    findings = run_rules(
        load_project([str(path)]), [rules_by_name()["print-diagnostics"]()]
    )
    assert suppression_stats(findings) == {"print-diagnostics": 2}


def test_check_budget_flags_growth_only(tmp_path):
    from tools.analyze.__main__ import check_budget

    budget = tmp_path / "budget.json"
    budget.write_text('{"print-diagnostics": 2, "swallowed-exceptions": 5}\n')
    # within budget (and below budget elsewhere): clean
    assert check_budget({"print-diagnostics": 2}, str(budget)) == []
    assert check_budget({"swallowed-exceptions": 3}, str(budget)) == []
    # growth fails, naming the rule and the budget file
    problems = check_budget({"print-diagnostics": 3}, str(budget))
    assert len(problems) == 1 and "print-diagnostics" in problems[0]
    # a rule absent from the budget has an implicit budget of zero
    problems = check_budget({"guarded-by": 1}, str(budget))
    assert len(problems) == 1 and "guarded-by" in problems[0]
    # missing budget file is itself a failure with a remedy
    problems = check_budget({}, str(tmp_path / "nope.json"))
    assert len(problems) == 1 and "--write-budget" in problems[0]


def test_repo_suppressions_within_budget():
    """The committed budget covers the CI sweep exactly: no rule suppresses
    more than tools/analyze/suppression_budget.json allows."""
    from tools.analyze.__main__ import (
        BUDGET_FILE, check_budget, config_excludes, suppression_stats,
    )

    project = load_project(
        [
            os.path.join(REPO_ROOT, "raydp_tpu"),
            os.path.join(REPO_ROOT, "tools"),
            os.path.join(REPO_ROOT, "examples"),
            os.path.join(REPO_ROOT, "tests", "conftest.py"),
        ],
        root=REPO_ROOT,
        exclude=config_excludes(REPO_ROOT),
    )
    findings = run_rules(project, [cls() for cls in ALL_RULES])
    stats = suppression_stats(findings)
    problems = check_budget(stats, os.path.join(REPO_ROOT, BUDGET_FILE))
    assert problems == [], "\n".join(problems)


# ---------------------------------------------------------------------------
# suppression mechanics + report contract
# ---------------------------------------------------------------------------


def test_suppression_forms(tmp_path):
    path = tmp_path / "sup.py"
    path.write_text(
        "def f(x):\n"
        "    try:\n"
        "        x()\n"
        "    except Exception:  # raydp-lint: disable=swallowed-exceptions (ok)\n"
        "        pass\n"
        "    try:\n"
        "        x()\n"
        "    # raydp-lint: disable=swallowed-exceptions (next-line form)\n"
        "    except Exception:\n"
        "        pass\n"
        "    print(x)  # raydp-lint: disable=all\n"
    )
    project = load_project([str(path)])
    findings = run_rules(project, [cls() for cls in ALL_RULES])
    assert findings, "findings should exist but all be suppressed"
    assert all(f.suppressed for f in findings)
    _, code = render_report(findings, as_json=False)
    assert code == 0


def test_file_wide_suppression(tmp_path):
    path = tmp_path / "filewide.py"
    path.write_text(
        "# raydp-lint: disable-file=print-diagnostics\n"
        "print('a')\n"
        "print('b')\n"
    )
    findings = run_rules(
        load_project([str(path)]), [rules_by_name()["print-diagnostics"]()]
    )
    assert len(findings) == 2 and all(f.suppressed for f in findings)


def test_marker_inside_string_is_not_a_suppression(tmp_path):
    path = tmp_path / "s.py"
    path.write_text(
        'MSG = "raydp-lint: disable=print-diagnostics"\n'
        "print(MSG)\n"
    )
    findings = run_rules(
        load_project([str(path)]), [rules_by_name()["print-diagnostics"]()]
    )
    assert len(findings) == 1 and not findings[0].suppressed


def test_parse_error_is_a_finding(tmp_path):
    path = tmp_path / "broken.py"
    path.write_text("def f(:\n")
    findings = run_rules(load_project([str(path)]), [])
    assert [f.rule for f in findings] == ["parse-error"]
    _, code = render_report(findings, as_json=False)
    assert code == 1


def test_json_report_shape():
    project = load_project([os.path.join(FIXTURES, "print_bad.py")])
    findings = run_rules(project, [rules_by_name()["print-diagnostics"]()])
    text, code = render_report(findings, as_json=True)
    payload = json.loads(text)
    assert code == 1
    assert payload["active"] == 2 and payload["suppressed"] == 0
    assert {f["rule"] for f in payload["findings"]} == {"print-diagnostics"}


# ---------------------------------------------------------------------------
# the CI gate itself
# ---------------------------------------------------------------------------


def test_cli_exit_codes():
    env = dict(os.environ, PYTHONPATH=REPO_ROOT)
    bad = subprocess.run(
        [sys.executable, "-m", "tools.analyze",
         os.path.join(FIXTURES, "print_bad.py")],
        cwd=REPO_ROOT, env=env, capture_output=True, text=True,
    )
    assert bad.returncode == 1
    assert "print-diagnostics" in bad.stdout
    good = subprocess.run(
        [sys.executable, "-m", "tools.analyze",
         os.path.join(FIXTURES, "swallowed_good.py")],
        cwd=REPO_ROOT, env=env, capture_output=True, text=True,
    )
    assert good.returncode == 0, good.stdout


def test_list_rules_names_all_sixteen():
    """--list-rules prints one line per registered rule, falling back to the
    module docstring for rules documented there rather than on the class."""
    env = dict(os.environ, PYTHONPATH=REPO_ROOT)
    done = subprocess.run(
        [sys.executable, "-m", "tools.analyze", "--list-rules"],
        cwd=REPO_ROOT, env=env, capture_output=True, text=True,
    )
    assert done.returncode == 0, done.stderr
    lines = [l for l in done.stdout.splitlines() if l.strip()]
    assert len(lines) == len(ALL_RULES) == 16
    listed = {l.split(":", 1)[0] for l in lines}
    assert {"rpc-closure", "rpc-payload-safety", "rpc-no-reply",
            "rpc-lock-flow", "conf-registry"} <= listed
    # every line carries a one-line description, none are bare
    assert all(l.split(":", 1)[1].strip() for l in lines)


def test_rule_comma_separated_cli():
    """--rule accepts a comma-separated list (and stays repeatable)."""
    env = dict(os.environ, PYTHONPATH=REPO_ROOT)
    both = subprocess.run(
        [sys.executable, "-m", "tools.analyze",
         os.path.join(FIXTURES, "lockorder_bad.py"),
         os.path.join(FIXTURES, "blocking_bad.py"),
         "--rule", "lock-order,blocking-under-lock"],
        cwd=REPO_ROOT, env=env, capture_output=True, text=True,
    )
    assert both.returncode == 1
    assert "lock-order" in both.stdout
    assert "blocking-under-lock" in both.stdout
    unknown = subprocess.run(
        [sys.executable, "-m", "tools.analyze", "--rule",
         "lock-order,nope"],
        cwd=REPO_ROOT, env=env, capture_output=True, text=True,
    )
    assert unknown.returncode == 2 and "nope" in unknown.stderr


def test_fixture_dir_excluded_via_config():
    """Analyzing tests/ from the repo root skips the seeded-violation
    fixtures through setup.cfg's [raydp-lint] exclude — no hardcoded path
    check in the analyzer."""
    from tools.analyze.__main__ import config_excludes

    patterns = config_excludes(REPO_ROOT)
    assert any("analyze_fixtures" in p for p in patterns)
    env = dict(os.environ, PYTHONPATH=REPO_ROOT)
    swept = subprocess.run(
        [sys.executable, "-m", "tools.analyze",
         os.path.join("tests", "analyze_fixtures")],
        cwd=REPO_ROOT, env=env, capture_output=True, text=True,
    )
    # every fixture is excluded -> nothing analyzed -> clean exit
    assert swept.returncode == 0, swept.stdout
    # an explicit --exclude pattern composes with the config
    narrowed = subprocess.run(
        [sys.executable, "-m", "tools.analyze", "raydp_tpu/store",
         "--exclude", "raydp_tpu/*"],
        cwd=REPO_ROOT, env=env, capture_output=True, text=True,
    )
    assert narrowed.returncode == 0
    assert "0 finding(s)" in narrowed.stdout


# ---------------------------------------------------------------------------
# the rpc-* rule family (v4): wire-surface closure on seeded fixtures
# ---------------------------------------------------------------------------


def test_rpc_closure_catches_seed():
    """All three planes in one fixture: unknown/dead/arity on the frame
    plane, unknown+arity on the actor plane, unknown+dead on the doorbell
    plane, plus the timeout `or`-default idiom."""
    found = run_rule("rpc-closure", "rpcclosure_bad.py")
    messages = "\n".join(f.message for f in found)
    assert len(found) == 8, messages
    for marker in (
        "unknown frame op 'ecoh'",
        "frame op 'put' arity mismatch",
        "dead wire surface: MiniHead.handle_orphaned",
        "actor arity mismatch for 'widget_op'",
        "unknown actor method 'frobnicate'",
        "unknown doorbell op '__dong__'",
        "dead doorbell surface: '__ding__'",
        "`timeout or <default>` in client",
    ):
        assert marker in messages, marker
    # every seeded violation sits on a BUG-marked line and vice versa
    src = open(os.path.join(FIXTURES, "rpcclosure_bad.py")).read().splitlines()
    assert sorted(f.line for f in found) == sorted(
        i + 1 for i, line in enumerate(src) if "# BUG" in line
    )


def test_rpc_closure_clean_on_fixed():
    assert run_rule("rpc-closure", "rpcclosure_good.py") == []


def test_rpc_payload_safety_catches_seed():
    found = run_rule("rpc-payload-safety", "rpcpayload_bad.py")
    messages = "\n".join(f.message for f in found)
    assert len(found) == 8, messages
    for marker in (
        "returns the lock",
        "is a generator — its 'return value' cannot cross the wire",
        "returns an OS handle (open(...))",
        "ships a generator expression",
        "ships the lock",  # via the project lock model
        "ships a threading primitive (threading.Lock(...))",
        "'chan', assigned an OS handle (socket.socket(...))",
        "a raw jax value (jnp.ones(...))",
    ):
        assert marker in messages, marker


def test_rpc_payload_safety_clean_on_fixed():
    """Marshaled payloads (list(...), np.asarray(jnp...), float(...)) and
    host-side handler returns pass — the approved-marshal early exit."""
    assert run_rule("rpc-payload-safety", "rpcpayload_good.py") == []


def test_rpc_no_reply_catches_seed():
    found = run_rule("rpc-no-reply", "rpcnoreply_bad.py")
    assert len(found) == 1
    assert "no_reply=True send of 'bump'" in found[0].message
    assert "Tally.bump(n)" in found[0].message


def test_rpc_no_reply_clean_on_fixed():
    """Dropping a constant ack (`return True`) is fine; the meaningful reply
    rides a replied call."""
    assert run_rule("rpc-no-reply", "rpcnoreply_good.py") == []


def test_rpc_lock_flow_catches_seed():
    """The acceptance-criteria fixture: a handler that reaches `rpc(...)`
    through a helper while a named lock is held — invisible to
    blocking-under-lock's lexical check."""
    found = run_rule("rpc-lock-flow", "rpclockflow_bad.py")
    assert len(found) == 1
    msg = found[0].message
    assert "handle_join" in msg
    assert "self._broadcast() -> outbound RPC 'rpc(...)'" in msg
    assert "MiniRegistry._lock" in msg
    assert "snapshot under the lock, send outside" in msg


def test_rpc_lock_flow_clean_on_fixed():
    """The same shape with the send hoisted off-lock (the
    Head._unlink_objects idiom) is clean — including the off-lock
    `self._broadcast()` in handle_leave."""
    assert run_rule("rpc-lock-flow", "rpclockflow_good.py") == []


# ---------------------------------------------------------------------------
# white-box: the shared RPC-surface extraction pass
# ---------------------------------------------------------------------------


def test_rpc_surface_extraction_on_fixture():
    """One extraction feeds all four rules: frame handlers with signatures,
    spawn()-derived actor surface, doorbell comparisons, literal 4-tuple
    doorbell sends, and timeout-`or` sites."""
    project = load_project([os.path.join(FIXTURES, "rpcclosure_bad.py")])
    surf = project.rpc_surface()
    assert set(surf.frame_handlers) == {"echo", "put", "orphaned"}
    put = surf.frame_handlers["put"][0]
    assert (put.required, put.optional) == (["key", "value"], ["ttl"])
    assert put.signature() == "MiniHead.handle_put(key, value, ttl=…)"
    assert surf.actor_classes == {"Widget"}
    assert set(surf.actor_handlers) == {"widget_op", "ack"}
    assert set(surf.doorbell_handlers) == {"__ding__"}
    assert {c.op for c in surf.calls_on("doorbell")} == {"__dong__"}
    assert [s.name for s in surf.timeout_or_sites] == ["timeout"]
    # memoized: the same object comes back on the second ask
    assert project.rpc_surface() is surf


def test_rpc_surface_no_reply_and_spawn_extraction():
    """`.options(no_reply=True).remote(...)` is one actor-plane site with the
    flag set; the plain `.remote(...)` next to it is not."""
    project = load_project([os.path.join(FIXTURES, "rpcnoreply_good.py")])
    surf = project.rpc_surface()
    assert surf.actor_classes == {"Tally"}
    by_op = {c.op: c for c in surf.calls_on("actor")}
    assert by_op["ping"].no_reply and by_op["ping"].via == "remote"
    assert not by_op["bump"].no_reply
    # `return True` is a droppable ack, `return self.total` is not
    assert not surf.actor_handlers["ping"][0].returns_value
    assert surf.actor_handlers["bump"][0].returns_value


def test_rpc_surface_envelope_and_head_rpc(tmp_path):
    """A literal ('__obs__', ctx, request) trace envelope unwraps to the
    inner request, and head_rpc eats its own timeout kwarg."""
    path = tmp_path / "wire.py"
    path.write_text(
        "def send(addr, ctx, spec):\n"
        "    rpc(addr, ('__obs__', ctx, ('put', {'key': 1})))\n"
        "    head_rpc('create_actor', spec=spec, timeout=5)\n"
    )
    surf = load_project([str(path)]).rpc_surface()
    shapes = {(c.op, frozenset(c.kwargs or ())) for c in surf.calls_on("frame")}
    assert ("put", frozenset({"key"})) in shapes
    assert ("create_actor", frozenset({"spec"})) in shapes


def _full_sweep_project():
    from tools.analyze.__main__ import config_excludes

    return load_project(
        [
            os.path.join(REPO_ROOT, "raydp_tpu"),
            os.path.join(REPO_ROOT, "tools"),
            os.path.join(REPO_ROOT, "examples"),
            os.path.join(REPO_ROOT, "tests", "conftest.py"),
        ],
        root=REPO_ROOT,
        exclude=config_excludes(REPO_ROOT),
    )


def test_rpc_surface_real_tree_anchors():
    """The extraction finds the protocol the docs describe: the head's
    create_actor frame op, every spawn()-ed actor class, and the worker
    doorbell — and the tree has zero timeout-`or` sites left (satellite 1)."""
    surf = _full_sweep_project().rpc_surface()
    h = surf.frame_handlers["create_actor"][0]
    assert (h.cls, h.required) == ("Head", ["spec"])
    assert surf.actor_classes == {
        "BlockService", "EtlExecutor", "ModelReplica", "ObjectHolder",
        "SpmdWorker",
    }
    assert set(surf.doorbell_handlers) == {"__ping__", "__shutdown__"}
    assert surf.timeout_or_sites == []
    # ActorHandle.__getattr__ refuses leading underscores: no _private
    # method may appear on the wire-reachable actor surface
    assert not [op for op in surf.actor_handlers if op.startswith("_")]


# ---------------------------------------------------------------------------
# the contract snapshot gate
# ---------------------------------------------------------------------------


def _committed_contract():
    from tools.analyze.rpc import CONTRACT_FILE

    with open(os.path.join(REPO_ROOT, CONTRACT_FILE), encoding="utf-8") as f:
        return json.load(f)


def test_rpc_contract_matches_committed():
    """Exactly what CI's --check-contract gates on: the live wire surface
    rebuilds byte-for-byte into the committed snapshot."""
    from tools.analyze.rpc import build_contract, check_contract

    surf = _full_sweep_project().rpc_surface()
    committed = _committed_contract()
    assert check_contract(surf, committed) == []
    assert build_contract(surf) == committed


def test_rpc_contract_mutation_drill():
    """The acceptance-criteria drill: rename a real handle_* in a mutated
    copy of head.py and the gate must fail from BOTH directions — rpc-closure
    flags the now-orphaned api.py caller AND the dead renamed handler, and
    --check-contract reports the surface change."""
    from tools.analyze.core import Project, SourceFile
    from tools.analyze.rpc import check_contract

    project = _full_sweep_project()
    target = os.path.join("raydp_tpu", "cluster", "head.py")
    src = project.file(target)
    assert src is not None and "def handle_create_actor(" in src.text
    mutated = SourceFile(
        src.path, src.display_path,
        src.text.replace("def handle_create_actor(",
                         "def handle_create_actorr("),
    )
    files = [mutated if f.display_path == target else f for f in project.files]
    mutated_project = Project(files, root=REPO_ROOT)
    findings = run_rules(
        mutated_project, [rules_by_name()["rpc-closure"]()]
    )
    active = [f for f in findings if not f.suppressed]
    rendered = "\n".join(f.render() for f in active)
    assert any(
        "unknown frame op 'create_actor'" in f.message
        and f.path.endswith("api.py")
        for f in active
    ), rendered
    assert any(
        "dead wire surface: Head.handle_create_actorr" in f.message
        for f in active
    ), rendered
    problems = check_contract(
        mutated_project.rpc_surface(), _committed_contract()
    )
    text = "\n".join(problems)
    assert "frame op 'create_actorr' exists in the tree" in text
    assert "frame op 'create_actor' is in the committed contract" in text
    assert all("--write-contract" in p for p in problems)


def test_rpc_contract_drift_on_signature_change():
    """Same op, new kwarg: the op survives both sets but its handler entry
    differs, so the contract reports a drift (not an add/remove)."""
    from tools.analyze.rpc import build_contract, check_contract

    surf = _full_sweep_project().rpc_surface()
    committed = _committed_contract()
    live = build_contract(surf)
    assert live == committed  # precondition
    committed["frame"]["create_actor"]["handlers"][0]["required"] = [
        "spec", "shiny_new_arg",
    ]
    problems = check_contract(surf, committed)
    assert len(problems) == 1
    assert "frame op 'create_actor' drifted" in problems[0]


def test_spliced_doc_replaces_between_markers():
    from tools.analyze.__main__ import spliced_doc
    from tools.analyze.rpc import RPC_TABLE_BEGIN, RPC_TABLE_END

    doc = f"# title\n\n{RPC_TABLE_BEGIN}\nold rows\n{RPC_TABLE_END}\ntail\n"
    out = spliced_doc(doc, "| new |")
    assert "| new |" in out and "old rows" not in out
    assert out.startswith("# title") and out.rstrip().endswith("tail")
    with pytest.raises(ValueError):
        spliced_doc("a doc without markers\n", "| new |")


def test_rpc_contract_cli_gates_pass():
    """The two CI steps verbatim: --check-contract and --check-rpc-table both
    exit 0 against the committed contract and docs table."""
    env = dict(os.environ, PYTHONPATH=REPO_ROOT)
    done = subprocess.run(
        [sys.executable, "-m", "tools.analyze",
         "raydp_tpu/", "tools/", "examples/", "chip_smoke.py",
         os.path.join("tests", "conftest.py"),
         "--check-contract", "--check-rpc-table"],
        cwd=REPO_ROOT, env=env, capture_output=True, text=True,
    )
    assert done.returncode == 0, done.stdout + done.stderr
    assert "matches the committed contract" in done.stdout
    assert "RPC surface table is current" in done.stdout


def test_repo_is_lint_clean():
    """The invocation CI gates on, plus chip_smoke.py: every finding in
    raydp_tpu/, the self-hosted tools/ tree, examples/,
    chip_smoke.py and tests/conftest.py carries an explicit suppression —
    with the full-surface registry rules (metric/conf/env closure) and
    exception-flow rules active."""
    from tools.analyze.__main__ import config_excludes

    project = load_project(
        [
            os.path.join(REPO_ROOT, "raydp_tpu"),
            os.path.join(REPO_ROOT, "tools"),
            os.path.join(REPO_ROOT, "examples"),
            os.path.join(REPO_ROOT, "chip_smoke.py"),
            os.path.join(REPO_ROOT, "tests", "conftest.py"),
        ],
        root=REPO_ROOT,
        exclude=config_excludes(REPO_ROOT),
    )
    findings = run_rules(project, [cls() for cls in ALL_RULES])
    active = [f for f in findings if not f.suppressed]
    assert active == [], "\n".join(f.render() for f in active)
