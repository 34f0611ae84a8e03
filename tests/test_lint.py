"""Tier-1 gate for the invariant lint suite (`ray_tpu/devtools/lint`).

Covers the engine (rule discovery, filtering, JSON schema, allowlist
parsing + hygiene), each rule against its seeded bad/good fixture tree
under tests/lint_fixtures/, and — the acceptance contract — a
zero-violations run over the live repository with all six rules enabled.
"""
import dataclasses
import functools
import json
import re
import shutil
from pathlib import Path

import pytest

from ray_tpu.devtools.lint import (
    LintContext,
    all_rules,
    parse_allow_comments,
    rule_names,
    run_lint,
    to_json,
)

REPO_ROOT = Path(__file__).resolve().parents[1]
FIXTURES = Path(__file__).resolve().parent / "lint_fixtures"

ALL_RULES = {
    "knob-registry",
    "wire-typed-errors",
    "protocol-fingerprint",
    "no-blocking-in-loop",
    "lock-order",
    "reserved-kwargs",
}


def lint(root, rules):
    violations, _ = run_lint(root, rules)
    return violations


# ---------------------------------------------------------------- engine

def test_rule_discovery():
    assert set(rule_names()) == ALL_RULES
    # every rule carries a distinct allow token and a description
    tokens = [r.allow_token for r in all_rules()]
    assert len(set(tokens)) == len(tokens)
    assert all(r.description for r in all_rules())


def test_unknown_rule_rejected():
    with pytest.raises(ValueError, match="unknown rule"):
        run_lint(FIXTURES / "lock_order" / "bad", ["no-such-rule"])


def test_rule_filtering():
    bad = FIXTURES / "lock_order" / "bad"
    only = lint(bad, ["lock-order"])
    assert only and all(v.rule == "lock-order" for v in only)
    # deselecting the rule hides its violations
    assert not [
        v for v in lint(bad, ["reserved-kwargs"]) if v.rule == "lock-order"
    ]


def test_json_schema():
    root = FIXTURES / "lock_order" / "bad"
    violations, rules = run_lint(root, ["lock-order"])
    doc = json.loads(to_json(root, violations, rules))
    assert doc["schema"] == 1
    assert doc["rules"] == ["lock-order"]
    assert doc["ok"] is False
    assert doc["counts"]["lock-order"] >= 1
    v = doc["violations"][0]
    assert set(v) == {"rule", "path", "line", "message"}
    assert isinstance(v["line"], int)


def test_allow_comment_parsing():
    src = (
        "x = 1  # lint: allow-blocking -- measured sub-ms\n"
        "y = 2  # lint: allow-knob\n"
        '"""docstring example: # lint: allow-blocking -- not a comment"""\n'
    )
    entries = parse_allow_comments(src, "f.py")
    assert len(entries) == 2  # the docstring example is NOT an entry
    assert entries[0].token == "blocking"
    assert entries[0].reason == "measured sub-ms"
    assert entries[0].line == 1
    assert entries[1].token == "knob"
    assert entries[1].reason == ""


def test_allowlist_hygiene(tmp_path):
    pkg = tmp_path / "ray_tpu"
    pkg.mkdir()
    (pkg / "mod.py").write_text(
        "a = 1  # lint: allow-blocking\n"          # missing reason
        "b = 2  # lint: allow-made-up -- reason\n"  # unknown token
        "c = 3  # lint: allow-knob -- fine\n"       # valid
    )
    hygiene = [v for v in lint(tmp_path, ["lock-order"]) if v.rule == "allowlist"]
    assert len(hygiene) == 2
    assert any("no reason" in v.message and v.line == 1 for v in hygiene)
    assert any("unknown rule token" in v.message and v.line == 2 for v in hygiene)


def test_allow_comment_suppresses_same_and_previous_line(tmp_path):
    pkg = tmp_path / "ray_tpu" / "core" / "distributed"
    pkg.mkdir(parents=True)
    (pkg / "d.py").write_text(
        "import time\n"
        "async def f():\n"
        "    # lint: allow-blocking -- reason above the call\n"
        "    time.sleep(1)\n"
        "    time.sleep(2)  # lint: allow-blocking -- reason on the call\n"
        "    time.sleep(3)\n"
    )
    vs = [v for v in lint(tmp_path, ["no-blocking-in-loop"])]
    assert [v.line for v in vs if v.rule == "no-blocking-in-loop"] == [6]


# ------------------------------------------------------------- per rule

def test_knob_registry_fixture():
    bad = lint(FIXTURES / "knob_registry" / "bad", ["knob-registry"])
    msgs = [v.message for v in bad]
    assert any(
        "RAY_TPU_FOO_KNOB outside the config registry" in m for m in msgs
    )
    assert any("ghost_knob" in m and "not documented" in m for m in msgs)
    assert any("RAY_TPU_ORPHAN" in m and "orphan" in m for m in msgs)
    assert len(bad) == 3
    assert not lint(FIXTURES / "knob_registry" / "good", ["knob-registry"])


def test_wire_typed_errors_fixture():
    bad = lint(FIXTURES / "wire_typed_errors" / "bad", ["wire-typed-errors"])
    msgs = [v.message for v in bad]
    assert any(m.startswith("BadError:") for m in msgs)
    assert any("StrayError" in m and "outside" in m for m in msgs)
    assert not lint(FIXTURES / "wire_typed_errors" / "good", ["wire-typed-errors"])


def test_protocol_fingerprint_fixture(tmp_path):
    bad = lint(FIXTURES / "protocol" / "bad", ["protocol-fingerprint"])
    assert len(bad) == 1
    assert "PROTOCOL_VERSION is still 5" in bad[0].message
    assert not lint(FIXTURES / "protocol" / "good", ["protocol-fingerprint"])

    # editing a layout constant without bumping the version trips the rule;
    # update_fingerprint clears it again
    from ray_tpu.devtools.lint.rules.protocol_fingerprint import (
        update_fingerprint,
    )

    work = tmp_path / "tree"
    shutil.copytree(FIXTURES / "protocol" / "good", work)
    wire = work / "ray_tpu" / "core" / "distributed" / "wire.py"
    wire.write_text(wire.read_text().replace("_T_INT = 0x03", "_T_INT = 0x04"))
    tripped = lint(work, ["protocol-fingerprint"])
    assert len(tripped) == 1 and "changed" in tripped[0].message
    update_fingerprint(work)
    assert not lint(work, ["protocol-fingerprint"])
    # a version bump with no recorded entry is also a violation
    wire.write_text(
        wire.read_text().replace("PROTOCOL_VERSION = 5", "PROTOCOL_VERSION = 6")
    )
    missing = lint(work, ["protocol-fingerprint"])
    assert len(missing) == 1 and "no fingerprint recorded" in missing[0].message


def test_no_blocking_fixture():
    bad = lint(FIXTURES / "no_blocking" / "bad", ["no-blocking-in-loop"])
    msgs = " | ".join(v.message for v in bad)
    assert "time.sleep" in msgs
    assert "ray_tpu.get" in msgs
    assert "socket" in msgs
    assert "Future.result" in msgs
    # rails hot-loop scope: RPC-shaped calls on the per-frame path
    assert "rails hot loop" in msgs
    assert "per-token actor" in msgs          # .remote(...) submission
    assert "pure mmap+poll" in msgs           # daemon .call(...)
    assert len(bad) == 8  # incl. the call_soon lambda + 3 rails hits
    # good tree: await asyncio.sleep, done-set .result(), allowlisted
    # sleep, a nested sync def, and a rails probe inside an except
    # handler (off the hot path) are all accepted
    assert not lint(FIXTURES / "no_blocking" / "good", ["no-blocking-in-loop"])


def test_no_blocking_rails_registry_rot(tmp_path):
    """A RAILS_HOT_LOOPS entry whose method vanished is itself flagged."""
    pkg = tmp_path / "ray_tpu" / "serve"
    pkg.mkdir(parents=True)
    (pkg / "replica.py").write_text("class Replica:\n    pass\n")
    vs = lint(tmp_path, ["no-blocking-in-loop"])
    assert len(vs) == 1 and "RAILS_HOT_LOOPS" in vs[0].message


def test_lock_order_fixture():
    bad = lint(FIXTURES / "lock_order" / "bad", ["lock-order"])
    assert len(bad) == 1
    assert "cycle" in bad[0].message
    assert "Daemon._a" in bad[0].message and "Daemon._b" in bad[0].message
    assert not lint(FIXTURES / "lock_order" / "good", ["lock-order"])


def test_reserved_kwargs_fixture():
    bad = lint(FIXTURES / "reserved_kwargs" / "bad", ["reserved-kwargs"])
    flagged = {v.message.split(" ")[0] for v in bad}
    assert flagged == {"App.__call__", "App.stream", "task"}
    assert not lint(FIXTURES / "reserved_kwargs" / "good", ["reserved-kwargs"])


# ----------------------------------------------------------------- live

def test_live_tree_is_clean():
    """Acceptance contract: the shipped tree passes all six rules with
    zero violations (and zero allowlist entries lacking a reason)."""
    violations, rules = run_lint(REPO_ROOT)
    assert {r.name for r in rules} == ALL_RULES
    assert violations == [], "\n".join(
        f"{v.path}:{v.line}: [{v.rule}] {v.message}" for v in violations
    )


def test_cli_lint_exit_codes(capsys):
    from ray_tpu.scripts.cli import main

    # clean tree -> returns (exit 0 path)
    main(["lint", "--root", str(REPO_ROOT)])
    assert "0 violations" in capsys.readouterr().out
    # seeded bad fixture -> exit 1 with a JSON report
    with pytest.raises(SystemExit) as exc:
        main(["lint", "--root", str(FIXTURES / "lock_order" / "bad"),
              "--rule", "lock-order", "--json"])
    assert exc.value.code == 1
    doc = json.loads(capsys.readouterr().out)
    assert doc["ok"] is False and doc["counts"]["lock-order"] >= 1


def test_knob_table_covers_registry():
    from ray_tpu.devtools.lint.rules.knob_registry import (
        knob_table_markdown,
        parse_registry,
    )

    ctx = LintContext(REPO_ROOT)
    table = knob_table_markdown(ctx)
    knobs = parse_registry(ctx.get_file("ray_tpu/core/config.py"))
    assert knobs, "registry parse found no knobs"
    for k in knobs:
        assert f"`{k.env}`" in table
    # and the README embeds the generated table
    readme = (REPO_ROOT / "README.md").read_text()
    for k in knobs:
        assert k.env in readme, f"{k.env} missing from README"


@functools.lru_cache(maxsize=None)
def _package_files():
    return LintContext(REPO_ROOT).package_files()


@pytest.mark.parametrize("name", [
    "data_stream_enabled",
    "task_lane_enabled",
    "metrics_rpc_enabled",
    "transfer_raw_frames",
    "pg_prewarm_enabled",
    "lineage_pinning_enabled",
])
def test_one_valued_switch_stays_removed(name):
    """Each of these selected between a path and the one that replaced
    it, and had one value in use; the other path went with it.  A field
    of that name coming back means a second path came back."""
    from ray_tpu.core.config import Config

    assert name not in {f.name for f in dataclasses.fields(Config)}
    word = re.compile(rf"\b{name}\b")
    assert [f.rel for f in _package_files() if word.search(f.source)] == []
