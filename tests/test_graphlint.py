"""graphlint test coverage: every AST rule catches its seeded fixture, every
graph-contract checker catches its known-bad jaxpr, clean code passes, and
the CLI's exit code reflects both.

The AST fixtures live in ``tests/graphlint_fixtures/`` and are PARSED, never
imported. The known-bad graphs are built here at test time (extra
collective, f64 leak, missing donation, wrong wire dtype/bytes, host
callback, non-identical disabled-config graph).
"""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from edgellm_tpu.lint.ast_rules import lint_file, lint_source
from edgellm_tpu.lint.contracts import (GRAPH_CONTRACTS, GraphContract,
                                        check_identity, check_traced,
                                        count_collectives,
                                        donated_input_count,
                                        graph_fingerprint, ppermute_traffic)
from edgellm_tpu.parallel.split import make_stage_mesh
from jax import shard_map

FIXTURES = os.path.join(os.path.dirname(__file__), "graphlint_fixtures")


def _fixture(name: str) -> str:
    return os.path.join(FIXTURES, name)


def _repo_root():
    import pathlib

    return pathlib.Path(__file__).resolve().parent.parent


def _rules(findings):
    return {f.rule for f in findings}


# ---------------------------------------------------------------------------
# layer 1: each AST rule catches its seeded fixture
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("fixture,rule,min_hits", [
    ("bad_eg001.py", "EG001", 3),  # if / while / assert on traced values
    ("bad_eg002.py", "EG002", 2),  # time.time + print reachable from jit
    ("bad_eg003.py", "EG003", 1),  # np.sqrt on a tracer
    ("bad_eg004.py", "EG004", 2),  # jit call + partial-decorated, cfg unstatic
    ("bad_eg005.py", "EG005", 2),  # int(...) + .item() in a generate loop
    ("bad_eg006.py", "EG006", 2),  # captured list append + dict store
])
def test_ast_rule_catches_fixture(fixture, rule, min_hits):
    findings = lint_file(_fixture(fixture))
    hits = [f for f in findings if f.rule == rule]
    assert len(hits) >= min_hits, \
        f"{fixture}: expected >= {min_hits} {rule} findings, got {findings}"
    assert all(f.line > 0 for f in hits)  # every finding is line-anchored


def test_clean_fixture_passes():
    assert lint_file(_fixture("clean.py")) == []


def test_real_package_ast_clean():
    """The shipped package must lint clean — the CI gate depends on it."""
    from edgellm_tpu.lint.ast_rules import iter_package_files, lint_paths

    import edgellm_tpu

    pkg_root = os.path.dirname(os.path.abspath(edgellm_tpu.__file__))
    findings = lint_paths(iter_package_files(pkg_root))
    assert findings == [], [f.format() for f in findings]


def test_suppression_comment_disables_rule():
    src = (
        "import jax\nimport jax.numpy as jnp\n\n"
        "@jax.jit\n"
        "def f(x):\n"
        "    if jnp.any(x > 0):  # graphlint: disable=EG001\n"
        "        return x + 1\n"
        "    return x\n")
    assert lint_source(src, "t.py") == []
    # ...but an unrelated rule id does not suppress it
    src_wrong = src.replace("disable=EG001", "disable=EG002")
    assert _rules(lint_source(src_wrong, "t.py")) == {"EG001"}


def test_suppression_comment_multi_rule():
    """Comma-separated disables silence every listed rule and nothing else."""
    src = (
        "import jax\nimport jax.numpy as jnp\nimport numpy as np\n\n"
        "@jax.jit\n"
        "def f(x):\n"
        "    return np.sqrt(x)  # graphlint: disable={rules}\n")
    # the fired rule is listed (alongside another): suppressed
    assert lint_source(src.format(rules="EG001,EG003"), "t.py") == []
    # listed rules don't include the fired one: still flagged
    assert _rules(lint_source(src.format(rules="EG001,EG002"), "t.py")) \
        == {"EG003"}
    # whitespace around the comma is tolerated
    assert lint_source(src.format(rules="EG003, EG001"), "t.py") == []


def test_collect_suppressions_inventory(tmp_path):
    from edgellm_tpu.lint.ast_rules import collect_suppressions

    p = tmp_path / "mod.py"
    p.write_text(
        "x = 1  # graphlint: disable=EG001,EG003\n"
        "y = 2\n"
        "z = 3  # graphlint: disable\n")
    marks = collect_suppressions([str(p)])
    assert marks == [(str(p), 1, {"EG001", "EG003"}), (str(p), 3, None)]


def test_unreachable_code_not_flagged():
    """Host-only modules may branch on arrays / print / use numpy freely —
    the rules only fire on jit-reachable functions."""
    src = (
        "import numpy as np\n\n"
        "def host(x):\n"
        "    print('fine')\n"
        "    return np.sqrt(x)\n")
    assert lint_source(src, "t.py") == []


# ---------------------------------------------------------------------------
# layer 2: each graph-contract checker catches its known-bad jaxpr
# ---------------------------------------------------------------------------


def _shmap(body, n_out_stage=False):
    mesh = make_stage_mesh(2)
    return shard_map(body, mesh=mesh, in_specs=(P("stage"),),
                     out_specs=P("stage") if n_out_stage else P(),
                     check_vma=False)


def test_extra_collective_caught():
    """A silently-added psum trips the declared collective count."""

    def one_psum(x):
        return jax.lax.psum(x, "stage")

    def two_psums(x):
        return jax.lax.psum(jax.lax.psum(x, "stage"), "stage")

    x = jnp.ones((2, 4), jnp.float32)
    contract = GraphContract(name="t.collectives",
                             collectives={"psum": 1}, forbid=())
    assert check_traced(contract, _shmap(one_psum), (x,)) == []
    bad = check_traced(contract, _shmap(two_psums), (x,))
    assert _rules(bad) == {"GC-collectives"}


def test_f64_leak_caught():
    contract = GraphContract(name="t.f64", forbid=("f64",))

    def promotes(x):
        return x.astype(jnp.float64) * 2.0

    x = jnp.ones((4,), jnp.float32)
    with jax.enable_x64(True):
        bad = check_traced(contract, promotes, (x,))
    assert _rules(bad) == {"GC-f64"}
    assert check_traced(contract, lambda y: y * 2.0, (x,)) == []


def test_host_callback_caught():
    contract = GraphContract(name="t.cb", forbid=("host_callback",))

    def with_debug(x):
        jax.debug.print("x={x}", x=x)
        return x + 1

    x = jnp.ones((4,), jnp.float32)
    bad = check_traced(contract, with_debug, (x,))
    assert _rules(bad) == {"GC-callback"}
    assert check_traced(contract, lambda y: y + 1, (x,)) == []


def test_missing_donation_caught():
    contract = GraphContract(name="t.donate", forbid=(), donate=1)
    x = jnp.ones((8,), jnp.float32)

    undonated = jax.jit(lambda c: c + 1)
    bad = check_traced(contract, undonated, (x,),
                       lowerable=undonated, lower_args=(x,))
    assert _rules(bad) == {"GC-donate"}

    donated = jax.jit(lambda c: c + 1, donate_argnums=(0,))
    assert check_traced(contract, donated, (x,),
                        lowerable=donated, lower_args=(x,)) == []
    assert donated_input_count(donated, x) >= 1
    assert donated_input_count(undonated, x) == 0


def test_wire_dtype_and_bytes_caught():
    """f32 crossing a hop that declares an int8 wire, and a payload that
    drifted from the declared byte width, are both flagged."""

    def hop_f32(x):
        return jax.lax.ppermute(x, "stage", [(0, 1)])

    fn = _shmap(hop_f32, n_out_stage=True)
    x = jnp.ones((2, 8), jnp.float32)  # local (1, 8) f32 = 32 wire bytes

    contract = GraphContract(name="t.wire", forbid=(),
                             wire_dtypes=frozenset({"int8"}),
                             wire_bytes=32)
    bad = check_traced(contract, fn, (x,))
    assert _rules(bad) == {"GC-wire-dtype"}

    contract2 = GraphContract(name="t.wire2", forbid=(),
                              wire_dtypes=frozenset({"float32"}),
                              wire_bytes=16)
    bad2 = check_traced(contract2, fn, (x,))
    assert _rules(bad2) == {"GC-wire-bytes"}

    good = GraphContract(name="t.wire3", forbid=(),
                         wire_dtypes=frozenset({"float32"}), wire_bytes=32)
    assert check_traced(good, fn, (x,)) == []
    traffic = ppermute_traffic(jax.make_jaxpr(fn)(x))
    assert traffic == [("float32", (1, 8), 32)]


def test_collective_count_recurses_into_scan():
    """Counts are static graph counts: a ppermute inside a scan body counts
    once, however many trip iterations run."""

    def body(x):
        def step(h, _):
            return jax.lax.ppermute(h, "stage", [(0, 1)]), None

        out, _ = jax.lax.scan(step, x, None, length=5)
        return out

    fn = _shmap(body, n_out_stage=True)
    jaxpr = jax.make_jaxpr(fn)(jnp.ones((2, 4), jnp.float32))
    assert count_collectives(jaxpr) == {"ppermute": 1}


def test_identity_checker_flags_divergent_graphs():
    x = jnp.ones((4,), jnp.float32)
    f = lambda a: a * 2.0  # noqa: E731
    g = lambda a: a * 2.0 + 1.0  # noqa: E731
    assert check_identity("t.same", f, (x,), f, (x,)) == []
    bad = check_identity("t.diff", f, (x,), g, (x,))
    assert _rules(bad) == {"GC-identity"}
    assert graph_fingerprint(f, x) != graph_fingerprint(g, x)


def test_production_contracts_registered():
    """Importing the stack registers every declared contract — the CLI's
    graph layer fails loudly if one goes missing."""
    import edgellm_tpu.codecs.faults  # noqa: F401
    import edgellm_tpu.models.transformer  # noqa: F401
    import edgellm_tpu.parallel.split  # noqa: F401
    import edgellm_tpu.serve.decode  # noqa: F401

    expected = {"transformer.prefill", "transformer.decode_step",
                "decode.prefill", "decode.step", "split.forward",
                "split.decode_step", "faults.hop"}
    assert expected <= set(GRAPH_CONTRACTS)
    # the decorator is zero-cost: the functions stay plain functions
    assert GRAPH_CONTRACTS["transformer.prefill"].fn.__name__ == "prefill"


# ---------------------------------------------------------------------------
# CLI exit codes
# ---------------------------------------------------------------------------


def _run_cli(*args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "-m", "edgellm_tpu.lint", *args],
        capture_output=True, text=True, timeout=300, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def test_cli_nonzero_on_seeded_violations(tmp_path):
    bad = [_fixture(f"bad_eg00{i}.py") for i in range(1, 7)]
    report_path = tmp_path / "report.json"
    proc = _run_cli("--ast-only", "--json", str(report_path), *bad)
    assert proc.returncode == 1, proc.stdout + proc.stderr
    import json

    report = json.loads(report_path.read_text())
    assert not report["ok"]
    assert {f["rule"] for f in report["findings"]} == {
        "EG001", "EG002", "EG003", "EG004", "EG005", "EG006"}


def test_cli_zero_on_clean_paths():
    proc = _run_cli("--ast-only", _fixture("clean.py"))
    assert proc.returncode == 0, proc.stdout + proc.stderr


def test_cli_thread_only_on_seeded_fixtures():
    bad = [_fixture(f"bad_eg10{i}.py") for i in range(1, 5)]
    proc = _run_cli("--thread-only", *bad)
    assert proc.returncode == 1, proc.stdout + proc.stderr
    for rule in ("EG101", "EG102", "EG103", "EG104"):
        assert rule in proc.stdout, (rule, proc.stdout)


def test_cli_show_suppressed_lists_markers():
    proc = _run_cli("--thread-only", "--show-suppressed",
                    _fixture("clean.py"))
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "suppressions: 0 marker(s)" in proc.stdout
    # with a real package walk the audit lists file:line for every marker
    proc = _run_cli("--thread-only", "--show-suppressed")
    assert "suppressions:" in proc.stdout, proc.stdout


def test_cli_sarif_on_violations(tmp_path):
    import json

    sarif_path = tmp_path / "out.sarif"
    proc = _run_cli("--thread-only", "--sarif", str(sarif_path),
                    _fixture("bad_eg102.py"))
    assert proc.returncode == 1, proc.stdout + proc.stderr
    doc = json.loads(sarif_path.read_text())
    assert doc["version"] == "2.1.0"
    run = doc["runs"][0]
    assert run["tool"]["driver"]["name"] == "graphlint"
    assert {r["id"] for r in run["tool"]["driver"]["rules"]} == {"EG102"}
    results = run["results"]
    assert results and all(r["ruleId"] == "EG102" for r in results)
    region = results[0]["locations"][0]["physicalLocation"]["region"]
    assert region["startLine"] > 0


def test_cli_sarif_on_clean_paths(tmp_path):
    import json

    sarif_path = tmp_path / "clean.sarif"
    proc = _run_cli("--ast-only", "--sarif", str(sarif_path),
                    _fixture("clean.py"))
    assert proc.returncode == 0, proc.stdout + proc.stderr
    doc = json.loads(sarif_path.read_text())
    assert doc["runs"][0]["results"] == []


def test_cli_json_report_unchanged_shape(tmp_path):
    """--json stays byte-compatible: same four keys, same ordering."""
    import json

    report_path = tmp_path / "r.json"
    proc = _run_cli("--ast-only", "--json", str(report_path),
                    _fixture("clean.py"))
    assert proc.returncode == 0, proc.stdout + proc.stderr
    text = report_path.read_text()
    report = json.loads(text)
    assert list(report) == sorted(report)  # sort_keys=True preserved
    assert set(report) == {"ok", "findings", "checked_contracts", "skipped"}
    assert text == json.dumps(report, indent=2, sort_keys=True) + "\n"


@pytest.mark.slow
def test_cli_zero_on_real_package(tmp_path):
    """Acceptance: the full CLI (AST + graph contracts + config lattice)
    exits 0 on the real package. Slow — it traces every entry point and
    AOT-lowers every config; CI's graphlint/latticelint jobs run it as the
    required gate."""
    report_path = tmp_path / "report.json"
    matrix_path = tmp_path / "capability_matrix.json"
    proc = _run_cli("--no-mypy", "--json", str(report_path),
                    "--matrix", str(matrix_path))
    assert proc.returncode == 0, proc.stdout + proc.stderr
    import json

    report = json.loads(report_path.read_text())
    assert report["ok"] and len(report["checked_contracts"]) >= 8
    # the lattice layer ran and covered every shipped config
    lattice = [c for c in report["checked_contracts"]
               if c.startswith("lattice.config:")]
    n_configs = len(list((_repo_root() / "configs").glob("*.json")))
    assert len(lattice) == n_configs
    assert "lattice.pairwise-compat" in report["checked_contracts"]
    matrix = json.loads(matrix_path.read_text())
    assert len(matrix["configs"]) == n_configs
