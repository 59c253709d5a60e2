"""repro.analysis tier (DESIGN.md §9): the linter and the HLO auditor.

Every lint rule gets a minimal fixture that triggers it EXACTLY once plus
a clean twin encoding the approved pattern, the disable directives are
exercised both ways, the CLI is driven as a subprocess (including the
repo-wide run, which must be clean), and the compiled-artifact layer is
pinned: census counts bit-identical to the historical inline regex,
baked-constant detection with a closure-baked positive control, and the
CompileCounter recompile sentinel.
"""
import json
import pathlib
import re
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import RULES, lint_source, violations_json
from repro.analysis.hlo_audit import (COLLECTIVE_KINDS, BakedDataError,
                                      CompileCounter, assert_no_baked_data,
                                      collective_census, find_baked_constants)

REPO = str(pathlib.Path(__file__).resolve().parents[1])


def _lint(src):
    return lint_source(textwrap.dedent(src))


# one (bad, good) pair per rule: bad fires the rule exactly once, good is
# the approved pattern for the same job and fires nothing
FIXTURES = {
    "R001": (
        """
        import time
        t0 = time.time()
        """,
        """
        import time
        t0 = time.perf_counter()
        """,
    ),
    "R002": (
        """
        seed = hash("silo-3") % 2**31
        """,
        """
        import zlib
        seed = zlib.crc32(b"silo-3") % 2**31
        """,
    ),
    "R003": (
        """
        import numpy as np
        x = np.random.standard_normal(4)
        """,
        """
        import numpy as np
        x = np.random.default_rng(0).standard_normal(4)
        """,
    ),
    "R004": (
        """
        import jax
        import jax.numpy as jnp
        data = jnp.asarray([[1.0, 2.0]])

        @jax.jit
        def f(p):
            return (data * p).sum()
        """,
        """
        import jax
        import jax.numpy as jnp
        data = jnp.asarray([[1.0, 2.0]])

        @jax.jit
        def f(p, d):
            return (d * p).sum()

        out = f(2.0, data)
        """,
    ),
    "R005": (
        """
        import numpy as np
        sizes = np.asarray([10, 20])
        w = sizes.astype(np.float32)
        """,
        """
        import numpy as np
        sizes = np.asarray([10, 20])
        w = (sizes / sizes.sum()).astype(np.float32)
        """,
    ),
    "R006": (
        """
        import jax.numpy as jnp

        def norm(weights):
            return weights / jnp.sum(weights)
        """,
        """
        import jax.numpy as jnp

        def norm(weights):
            return weights / jnp.maximum(jnp.sum(weights), 1e-12)
        """,
    ),
    "R007": (
        """
        import numpy as np

        def save(path, arr):
            np.savez(path, arr=arr)
        """,
        """
        import os
        import tempfile
        import numpy as np

        def save(path, arr):
            fd, tmp = tempfile.mkstemp(suffix=".npz")
            with os.fdopen(fd, "wb") as f:
                np.savez(f, arr=arr)
            os.replace(tmp, path)
        """,
    ),
    "R008": (
        """
        import jax

        def drive(plan, args, rounds):
            for rnd in range(rounds):
                out = jax.device_get(plan(*args))
            return out
        """,
        """
        import jax

        def drive(plan, args, rounds):
            for rnd in range(rounds):
                out = plan(*args)
            return jax.device_get(out)
        """,
    ),
}


# ---------------------------------------------------------------------------
# rules: each fixture fires exactly once; its clean twin not at all
# ---------------------------------------------------------------------------

def test_fixture_set_covers_every_rule():
    assert set(FIXTURES) == set(RULES)


@pytest.mark.parametrize("rule", sorted(FIXTURES))
def test_rule_fires_exactly_once_on_fixture(rule):
    bad, good = FIXTURES[rule]
    vs = _lint(bad)
    assert [v.rule for v in vs] == [rule], (rule, [v.format() for v in vs])
    assert vs[0].line > 0 and vs[0].snippet
    assert _lint(good) == [], (rule, [v.format() for v in _lint(good)])


def test_r004_jit_call_and_lambda_forms():
    base = ("import jax\n"
            "import jax.numpy as jnp\n"
            "data = jnp.asarray([[1.0, 2.0]])\n")
    for form in ("g = jax.jit(lambda p: (data * p).sum())\n",
                 "def f(p):\n"
                 "    return (data * p).sum()\n"
                 "g = jax.jit(f)\n"):
        vs = lint_source(base + form)
        assert [v.rule for v in vs] == ["R004"], (form,
                                                  [v.format() for v in vs])


def test_r006_flags_oversized_clamp():
    vs = _lint("""
    import jax.numpy as jnp

    def norm(mask):
        return mask / jnp.maximum(jnp.sum(mask), 1.0)
    """)
    assert [v.rule for v in vs] == ["R006"]
    assert "deflates" in vs[0].message


@pytest.mark.parametrize("call", ["jax.device_get(plan(*args))",
                                  "_fetch(plan(*args))"])
def test_r008_flags_each_host_read_in_a_round_loop(call):
    """A module's counted `_fetch` helper is a host sync like device_get."""
    vs = _lint(f"""
    import jax

    def drive(plan, args, rounds):
        for rnd in range(rounds):
            host = {call}
        return host
    """)
    assert [v.rule for v in vs] == ["R008"], [v.format() for v in vs]


def test_syntax_error_reported_not_raised():
    vs = lint_source("def broken(:\n")
    assert len(vs) == 1 and vs[0].rule == "E000"


# ---------------------------------------------------------------------------
# disable directives: trailing, preceding-line, file-level, wrong-rule
# ---------------------------------------------------------------------------

def test_disable_trailing_and_preceding_line():
    bad, _ = FIXTURES["R001"]
    lines = textwrap.dedent(bad).strip().splitlines()
    trailing = "\n".join(
        ln + "  # feddcl-lint: disable=R001  fixture" if "time.time" in ln
        else ln for ln in lines)
    assert lint_source(trailing) == []
    preceding = "\n".join(
        f"# feddcl-lint: disable=R001  fixture\n{ln}" if "time.time" in ln
        else ln for ln in lines)
    assert lint_source(preceding) == []


def test_disable_file_level_and_wrong_rule():
    bad, _ = FIXTURES["R003"]
    assert lint_source("# feddcl-lint: disable-file=R003  fixture\n"
                       + textwrap.dedent(bad)) == []
    # disabling a DIFFERENT rule must not silence the violation
    survived = lint_source("# feddcl-lint: disable-file=R001  fixture\n"
                           + textwrap.dedent(bad))
    assert [v.rule for v in survived] == ["R003"]


def test_violations_json_shape():
    vs = _lint(FIXTURES["R001"][0])
    doc = json.loads(violations_json(vs, files_checked=1))
    assert doc["tool"] == "feddcl_lint"
    assert doc["violation_count"] == 1 and doc["files_checked"] == 1
    assert doc["violations"][0]["rule"] == "R001"
    assert set(doc["rules"]) == set(RULES)


# ---------------------------------------------------------------------------
# the CLI as users run it (stdlib-only: no jax import in the subprocess)
# ---------------------------------------------------------------------------

def _cli(*argv, cwd=REPO):
    return subprocess.run(
        [sys.executable, "scripts/feddcl_lint.py", *argv],
        capture_output=True, text=True, timeout=120, cwd=cwd,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin"})


def test_cli_nonzero_and_json_on_each_rule_fixture(tmp_path):
    for rule, (bad, _) in sorted(FIXTURES.items()):
        f = tmp_path / f"{rule.lower()}_fixture.py"
        f.write_text(textwrap.dedent(bad))
        r = _cli(str(f), "--json")
        assert r.returncode == 1, (rule, r.stdout, r.stderr)
        doc = json.loads(r.stdout)
        assert [v["rule"] for v in doc["violations"]] == [rule]


def test_cli_clean_on_this_repo():
    """Satellite (a) pinned: the shipped tree carries zero violations —
    every deliberate exception is allowlisted in-source."""
    r = _cli()
    assert r.returncode == 0, r.stdout + r.stderr
    assert "0 violation(s)" in r.stdout


def test_cli_rules_filter_and_usage_error(tmp_path):
    f = tmp_path / "mixed.py"
    f.write_text(textwrap.dedent(FIXTURES["R001"][0]) +
                 textwrap.dedent(FIXTURES["R003"][0]))
    r = _cli(str(f), "--rules", "R003", "--json")
    assert r.returncode == 1
    assert [v["rule"] for v in json.loads(r.stdout)["violations"]] == ["R003"]
    assert _cli(str(f), "--rules", "R999").returncode == 2


# ---------------------------------------------------------------------------
# collective census: bit-identical to the historical inline counter
# ---------------------------------------------------------------------------

_FAKE_HLO = """
  %ar = f32[4]{0} all-reduce(f32[4]{0} %p), replica_groups={}
  %ars = f32[4]{0} all-reduce-start(f32[4]{0} %q), replica_groups={}
  %ard = f32[4]{0} all-reduce-done(f32[4]{0} %ars)
  %ag = f32[8]{0} all-gather(f32[4]{0} %p), dimensions={0}
  %cp = f32[4]{0} collective-permute(f32[4]{0} %p)
  ROOT %t = tuple(%ar, %ag)
"""


def _inline_histogram(txt):
    # the exact counter tests/test_fed_sharded.py and benchmarks/fed_bench.py
    # used before PR 9 — census must match it token for token
    out = {}
    for kind in ("all-reduce", "all-gather", "all-to-all",
                 "collective-permute", "reduce-scatter"):
        n = len(re.findall(rf"= \S+ {kind}(?:-start)?\(", txt))
        if n:
            out[kind] = n
    return out


def test_census_matches_inline_regex_on_synthetic_hlo():
    want = _inline_histogram(_FAKE_HLO)
    assert want == {"all-reduce": 2, "all-gather": 1,
                    "collective-permute": 1}     # -done NOT double-counted
    assert collective_census(_FAKE_HLO) == want
    assert set(COLLECTIVE_KINDS) == {"all-reduce", "all-gather", "all-to-all",
                                     "collective-permute", "reduce-scatter"}


def test_census_counts_operands_of_combined_collectives():
    """XLA's combiner merges same-kind collectives into ONE tuple-shaped
    instruction; the census counts its operands, so a combined all-reduce
    of 3 leaves counts 3, exactly like 3 separate ones."""
    combined = """
  %all-reduce = (f32[8]{0}, f32[4,8]{1,0}, f32[]) all-reduce(%a, %b, %c), replica_groups={{0,1}}
  %g0 = f32[8]{0} get-tuple-element(%all-reduce), index=0
  %ags = (f32[4]{0}, f32[8]{0}) all-gather-start(%p), dimensions={0}
  %agd = f32[8]{0} all-gather-done(%ags)
"""
    assert collective_census(combined) == {"all-reduce": 3, "all-gather": 1}


def test_census_accepts_lowered_and_single_device_is_empty():
    low = jax.jit(lambda x: (x @ x.T).sum()).lower(
        jnp.zeros((8, 8), jnp.float32))
    assert collective_census(low) == {}
    assert collective_census(low.compile()) == {}


# ---------------------------------------------------------------------------
# baked-data audit: splats pass, captured tenant data fails
# ---------------------------------------------------------------------------

def test_find_baked_constants_splat_vs_data():
    big = jnp.asarray(np.random.default_rng(0).standard_normal((64, 32)),
                      jnp.float32)
    leaky = jax.jit(lambda p: jnp.sum(big * p)).lower(jnp.float32(1.0))
    found = find_baked_constants(leaky, min_elems=1024)
    assert len(found) == 1 and found[0]["elements"] == 2048
    with pytest.raises(BakedDataError):
        assert_no_baked_data(leaky, min_elems=1024)
    # an equally large SPLAT (zeros) carries no data and must pass
    clean = jax.jit(lambda p: jnp.sum(jnp.zeros((64, 32)) * p)).lower(
        jnp.float32(1.0))
    assert find_baked_constants(clean, min_elems=1024) == []
    assert_no_baked_data(clean, min_elems=1024)
    # below the threshold the same capture is tolerated (tiny tables are
    # legitimate compile-time constants)
    assert find_baked_constants(leaky, min_elems=4096) == []


def test_baked_data_error_is_assertion_error():
    assert issubclass(BakedDataError, AssertionError)


def test_streamed_chunk_plan_audits_clean():
    """The chunked StreamedPlan flavor (the one lower_fl_plan special-cases)
    passes the baked-data audit and, unsharded, holds zero collectives.
    Together with test_fed_robust (unsharded whole-phase, all aggregators)
    and test_fed_sharded (sharded flavors, 8 devices) this completes the
    make_fl_plan flavor matrix of the audit."""
    from repro.core import federated
    from repro.core.federated import lower_fl_plan, pad_silo_data
    from repro.models import mlp
    from repro.optim import adamw

    rng = np.random.default_rng(0)
    wt = rng.standard_normal((8, 1))
    silos = []
    for n in (24, 17, 20):
        X = rng.standard_normal((n, 8))
        silos.append((X, X @ wt + 0.01 * rng.standard_normal((n, 1))))
    params = mlp.init_mlp_params(jax.random.PRNGKey(0), 8, (8,), 1)
    loss = lambda p, x, y: mlp.mlp_per_example_loss(p, x, y, "regression")
    bl = federated._make_batch_loss(loss, True, 0.0)
    padded = pad_silo_data(silos, 8)
    plan = federated.make_fl_plan(
        num_silos=padded.num_silos, num_batches=padded.num_batches,
        batch_size=padded.batch_size, opt=adamw(1e-2), batch_loss=bl,
        rounds=4, local_epochs=1, aggregator="fedavg", masked=True,
        collect="chunk")
    lowered = lower_fl_plan(plan, params, padded, rounds=4)
    assert_no_baked_data(lowered, min_elems=256)
    assert collective_census(lowered) == {}


# ---------------------------------------------------------------------------
# CompileCounter: counts executable builds, not cache hits
# ---------------------------------------------------------------------------

def test_compile_counter_counts_builds_not_hits():
    f = jax.jit(lambda x: jnp.tanh(x) * 3.0 + x)
    x = jnp.arange(24.0).reshape(4, 6)
    with CompileCounter() as cold:
        f(x).block_until_ready()
    assert cold.count >= 1
    with CompileCounter() as warm:
        f(x).block_until_ready()
    assert warm.count == 0
    with CompileCounter() as reshaped:               # new shape recompiles
        f(jnp.arange(12.0).reshape(3, 4)).block_until_ready()
    assert reshaped.count >= 1


def test_compile_counter_restores_patch_on_exit():
    """The listener is removed on exit, also when the block raises: builds
    after the block no longer count."""
    with pytest.raises(RuntimeError):
        with CompileCounter() as cc:
            raise RuntimeError("boom")
    with CompileCounter() as outer:
        jax.jit(lambda x: x * 7.0 - 1.0)(jnp.ones(5)).block_until_ready()
    assert outer.count >= 1
    assert cc.count == 0
