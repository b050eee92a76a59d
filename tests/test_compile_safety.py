"""graftguard: compile-safety lint (GL3xx) over trace-eligible closures.

The contract under test (analysis/compile_safety.py):

* **Static pass** — every GL301-GL308 fixture fires exactly its code and
  every clean twin stays silent; taint survives factory indirection
  (``jax.jit(make_step())``) but NOT host-static predicates (``x is
  None``, ``name in params``, dict-KEY iteration); a local ``step =
  self._make_step()`` shadows the method of the same name (the
  data_parallel false-positive regression); suppression works at line
  level and at def (scope) level, keeps its justification, and never
  hides a different code.
* **Coverage** — the package walk reaches serving/, armor/ and
  parallel/ (planted-finding regression), and the repo itself holds
  ZERO active findings on both the package and registry passes.
* **Baseline** — ``graftlint --baseline`` masks snapshot findings by
  per-key count budget and fails only on NEW ones.
"""
import os

import pytest

from incubator_mxnet_tpu.analysis import compile_safety as cs
from incubator_mxnet_tpu.analysis import contracts, graftlint


def active_codes(src, **kw):
    return sorted({d.code for d in cs.lint_source(src, **kw)
                   if not d.suppressed})


# ---------------------------------------------------------------------------
# GL301-GL308: each fixture fires its code, each clean twin is silent
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("code", sorted(cs._GL_FIXTURES))
def test_gl_fixture_fires(code):
    bad, _clean = cs._GL_FIXTURES[code]
    assert active_codes(bad, filename="fixture_%s.py" % code) == [code]


@pytest.mark.parametrize("code", sorted(cs._GL_FIXTURES))
def test_gl_clean_twin_silent(code):
    _bad, clean = cs._GL_FIXTURES[code]
    assert active_codes(clean, filename="fixture_%s_ok.py" % code) == []


def test_rule_tables_cover_all_codes():
    assert sorted(cs.RULES) == ["GL30%d" % i for i in range(1, 9)
                                if i != 7]
    assert set(cs._GL_FIXTURES) == set(cs.RULES)


# ---------------------------------------------------------------------------
# taint refinements (each one a shipped false-positive regression)
# ---------------------------------------------------------------------------

def test_gl302_host_static_predicates_exempt():
    # `is None`, membership with an untainted probe, and their boolean
    # combinations branch on Python structure, not traced values
    src = (
        "import jax\n"
        "def mk(f, use_b):\n"
        "    def loss(x, b=None):\n"
        "        if use_b and b is not None:\n"
        "            x = x + b\n"
        "        names = {'w0': x}\n"
        "        if 'w0' in names:\n"
        "            x = x * 2\n"
        "        return x.sum()\n"
        "    return jax.jit(loss)\n")
    assert active_codes(src) == []


def test_gl302_dict_key_iteration_not_tainted():
    # for n, v in tainted.items(): the KEY is a host string; the VALUE
    # still carries taint (second variant must fire)
    clean = (
        "import jax\n"
        "def mk(tvals):\n"
        "    def loss(aux, x):\n"
        "        for n, v in aux.items():\n"
        "            if n not in tvals:\n"
        "                x = x + v\n"
        "        return x.sum()\n"
        "    return jax.jit(loss)\n")
    assert active_codes(clean) == []
    bad = clean.replace("if n not in tvals:", "if v > 0:")
    assert active_codes(bad) == ["GL302"]


def test_traced_set_follows_factory_return():
    src = (
        "import jax\n"
        "def make_step():\n"
        "    def step(x):\n"
        "        if x > 0:\n"
        "            return x\n"
        "        return -x\n"
        "    return step\n"
        "def compile():\n"
        "    step = make_step()\n"
        "    return jax.jit(step)\n")
    assert active_codes(src) == ["GL302"]


def test_local_factory_shadows_method():
    # the data_parallel regression: `step = self._make_step()` then
    # `jax.jit(step)` must resolve to the factory's closure, NOT the
    # host-side method of the same name
    src = (
        "import jax\n"
        "class T:\n"
        "    def _make_step(self):\n"
        "        def step(x):\n"
        "            return x * 2\n"
        "        return step\n"
        "    def step(self, data):\n"
        "        if float(data.sum()) > 0:\n"
        "            return self._place(data)\n"
        "        return data\n"
        "    def compile(self):\n"
        "        step = self._make_step()\n"
        "        return jax.jit(step)\n")
    assert active_codes(src) == []


def test_literal_call_arg_does_not_taint():
    # helper(x, False): the literal must not taint `flat` — branching
    # on a host bool inside the traced helper is fine
    src = (
        "import jax\n"
        "def helper(x, flat):\n"
        "    if flat:\n"
        "        return x.reshape((-1,))\n"
        "    return x\n"
        "def mk():\n"
        "    def loss(x):\n"
        "        return helper(x, False).sum()\n"
        "    return jax.jit(loss)\n")
    assert active_codes(src) == []


def test_static_attrs_break_taint():
    src = (
        "import jax\n"
        "def mk():\n"
        "    def loss(x):\n"
        "        if x.ndim > 2 or x.shape[0] == 1:\n"
        "            return x.sum()\n"
        "        return x.mean()\n"
        "    return jax.jit(loss)\n")
    assert active_codes(src) == []


# ---------------------------------------------------------------------------
# suppression: line level, scope level, justification, no cross-code hiding
# ---------------------------------------------------------------------------

def test_line_suppression_keeps_justification():
    bad, _ = cs._GL_FIXTURES["GL304"]
    sup = bad.replace(
        "seen.append(1)",
        "seen.append(1)  # graftlint: disable=GL304 -- trace-time memo")
    diags = [d for d in cs.lint_source(sup) if d.code == "GL304"]
    assert diags and all(d.suppressed for d in diags)
    assert any(d.justification == "trace-time memo" for d in diags)


def test_scope_suppression_covers_whole_def():
    # one directive above the def silences every occurrence inside it
    # (the optimizer.py fused-apply convention: 9 deliberate bakes)
    src = (
        "import jax\n"
        "def mk(lr, wd):\n"
        "    # graftlint: disable=GL305 -- baked by design\n"
        "    def step(x):\n"
        "        return x * lr + x * wd\n"
        "    return jax.jit(step)\n")
    diags = [d for d in cs.lint_source(src) if d.code == "GL305"]
    assert diags and all(d.suppressed for d in diags)


def test_suppression_does_not_hide_other_codes():
    bad, _ = cs._GL_FIXTURES["GL302"]
    sup = "\n".join(
        line + "  # graftlint: disable=GL304 -- wrong code"
        if "if " in line else line for line in bad.splitlines())
    assert "GL302" in active_codes(sup)


# ---------------------------------------------------------------------------
# coverage: the walk reaches serving/armor/parallel; the repo is clean
# ---------------------------------------------------------------------------

def test_package_walk_reaches_subsystem_dirs(tmp_path):
    bad, _ = cs._GL_FIXTURES["GL301"]
    pkg = tmp_path / "fakepkg"
    for sub in ("serving", "armor", "parallel"):
        (pkg / sub).mkdir(parents=True)
        (pkg / sub / "__init__.py").write_text("")
    (pkg / "__init__.py").write_text("")
    (pkg / "serving" / "batcher.py").write_text(bad)
    (pkg / "armor" / "faults.py").write_text(bad)
    (pkg / "parallel" / "data_parallel.py").write_text(bad)
    diags = [d for d in cs.lint_package(root=str(pkg)) if not d.suppressed]
    hit = {os.path.basename(d.file) for d in diags}
    assert hit == {"batcher.py", "faults.py", "data_parallel.py"}


def test_repo_package_pass_clean():
    diags = cs.lint_package()
    active = [d for d in diags if not d.suppressed]
    assert active == [], "\n".join(repr(d) for d in active)
    # the intentional bakes stay visible as suppressed findings WITH
    # their reasons (audit trail, not silence)
    sup = [d for d in diags if d.suppressed]
    assert any("optimizer.py" in (d.file or "") for d in sup)
    assert any("block.py" in (d.file or "") for d in sup)
    assert all(d.justification for d in sup)


def test_repo_registry_pass_clean():
    import incubator_mxnet_tpu.ops  # noqa: F401  registration effects
    diags = cs.lint_registry()
    active = [d for d in diags if not d.suppressed]
    assert active == [], "\n".join(repr(d) for d in active)


def test_registry_seeds_only_array_params():
    # num_inputs=None + input_names: host kwargs (no_bias/flatten) must
    # not be seeded — FullyConnected's `if not no_bias and bias is not
    # None` stays clean while a traced-value branch still fires
    import incubator_mxnet_tpu.ops  # noqa: F401
    diags = cs.lint_registry(names={"FullyConnected", "Convolution",
                                    "SequenceMask"})
    assert [d for d in diags if not d.suppressed] == []


# ---------------------------------------------------------------------------
# baseline: mask by per-key count budget, fail only on NEW findings
# ---------------------------------------------------------------------------

def _diag(code, op, file, line):
    return contracts.Diagnostic(code, op, "synthetic", file=file,
                                line=line)


def test_baseline_masks_by_count_and_fails_new(tmp_path):
    path = str(tmp_path / "base.json")
    old = [_diag("GL302", "mod.fn", "/a/x.py", 10),
           _diag("GL302", "mod.fn", "/a/x.py", 20),
           _diag("GL305", "mod.g", "/a/y.py", 5)]
    graftlint.write_baseline(path, old)

    # same findings at DIFFERENT lines: still masked (lines are not
    # part of the key), plus one genuinely new finding that must fail
    now = [_diag("GL302", "mod.fn", "/b/x.py", 11),
           _diag("GL302", "mod.fn", "/b/x.py", 99),
           _diag("GL302", "mod.fn", "/b/x.py", 100),   # over budget
           _diag("GL301", "mod.h", "/b/z.py", 1)]      # new code
    new, masked = graftlint.apply_baseline(path, now)
    assert len(masked) == 2
    assert sorted(d.code for d in new) == ["GL301", "GL302"]


def test_baseline_suppressed_findings_stay_out(tmp_path):
    path = str(tmp_path / "base.json")
    d = _diag("GL302", "mod.fn", "/a/x.py", 10)
    d.suppressed = True
    graftlint.write_baseline(path, [d])
    new, masked = graftlint.apply_baseline(
        path, [_diag("GL302", "mod.fn", "/a/x.py", 10)])
    assert len(new) == 1 and not masked


def test_selftest_is_green():
    problems = cs.selftest()
    assert problems == [], "\n".join(problems)
