"""Which way the imports point between ``telemetry/`` and the rest.

``telemetry/`` is the package's lowest layer.  The core reaches it through
five modules (``tracing``, ``metrics``, ``blackbox``, ``watchdog``,
``xray``), and it reaches nothing that trains, serves or talks on the
wire.  ``ndarray/``, the lowest data layer, takes the race detector's
hooks from ``analysis/`` and nothing else: no linter, and no auditor of a
path above it.  All are read off the source by ``ast``, imports inside
functions included, so nothing is imported to find out.
"""
import ast
import glob
import os

import pytest

PKG = "incubator_mxnet_tpu"
ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")

# the modules that booked into the step lens or registered with the
# autotuner above it; what they take from telemetry now is its surface
CORE_MODULES = [
    "ndarray/ndarray.py", "engine.py", "autograd/__init__.py",
    "kvstore.py", "io.py", "profiler.py", "overlap.py",
    "gluon/trainer.py",
    "gluon/data/dataloader.py", "serving/batcher.py", "parallel/dist.py",
    "telemetry/tracing.py", "telemetry/blackbox.py",
]
SURFACE = {"tracing", "metrics", "blackbox", "watchdog", "xray"}

# (module, the layer it reaches into, what it may take from there)
REACHES = [(m, "telemetry", SURFACE) for m in CORE_MODULES] + [
    # every eager op reads and writes through ndarray/: it carries
    # analysis.tsan's two flag-gated hooks, and imports neither
    # analysis.compile_safety (a 1,200-line AST linter) nor anything else
    ("ndarray/" + os.path.basename(p), "analysis", {"tsan"})
    for p in sorted(glob.glob(os.path.join(ROOT, PKG, "ndarray", "*.py")))]

TELEMETRY_FILES = sorted(
    os.path.basename(p)
    for p in glob.glob(os.path.join(ROOT, PKG, "telemetry", "*.py")))

# the layers the autotuner steered: nothing in telemetry/ imports them
ABOVE = {"gluon", "serving", "parallel", "kvstore", "module", "io"}
# what telemetry still imports from beside it (ROADMAP D5): metrics.py's
# snapshot collectors, blackbox.py's lockstep fold and chrome spans,
# watchdog.py's typed errors
BESIDE = {"engine", "autograd", "optimizer", "profiler",
          "analysis.lockstep", "armor.errors"}
# a selftest and the CLI's --summary demo drive the package from its root
# ("" here) the way a user's script does; the demo is a Gluon loop over an
# io iterator
DRIVERS = {"blackbox.py": {""}, "__main__.py": {"", "gluon", "io"}}


def _package_imports(relpath):
    """The package's modules ``relpath`` imports at any depth, as dotted
    names under the package ("" for the package itself); a name imported
    *from* a module counts as ``module.name``."""
    path = os.path.join(ROOT, PKG, relpath)
    here = [PKG] + relpath.split("/")[:-1]
    with open(path) as f:
        tree = ast.parse(f.read(), path)
    found = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            names = [a.name for a in node.names]
        elif isinstance(node, ast.ImportFrom):
            base = here[:len(here) - (node.level - 1)] if node.level else []
            mod = ".".join(base + ([node.module] if node.module else []))
            names = [mod + "." + a.name for a in node.names]
        else:
            continue
        for name in names:
            if name == PKG or name.startswith(PKG + "."):
                found.add(name[len(PKG) + 1:])
    return found


def _under(names, prefix):
    """Of dotted ``names``, what lies under ``prefix``, relative to it."""
    return {n[len(prefix) + 1:] for n in names
            if n == prefix or n.startswith(prefix + ".")}


@pytest.mark.parametrize("module,layer,surface", REACHES,
                         ids=["%s->%s" % r[:2] for r in REACHES])
def test_core_reaches_a_layer_through_its_surface(module, layer, surface):
    taken = {n.split(".")[0]
             for n in _under(_package_imports(module), layer) if n}
    assert taken <= surface, sorted(taken - surface)


@pytest.mark.parametrize("module", TELEMETRY_FILES)
def test_telemetry_imports_nothing_above_it(module):
    outside = {n for n in _package_imports("telemetry/" + module)
               if n != "telemetry" and not n.startswith("telemetry.")}
    # each import by the layer it belongs to; one of no known layer
    # stands for itself, and is then not among the allowed
    reached = {next((b for b in BESIDE | ABOVE
                     if n == b or n.startswith(b + ".")), n)
               for n in outside}
    allowed = BESIDE | DRIVERS.get(module, set())
    assert reached <= allowed, sorted(reached - allowed)
