"""Runtime environment-variable configuration layer.

TPU-native rebirth of the reference's ~35 ``MXNET_*`` env vars
(read via dmlc::GetEnv at point of use, documented centrally in
docs/faq/env_var.md — SURVEY §5.6).  Variables use the ``MXTPU_`` prefix;
the matching ``MXNET_`` spelling is accepted as a fallback so reference
launch scripts keep working.

Many reference knobs configured machinery XLA now owns (worker thread
counts, GPU memory pools, engine bulking); those are intentionally absent
— the registry below only lists variables that change behavior here.
``docs/env_var.md`` (generated by :func:`document`) is the central doc.
"""
from __future__ import annotations

import os

__all__ = ["get", "get_bool", "get_int", "variables", "document",
           "apply_compile_cache"]

# name (without prefix) -> (kind, default, doc)
_REGISTRY = {
    "ENGINE_TYPE": (
        "str", "AsyncEngine",
        "Execution engine. 'NaiveEngine' blocks after every op dispatch "
        "(fully synchronous, for bisecting async bugs — parity with "
        "MXNET_ENGINE_TYPE=NaiveEngine, src/engine/engine.cc:33). Default "
        "'AsyncEngine' lets XLA dispatch asynchronously. Read at import."),
    "PROFILER_AUTOSTART": (
        "bool", False,
        "Start the profiler at import and dump on exit (parity: "
        "MXNET_PROFILER_AUTOSTART, docs/faq/env_var.md:105)."),
    "SEED": (
        "int", None,
        "Global RNG seed applied at import (parity: common.py test "
        "seeding; there is no reference env var — convenience addition)."),
    "HOME": (
        "str", os.path.join("~", ".mxnet"),
        "Cache root for datasets and model zoo downloads (parity: the "
        "reference's hard-coded ~/.mxnet plus MXNET_HOME in later "
        "versions)."),
    "GLUON_REPO": (
        "str", "https://apache-mxnet.s3-accelerate.dualstack.amazonaws.com/",
        "Base URL for model zoo downloads (parity: MXNET_GLUON_REPO). "
        "Unused when the environment has no egress."),
    "NATIVE_IO": (
        "bool", True,
        "Use the C++ RecordIO data plane (src/io/recordio.cc) when built; "
        "set to 0 to force the pure-Python reader/writer (parity role: "
        "the reference's C++ src/io/ iterators are always native)."),
}
# Not registered on purpose: MXNET_SAFE_ACCUMULATION — the MXU always
# accumulates bf16 matmuls in float32, so the knob has no off position
# on TPU; thread-pool/memory-pool/engine-bulking vars — absorbed by XLA.

_PREFIXES = ("MXTPU_", "MXNET_")


def get(name, default=None):
    """String value of config var ``name`` (no prefix), env overriding the
    registry default."""
    for p in _PREFIXES:
        v = os.environ.get(p + name)
        if v is not None:
            return v
    if name in _REGISTRY:
        d = _REGISTRY[name][1]
        return d if default is None else default
    return default


def get_bool(name, default=None):
    v = get(name, default)
    if isinstance(v, str):
        return v.strip().lower() in ("1", "true", "yes", "on")
    return bool(v) if v is not None else False


def get_int(name, default=None):
    v = get(name, default)
    if v is None:
        return None
    return int(v)


def variables():
    """All registered vars with their current values."""
    return {n: (get(n), doc) for n, (_, _, doc) in sorted(_REGISTRY.items())}


def document():
    """Render docs/env_var.md (parity: docs/faq/env_var.md)."""
    lines = ["# Environment variables",
             "",
             "Variables use the `MXTPU_` prefix; the `MXNET_` spelling is "
             "accepted as a fallback. Knobs the reference exposed for its "
             "thread pools, GPU memory pools and engine bulking are absorbed "
             "by XLA and intentionally absent.",
             ""]
    for name, (kind, default, doc) in sorted(_REGISTRY.items()):
        lines.append("## MXTPU_%s" % name)
        lines.append("")
        lines.append("- type: %s, default: %r" % (kind, default))
        lines.append("- %s" % doc)
        lines.append("")
    return "\n".join(lines)


def data_home():
    """Resolved cache root directory."""
    return os.path.expanduser(get("HOME"))


def naive_engine():
    """True when MXTPU_ENGINE_TYPE=NaiveEngine (synchronous dispatch)."""
    return get("ENGINE_TYPE") == "NaiveEngine"


# <checkout>/.jax_cache, from this file's own location.  The path has to
# be the same in every process and every run for an entry to be found
# again, so it is never built from cwd, a temp name, a pid or the time.
_COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache")

# Every program is stored, not only those that took JAX's default of 1 s to
# compile.  Measured on the v5e (PERF.md, PR 21): a cold ResNet-50 start
# compiles ~100 eager per-op programs before the fused step, 95 of them
# under 1 s and 14.9 s together; read back from the cache the whole set
# costs 0.3 s.  With the 1 s default they would be compiled again on every
# start: 14.9 s on top of a warm start that otherwise takes 10 s.
_CACHE_MIN_COMPILE_SECS = 0.0


def apply_compile_cache():
    """Turn on JAX's persistent compilation cache (called once at import;
    ``jax.config.update`` opens no backend).

    Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX has already read it and
    no directory is set here; otherwise the cache lives in
    ``<checkout>/.jax_cache``.  JAX's own
    ``JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS`` is honoured the same way.
    """
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", _COMPILE_CACHE_DIR)
    if not os.environ.get("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"):
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          _CACHE_MIN_COMPILE_SECS)
