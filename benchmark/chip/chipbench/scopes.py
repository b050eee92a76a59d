"""Device time by the scopes a layer stages itself under, wherever a scope
lies on an op's ``op_name`` path (a Dense layer or a kernel's own scope may
put names below it), for the readers that need more than the innermost scope
``program.device_split`` keeps."""
import re

from chipbench import program

# "transpose(jvp(ssm_scan))": what a derived op's scopes are wrapped in
_WRAPPED = re.compile(r"^(?:(?:transpose|jvp|vmap)\()+|\)+$")


def under(path, scope):
    """Whether ``scope`` is a component of the ``op_name`` path."""
    return any(_WRAPPED.sub("", part) == scope
               for part in (path or "").split("/"))


def op_paths(run):
    """{short HLO name: op_name path} over the registry's programs; None
    where the program has no registry (an older commit)."""
    t = program.telemetry(run)
    if t is None:
        return None
    paths = {}
    for p in t.programs().values():
        paths.update(p.ops)
    return paths


def traced_ops(run, paths):
    """``(op, its path, its self seconds)`` over the traced window's ops."""
    for op, seconds in run.reduced["op_s"].items():
        yield op, paths.get(op.lstrip("%").partition("~")[0]), seconds


def gauge(run, name):
    """The value of an unlabeled gauge of the program's registry, or None."""
    registry = getattr(getattr(run.mx, "telemetry", None), "registry", None)
    snap = registry().snapshot() if callable(registry) else {}
    samples = snap.get(name, {"samples": []})["samples"]
    return samples[0]["value"] if samples else None
