"""``flash_bwd_ms_per_step``: the flash-attention backward
(``ops/attention.py::_flash_bwd``), a step.

It is a ``jnp`` scan, not a kernel: two ``while`` loops whose bodies are
HIGHEST-precision einsum fusions, named ``fusion.N`` like every other
matmul.  The program stages all of it under the scope
``flash_attention_bwd``, and this is the self time of the ops whose
``op_name`` path holds that scope (the bodies of both loops and the loops'
own overhead), found through ``telemetry.programs()``.  Left out where the
configuration has no attention or the program no such scope.
"""
from chipbench import program


def read(run):
    if "num_attention_heads" not in run.sizes:
        return {}
    split = program.device_split(run)
    if split is None:
        if run.rehearse and not run.reduced and \
                program.FLASH_BWD in program.offers(run):
            return {"flash_bwd_ms_per_step": 0.0}
        return {}
    if not split["flash_bwd_s"]:
        return {}
    return {"flash_bwd_ms_per_step":
            1e3 * split["flash_bwd_s"] / split["steps"]}
