"""A kernel's share of its roofline over the device time of the *scope* it
runs under, in percent.

``{"kind": "scope_roofline", "kernel": "<name>", "program": "<program>",
"phase": "<scope>"}``: the least time the chip could take for the kernel's
executed work a step — ``costs.step_floor_seconds(...)["kernels"][name]
["seconds"]`` — over the device time a step of the program's instructions
under ``phase`` (``trace_scope``).  For a kernel the compiler builds from
many instructions with no name of their own (blockwise attention's tile
loop: fusions and a ``while``), where ``kernel_roofline``'s match by op name
finds nothing to match; the scope's other work (here the projections, norms
and RoPE) counts against the share.

``None`` when the family's costs name no such kernel, or ``trace_scope``
finds nothing to read (no device plane, a program without that scope).
"""

from . import trace_scope


def read(params: dict, ctx: dict):
    floor = ctx.get("floor") or {}
    work = floor.get("kernels", {}).get(params["kernel"])
    if work is None:
        return None
    ms = trace_scope.read({"program": params["program"],
                           "phase": params["phase"]}, ctx)
    if not ms:
        return None
    return 100.0 * work["seconds"] / (ms / 1e3)
