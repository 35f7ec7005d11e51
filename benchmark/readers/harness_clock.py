"""A host-clock reading the harness took around its own calls into a layer:
``{"kind": "harness_clock", "key": "model_build_s"}``."""


def read(params: dict, ctx: dict):
    return ctx["harness"].get(params["key"])
