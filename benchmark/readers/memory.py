"""Device memory beyond the table: the fullest chip's peak minus the table
bytes it holds, in GB (``{"kind": "memory"}``)."""


def read(params: dict, ctx: dict):
    mem = ctx.get("memory")
    if not mem or mem.get("peak_bytes") is None:
        return None
    return (max(mem["peak_bytes"]) - mem["table_bytes_per_device"]) / 1e9
