"""A value the harness took on the host's clock. args: `key` into
ctx["host"]; absent returns None."""


def read(ctx: dict, key: str):
    return ctx["host"].get(key)
