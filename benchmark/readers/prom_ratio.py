"""Window delta of some series over the window delta of others.

args: `num` and `den`, each a list of {"name": ..., "labels": {...}}
(series `dgraph_tpu_<name>` whose labels include those), and `scale`.
Nothing to read (a zero denominator) returns None.
"""

from harness.server import msum


def delta(ctx: dict, specs: list) -> float:
    return sum(msum(ctx["prom_after"], s["name"], **s.get("labels", {}))
               - msum(ctx["prom_before"], s["name"], **s.get("labels", {}))
               for s in specs)


def read(ctx: dict, num: list, den: list, scale: float = 1.0):
    d = delta(ctx, den)
    if d <= 0:
        return None
    return scale * delta(ctx, num) / d
