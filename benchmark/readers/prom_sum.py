"""Window delta of some series, summed (a count). args: `series`, a list
of {"name": ..., "labels": {...}}."""

from readers.prom_ratio import delta


def read(ctx: dict, series: list):
    return delta(ctx, series)
