"""Some series as they stand at the window's end, summed and scaled: for
what the program did before the window opened (set-up), where a window
delta reads nothing.

args: `series`, a list of {"name": ..., "labels": {...}} (series
`dgraph_tpu_<name>` whose labels include those), and `scale`. A program
that exports no such series (one from before the counter) returns None.
"""


def read(ctx: dict, series: list, scale: float = 1.0):
    found = [v for s in series for n, ls, v in ctx["prom_after"]
             if n == "dgraph_tpu_" + s["name"]
             and all(ls.get(k) == x
                     for k, x in s.get("labels", {}).items())]
    return scale * sum(found) if found else None
