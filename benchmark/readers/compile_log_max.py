"""The slowest single program of the set-up: the largest ``lower_s +
compile_s`` of one row of ``engine.compile_log()`` stamped before the
window (a row carries the ``dispatches`` counter as its lowering began;
the window's first request finds it at the ``begin_window`` snapshot's).
The row's ``family`` names the program, so the row is logged.  Nothing
where the program keeps no such log or compiled nothing."""


def read(ctx):
    cell = ctx["cell"]
    try:
        before = cell.counters0["dispatches"]
        rows = [r for r in cell.engine.compile_log()
                if r["dispatches"] < before]
    except (AttributeError, KeyError, TypeError):
        return None
    if not rows:
        return None
    row = max(rows, key=lambda r: r["lower_s"] + r["compile_s"])
    cell.log("slowest program of the set-up: %s %s, lower %.3f s, compile "
             "%.3f s (%s, read %.3f s)"
             % (row["family"], row["program"], row["lower_s"],
                row["compile_s"], row["cache"], row["read_s"]))
    return row["lower_s"] + row["compile_s"]
